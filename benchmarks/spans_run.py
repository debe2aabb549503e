"""`python benchmarks/spans_run.py --workload <cell> --seed <n> --seconds <s>
[--trace 1]`: run.py's run of one cell, with PR 26's readers attached.

The harness attaches a per-layer reader to a cell through `per_layer` in
`cells/<cell>.json` and nothing else, and only a `benchmark` PR may edit
that file.  Until one does, this runs the same harness on a copy of the
benchmark's data files (under benchmarks/.trace/, which git ignores) in
which the names `spans_cells.json` lists for the cell are appended to its
`per_layer`: run.py's result line plus the new metrics, and the lines
reduce/spans.py prints (scoped share, programs, scopes, phases, copies by
program, the longest idle gaps by innermost `tds.*` span).  TPU or exit 1,
as run.py.
"""

import argparse
import json
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
DATA_DIRS = ("configs", "traffic", "cells", "metrics", "kinds")


def append_per_layer(root: str, cell: str, names) -> None:
    """Append metric names to `per_layer` of <root>/cells/<cell>.json."""
    path = os.path.join(root, "cells", cell + ".json")
    with open(path) as f:
        spec = json.load(f)
    spec["per_layer"] += [n for n in names if n not in spec["per_layer"]]
    with open(path, "w") as f:
        json.dump(spec, f)


def cells_readers() -> dict:
    with open(os.path.join(HERE, "spans_cells.json")) as f:
        return json.load(f)["cells"]


def overlay(workload: str) -> str:
    """-> the root of a copy of the data files with the cell's new readers
    attached."""
    root = os.path.join(HERE, ".trace", "overlay." + workload)
    shutil.rmtree(root, ignore_errors=True)
    for d in DATA_DIRS:
        shutil.copytree(os.path.join(HERE, d), os.path.join(root, d))
    shutil.copy(os.path.join(HERE, "peaks.json"), root)
    append_per_layer(root, workload, cells_readers().get(workload, ()))
    return root


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=1)
    ap.add_argument("--keep-trace", metavar="DIR", default=None,
                    help="copy the run's .xplane.pb to DIR/<cell>.xplane.pb")
    args = ap.parse_args(argv)

    sys.path.insert(0, os.path.dirname(HERE))
    from benchmarks import harness
    t_process = harness.process_start_monotonic()

    import jax
    from tiny_deepspeed_tpu.utils.startup import select_platform
    select_platform()   # run.py's start-up, to the letter
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

    root = overlay(args.workload)
    result = harness.run_cell(
        args.workload, args.seed, args.seconds, bool(args.trace),
        root=root, t_process=t_process)
    if args.keep_trace and args.trace:
        from benchmarks.reduce.xplane import newest_xplane
        os.makedirs(args.keep_trace, exist_ok=True)
        shutil.copy(
            newest_xplane(os.path.join(root, ".trace", args.workload)),
            os.path.join(args.keep_trace, args.workload + ".xplane.pb"))
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
