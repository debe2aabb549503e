"""`python benchmarks/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`: one cell, once, in a new process, on the TPU.

Prints earlier lines freely (each names the device) and LAST one JSON
object with exactly the keys `correct`, `attempted`, `failed`, `metrics`,
`device` and, in a traced run, `breakdown`.  --trace 0 gives the cell's
end-to-end metrics with the profiler off; --trace 1 is a run of its own
that traces a short steady part of the window and gives the per-layer
metrics.  With no TPU, or fewer chips than the cell asks for, it exits
non-zero and prints no result; there is no CPU switch (tests call
`harness.run_cell` with tiny data files of their own).
"""

import argparse
import json
import os
import sys


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from benchmarks import harness
    t_process = harness.process_start_monotonic()

    import jax
    from tiny_deepspeed_tpu.utils.startup import select_platform
    # the program's own rules: the TPU or exit non-zero before anything
    # compiles; JAX_COMPILATION_CACHE_DIR if set, else <checkout>/.jax_cache
    select_platform()
    # every program rests in the cache after a checkout's first run, the
    # ones that compile in under a second too
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

    result = harness.run_cell(args.workload, args.seed, args.seconds,
                              bool(args.trace), t_process=t_process)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
