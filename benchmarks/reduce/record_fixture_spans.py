"""Record `fixture_spans_train.xplane.pb` and `fixture_spans_serve.xplane.pb`
again: record_fixture.py's twin for reduce/spans.py.  The tests' tiny train
cell (a scanned two-layer GPT-2 with remat, sequences of 128) and tiny serve
cell traced on the TPU through the harness, PR 26's readers attached in the
temporary copy, so the fixtures have what the span reduction reads on the
real device: `jit_tds_*` events on the `XLA Modules` line, `tf_op` on the
operations' event metadata with `tds.` scopes and the transforms of forward,
backward and recompute, and the program's `tds.*` host spans with their ids.

    chiprun -- python benchmarks/reduce/record_fixture_spans.py
    cp chiprun_out/fixture_spans_*.xplane.pb benchmarks/reduce/
"""

import importlib.util
import json
import os
import shutil
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
# (cell, fixture, seconds, data file -> what to change in it, new readers)
CELLS = (
    ("tiny.tiny-train", "fixture_spans_train.xplane.pb", 1.0,
     {"traffic/tiny-train.json": {"seq_len": 128, "loss_tolerance": 0.05},
      "cells/tiny.tiny-train.json": {"sizes": {
          "batch_per_chip": 2,
          "model": {"remat": True, "remat_policy": "nothing"}}}},
     ("fwd_ms", "bwd_ms", "optim_ms", "head_ms", "attn_fwd_ms",
      "attn_bwd_ms", "import_s", "backend_init_s")),
    ("tiny.tiny-chat", "fixture_spans_serve.xplane.pb", 1.0,
     {"traffic/tiny-chat.json": {
         "trace_seconds": 0.12,
         "check": {"prompt_lens": [10, 20, 40], "logit_tolerance": 0.1}}},
     ("decode_ms", "prefill_ms", "copies_ms.decode", "copies_ms.prefill",
      "tick_host_ms")),
)


def _load(name: str, path: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def main() -> int:
    sys.path.insert(0, REPO)
    from tiny_deepspeed_tpu.utils.startup import select_platform
    select_platform()
    from benchmarks import harness
    from benchmarks.reduce.xplane import newest_xplane
    from benchmarks.spans_run import append_per_layer
    tinyroot = _load("tinyroot", os.path.join(
        REPO, "tests", "benchmarks", "tinyroot.py"))
    out = os.path.join(REPO, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with tempfile.TemporaryDirectory(dir=REPO) as tmp:
        root, manifest = tinyroot.build(tmp)
        for cell, fixture, seconds, changes, readers in CELLS:
            for rel, change in changes.items():
                path = os.path.join(root, rel)
                with open(path) as f:
                    data = json.load(f)
                data.update(change)
                with open(path, "w") as f:
                    json.dump(data, f)
            append_per_layer(root, cell, readers)
            result = harness.run_cell(cell, seed=26, seconds=seconds,
                                      trace=True, root=root,
                                      manifest=manifest)
            print(json.dumps(result))
            shutil.copy(newest_xplane(os.path.join(root, ".trace", cell)),
                        os.path.join(out, fixture))
    return 0


if __name__ == "__main__":
    sys.exit(main())
