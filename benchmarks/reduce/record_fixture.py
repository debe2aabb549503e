"""Record `fixture.xplane.pb` again: the tests' tiny train cell (a scanned
two-layer GPT-2, sequences of 128) traced on the TPU through the harness,
so the fixture has what the reduction must cope with on the real device --
operations nested in a `while`, Pallas kernels by name, and the harness's
own `bench.*` annotations on the host's lines.

    chiprun -- python benchmarks/reduce/record_fixture.py
    cp chiprun_out/fixture.xplane.pb benchmarks/reduce/fixture.xplane.pb
"""

import importlib.util
import json
import os
import shutil
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))


def main() -> int:
    sys.path.insert(0, REPO)
    from tiny_deepspeed_tpu.utils.startup import select_platform
    select_platform()
    from benchmarks import harness
    spec = importlib.util.spec_from_file_location(
        "tinyroot", os.path.join(REPO, "tests", "benchmarks", "tinyroot.py"))
    tinyroot = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tinyroot)
    with tempfile.TemporaryDirectory(dir=REPO) as tmp:
        root, manifest = tinyroot.build(tmp)
        mix_path = os.path.join(root, "traffic", "tiny-train.json")
        with open(mix_path) as f:
            mix = json.load(f)
        mix.update(seq_len=128, loss_tolerance=0.05)  # bf16 passes on TPU
        with open(mix_path, "w") as f:
            json.dump(mix, f)
        result = harness.run_cell("tiny.tiny-train", seed=24, seconds=1.0,
                                  trace=True, root=root, manifest=manifest)
        print(json.dumps(result))
        from benchmarks.reduce.xplane import newest_xplane
        out = os.path.join(REPO, "chiprun_out")
        os.makedirs(out, exist_ok=True)
        shutil.copy(newest_xplane(os.path.join(
            root, ".trace", "tiny.tiny-train")),
            os.path.join(out, "fixture.xplane.pb"))
    return 0


if __name__ == "__main__":
    sys.exit(main())
