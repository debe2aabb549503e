"""A temporary copy of the benchmark's data files with the tests' tiny
config, mixes, cells and one new metric ADDED beside them (data/), and a
copy of BENCHMARK.json with one `workloads` entry more per tiny cell: what a
later PR does when it adds a cell, with no edit to any existing file."""

import json
import os
import shutil

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
DATA = os.path.join(HERE, "data")
# data/<kind>.<name> lands in <root>/<directory>/<name>
_DIRS = {"config": "configs", "traffic": "traffic", "cell": "cells",
         "metric": "metrics"}


def build(tmp: str):
    """-> (root, manifest path)."""
    root = os.path.join(tmp, "benchmarks")
    for d in ("configs", "traffic", "cells", "metrics", "kinds"):
        shutil.copytree(os.path.join(REPO, "benchmarks", d),
                        os.path.join(root, d))
    shutil.copy(os.path.join(REPO, "benchmarks", "peaks.json"), root)
    before = {d: set(os.listdir(os.path.join(root, d)))
              for d in _DIRS.values()}
    for f in sorted(os.listdir(DATA)):
        kind, name = f.split(".", 1)
        assert name not in before[_DIRS[kind]], f"{f} would replace a file"
        shutil.copy(os.path.join(DATA, f),
                    os.path.join(root, _DIRS[kind], name))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    for f in sorted(os.listdir(os.path.join(DATA))):
        if f.startswith("cell."):
            name = f[len("cell."):-len(".json")]
            with open(os.path.join(DATA, f)) as g:
                cell = json.load(g)
            manifest["workloads"].append(
                {"name": name, "config": cell["config"],
                 "traffic": cell["traffic"], "chips": cell["chips"],
                 "why": cell["why"]})
    path = os.path.join(tmp, "BENCHMARK.json")
    with open(path, "w") as f:
        json.dump(manifest, f)
    return root, path
