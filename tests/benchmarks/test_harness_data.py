"""The harness is driven by data: a config, a mix, a cell and a metric file
added beside the existing ones, plus one `workloads` entry, are found by
name and run -- here on the CPU at a tiny size, through the harness's
Python entry.  And BENCHMARK.json itself keeps the contract's form.

No assertion is on a time: a CPU run says nothing about speed.
"""

import importlib.util
import json
import os
import re

import pytest

import tinyroot
from benchmarks import harness

REPO = tinyroot.REPO
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def manifest():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    return tinyroot.build(str(tmp_path_factory.mktemp("bench")))


def _run(tiny, workload, trace, seconds=0.6):
    root, manifest = tiny
    return harness.run_cell(workload, seed=2**31 + 11, seconds=seconds,
                            trace=trace, root=root, manifest=manifest)


def _check_line(result, traced):
    keys = {"correct", "attempted", "failed", "metrics", "device"}
    assert set(result) - {"breakdown"} == keys
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(
        result["device"])
    for m in result["metrics"].values():
        assert set(m) == {"value", "unit"} and UNIT.match(m["unit"])
    json.dumps(result, allow_nan=False)
    assert ("setup_s" in result["metrics"]) == (not traced)


def test_added_train_cell_runs_end_to_end(tiny):
    result = _run(tiny, "tiny.tiny-train", trace=False)
    _check_line(result, traced=False)
    assert {"tokens_per_s_chip", "peak_hbm_gib", "setup_s"} == set(
        result["metrics"])


def test_added_metric_is_found_by_name_in_the_traced_run(tiny):
    result = _run(tiny, "tiny.tiny-train", trace=True)
    _check_line(result, traced=True)
    got = result["metrics"]
    # the metric this test ADDED (data/metric.steps_done.py) is read ...
    assert got["steps_done"]["value"] >= 1 and \
        got["steps_done"]["unit"] == "count"
    # ... host-side readers report, and readers of a device trace find no
    # TPU plane on the CPU, return nothing and are left out of the line
    assert {"compile_s", "cache_misses", "input_wait_ms", "step_ms_p50",
            "hbm_resting_gib"} <= set(got)
    assert not {"idle_share.train", "attn_roofline", "vocab_head_ms",
                "mfu"} & set(got)
    assert "busy_s" not in result["device"]


def test_added_serve_cell_runs_end_to_end(tiny):
    result = _run(tiny, "tiny.tiny-chat", trace=False, seconds=1.0)
    _check_line(result, traced=False)
    assert {"tpot_p95_ms", "setup_s"} == set(result["metrics"])
    # the measured set is fixed by the mix: round(rate x window) requests
    assert result["attempted"] == 20


def test_added_serve_cell_traced(tiny):
    result = _run(tiny, "tiny.tiny-chat", trace=True, seconds=1.0)
    _check_line(result, traced=True)
    assert {"tick_ms_p50", "queue_ms_p95", "occupancy_mean",
            "serve.ttft_p95_ms", "serve.itl_p95_ms",
            "gen_late_ms_p95"} <= set(result["metrics"])


def test_added_four_chip_cell_runs_on_virtual_devices(tiny):
    result = _run(tiny, "tiny.tiny-zero3", trace=True)
    _check_line(result, traced=True)
    # the collective ledger of the compiled sharded step: a count
    assert result["metrics"]["coll_wire_mib"]["value"] > 0


def test_a_cell_may_not_change_a_width(tiny):
    root, _ = tiny
    cell = harness.load_cell("tiny.tiny-train", root)
    cell.sizes = {"model": {"n_layer": 1}}
    with pytest.raises(ValueError, match="may not set"):
        cell.model_config()


def test_config_file_must_agree_with_its_preset(tiny):
    root, _ = tiny
    cell = harness.load_cell("tiny.tiny-train", root)
    cell.config = dict(cell.config, n_embd=128)
    with pytest.raises(ValueError, match="n_embd"):
        cell.model_config()


def test_unknown_device_has_no_peak():
    assert harness.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(SystemExit, match="peaks.json"):
        harness.peaks_for("TPU v9 imaginary")


# -- BENCHMARK.json itself ---------------------------------------------------

def test_manifest_keys_names_and_units(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert manifest["paths"] == ["benchmarks", "tests/benchmarks"]
    assert 1 <= manifest["run_seconds"] <= 51
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in manifest[group]:
            assert NAME.match(e["name"]), e["name"]
            names.append((group in ("end_to_end", "per_layer"), e["name"]))
    assert len(names) == len(set(names)), "a name appears twice"
    for w in manifest["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in manifest["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in manifest["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
    assert any(m["name"] == "setup_s" and "workloads" not in m
               for m in manifest["end_to_end"])


def test_every_file_a_cell_names_exists_and_agrees(manifest):
    configs = {c["name"]: c for c in manifest["configs"]}
    pairs = set()
    for w in manifest["workloads"]:
        cell = harness.load_cell(w["name"])  # reads all three files
        assert (cell.chips, cell.mix["kind"]) == (
            w["chips"], cell.mix["kind"]) and w["chips"] in (1, 4)
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        c = configs[w["config"]]
        assert os.path.join(REPO, c["file"]) == os.path.join(
            REPO, "benchmarks", "configs", w["config"] + ".json")
        assert cell.config["source"] == c["source"]
        assert cell.config["reduced"] == c["reduced"] == []
        assert os.path.exists(os.path.join(
            REPO, "benchmarks", "kinds", cell.kind + ".py"))
        for name in cell.per_layer:
            assert os.path.exists(os.path.join(
                REPO, "benchmarks", "metrics", name + ".py")), name
    used = {w["config"] for w in manifest["workloads"]}
    assert used == set(configs), "a configuration no cell uses"
    four = sum(w["chips"] == 4 for w in manifest["workloads"])
    assert four <= max(1, len(manifest["workloads"]) // 4)


def _cells_of(metric, manifest):
    return set(metric.get("workloads")
               or [w["name"] for w in manifest["workloads"]])


def test_per_layer_metrics_agree_with_their_readers_and_cells(manifest):
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    reporting = {}
    for m in manifest["per_layer"]:
        spec = importlib.util.spec_from_file_location(
            "reader", os.path.join(REPO, "benchmarks", "metrics",
                                   m["name"] + ".py"))
        reader = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(reader)
        assert (reader.UNIT, reader.BETTER, reader.LAYER, reader.MOVES,
                reader.SOURCE) == (m["unit"], m["better"], m["layer"],
                                   m["moves"], m["source"]), m["name"]
        # `moves` is an end-to-end metric that every cell reporting this
        # metric also reports
        assert m["moves"] in e2e, m
        assert _cells_of(m, manifest) <= _cells_of(e2e[m["moves"]], manifest)
        for cell in _cells_of(m, manifest):
            reporting.setdefault(cell, set()).add(m["name"])
    # BENCHMARK.json and each cell's own list say the same
    for w in manifest["workloads"]:
        assert reporting[w["name"]] == set(
            harness.load_cell(w["name"]).per_layer), w["name"]
        others = [m for m in manifest["end_to_end"] if m["name"] != "setup_s"
                  and w["name"] in _cells_of(m, manifest)]
        assert others, f"{w['name']} reports no end-to-end metric"


def test_roofline_and_mfu_names_follow_the_contract(manifest):
    for m in manifest["per_layer"]:
        if "roofline" in m["name"] or "mfu" in m["name"]:
            assert m["unit"] == "%" and m["better"] == "higher"


def test_benchmark_imports_nothing_from_the_old_measuring_code():
    bad = re.compile(r"^\s*(from|import)\s+(bench|chip_smoke|scripts|"
                     r"examples)\b", re.M)
    for top, _, files in os.walk(os.path.join(REPO, "benchmarks")):
        for f in files:
            if f.endswith(".py"):
                with open(os.path.join(top, f)) as g:
                    assert not bad.search(g.read()), os.path.join(top, f)
