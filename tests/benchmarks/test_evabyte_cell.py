"""A family with two kinds of cache a slot (EvaByte: a window ring and
chunk summaries), added to the benchmark as files alone: served and traced
at its tiny preset on the CPU, its arithmetic against counted sets, and the
two readings beside the cell's tolerance (scripts/evabyte_control.py) made
through the harness's own comparison.

No assertion is on a time: a CPU run says nothing about speed.
"""

import importlib.util
import os

import jax.numpy as jnp
import pytest

from benchmarks import harness
from test_harness_data import _check_line, _run, tiny  # noqa: F401

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "tiny-evabyte.tiny-chat"


def _control_script():
    spec = importlib.util.spec_from_file_location(
        "evabyte_control", os.path.join(REPO, "scripts",
                                        "evabyte_control.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_a_family_with_two_kinds_of_cache_is_served_and_traced(tiny):
    """EvaByte at its tiny preset against benchmarks/reference/evabyte.py
    (the cell's own reference, float32 on both sides), one prompt of the
    check past the first window.  Traced: the program's counters are read
    on the CPU as on the chip; the readers of device time find no TPU
    plane, return nothing and are left out of the line."""
    result = _run(tiny, CELL, trace=False, seconds=1.0)
    _check_line(result, traced=False)
    assert {"tpot_p95_ms", "setup_s"} == set(result["metrics"])
    assert result["attempted"] == 20
    assert result["checks"]["logit_gap_max"][0] < 1e-5
    traced = _run(tiny, CELL, trace=True, seconds=1.0)
    _check_line(traced, traced=True)
    got = traced["metrics"]
    assert {"tick_ms_p50", "occupancy_mean", "cache_blocks_per_slot"} <= set(
        got)
    # 2 window blocks of 16 rows, and a summary block per 64 positions
    assert 2.0 <= got["cache_blocks_per_slot"]["value"] <= 4.0
    assert got["cache_blocks_per_slot"]["unit"] == "blocks"
    assert not {"eva_attn_ms", "eva_attn_roofline", "eva_summary_ms",
                "eva_prefill_attn_ms", "mfu.evabyte"} & set(got)


def test_evabyte_arithmetic_counts_what_a_query_sees():
    """benchmarks/evabyte_arith.py against the sets E_n and R_n counted
    one position at a time, and the published per-layer parameters."""
    from benchmarks import evabyte_arith as ea
    from tiny_deepspeed_tpu.models import ALL_PRESETS
    cfg = ALL_PRESETS["evabyte-6.5b-6l"]
    w, c = cfg.window_size, cfg.chunk_size
    assert ea.matmul_params(cfg) == 6 * (
        4 * 4096 ** 2 + 3 * 4096 * 11008) + 4096 * 8 * 320
    for n in (0, 1, w - 1, w, w + 1, 3 * w + 17, 16384):
        e_n = sum(1 for m in range(n + 1) if m >= n // w * w)
        r_n = sum(1 for ch in range(n // c + 1) if (ch + 1) * c <= n // w * w)
        assert ea.attended(n, cfg) == e_n + r_n
    for p in (1, w, w + 5, 3 * w, 5000):
        entries = sum(ea.attended(n, cfg) for n in range(p))
        assert ea.prefill_flops(p, cfg) == pytest.approx(
            2.0 * ea.matmul_params(cfg) * p
            + ea.attention_flops(entries, cfg) + 8.0 * p * 4096 * 6)
    # a decode tick of 16 slots at 9000 bytes each reads 2.1 GB of K and V
    # (808 window rows and 512 summaries a slot, 6 layers of 4096 in bf16)
    rows = 16 * (ea.attended(9000, cfg) - 1)
    assert rows == 16 * (808 + 512)
    assert ea.kernel_bytes(rows, 16, cfg) == pytest.approx(
        2 * rows * 4096 * 6 * 2 + 4 * 16 * 4096 * 6 * 2)
    assert 2.0e9 < ea.kernel_bytes(rows, 16, cfg) < 2.2e9


def test_the_reference_in_bfloat16_served_in_the_programs_place_is_refused(
        tiny):
    """The control a tolerance is set against, through `kinds/serve._check`
    and `harness.within`: the reference itself in bfloat16 comes out not
    correct by the gap alone; in float32 it is the comparison's zero."""
    root, _ = tiny
    script, cell = _control_script(), harness.load_cell(CELL, root)
    low = script.control(cell, 5, root, jnp.bfloat16, say=lambda msg: None)
    assert not harness.within(low)
    gap, limit = low.pop("logit_gap_max")
    assert gap > limit and harness.within(low)
    same = script.control(cell, 5, root, jnp.float32, say=lambda msg: None)
    assert same["logit_gap_max"][0] == 0.0 and harness.within(same)


def test_decode_steps_across_a_window_boundary_agree_with_the_reference(
        tiny):
    """What the cell's one checked step leaves out: summary rows written
    by decode and ring rows written again after the roll, attended by the
    steps that follow (the chip run reads the same at published widths)."""
    root, _ = tiny
    cell = harness.load_cell(CELL, root)
    rows, rolled = _control_script().decode_gaps(
        cell.model_config(param_dtype="float32"), cell.reference(), 5, 26,
        int(cell.sizes["slots"]), int(cell.mix["block_tokens"]))
    assert rolled == 2 and [r["prompt"] for r in rows] == [24, 88]
    for r in rows:
        assert r["steps_from_roll"] == 18
        assert max(r["gap_max_before_roll"], r["gap_max_from_roll"]) < 1e-5
