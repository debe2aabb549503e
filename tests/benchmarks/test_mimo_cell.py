"""A family with two kinds of layer, a cache of its own kind of block for
each, and dropless experts of which a share is held (MiMo-V2-Flash), added
to the benchmark as files alone: served and traced at its tiny preset on the
CPU through the harness's own `run_cell`, its counters read on the CPU as on
the chip, its arithmetic against sets counted by hand, and the readings
beside the cell's tolerance (scripts/mimo_control.py) made through the
harness's own comparison.

No assertion is on a time: a CPU run says nothing about speed.
"""

import importlib.util
import json
import os
import sys

import jax.numpy as jnp
import pytest

from benchmarks import harness
from test_harness_data import _check_line, _run, tiny  # noqa: F401

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "tiny-mimo.tiny-chat"


@pytest.fixture(scope="module")
def control():
    sys.path.insert(0, os.path.join(REPO, "scripts"))
    spec = importlib.util.spec_from_file_location(
        "mimo_control", os.path.join(REPO, "scripts", "mimo_control.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_the_family_is_served_and_traced_and_its_counters_are_read(tiny):
    """`mimo-tiny` against benchmarks/reference/mimo.py (the cell's own
    reference, float32 on both sides), prompts of the check past the
    window.  Traced: the program's counters are read on the CPU as on the
    chip; the readers of device time find no TPU plane, return nothing and
    are left out of the line."""
    result = _run(tiny, CELL, trace=False, seconds=1.0)
    _check_line(result, traced=False)
    assert {"tpot_p95_ms", "setup_s"} == set(result["metrics"])
    assert result["attempted"] == 20
    assert result["checks"]["logit_gap_max"][0] < 1e-5
    traced = _run(tiny, CELL, trace=True, seconds=1.0)
    _check_line(traced, traced=True)
    got = traced["metrics"]
    assert {"tick_ms_p50", "occupancy_mean", "cache_mib_per_slot",
            "moe_tokens_per_expert"} <= set(got)
    # a live slot holds 1-4 global blocks of 16 rows x 2 layers x 1 head x
    # (24 + 16) numbers and the ring's one of 16 x 5 x 2 x 40, float32
    table, ring = 16 * 2 * 40 * 4 / 2**20, 16 * 5 * 2 * 40 * 4 / 2**20
    assert ring + table <= got["cache_mib_per_slot"]["value"] \
        <= ring + 4 * table, got["cache_mib_per_slot"]
    assert got["cache_mib_per_slot"]["unit"] == "MiB"
    # every expert is held at this size: 4 choices a live slot in each
    # layer over 16 experts, at most 8 slots
    assert 0.0 < got["moe_tokens_per_expert"]["value"] <= 8 * 4 / 16
    assert not {"moe_ms", "moe_roofline", "prefill_moe_ms", "tds_paged_attn_ms",
                "tds_paged_attn_roofline", "mfu.mimo"} & set(got)


def test_mimo_arithmetic_against_sets_counted_by_hand():
    """benchmarks/mimo_arith.py at the published widths: parameters by
    layer, what a query sees by kind, the bytes of a row and a block."""
    from benchmarks import mimo_arith as ma
    from tiny_deepspeed_tpu.models import ALL_PRESETS
    cfg = ALL_PRESETS["mimo-v2-flash-7l"]
    assert ma.layers(cfg) == {"global": 2, "window": 5, "dense": 1, "moe": 6}
    attn_g = 4096 * (64 * 192 + 4 * 320) + 64 * 128 * 4096
    attn_w = 4096 * (64 * 192 + 8 * 320) + 64 * 128 * 4096
    assert (attn_g, attn_w) == (89_128_960, 94_371_840)
    assert ma.expert_params(cfg) == 3 * 4096 * 2048 == 25_165_824
    assert ma.dense_params(cfg) == (
        2 * attn_g + 5 * attn_w + 3 * 4096 * 16384 + 6 * 4096 * 256
        + 4096 * 19072)
    # the whole share: 3,430 M parameters = 6.39 GiB in bf16 (norms,
    # sinks and biases apart)
    whole = ma.dense_params(cfg) + 96 * ma.expert_params(cfg) \
        + 4096 * 19072
    assert 3.42e9 < whole < 3.44e9
    # a row of each kind: 2 x 4 x 320 and 5 x 8 x 320 numbers in bf16
    assert (ma.row_bytes(0, cfg), ma.row_bytes(1, cfg)) == (5120, 25600)
    assert ma.block_mib(0, 16, cfg) * 2**20 == 80 * 1024
    assert ma.block_mib(1, 16, cfg) * 2**20 == 400 * 1024
    # what the positions of a prompt see, one position at a time
    for p in (1, 127, 128, 129, 1000):
        seen_g = sum(n + 1 for n in range(p))
        seen_w = sum(len([m for m in range(n + 1) if m > n - 128])
                     for n in range(p))
        assert ma.prefill_flops(p, cfg) == pytest.approx(
            2.0 * ma.dense_params(cfg) * p
            + 2.0 * ma.expert_params(cfg) * p * 8 * 16 / 256 * 6
            + ma.attention_flops(seen_g, seen_w, cfg))
    assert ma.attention_flops(10, 20, cfg) == 2 * 64 * 320 * (10 * 2 + 20 * 5)
    # a decode tick of 48 slots at 3,500 positions reads 0.86 GB of
    # global rows and 0.16 GB of ring rows; 12.4 experts a layer 1.9 GB
    assert ma.attention_bytes(48 * 3500, 48 * 127, cfg) == pytest.approx(
        48 * 3500 * 5120 + 48 * 127 * 25600)
    assert 0.85e9 < 48 * 3500 * 5120 < 0.87e9
    assert ma.experts_bytes(6 * 12.4, cfg) == pytest.approx(
        6 * 12.4 * 25_165_824 * 2)
    assert ma.decode_flops(48, 140, 48 * 3500, 48 * 127, cfg) == \
        pytest.approx(2.0 * ma.dense_params(cfg) * 48
                      + 2.0 * 25_165_824 * 140
                      + ma.attention_flops(48 * 3501, 48 * 128, cfg))
    assert ma.expected_pairs(64, cfg) == 64 * 8 / 16 * 6


def test_the_controls_readings_through_the_harness_own_comparison(
        tiny, control):
    """The reference itself in bfloat16 in the program's place comes out
    not correct by the gap alone, in float32 it is the comparison's zero,
    and each planted fault is refused; the served path reads as `run_cell`
    reads it, with no routing choice apart (float32 on both sides)."""
    root, _ = tiny
    cell = harness.load_cell(CELL, root)
    low, rms = control.reference_in_place(cell, 5, root, jnp.bfloat16)
    assert not harness.within(low) and rms > 5e-4
    gap, limit = low.pop("logit_gap_max")
    assert gap > limit and harness.within(low)
    same, rms = control.reference_in_place(cell, 5, root, jnp.float32)
    assert same["logit_gap_max"][0] == 0.0 == rms and harness.within(same)
    for fault in cell.reference().FAULTS:
        wrong, _ = control.reference_in_place(cell, 5, root, jnp.float32,
                                              fault)
        # the selection bias is N(0, 0.01) at init: in the gate it moves
        # the logits by half the tiny cell's limit, still thousands of
        # times the served path's 1.5e-7 (tests/test_mimo.py makes the
        # bias larger and holds the fault to the others' floor)
        floor = 3e-4 if fault == "bias_in_gate" else limit
        assert wrong["logit_gap_max"][0] > floor, fault
    checks, rms, reqs, params = control.served(cell, 5, root)
    assert harness.within(checks) and rms < 1e-5
    compared, differ, held = control.Routes(cell).differences(reqs, params)
    assert compared == 3 * 6 * 4 and differ == 0 == held


def test_the_cell_runs_by_hand_at_another_rate(tiny, control):
    """`mimo_control.py run` (the knee's sweep): a cell through `run_cell`
    at the mix's rate and at another, with the growth of the queue beside
    the result line; also where the manifest does not list the cell yet
    (its file alone gives the `workloads` entry)."""
    root, path = tiny
    with open(path) as f:
        manifest = json.load(f)
    manifest["workloads"] = [w for w in manifest["workloads"]
                             if w["name"] != CELL]
    unlisted = os.path.join(root, "unlisted.json")
    with open(unlisted, "w") as f:
        json.dump(manifest, f)
    with pytest.raises(SystemExit, match="no workload"):
        harness.run_cell(CELL, 7, 1.0, False, root=root, manifest=unlisted)
    for rate, measured in ((None, 20), (8.0, 8)):
        result, grew = control.run_at_rate(CELL, root, unlisted, 2**31 + 5,
                                           1.0, False, rate)
        _check_line(result, traced=False)
        assert result["attempted"] == measured == grew["measured"]
        assert grew["rate"] == rate and 0 < grew["occupancy_mean"] <= 100
        assert {"queue_growth_ms", "queue_first_third_ms",
                "queue_last_third_ms", "tick_ms_p50", "plain_tick_ms_p50",
                "experts_touched_per_tick", "admission_s"} <= set(grew)
        # every expert is held at this size: 4 choices a slot and layer
        assert grew["pairs_per_slot_layer"] == 4.0
        assert grew["admissions"] >= measured
    # and the harness is left as it was found
    assert harness.load_cell.__name__ == "load_cell"
    assert harness.load_kind(root, "serve").run.__name__ == "run"


@pytest.mark.parametrize("rate", [None, 1.75])
def test_the_schedule_replayed_against_a_clock_of_chip_readings(control,
                                                                rate):
    """`mimo_control.py replay`: the benchmark's own cell, its mix's
    schedule at two seeds against `TICK_MS` and `PREFILL_MS`, with no
    engine.  Both seeds hold the same multiset (rate x 40 s requests), a
    seed reads the same number twice, another local order reads another,
    and no request's time per token lies under a tick of its clock."""
    cell = harness.load_cell("mimo-v2-flash.reason-open")
    args = (cell.mix, 40.0, cell.config["vocab_size"],
            int(cell.sizes["slots"]), rate)

    def read(seed):
        return control.replay(args[0], seed, *args[1:])

    (a, n), (b, m) = read(2**31 + 11), read(2**31 + 12)
    want = round((rate or cell.mix["arrival"]["rate_rps"]) * 40)
    assert n == m == want
    assert read(2**31 + 11) == (a, n) and a != b
    floor = control.TICK_MS[0][1]
    assert floor < min(a, b) and max(a, b) < 3 * control.TICK_MS[-1][1]
    assert abs(a - b) / a < 0.2
