# Copyright 2026 tiny-deepspeed-tpu authors
# SPDX-License-Identifier: Apache-2.0

"""HLO cost ledger (utils/hlo_cost.py) + perf_diff sentinel.

Three layers of pins:
  * exact dot/fusion FLOP arithmetic and while-trip multiplication on
    tiny SYNTHETIC HLO text (no compile, no jax numerics);
  * the 124M GPT-2 train step's HLO-counted matmul FLOPs within 2% of
    the analytic matmul formula — the "measured ground truth
    agrees with the honest hand formula" acceptance — and the MoE
    dispatch/combine undercount first DEMONSTRATED (counted >> the old
    formula) then CORRECTED (counted ~= formula + the new
    `dispatch_combine_flops_per_token` term);
  * scripts/perf_diff.py verdicts via its real CLI: injected 10%
    regression exits nonzero naming metric + fingerprint, identical
    rounds exit 0, modeled-vs-measured MFU drift exits nonzero.
"""

import dataclasses
import json
import math
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from tiny_deepspeed_tpu.utils.hlo_cost import (
    cost_ledger,
    cost_summary,
    hbm_bw_per_chip,
    peak_flops_per_chip,
    roofline_verdict,
    wire_bw_per_chip,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PERF_DIFF = os.path.join(REPO, "scripts", "perf_diff.py")


# ---------------------------------------------------------------------------
# synthetic HLO: exact arithmetic
# ---------------------------------------------------------------------------

SYN_DOT = """
HloModule syn
ENTRY %main (p0: f32[4,5]) -> f32[4,6] {
  %p0 = f32[4,5] parameter(0)
  %w = f32[5,6] parameter(1)
  ROOT %d = f32[4,6] dot(f32[4,5] %p0, f32[5,6] %w), lhs_contracting_dims={1}, rhs_contracting_dims={0}
}
"""

# the installed XLA's spelling: operands by NAME, shapes only on the
# defining instructions (layouts included)
SYN_DOT_NAMED = """
HloModule syn
ENTRY %main.1 (p0.1: f32[4,5], w.1: f32[5,6]) -> f32[4,6] {
  %p0.1 = f32[4,5]{1,0} parameter(0), metadata={op_name="p0"}
  %w.1 = f32[5,6]{1,0} parameter(1), metadata={op_name="w"}
  ROOT %d.3 = f32[4,6]{1,0} dot(%p0.1, %w.1), lhs_contracting_dims={1}, rhs_contracting_dims={0}, metadata={op_name="jit(f)/dot_general"}
}
"""

SYN_DOT_UNRESOLVED = """
HloModule syn
ENTRY %main.1 (p0.1: f32[4,5]) -> f32[4,6] {
  %p0.1 = f32[4,5]{1,0} parameter(0)
  ROOT %d.3 = f32[4,6]{1,0} dot(%p0.1, %gone.7), lhs_contracting_dims={1}, rhs_contracting_dims={0}
}
"""

SYN_BATCHED = """
HloModule syn
ENTRY %main (p0: f32[2,4,5]) -> f32[2,4,6] {
  %p0 = f32[2,4,5] parameter(0)
  %w = f32[2,5,6] parameter(1)
  ROOT %d = f32[2,4,6] dot(f32[2,4,5] %p0, f32[2,5,6] %w), lhs_batch_dims={0}, rhs_batch_dims={0}, lhs_contracting_dims={2}, rhs_contracting_dims={1}
}
"""

SYN_FUSION = """
HloModule syn
%fused_computation.1 (fp: f32[4,5]) -> f32[4,6] {
  %fp = f32[4,5] parameter(0)
  %fw = f32[5,6] constant({...})
  %big = f32[1000,1000] broadcast(%fp), dimensions={}
  ROOT %fd = f32[4,6] dot(f32[4,5] %fp, f32[5,6] %fw), lhs_contracting_dims={1}, rhs_contracting_dims={0}
}
ENTRY %main (p0: f32[4,5]) -> f32[4,6] {
  %p0 = f32[4,5] parameter(0)
  ROOT %f = f32[4,6] fusion(f32[4,5] %p0), kind=kOutput, calls=%fused_computation.1
}
"""

SYN_LOOP = """
HloModule syn
%cond (cp: (s32[], f32[4,5])) -> pred[] {
  %cp = (s32[], f32[4,5]) parameter(0)
  %iv = s32[] get-tuple-element(%cp), index=0
  %bound = s32[] constant(3)
  ROOT %lt = pred[] compare(s32[] %iv, s32[] %bound), direction=LT
}
%body (bp: (s32[], f32[4,5])) -> (s32[], f32[4,5]) {
  %bp = (s32[], f32[4,5]) parameter(0)
  %x = f32[4,5] get-tuple-element(%bp), index=1
  %w = f32[5,5] constant({...})
  %d = f32[4,5] dot(f32[4,5] %x, f32[5,5] %w), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %i = s32[] get-tuple-element(%bp), index=0
  %one = s32[] constant(1)
  %i2 = s32[] add(s32[] %i, s32[] %one)
  ROOT %t = (s32[], f32[4,5]) tuple(s32[] %i2, f32[4,5] %d)
}
ENTRY %main (p0: f32[4,5]) -> f32[4,5] {
  %p0 = f32[4,5] parameter(0)
  %iv0 = s32[] constant(0)
  %init = (s32[], f32[4,5]) tuple(s32[] %iv0, f32[4,5] %p0)
  %wh = (s32[], f32[4,5]) while(%init), condition=%cond, body=%body
  %out = f32[4,5] get-tuple-element(%wh), index=1
  %wt = f32[5,6] parameter(1)
  ROOT %top = f32[4,6] dot(f32[4,5] %out, f32[5,6] %wt), lhs_contracting_dims={1}, rhs_contracting_dims={0}
}
"""

SYN_DUS = """
HloModule syn
ENTRY %main (p0: f32[100,10]) -> f32[100,10] {
  %p0 = f32[100,10] parameter(0)
  %upd = f32[1,10] parameter(1)
  %i = s32[] parameter(2)
  ROOT %dus = f32[100,10] dynamic-update-slice(f32[100,10] %p0, f32[1,10] %upd, s32[] %i, s32[] %i)
}
"""


class TestDotFlops:
    def test_plain_dot_exact(self):
        led = cost_ledger(SYN_DOT)
        # 2 * (4*6 result) * (5 contracting) = 240
        assert led["total_flops"] == 240.0
        assert led["flops"] == {"dot": 240.0}
        assert led["count"] == {"dot": 1.0}
        assert led["flops_in_loops"] == 0.0
        (c,) = led["cost_centers"]
        assert c["flops"] == 240.0 and not c["in_loop"]
        assert "f32[4,6]" in c["sig"]

    def test_operands_by_name_resolve_to_defining_shapes(self):
        led = cost_ledger(SYN_DOT_NAMED)
        assert led["total_flops"] == 240.0
        (c,) = led["cost_centers"]
        assert c["sig"] == "dot f32[4,6] <- f32[4,5] x f32[5,6]"
        # HBM: operands (80 + 120 B) + result (96 B), shapes taken from
        # the parameter definitions
        assert led["hbm_bytes"] == pytest.approx(4 * (20 + 30 + 24))

    def test_unresolvable_dot_operand_raises(self):
        """A dot whose operand shape cannot be found must not count 0
        FLOPs in silence (every hlo_flops / mfu gauge read 0 that way)."""
        with pytest.raises(ValueError, match="gone.7"):
            cost_ledger(SYN_DOT_UNRESOLVED)

    def test_batched_dot_exact(self):
        led = cost_ledger(SYN_BATCHED)
        # 2 * (2*4*6 result) * (5 contracting) = 480 — batch dims ride
        # the result product, the contracting product excludes them
        assert led["total_flops"] == 480.0

    def test_dot_inside_fusion_payload_counted(self):
        led = cost_ledger(SYN_FUSION)
        assert led["total_flops"] == 240.0
        # HBM: the fusion LINE (operands + result = 80 + 96 bytes), not
        # the payload's internals — the f32[1000,1000] intermediate
        # (4 MB) lives in registers/VMEM and must not be charged
        assert led["hbm_bytes"] == pytest.approx(4 * (4 * 5 + 4 * 6))
        assert led["hbm_bytes"] < 1e5

    def test_trip_count_multiplies_loop_flops(self):
        led = cost_ledger(SYN_LOOP)
        # body dot: 2*(4*5)*5 = 200, x3 trips; top-level dot: 240
        assert led["flops_in_loops"] == 600.0
        assert led["total_flops"] == 840.0
        (loop,) = led["loops"]
        assert loop["trips"] == 3 and loop["resolved"]
        assert loop["flops"] == 600.0
        assert led["unresolved_loops"] == []
        # the in-loop dot's cost center is flagged loop-resident
        sigs = {c["sig"]: c for c in led["cost_centers"]}
        in_loop = [c for c in sigs.values() if c["in_loop"]]
        assert len(in_loop) == 1 and in_loop[0]["flops"] == 600.0
        assert in_loop[0]["count"] == 3.0

    def test_dynamic_update_slice_counts_slice_not_accumulator(self):
        led = cost_ledger(SYN_DUS)
        # read update (40 B) + 2 s32 indices (8 B) + write update
        # (40 B); the aliased 4000 B destination is NOT charged
        # (in-place slice update)
        assert led["hbm_bytes"] == pytest.approx(88.0)


class TestRoofline:
    def test_bound_classification(self):
        # times: compute = flops/peak, hbm = bytes/bw, wire = bytes/bw —
        # synthetic ledgers pin each verdict
        v = roofline_verdict(1e15, 1e6, 1e3, device_kind="v5e")
        assert v["bound"] == "compute"
        v = roofline_verdict(1e9, 1e12, 1e3, device_kind="v5e")
        assert v["bound"] == "hbm"
        v = roofline_verdict(1e9, 1e6, 1e12, device_kind="v5e")
        assert v["bound"] == "wire"

    def test_arithmetic_intensity_and_ridge(self):
        v = roofline_verdict(2e12, 1e9, 0.0, device_kind="v5e")
        assert v["arithmetic_intensity"] == pytest.approx(2000.0)
        assert v["ridge_intensity"] == pytest.approx(197e12 / 819e9)

    def test_device_tables(self):
        assert peak_flops_per_chip("TPU v5e") == 197e12
        # the string a v5e chip reports as device_kind
        assert peak_flops_per_chip("TPU v5 lite") == 197e12
        assert peak_flops_per_chip("TPU v5p") == 459e12
        assert hbm_bw_per_chip("TPU v4") == 1228e9
        assert wire_bw_per_chip("TPU v6 lite") == 448e9

    def test_unknown_device_has_no_peak(self):
        """The CPU mesh (or any device missing from the table) is never
        priced at some chip's peaks: no peak, no roofline, counts only."""
        for kind in (None, "", "cpu", "TPU v9"):
            assert peak_flops_per_chip(kind) is None
            assert hbm_bw_per_chip(kind) is None
            assert wire_bw_per_chip(kind) is None
        with pytest.raises(ValueError, match="cpu"):
            roofline_verdict(1e12, 1e9, 0.0, device_kind="cpu")
        s = cost_summary(cost_ledger(SYN_LOOP), device_kind="cpu",
                         wire_bytes=123.0)
        assert s["total_flops"] == 840.0 and s["wire_bytes"] == 123.0
        for k in ("bound", "ridge_intensity", "t_compute_s", "t_hbm_s",
                  "t_wire_s"):
            assert k not in s
        json.dumps(s)

    def test_cost_summary_shape(self):
        led = cost_ledger(SYN_LOOP)
        s = cost_summary(led, device_kind="TPU v5 lite", wire_bytes=123.0)
        assert s["bound"] in ("compute", "hbm", "wire")
        assert s["total_flops"] == 840.0
        assert s["wire_bytes"] == 123.0
        assert len(s["top_cost_centers"]) <= 3
        assert s["top_cost_centers"][0]["share"] <= 1.0
        json.dumps(s)  # JSON-safe by construction


# ---------------------------------------------------------------------------
# compiled-program pins (abstract state: eval_shape, no real buffers)
# ---------------------------------------------------------------------------

def _compiled_text(model_name: str, b=1, t=1024):
    from tiny_deepspeed_tpu import AdamW, SingleDevice
    from tiny_deepspeed_tpu.models import ALL_PRESETS
    from tiny_deepspeed_tpu.models.gpt2 import GPT2Model
    from tiny_deepspeed_tpu.models.moe import MoEConfig, MoEGPT

    cfg = dataclasses.replace(ALL_PRESETS[model_name], remat=False)
    model = MoEGPT(cfg) if isinstance(cfg, MoEConfig) else GPT2Model(cfg)
    eng = SingleDevice(model, AdamW(lr=1e-3))
    abstate = jax.eval_shape(eng.init, jax.random.PRNGKey(0))
    idx = jax.ShapeDtypeStruct((b, t), jnp.int32)
    text = eng._step.lower(abstate, (idx, idx)).compile().as_text()
    return cfg, model, text


class TestPinned124M:
    def test_hlo_counted_within_2pct_of_bench_formula(self):
        """The acceptance pin: the analytic matmul formula for
        the 124M GPT-2 train step (b=1, t=1024, remat off) agrees with
        the FLOPs counted from the compiled program within 2%."""
        b, t = 1, 1024
        cfg, model, text = _compiled_text("gpt2-124m", b=b, t=t)
        led = cost_ledger(text)
        n_params = model.num_params()
        embed = cfg.vocab_size * cfg.n_embd + cfg.block_size * cfg.n_embd
        analytic_tok = (6 * (n_params - embed)
                        + 12 * cfg.n_layer * t * cfg.n_embd)
        analytic_step = analytic_tok * b * t
        assert led["total_flops"] == pytest.approx(analytic_step,
                                                   rel=0.02)
        # per-layer attribution rides the scan: a 12-trip loop carries
        # the layer compute (in-loop trip multiplication vs scan length)
        scan_loops = [lo for lo in led["loops"]
                      if lo["trips"] == cfg.n_layer and lo["flops"] > 0]
        assert scan_loops, led["loops"]
        assert led["flops_in_loops"] > 0.5 * led["total_flops"]
        assert led["unresolved_loops"] == []


class TestPinnedMoE:
    def test_dispatch_undercount_demonstrated_then_corrected(self):
        """models/moe.py:52's admission, quantified: the old analytic
        formula (active expert params only) undercounts the compiled
        moe-8x124m step by the dispatch/combine einsum FLOPs; adding
        `dispatch_combine_flops_per_token` closes it to within 2%."""
        from tiny_deepspeed_tpu.models.moe import (
            dispatch_combine_flops_per_token,
        )

        b, t = 1, 1024
        cfg, model, text = _compiled_text("moe-8x124m", b=b, t=t)
        led = cost_ledger(text)
        n_params = model.num_params()
        embed = cfg.vocab_size * cfg.n_embd + cfg.block_size * cfg.n_embd
        expert = sum(
            int(math.prod(s.shape))
            for n, s in model.param_shapes().items()
            if ".moe." in n and "router" not in n
        )
        # the OLD accounting: expert params scaled k/E, einsum
        # pair ignored entirely
        old_active = (n_params - expert
                      + expert * cfg.expert_top_k // cfg.n_expert)
        old_tok = (6 * (old_active - embed)
                   + 12 * cfg.n_layer * t * cfg.n_embd)
        # the CORRECTED accounting:
        # capacity-padded expert compute (E*C slot-rows, not k/E) + the
        # dispatch/combine einsum matmuls
        cap = max(1, int(cfg.capacity_factor * cfg.expert_top_k * b * t
                         / cfg.n_expert))
        new_active = n_params - expert + expert * cap // (b * t)
        fix_tok = (6 * (new_active - embed)
                   + 12 * cfg.n_layer * t * cfg.n_embd
                   + dispatch_combine_flops_per_token(cfg, b * t))
        counted = led["total_flops"]
        # demonstrated: the compiled program does >10% more matmul work
        # than the old formula claims (uncounted einsums + the
        # capacity padding)
        assert counted > 1.10 * old_tok * b * t, (
            counted, old_tok * b * t)
        # corrected: the new formula agrees with the counted number
        assert counted == pytest.approx(fix_tok * b * t, rel=0.02)


# ---------------------------------------------------------------------------
# perf_diff sentinel (real CLI: the exit codes ARE the contract)
# ---------------------------------------------------------------------------

def _round(tmp_path, name, value, mm=None, mh=None,
           cached=False, metric="gpt2-124m_train_tokens_per_sec_per_chip"):
    extra = {"chips": 1, "seq_len": 1024}
    if mm is not None:
        extra["matmul_mfu"] = mm
    if mh is not None:
        extra["hlo_cost"] = {"mfu_hlo": mh, "total_flops": 1e12}
    if cached:
        extra["cached_result"] = True
    p = tmp_path / name
    p.write_text(json.dumps({
        "n": 1, "cmd": "bench", "rc": 0, "tail": "",
        "parsed": {"metric": metric, "value": value,
                   "unit": "tokens/s/chip", "extra": extra},
    }))
    return str(p)


def _run(*args):
    return subprocess.run(
        [sys.executable, PERF_DIFF, *args],
        capture_output=True, text=True, timeout=60,
    )


class TestPerfDiff:
    def test_injected_regression_exits_nonzero_naming_fingerprint(
            self, tmp_path):
        r1 = _round(tmp_path, "BENCH_r01.json", 100000.0)
        r2 = _round(tmp_path, "BENCH_r02.json", 90000.0)  # -10%
        r = _run("--check", r1, r2)
        assert r.returncode == 1, r.stdout + r.stderr
        assert "REGRESSION" in r.stdout
        assert "gpt2-124m_train_tokens_per_sec_per_chip" in r.stdout
        assert "chips=1" in r.stdout and "seq_len=1024" in r.stdout

    def test_identical_rounds_exit_zero(self, tmp_path):
        r1 = _round(tmp_path, "BENCH_r01.json", 100000.0)
        r2 = _round(tmp_path, "BENCH_r02.json", 100000.0)
        r = _run("--check", r1, r2)
        assert r.returncode == 0, r.stdout + r.stderr
        assert "OK" in r.stdout

    def test_delta_inside_noise_spread_not_flagged(self, tmp_path):
        # prior rounds spread 10% -> an 8% drop proves nothing
        r1 = _round(tmp_path, "BENCH_r01.json", 90000.0)
        r2 = _round(tmp_path, "BENCH_r02.json", 100000.0)
        r3 = _round(tmp_path, "BENCH_r03.json", 92000.0)
        r = _run("--check", r1, r2, r3)
        assert r.returncode == 0, r.stdout + r.stderr

    def test_mfu_drift_flagged(self, tmp_path):
        r1 = _round(tmp_path, "BENCH_r01.json", 100000.0,
                    mm=0.50, mh=0.30)
        r = _run("--check", r1)
        assert r.returncode == 1, r.stdout + r.stderr
        assert "DRIFT" in r.stdout and "matmul_mfu" in r.stdout

    def test_mfu_agreement_not_flagged(self, tmp_path):
        r1 = _round(tmp_path, "BENCH_r01.json", 100000.0,
                    mm=0.31, mh=0.30)
        r = _run("--check", r1)
        assert r.returncode == 0, r.stdout + r.stderr

    def test_cached_replays_are_not_fresh(self, tmp_path):
        # BENCH_r04/r05 shape: same value replayed from the last-good
        # cache — must not be diffed (and must not mask a later drop)
        r1 = _round(tmp_path, "BENCH_r01.json", 127603.2, cached=True)
        r2 = _round(tmp_path, "BENCH_r02.json", 127603.2, cached=True)
        r = _run("--check", r1, r2)
        assert r.returncode == 0
        assert "0 fresh" in r.stdout
