# Copyright 2026 tiny-deepspeed-tpu authors
# SPDX-License-Identifier: Apache-2.0

"""Raw-speed kernels + the end-to-end autotuner (ISSUE 14).

Acceptance pins:
  * the Pallas paged-attention kernel (ops/paged_attn_pallas.py, run in
    interpret mode on the CPU CI mesh) matches the XLA reference —
    `paged_panel` + `_span_attention` — to float
    tolerance on random pool contents, GQA and quantized pools
    included, and is greedy TOKEN-IDENTICAL through a real
    ServingEngine staggered-admission trace (plain decode AND the
    spec-verify span variant);
  * kernel-off paths stay byte-identical: `paged_kernel="off"` lowers
    the same HLO as the default CPU path, and the fp8 matmul mode
    "off" leaves `linear_forward`'s lowering untouched;
  * fp8 matmuls (ops/matmul_fp8.py): e4m3 numerics within quantization
    tolerance, delayed-scaling history semantics, candidate-list
    gating, and the 20-step training loss parity (<5%) the gather_quant
    precedent set (slow tier);
  * tune_e2e: coordinate-descent mechanics (bool-vs-int knob identity,
    failure tolerance, objective direction) and plan persistence
    through the AOT cache's v2 envelope (legacy flat files still load);
  * autotuner diagnostics land in the Telemetry registry / MetricsLogger
    (run_meta records, candidate-failure counter+gauge) instead of
    bare prints;
  * scripts/tier1_times.py --budget output stays asserted (the CI gate
    this suite's own additions are budgeted against).

Budget note: tier-1 headroom is under a minute on the 2-vCPU box, so
every multi-engine trace here is slow-marked from the start; the quick
tier keeps one numeric-parity pin and one wiring pin per kernel.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tiny_deepspeed_tpu.ops.paged_attn_pallas as PAP
from tiny_deepspeed_tpu import GPTConfig, GPT2Model
from tiny_deepspeed_tpu.ops import matmul_fp8 as MF
from tiny_deepspeed_tpu.serving.pool import (
    PagedKVPool, page_ref, paged_panel,
)

CFG = dict(block_size=64, vocab_size=128, n_layer=2, n_head=2,
           n_embd=32, compute_dtype=jnp.float32)


@pytest.fixture(autouse=True)
def interpret_mode(monkeypatch):
    monkeypatch.setattr(PAP, "INTERPRET", True)


@pytest.fixture(scope="module")
def model():
    return GPT2Model(GPTConfig(**CFG))


@pytest.fixture(scope="module")
def params(model):
    return model.init(jax.random.PRNGKey(0))


def _pool_view(quant, kvh=2, dh=16, L=2, bt=8, blocks=16):
    """A pool whose blocks hold random content (quantized through the
    real codec when quant is set)."""
    pool = PagedKVPool.dense(n_layer=L, kv_heads=kvh, head_dim=dh,
                            num_blocks=blocks, block_tokens=bt,
                            dtype=jnp.float32, quant=quant)
    view = pool.view
    k1, k2 = jax.random.split(jax.random.PRNGKey(7))
    # drawn (and quantized) per head vector, then merged into the
    # pool's resting (blocks, bt, L * KVH * Dh)
    raw = (blocks + 1, bt, L, kvh, dh)
    raw_k = jax.random.normal(k1, raw, jnp.float32)
    raw_v = jax.random.normal(k2, raw, jnp.float32)
    if quant:
        from tiny_deepspeed_tpu.serving.pool import _quant_vectors
        qk, sk = _quant_vectors(raw_k, quant)
        qv, sv = _quant_vectors(raw_v, quant)
        return view._replace(
            k=qk.reshape(view.k.shape), v=qv.reshape(view.v.shape),
            k_scale=sk.reshape(view.k_scale.shape),
            v_scale=sv.reshape(view.v_scale.shape))
    return view._replace(k=raw_k.reshape(view.k.shape),
                         v=raw_v.reshape(view.v.shape))


_TABLES = [[1, 2, 3, 0], [4, 5, 0, 0], [6, 0, 0, 0]]


class TestPagedKernelParity:
    """Kernel numerics vs the XLA reference on the same pool operands."""

    # quick tier carries ONE representative case (GQA + int8: the
    # grouped heads AND the in-kernel dequant in one pin); the full
    # matrix is slow-marked per the tier-1 zero-sum budget rule
    @pytest.mark.parametrize("quant,hq", [
        ("int8", 4),
        pytest.param(None, 2, marks=pytest.mark.slow),
        pytest.param(None, 4, marks=pytest.mark.slow),
        pytest.param("int8", 2, marks=pytest.mark.slow),
        pytest.param("fp8", 2, marks=pytest.mark.slow),
        pytest.param("fp8", 4, marks=pytest.mark.slow),
    ])
    def test_decode_matches_xla(self, model, quant, hq):
        """The decode step: a span of ONE token per slot, its own K/V
        beside the committed prefix in the pool."""
        view = _pool_view(quant)
        tables = jnp.asarray(_TABLES, jnp.int32)
        pos = jnp.asarray([25, 9, 0], jnp.int32)  # mid/partial/first token
        page = page_ref(tables, pos, 8)
        ks = jax.random.split(jax.random.PRNGKey(3), 3)
        q = jax.random.normal(ks[0], (3, hq, 1, 16), jnp.float32)
        sk = jax.random.normal(ks[1], (3, 2, 1, 16), jnp.float32)
        sv = jax.random.normal(ks[2], (3, 2, 1, 16), jnp.float32)
        for layer in range(2):
            ck, cv = paged_panel(view, layer, page, 2, 16, jnp.float32)
            ref = model._span_attention(q, ck, cv, sk, sv, pos)
            got = PAP.paged_attention(q, view, page, layer, (sk, sv),
                                      kv_heads=2)
            np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                       rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("quant", [
        None, pytest.param("int8", marks=pytest.mark.slow)])
    def test_span_matches_xla_incl_empty_prefix(self, model, quant):
        """Span-verify variant vs `_span_attention`, with one slot at
        pos0=0 (pool prefix fully masked — the online-softmax edge) and
        a traced layer index under jit+scan, exactly how paged_verify
        consumes it."""
        view = _pool_view(quant)
        k1 = 5
        tables = jnp.asarray(_TABLES, jnp.int32)
        pos0 = jnp.asarray([25, 9, 0], jnp.int32)
        page = page_ref(tables, jnp.minimum(pos0, 31), 8)._replace(pos=pos0)
        ks = jax.random.split(jax.random.PRNGKey(4), 3)
        q = jax.random.normal(ks[0], (3, 4, k1, 16), jnp.float32)
        sk = jax.random.normal(ks[1], (3, 2, k1, 16), jnp.float32)
        sv = jax.random.normal(ks[2], (3, 2, k1, 16), jnp.float32)

        def run(view, q, sk, sv, page):
            def body(c, layer):
                return c, PAP.paged_attention(q, view, page, layer,
                                              (sk, sv), kv_heads=2)
            _, ys = jax.lax.scan(body, 0, jnp.arange(2))
            return ys

        ys = jax.jit(run)(view, q, sk, sv, page)
        for layer in range(2):
            ck, cv = paged_panel(view, layer, page, 2, 16, jnp.float32)
            ref = model._span_attention(q, ck, cv, sk, sv, pos0)
            np.testing.assert_allclose(np.asarray(ys[layer]),
                                       np.asarray(ref),
                                       rtol=2e-5, atol=2e-5)

    def test_dispatch_gate(self):
        """use_paged_kernel: off/on force both ways; auto follows the
        kernel target (CPU mesh -> XLA path)."""
        from tiny_deepspeed_tpu.ops.dispatch import kernel_target_forced
        assert PAP.paged_kernel_mode() == "auto"
        assert not PAP.use_paged_kernel()  # CPU target
        with PAP.paged_kernel_forced("on"):
            assert PAP.use_paged_kernel()
            assert PAP.effective_paged_kernel() == "pallas"
        with PAP.paged_kernel_forced("off"):
            with kernel_target_forced("tpu"):
                assert not PAP.use_paged_kernel()
        with kernel_target_forced("tpu"):
            assert PAP.use_paged_kernel()
        with pytest.raises(ValueError):
            PAP.set_paged_kernel("sometimes")


def _slots_at(poss, w, bt, dead):
    """A table row a slot: its live entries (the first ceil(pos / bt))
    name blocks of its own, numbered from 1; every entry past them
    names a block of `dead`."""
    tables = np.empty((len(poss), w), np.int32)
    fresh = iter(range(1, 1 + len(poss) * w))
    for s, pos in enumerate(poss):
        live = -(-pos // bt)
        tables[s] = [next(fresh) if e < live else dead[(s + e) % len(dead)]
                     for e in range(w)]
    return tables


class TestOnlyLiveSteps:
    """The kernel copies and folds the blocks below a slot's length and
    no other (ISSUE 31).  The file's pools are tiny, so a chunk is cut
    to 16 tokens (two 8-token blocks) to give a table several."""

    BT, STEP = 8, 16

    @pytest.fixture(autouse=True)
    def small_steps(self, monkeypatch):
        monkeypatch.setattr(PAP, "_STEP_TOKENS", self.STEP)

    # slots at pos 0, 1, one whole step, one step + 1, the full table
    @staticmethod
    def _poss(w):
        return [0, 1, 16, 17, w * 8]

    @pytest.mark.parametrize("k1,w,quant,max_rows", [
        (1, 4, None, 256),      # the decode step
        (16, 4, None, 16),      # a span cut into two row tiles
        (1, 4, "int8", 256),    # scales go through the same rule
        (1, 5, None, 256),      # a table that is no whole number of steps
    ], ids=["decode", "span-row-tiles", "int8", "ragged-table"])
    def test_poison_past_a_slots_length_is_never_read(
            self, model, monkeypatch, k1, w, quant, max_rows):
        """Every block but those below a slot's length holds NaN, the
        ones its table names past ceil(pos / bt) and the scratch block
        among them: the output is finite, bit-equal to the clean
        pool's, and the XLA reference's to float tolerance.  (The
        parent multiplied V's NaN by p = 0.)"""
        monkeypatch.setattr(PAP, "_MAX_ROWS", max_rows)
        poss = self._poss(w)
        named = _slots_at(poss, w, self.BT, dead=[0, 30, 31])  # 0: scratch
        live = {int(b) for row, p in zip(named, poss)
                for b in row[:-(-p // self.BT)]}
        dead = sorted(set(range(32)) - live)
        assert {0, 30, 31} <= set(dead) and len(live) == (10, 11)[w - 4]
        tables = jnp.asarray(named)
        pos = jnp.asarray(poss, jnp.int32)
        page = page_ref(tables, jnp.minimum(pos, w * self.BT - 1),
                        self.BT)._replace(pos=pos)
        clean = _pool_view(quant, blocks=31)
        nan = jnp.asarray(dead)
        if quant:
            bad = clean._replace(
                k_scale=clean.k_scale.at[nan].set(jnp.nan),
                v_scale=clean.v_scale.at[nan].set(jnp.nan))
        else:
            bad = clean._replace(k=clean.k.at[nan].set(jnp.nan),
                                 v=clean.v.at[nan].set(jnp.nan))
        ks = jax.random.split(jax.random.PRNGKey(5), 3)
        q = jax.random.normal(ks[0], (len(poss), 2, k1, 16), jnp.float32)
        sk = jax.random.normal(ks[1], (len(poss), 2, k1, 16), jnp.float32)
        sv = jax.random.normal(ks[2], (len(poss), 2, k1, 16), jnp.float32)
        nt = k1 * 2 // min(k1 * 2, max_rows)
        assert PAP.pool_steps(w, self.BT) == (2, -(-w // 2))
        assert nt == (2 if k1 > 1 else 1)
        for layer in range(2):
            want = PAP.paged_attention(q, clean, page, layer, (sk, sv),
                                       kv_heads=2)
            got = PAP.paged_attention(q, bad, page, layer, (sk, sv),
                                      kv_heads=2)
            assert np.isfinite(np.asarray(got)).all()
            np.testing.assert_array_equal(np.asarray(got),
                                          np.asarray(want))
            ck, cv = paged_panel(clean, layer, page, 2, 16, jnp.float32)
            ref = model._span_attention(q, ck, cv, sk, sv, pos)
            np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                       rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("k1,w,max_rows", [
        (1, 4, 256), (16, 5, 16)], ids=["decode", "span-row-tiles-ragged"])
    def test_the_copies_started_are_the_live_blocks(
            self, monkeypatch, k1, w, max_rows):
        """A dead block costs nothing because nothing is done for it:
        the K and V copies the kernel starts, counted as it runs, are
        one each for every block below a slot's length, a row tile, and
        as many are waited for; an empty slot starts none."""
        monkeypatch.setattr(PAP, "_MAX_ROWS", max_rows)
        done = {"start": 0, "wait": 0}
        make = PAP.pltpu.make_async_copy

        class Counted:
            def __init__(self, *a):
                self.copy = make(*a)

            def start(self):
                jax.debug.callback(
                    lambda: done.__setitem__("start", done["start"] + 1))
                self.copy.start()

            def wait(self):
                jax.debug.callback(
                    lambda: done.__setitem__("wait", done["wait"] + 1))
                self.copy.wait()

        monkeypatch.setattr(PAP.pltpu, "make_async_copy", Counted)
        poss = self._poss(w)
        tables = jnp.asarray(_slots_at(poss, w, self.BT, dead=[0]))
        pos = jnp.asarray(poss, jnp.int32)
        page = page_ref(tables, jnp.minimum(pos, w * self.BT - 1),
                        self.BT)._replace(pos=pos)
        ks = jax.random.split(jax.random.PRNGKey(6), 3)
        q, sk, sv = (jax.random.normal(k, (len(poss), 2, k1, 16))
                     for k in ks)
        out = PAP.paged_attention(q, _pool_view(None, blocks=31), page, 1,
                                  (sk, sv), kv_heads=2)
        jax.block_until_ready(out)
        jax.effects_barrier()
        nt = 2 if k1 > 1 else 1
        blocks = sum(-(-p // self.BT) for p in poss)
        assert blocks == (10 if w == 4 else 11)
        assert done == {"start": 2 * nt * blocks, "wait": 2 * nt * blocks}

    def test_pool_steps_are_what_the_engine_counts(self, monkeypatch):
        """`pool_steps`: the entries a chunk holds and the chunks a
        table row can fill, at the tests' sizes and the serve cell's."""
        assert PAP.pool_steps(5, self.BT) == (2, 3)
        monkeypatch.setattr(PAP, "_STEP_TOKENS", 256)
        assert PAP.pool_steps(64, 16) == (16, 4)
        assert PAP.pool_steps(21, 16) == (16, 2)
        assert PAP.pool_steps(4, 8) == (4, 1)


def _staggered_trace(model, params, kmode, spec=None, quant=None):
    """Three requests through a real ServingEngine, the third admitted
    mid-flight; returns each request's committed tokens."""
    from tiny_deepspeed_tpu.serving import ServeConfig, ServingEngine
    prompts = [
        np.asarray(jax.random.randint(jax.random.PRNGKey(s), (n,), 0, 128),
                   np.int32).tolist()
        for s, n in ((1, 7), (2, 13), (3, 9))
    ]
    eng = ServingEngine(model, params, ServeConfig(
        max_active=2, num_blocks=24, block_tokens=8, max_seq_tokens=48,
        paged_kernel=kmode, spec_draft=spec, quant=quant))
    handles = [eng.submit(prompts[0], 12), eng.submit(prompts[1], 12)]
    for _ in range(4):
        eng.tick()
    handles.append(eng.submit(prompts[2], 12))
    while not all(r.state == "done" for r in handles):
        eng.tick()
    assert all(r.status == "ok" for r in handles)
    return [r.tokens for r in handles]


class TestEngineTokenIdentity:
    """The serving contract: the kernel may change speed, never tokens."""

    def test_greedy_token_identity_staggered(self, model, params):
        """Quick wiring pin: kernel-on (interpret) vs kernel-off greedy
        decode through the real engine, staggered admission."""
        off = _staggered_trace(model, params, "off")
        on = _staggered_trace(model, params, "on")
        assert on == off

    @pytest.mark.slow
    def test_spec_span_token_identity(self, model, params):
        """The span-verify variant: a spec engine (ngram drafter) with
        the kernel on commits the same tokens as kernel-off — and the
        same tokens as the plain decode path (spec's own guarantee)."""
        off = _staggered_trace(model, params, "off", spec="ngram")
        on = _staggered_trace(model, params, "on", spec="ngram")
        plain = _staggered_trace(model, params, "off")
        assert on == off == plain

    @pytest.mark.slow
    def test_quantized_pool_token_identity(self, model, params):
        """int8 pool: kernel and XLA read the SAME quantized blocks, so
        greedy tokens stay identical between the arms."""
        off = _staggered_trace(model, params, "off", quant="int8")
        on = _staggered_trace(model, params, "on", quant="int8")
        assert on == off

    def test_bad_mode_refused(self, model, params):
        from tiny_deepspeed_tpu.serving import ServeConfig, ServingEngine
        with pytest.raises(ValueError, match="paged_kernel"):
            ServingEngine(model, params,
                          ServeConfig(paged_kernel="maybe"))


class TestFp8Matmul:
    def test_numerics_within_quantization_tolerance(self):
        k = jax.random.split(jax.random.PRNGKey(0), 2)
        x = jax.random.normal(k[0], (4, 32, 64), jnp.float32)
        w = jax.random.normal(k[1], (64, 48), jnp.float32) * 0.2
        from tiny_deepspeed_tpu.ops.linear import _fwd_xla
        ref = _fwd_xla(x, w, None)
        got = MF._fwd_fp8(x, w, None)
        rel = float(jnp.max(jnp.abs(got - ref)) / jnp.max(jnp.abs(ref)))
        assert rel < 0.1  # e4m3 rowwise/colwise-scaled quantization

    def test_off_path_hlo_byte_identical(self):
        """The no-fp8 trace is the EXACT pre-fp8 program (fresh
        closures per lowering: jit's trace cache keys on function
        identity)."""
        from tiny_deepspeed_tpu.ops.linear import linear_forward
        k = jax.random.split(jax.random.PRNGKey(0), 2)
        x = jax.random.normal(k[0], (2, 16, 32), jnp.float32)
        w = jax.random.normal(k[1], (32, 8), jnp.float32)

        def make():
            def f(a, b):
                return linear_forward(a, b, None)
            return f

        t0 = jax.jit(make()).lower(x, w).as_text()
        with MF.fp8_matmul_forced("on"):
            t_on = jax.jit(make()).lower(x, w).as_text()
        t1 = jax.jit(make()).lower(x, w).as_text()
        assert t0 == t1, "fp8 'off' drifted the default lowering"
        assert t_on != t0 and "f8" in t_on

    def test_candidate_mode_gates_list(self):
        from tiny_deepspeed_tpu.autotuner import RuntimeAutoTuner
        from tiny_deepspeed_tpu.ops.linear import linear_forward
        x = jnp.ones((2, 8, 16))
        w = jnp.ones((16, 4))
        with MF.fp8_matmul_forced("candidate"):
            t = RuntimeAutoTuner(warmup=1, iters=1)
            linear_forward(x, w, None, tuner=t)
            (key, winner), = t.cache.items()
            assert any("_fwd_fp8" in n for n in key[0])
        t2 = RuntimeAutoTuner(warmup=1, iters=1)
        linear_forward(x, w, None, tuner=t2)
        (key2, _), = t2.cache.items()
        assert not any("_fwd_fp8" in n for n in key2[0])

    def test_delayed_scaling_history(self):
        """Step 0 falls back to JIT scaling (cold history); later steps
        quantize against the recorded maxima, and the history rolls."""
        k = jax.random.split(jax.random.PRNGKey(1), 2)
        x = jax.random.normal(k[0], (8, 16), jnp.float32)
        w = jax.random.normal(k[1], (16, 8), jnp.float32)
        h = MF.fp8_history(4)
        y0, h = MF.fp8_matmul_delayed(x, w, h)
        assert float(h.x_amax[0]) == pytest.approx(
            float(jnp.max(jnp.abs(x))))
        exact = np.asarray(x) @ np.asarray(w)
        rel = (np.linalg.norm(np.asarray(y0) - exact)
               / np.linalg.norm(exact))
        assert rel < 0.1  # per-tensor e4m3 quantization error envelope
        # a 2x-hotter step quantizes against the STALE amax: values
        # clip into e4m3 range instead of overflowing
        y1, h = MF.fp8_matmul_delayed(x * 2, w, h)
        assert np.all(np.isfinite(np.asarray(y1)))
        assert float(h.x_amax[0]) == pytest.approx(
            2 * float(jnp.max(jnp.abs(x))), rel=1e-6)
        assert float(h.x_amax[1]) == pytest.approx(
            float(jnp.max(jnp.abs(x))), rel=1e-6)

    def test_bad_mode_refused(self):
        with pytest.raises(ValueError, match="fp8_matmul"):
            MF.set_fp8_matmul("half")

    @pytest.mark.slow
    def test_twenty_step_loss_parity(self):
        """fp8 'on' (every linear fwd + the fused-xent head) composes
        with the real training engine: 20 AdamW steps land within 5% of
        the exact path — the gather_quant convergence precedent."""
        from tiny_deepspeed_tpu import AdamW, SingleDevice
        cfg = GPTConfig(block_size=32, vocab_size=128, n_layer=2,
                        n_head=2, n_embd=32, compute_dtype=jnp.float32,
                        fused_xent=True)

        def final_loss(mode):
            MF.set_fp8_matmul(mode)
            try:
                eng = SingleDevice(GPT2Model(cfg), AdamW(lr=1e-3))
                st = eng.init(jax.random.PRNGKey(0))
                rng = np.random.default_rng(0)
                for _ in range(20):
                    a = rng.integers(0, 128, (4, 33))
                    st, loss = eng.step(st, (
                        jnp.asarray(a[:, :-1], jnp.int32),
                        jnp.asarray(a[:, 1:], jnp.int32)))
                return float(loss)
            finally:
                MF.set_fp8_matmul("off")

        base = final_loss("off")
        f8 = final_loss("on")
        assert abs(f8 - base) / abs(base) < 0.05


class TestTuneE2E:
    def test_coordinate_descent_finds_min_and_types_distinct(self):
        from tiny_deepspeed_tpu.autotuner import tune_e2e
        seen = []

        def measure(plan):
            seen.append(dict(plan))
            cost = {1: 3.0, True: 1.0}[plan["unroll"]]
            return cost + {"off": 0.5, "on": 0.0}[plan["fp8"]]

        best, score, trials = tune_e2e(
            measure, {"unroll": [1, True], "fp8": ["off", "on"]},
            objective="min")
        assert best == {"unroll": True, "fp8": "on"} and score == 1.0
        # bool-vs-int knob values are distinct assignments (True != 1)
        assert any(p["unroll"] is True for p in seen)
        assert trials[0]["plan"] == {"unroll": 1, "fp8": "off"}
        assert len(trials) == 3

    def test_objective_max_and_failures_tolerated(self):
        from tiny_deepspeed_tpu.autotuner import tune_e2e

        def measure(plan):
            if plan["k"] == 8:
                raise RuntimeError("does not compile")
            return float(plan["k"])

        best, score, trials = tune_e2e(measure, {"k": [2, 4, 8]},
                                       objective="max")
        assert best == {"k": 4} and score == 4.0
        assert any(t["score"] is None for t in trials)  # the failed arm
        with pytest.raises(RuntimeError, match="every candidate"):
            tune_e2e(lambda p: 1 / 0, {"k": [1, 2]})

    def test_plan_persistence_v2_envelope(self, tmp_path):
        from tiny_deepspeed_tpu.autotuner import (
            RuntimeAutoTuner, plan_hash, plan_key,
        )
        t = RuntimeAutoTuner(warmup=1, iters=1)
        key = plan_key("tiny", "1dev", "cpu")
        plan = {"spec_k": 6, "scan_unroll": True}
        h = t.store_plan(key, plan, {"serve_tok_s_tuned": 123.0})
        assert h == plan_hash(plan)
        p = str(tmp_path / "cache.json")
        t.save(p)
        t2 = RuntimeAutoTuner()
        t2.load(p)
        entry = t2.get_plan(key)
        assert entry["plan"] == plan and entry["hash"] == h
        assert entry["record"]["serve_tok_s_tuned"] == 123.0
        with open(p) as f:
            assert json.load(f)["version"] == 2

    def test_legacy_flat_cache_still_loads(self, tmp_path):
        """Pre-plan AOT caches (flat {key: winner}) keep working."""
        from tiny_deepspeed_tpu.autotuner import RuntimeAutoTuner

        def fast(x):
            return x + 1.0

        def slow(x):
            return x + 1.0

        t = RuntimeAutoTuner(warmup=1, iters=1)
        x = jnp.ones((16, 16))
        t.choose([slow, fast], (x,))
        p = str(tmp_path / "legacy.json")
        # write the OLD format by hand
        flat = {json.dumps(k): fn.__module__ + "." + fn.__name__
                for k, fn in t.cache.items()}
        with open(p, "w") as f:
            json.dump(flat, f)
        t2 = RuntimeAutoTuner(warmup=1, iters=1)
        assert t2.load(p) == 1
        assert t2.choose([slow, fast], (x,)) in (slow, fast)
        assert len(t2.cache) == 1  # resolved from the store, no timing
        # and a save() round-trips it into the v2 envelope
        t2.save(p)
        t3 = RuntimeAutoTuner()
        assert t3.load(p) == 1


class TestAutotunerDiagnostics:
    """Satellite: runtime_tuner's bare prints became telemetry."""

    def test_candidate_failure_counts_and_decision_records(self, tmp_path):
        from tiny_deepspeed_tpu.autotuner import RuntimeAutoTuner
        from tiny_deepspeed_tpu.telemetry import Telemetry
        from tiny_deepspeed_tpu.utils.profiling import MetricsLogger

        def broken(x):
            raise ValueError("unsupported")

        def fine(x):
            return x + 1.0

        path = str(tmp_path / "m.jsonl")
        tel = Telemetry()
        with MetricsLogger(path, stdout=False) as ml:
            t = RuntimeAutoTuner(warmup=1, iters=1)
            t.attach_diagnostics(tel, ml)
            winner = t.choose([broken, fine], (jnp.ones((8, 8)),))
        assert winner is fine
        assert tel.counters["autotune_candidate_failures"].value == 1
        assert tel.gauges["autotune_candidate_failures"] == 1.0
        with open(path) as f:
            recs = [json.loads(line) for line in f]
        events = [r["autotune"]["event"] for r in recs if "autotune" in r]
        assert "candidate_failed" in events and "decision" in events
        dec = next(r["autotune"] for r in recs
                   if r.get("autotune", {}).get("event") == "decision")
        assert dec["winner"] == "fine"
        failed = next(e for e in dec["ranking"]
                      if e["candidate"] == "broken")
        assert failed["us"] is None

    def test_gauge_documented(self):
        from tiny_deepspeed_tpu.telemetry import schema
        assert "autotune_candidate_failures" in schema.GAUGES
        assert "autotune" in schema.META_FIELDS

    def test_record_validates_against_schema(self, tmp_path):
        """The autotune run_meta record passes report_run --check's
        field validation (schema drift would fail CI there)."""
        from tiny_deepspeed_tpu.telemetry.schema import validate_record
        err = validate_record({"kind": "run_meta", "ts": 0.0,
                               "autotune": {"event": "decision"}})
        assert not err


class TestTier1Budget:
    """Satellite: the tier-1 budget gate's output stays asserted here
    (the suite these kernels' quick pins are budgeted against)."""

    def test_budget_check_predicate(self):
        import sys
        sys.path.insert(0, os.path.join(os.path.dirname(__file__),
                                        os.pardir, "scripts"))
        try:
            from tier1_times import (
                TIER1_BUDGET_S, TIER1_HEADROOM_WARN_S, budget_check,
            )
        finally:
            sys.path.pop(0)
        ok, msg = budget_check(100.0, 870.0)
        assert ok and "within budget" in msg and "headroom 770.0s" in msg
        ok, msg = budget_check(TIER1_BUDGET_S - TIER1_HEADROOM_WARN_S / 2)
        assert ok and "WARNING" in msg
        ok, msg = budget_check(900.0, 870.0)
        assert not ok and "BUDGET EXCEEDED" in msg

    def test_cli_budget_exit_codes(self, tmp_path):
        """`tier1_times.py --from-log --budget S` exits 1 past the
        budget, 0 inside it, and prints the shared message."""
        import subprocess
        import sys
        log = tmp_path / "t1.log"
        log.write_text(
            "  500.00s call     tests/test_x.py::test_a\n"
            "  100.00s call     tests/test_y.py::test_b[p0]\n"
        )
        script = os.path.join(os.path.dirname(__file__), os.pardir,
                              "scripts", "tier1_times.py")
        r = subprocess.run(
            [sys.executable, script, "--from-log", str(log),
             "--budget", "870"],
            capture_output=True, text=True)
        assert r.returncode == 0 and "within budget" in r.stdout
        r = subprocess.run(
            [sys.executable, script, "--from-log", str(log),
             "--budget", "550"],
            capture_output=True, text=True)
        assert r.returncode == 1 and "BUDGET EXCEEDED" in r.stderr
