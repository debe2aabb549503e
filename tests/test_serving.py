# Copyright 2026 tiny-deepspeed-tpu authors
# SPDX-License-Identifier: Apache-2.0

"""Serving tier: paged KV pool, continuous batching, quantized cache,
and the fault-tolerance layer (SLOs, decode-health guard, journal).

Acceptance pins (ISSUE 7):
  * paged decode is token-exact with `GPT2Model.generate` greedy, per
    request, under concurrency and staggered admission;
  * pool accounting is exact at every scheduler tick (blocks-in-use ==
    sum of active block-table lengths) and freed blocks are reused
    deterministically without corrupting neighbors;
  * int8/fp8 cache blocks quarter the pool's resting KV bytes vs f32
    (asserted from array dtypes/shapes) within decode-parity tolerance;
  * importing/instantiating the serving package leaves the TRAINING
    step's HLO byte-identical (subprocess-pinned, fresh import order);
  * the Poisson soak (slow tier): >= 4 concurrent requests beat the
    same trace served one-at-a-time through `generate`.

Acceptance pins (ISSUE 8, robustness):
  * terminal statuses are exact and exclusive (ok/shed/expired/failed),
    each with its JSONL `request` record;
  * a NaN-poisoned slot is quarantined WITHOUT taking the batch down —
    neighbors stay token-exact — and every freed block returns to the
    pool exactly once under a quarantine storm;
  * the watchdog warm-restarts on K consecutive poisoned ticks or a
    tick exception, and the re-queued requests continue token-exact;
  * kill-mid-trace (slow tier): SIGKILL the serving process, recover a
    fresh engine from the journal, final sequences identical to the
    uninterrupted run;
  * temperature > 0 preemption resume is deterministic under the
    (request seed, position) sampling keys.
"""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tiny_deepspeed_tpu import GPTConfig, GPT2Model

# small-and-fast config (test_model.py's TestKVCacheDecode family): XLA-CPU
# compiles of the serving programs dominate this module's budget
CFG = dict(block_size=64, vocab_size=128, n_layer=2, n_head=2,
           n_embd=32, compute_dtype=jnp.float32)


@pytest.fixture(scope="module")
def model():
    return GPT2Model(GPTConfig(**CFG))


@pytest.fixture(scope="module")
def params(model):
    return model.init(jax.random.PRNGKey(0))


def _prompt(seed, n, vocab=128):
    return np.asarray(
        jax.random.randint(jax.random.PRNGKey(seed), (n,), 0, vocab),
        np.int32,
    ).tolist()


def _ref_tokens(model, params, prompt, new):
    out = model.generate(
        params, np.asarray(prompt, np.int32)[None, :], new,
        temperature=0.0,
    )
    return np.asarray(out)[0, len(prompt):]


def _serve_config(**kw):
    from tiny_deepspeed_tpu.serving import ServeConfig
    kw.setdefault("max_active", 3)
    kw.setdefault("num_blocks", 24)
    kw.setdefault("block_tokens", 8)
    return ServeConfig(**kw)


def _assert_accounting(eng):
    used = sum(len(t) for t in eng.active_block_tables().values())
    assert used == eng.pool.blocks_in_use, (
        f"pool accounting drift: tables hold {used}, pool reports "
        f"{eng.pool.blocks_in_use}"
    )


class TestSamplingCore:
    """ONE sampling core (models/sampling.py) for generate + serving."""

    def test_greedy_is_argmax_and_ignores_key(self):
        from tiny_deepspeed_tpu.models.sampling import sample_logits
        logit = jnp.asarray(np.random.default_rng(0).normal(
            size=(3, 16)).astype(np.float32))
        a = sample_logits(logit, jax.random.PRNGKey(0), 0.0, None)
        b = sample_logits(logit, jax.random.PRNGKey(7), 0.0, None)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        np.testing.assert_array_equal(
            np.asarray(a), np.argmax(np.asarray(logit), -1))

    def test_top_k_restricts_support(self):
        from tiny_deepspeed_tpu.models.sampling import sample_logits
        logit = jnp.asarray(
            np.arange(12, dtype=np.float32)[None, :])  # top-2 = {10, 11}
        for seed in range(8):
            t = int(sample_logits(
                logit, jax.random.PRNGKey(seed), 1.0, 2)[0])
            assert t in (10, 11)

    def test_generate_sample_delegates_to_core(self, monkeypatch):
        """GPT2Model._sample IS the shared core, not a drifted copy."""
        from tiny_deepspeed_tpu.models import sampling
        calls = {}
        orig = sampling.sample_logits

        def spy(logit, key, temperature, top_k=None):
            calls["hit"] = True
            return orig(logit, key, temperature, top_k)

        monkeypatch.setattr(sampling, "sample_logits", spy)
        GPT2Model._sample(jnp.zeros((1, 4)), jax.random.PRNGKey(0),
                          0.0, None)
        assert calls.get("hit")


class TestContinuousBatching:
    def test_staggered_greedy_parity_and_exact_accounting(
            self, model, params):
        """Requests admitted and evicted at DIFFERENT ticks (two shape
        groups, second wave submitted mid-flight) each reproduce their
        `generate` tokens exactly, with pool accounting exact at every
        tick — the continuous-batching core contract."""
        from tiny_deepspeed_tpu.serving import ServingEngine
        eng = ServingEngine(model, params, _serve_config())
        specs = [(1, 7, 10), (2, 13, 6)]
        reqs = [eng.submit(_prompt(s, n), new) for s, n, new in specs]
        for _ in range(3):
            eng.tick()
            _assert_accounting(eng)
        late = [(3, 7, 10), (4, 13, 6)]  # same shapes: no new compiles
        reqs += [eng.submit(_prompt(s, n), new) for s, n, new in late]
        ticks = 0
        while eng.queue_depth or eng.n_active:
            eng.tick()
            _assert_accounting(eng)
            ticks += 1
            assert ticks < 100
        assert eng.pool.blocks_in_use == 0
        for r, (s, n, new) in zip(reqs, specs + late):
            assert len(r.tokens) == new
            np.testing.assert_array_equal(
                np.asarray(r.tokens),
                _ref_tokens(model, params, r.prompt, new),
                err_msg=f"request {r.id} diverged from generate()",
            )
            assert r.state == "done" and r.finish_reason == "length"

    @pytest.mark.slow  # redundant in tier-1 since ISSUE 13: the
    # prefix-cache choreography (test_serving_prefix.py::
    # TestPrefixServing) admits a COLD boundary-length prompt through
    # this same plain full-prefill path (its first request, p == 2*bt)
    # and pins token parity — the boundary +1-block rule stays quick
    # there; this dedicated two-prompt variant keeps the coverage in
    # the slow tier
    def test_block_boundary_prompt_parity(self, model, params):
        """Prompt length exactly on a block boundary (p % block_tokens
        == 0): the first decode write lands at position p, i.e. in a
        block BEYOND ceil(p/bt) — admission must allocate it up front
        or that K/V silently lands in the scratch block and every later
        token attends to a hole.  Token-exact parity pins it."""
        from tiny_deepspeed_tpu.serving import ServingEngine
        eng = ServingEngine(model, params, _serve_config())
        specs = [(11, 8, 6), (12, 16, 6)]  # p == bt and p == 2*bt
        reqs = [eng.submit(_prompt(s, n), new) for s, n, new in specs]
        ticks = 0
        while eng.queue_depth or eng.n_active:
            eng.tick()
            _assert_accounting(eng)
            ticks += 1
            assert ticks < 50
        for r, (s, n, new) in zip(reqs, specs):
            np.testing.assert_array_equal(
                np.asarray(r.tokens),
                _ref_tokens(model, params, r.prompt, new),
                err_msg=f"boundary request {r.id} diverged",
            )

    @pytest.mark.slow  # redundant in tier-1 since ISSUE 13: realloc
    # cleanliness is now exercised HARDER quick by the refcounted-pool
    # tests (test_serving_prefix.py) — LIFO realloc determinism is
    # pinned at the pool level, and the prefix choreography reuses
    # tree-evicted blocks mid-trace with per-tick refcount accounting
    # + token parity; this engine-level variant keeps the
    # evictee-block-overlap assertion in the slow tier
    def test_block_realloc_after_eviction_is_clean(self, model, params):
        """A request admitted AFTER an eviction reuses the evictee's
        freed blocks (the free list is LIFO, so they come back first)
        without corrupting the still-active neighbor."""
        from tiny_deepspeed_tpu.serving import ServingEngine
        # 2 slots: r2 must WAIT until short-lived r0 finishes; r0's
        # blocks are the most recently freed when r2 admits
        eng = ServingEngine(model, params,
                            _serve_config(max_active=2, num_blocks=6))
        r0 = eng.submit(_prompt(1, 7), 6)    # finishes first
        r1 = eng.submit(_prompt(2, 13), 10)  # active throughout
        eng.tick()
        r0_blocks = set(eng.active_block_tables()[r0.id])
        r2 = eng.submit(_prompt(3, 13), 6)
        ticks = 0
        r2_blocks = None
        while eng.queue_depth or eng.n_active:
            eng.tick()
            _assert_accounting(eng)
            if r2.state == "active" and r2_blocks is None:
                r2_blocks = set(eng.active_block_tables()[r2.id])
                assert r0.done  # admission had to wait for the eviction
                assert r1.state == "active"  # the neighbor lives on
            ticks += 1
            assert ticks < 100
        assert r2_blocks is not None and r2_blocks & r0_blocks, (
            "r2 was expected to reuse blocks freed by r0"
        )
        for r, new in ((r0, 6), (r1, 10), (r2, 6)):
            np.testing.assert_array_equal(
                np.asarray(r.tokens),
                _ref_tokens(model, params, r.prompt, new),
                err_msg=f"request {r.id} corrupted across realloc",
            )

    def test_refusals(self, model, params):
        from tiny_deepspeed_tpu import MoEConfig, MoEGPT
        from tiny_deepspeed_tpu.serving import ServingEngine
        with pytest.raises(ValueError, match="paged_decode_capable"):
            ServingEngine(MoEGPT(MoEConfig(n_expert=2, **CFG)), params,
                          _serve_config())
        with pytest.raises(ValueError, match="must divide"):
            ServingEngine(model, params, _serve_config(block_tokens=7))
        with pytest.raises(ValueError, match="KV-cache quant"):
            ServingEngine(model, params, _serve_config(quant="int4"))
        eng = ServingEngine(model, params, _serve_config(num_blocks=2))
        with pytest.raises(ValueError, match="blocks"):
            eng.submit(_prompt(1, 30), 30)  # can never fit the pool
        with pytest.raises(ValueError, match="block_size"):
            eng.submit(_prompt(1, 60), 30)  # exceeds the model context


class TestQuantizedCache:
    def test_pool_bytes_quartered_from_dtypes(self):
        """int8/fp8 pools rest at 1 byte/element vs the f32 baseline's 4
        — asserted from the device arrays' dtypes and shapes, not a
        model.  (On a bf16-compute config the same blocks HALVE.)"""
        from tiny_deepspeed_tpu.serving.pool import PagedKVPool
        kw = dict(n_layer=2, kv_heads=2, head_dim=16, num_blocks=8,
                  block_tokens=8)
        base = PagedKVPool.dense(dtype=jnp.float32, **kw).kv_bytes()
        half = PagedKVPool.dense(dtype=jnp.bfloat16, **kw).kv_bytes()
        assert half["kv_block_bytes"] * 2 == base["kv_block_bytes"]
        for quant, dt in (("int8", jnp.int8), ("fp8", jnp.float8_e4m3fn)):
            q = PagedKVPool.dense(dtype=jnp.float32, quant=quant, **kw)
            b = q.kv_bytes()
            assert jnp.dtype(q.view.k.dtype) == jnp.dtype(dt)
            assert b["itemsize"] == 1
            assert b["kv_block_bytes"] * 4 == base["kv_block_bytes"]
            assert b["scale_bytes"] > 0  # f32 absmax per head vector

    def test_codec_roundtrip_error_bounded(self):
        """paged_append -> paged_panel through an int8 pool stays within
        the blockwise-absmax codec's per-element bound (scale/2, scale =
        vector absmax / 127) — the grad-comm machinery reused verbatim."""
        from tiny_deepspeed_tpu.serving.pool import (
            PagedKVPool, page_ref, paged_append, paged_panel,
        )
        dh, kvh, s = 16, 2, 3
        pool = PagedKVPool.dense(n_layer=1, kv_heads=kvh, head_dim=dh,
                                 num_blocks=4, block_tokens=4,
                                 dtype=jnp.float32, quant="int8")
        rng = np.random.default_rng(0)
        k = rng.normal(size=(s, kvh, dh)).astype(np.float32)
        v = rng.normal(size=(s, kvh, dh)).astype(np.float32)
        tables = np.asarray([[1, 0], [2, 0], [3, 0]], np.int32)
        ref = page_ref(jnp.asarray(tables), jnp.zeros((s,), jnp.int32), 4)
        view = paged_append(pool.view, jnp.asarray(k)[None],
                            jnp.asarray(v)[None], ref)
        ck, cv = paged_panel(view, 0, ref, kvh, dh, jnp.float32)
        got_k = np.asarray(ck)[:, :, 0, :]  # position 0 of each panel
        got_v = np.asarray(cv)[:, :, 0, :]
        for got, ref_a in ((got_k, k), (got_v, v)):
            bound = np.abs(ref_a).max(-1, keepdims=True) / 127.0 * 0.5001
            assert (np.abs(got - ref_a) <= bound + 1e-7).all()

    # fp8 demoted to slow (ISSUE-12 tier-1 budget): the fp8 codec is
    # primitive-pinned by the quick roundtrip-bound test and the decode
    # integration path is identical per mode — the int8 case keeps the
    # quantized-decode wiring quick
    @pytest.mark.parametrize("quant", [
        "int8", pytest.param("fp8", marks=pytest.mark.slow)])
    def test_quantized_decode_parity_tolerance(self, model, params,
                                               quant):
        """Quantized-cache greedy decode tracks the f32 reference: the
        prefill/first token is exact (full-precision forward), and the
        decode logits stay close enough that tokens rarely flip at this
        scale."""
        from tiny_deepspeed_tpu.serving import ServingEngine
        eng = ServingEngine(model, params,
                            _serve_config(quant=quant, max_active=2))
        specs = [(1, 7, 8), (2, 13, 8)]
        reqs = [eng.submit(_prompt(s, n), new) for s, n, new in specs]
        eng.drain(max_ticks=200)
        for r, (s, n, new) in zip(reqs, specs):
            ref = _ref_tokens(model, params, r.prompt, new)
            assert len(r.tokens) == new
            assert r.tokens[0] == ref[0], "prefill token must be exact"
            agree = float((np.asarray(r.tokens) == ref).mean())
            assert agree >= 0.75, (
                f"{quant} cache diverged: {agree:.2f} agreement"
            )


class TestCacheDtypeKnob:
    def test_bf16_cache_greedy_parity_with_full_forward(self):
        """cache_dtype="bf16" on an f32-compute config: cached greedy
        decode still equals the uncached full-forward tokens (seed-
        pinned) — retiring gpt2.py's '(future-knob) cache dtype'."""
        m = GPT2Model(GPTConfig(cache_dtype="bf16", **CFG))
        p = m.init(jax.random.PRNGKey(0))
        idx = np.asarray(_prompt(5, 7), np.int32)[None, :]
        a = m.generate(p, idx, 10, temperature=0.0, use_cache=True)
        b = m.generate(p, idx, 10, temperature=0.0, use_cache=False)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        # and the cache really rests narrower: the serving pool derives
        # its dtype from the same resolver
        from tiny_deepspeed_tpu.models.gpt2 import resolved_cache_dtype
        assert resolved_cache_dtype(m.config) == jnp.bfloat16

    def test_resolver(self):
        from tiny_deepspeed_tpu.models.gpt2 import resolved_cache_dtype
        assert resolved_cache_dtype(GPTConfig(**CFG)) == jnp.float32
        assert resolved_cache_dtype(
            GPTConfig(cache_dtype=jnp.float16, **CFG)) == jnp.float16
        with pytest.raises(ValueError, match="cache_dtype"):
            resolved_cache_dtype(GPTConfig(cache_dtype="int8", **CFG))


class TestServingTelemetry:
    @pytest.mark.slow  # redundant in tier-1 since ISSUE 13: the
    # prefix-cache choreography (test_serving_prefix.py) validates a
    # full engine record file against the schema (superset: v9 tenant/
    # prefix fields + gauges), and test_serve_observability pins the
    # plain request-record field surface quick; the gauge registry/
    # GAUGES cross-check stays quick via the repo-hygiene grep guard
    def test_gauges_counters_and_request_records(self, model, params,
                                                 tmp_path):
        from tiny_deepspeed_tpu.serving import ServingEngine
        from tiny_deepspeed_tpu.telemetry import Telemetry
        from tiny_deepspeed_tpu.telemetry import schema
        from tiny_deepspeed_tpu.utils.profiling import MetricsLogger
        tel = Telemetry()
        path = str(tmp_path / "serve.jsonl")
        with MetricsLogger(path, stdout=False) as ml:
            ml.log_meta(schema_version=schema.SCHEMA_VERSION,
                        engine="serve:test")
            eng = ServingEngine(model, params, _serve_config(),
                                telemetry=tel, logger=ml)
            reqs = [eng.submit(_prompt(1, 7), 10),
                    eng.submit(_prompt(2, 7), 10)]
            eng.drain(max_ticks=200)
            tel.flush(ml)
        assert all(r.done for r in reqs)
        g = tel.gauges
        assert g["serve_batch_occupancy"] == 0.0  # drained
        assert g["serve_pool_utilization"] == 0.0
        assert g["serve_queue_depth"] == 0.0
        assert g["serve_eviction_rate"] > 0.0
        assert tel.counters["serve_tokens"].value == 20
        assert tel.counters["serve_evictions"].value == 2
        # every serve gauge name is documented (the schema drift guard
        # enforces the same via grep; this pins the registry side)
        for name in g:
            assert name in schema.GAUGES
        counts, errs = schema.validate_file(path)
        assert not errs, errs
        with open(path) as f:
            kinds = [json.loads(ln).get("kind") for ln in f]
        assert kinds.count("request") == 2

    @pytest.mark.slow  # redundant in tier-1 since ISSUE 13: the
    # tenant-isolation pin (test_serving_prefix.py) drives the SAME
    # run_trace closed-loop path with richer asserts (per-tenant
    # aggregates + status counts), and the staggered-parity test keeps
    # plain-engine scheduling quick; this smoke keeps the poisson_trace
    # shape assertions in the slow tier
    def test_driver_closed_loop_smoke(self, model, params):
        """poisson_trace + run_trace (the serve_bench code
        path), closed-loop so the smoke never sleeps."""
        from tiny_deepspeed_tpu.serving import ServingEngine
        from tiny_deepspeed_tpu.serving.driver import (
            poisson_trace, run_trace,
        )
        trace = poisson_trace(3, rate_rps=None, prompt_lens=[7, 13],
                              max_new_tokens=5, vocab_size=128, seed=0)
        assert [a.at_s for a in trace] == [0.0, 0.0, 0.0]
        eng = ServingEngine(model, params, _serve_config())
        res = run_trace(eng, trace, realtime=False)
        assert res["tokens"] == 15 and res["tokens_per_s"] > 0
        assert len(res["outputs"]) == 3
        assert set(res["token_latency"]) == {"p50_ms", "p99_ms",
                                             "mean_ms"}
        assert 0 < res["mean_occupancy"] <= 1.0


class TestServeSLOs:
    """Request deadlines + load shedding: every terminal outcome is a
    distinct status and nothing queues unboundedly."""

    def test_submit_sheds_on_queue_watermark(self, model, params,
                                             tmp_path):
        from tiny_deepspeed_tpu.serving import ServingEngine
        from tiny_deepspeed_tpu.telemetry import schema
        from tiny_deepspeed_tpu.utils.profiling import MetricsLogger
        path = str(tmp_path / "shed.jsonl")
        with MetricsLogger(path, stdout=False) as ml:
            eng = ServingEngine(model, params,
                                _serve_config(max_queue=2), logger=ml)
            reqs = [eng.submit(_prompt(s, 7), 4) for s in range(5)]
        shed = [r for r in reqs if r.status == "shed"]
        # 5 submitted, 0 active yet, watermark 2: the last 3 shed at the
        # door with a terminal record, never queued
        assert len(shed) == 3 and eng.queue_depth == 2
        assert all(r.done and r.finish_reason == "shed:queue_watermark"
                   and not r.tokens for r in shed)
        counts, errs = schema.validate_file(path)
        assert not errs, errs
        with open(path) as f:
            recs = [json.loads(ln) for ln in f]
        assert [r["status"] for r in recs
                if r.get("kind") == "request"] == ["shed"] * 3

    def test_submit_sheds_on_pool_pressure(self, model, params):
        from tiny_deepspeed_tpu.serving import ServingEngine
        eng = ServingEngine(
            model, params,
            _serve_config(max_active=1, num_blocks=4,
                          shed_pool_util=0.5))
        r0 = eng.submit(_prompt(1, 13), 10)  # holds >= 2/4 blocks
        eng.tick()
        r1 = eng.submit(_prompt(2, 7), 4)    # queued (backlog forms)
        r2 = eng.submit(_prompt(3, 7), 4)    # pool full + backlog: shed
        assert r1.status is None and r2.status == "shed"
        assert r2.finish_reason == "shed:pool_watermark"
        eng.drain(max_ticks=200)
        assert r0.status == "ok" and r1.status == "ok"

    def test_active_deadline_expiry_evicts(self, model, params):
        """An active request past its deadline is evicted as `expired`
        (partial tokens kept, blocks freed); its neighbor without a
        deadline is untouched and stays token-exact."""
        from tiny_deepspeed_tpu.serving import ServingEngine
        eng = ServingEngine(model, params, _serve_config(max_active=2))
        ra = eng.submit(_prompt(1, 7), 12, deadline_s=60.0)
        rb = eng.submit(_prompt(2, 7), 12)
        eng.tick()
        assert ra.state == "active"
        ra.t_arrival -= 120.0  # move its deadline into the past
        eng.tick()
        _assert_accounting(eng)
        assert ra.status == "expired" and ra.finish_reason == "deadline"
        assert 0 < len(ra.tokens) < 12  # partial delivery
        eng.drain(max_ticks=100)
        assert rb.status == "ok"
        np.testing.assert_array_equal(
            np.asarray(rb.tokens), _ref_tokens(model, params, rb.prompt,
                                               12),
            err_msg="neighbor diverged across an expiry eviction",
        )
        assert eng.pool.blocks_in_use == 0

    def test_queue_shed_on_unmeetable_deadline(self, model, params):
        """A queued request whose deadline cannot be met at the
        measured inter-token rate is shed BEFORE wasting a prefill.
        The price comes from the engine's decode-wall history, so warm
        it first; the overdue case needs no history at all."""
        from tiny_deepspeed_tpu.serving import ServingEngine
        eng = ServingEngine(model, params, _serve_config(max_active=1))
        warm = eng.submit(_prompt(1, 7), 8)
        eng.drain(max_ticks=100)  # 8 decode walls measured
        assert warm.status == "ok" and eng._gap_p50() is not None
        holder = eng.submit(_prompt(2, 7), 12)   # occupies the 1 slot
        eng.tick()
        # queued behind it: needs 30 tokens but the deadline is one
        # measured tick wide — unmeetable at any realistic rate
        tight = eng.submit(_prompt(3, 7), 30,
                           deadline_s=eng._gap_p50() * 1.0)
        eng.tick()
        assert tight.status == "shed"
        assert tight.finish_reason.startswith("shed:deadline")
        assert not tight.tokens  # never admitted, no prefill paid
        eng.drain(max_ticks=200)
        assert holder.status == "ok"

    def test_drain_max_ticks_truncation(self, model, params):
        from tiny_deepspeed_tpu.serving import ServingEngine
        eng = ServingEngine(model, params, _serve_config())
        eng.submit(_prompt(1, 7), 20)
        with pytest.raises(RuntimeError, match="drain exceeded 2 ticks"):
            eng.drain(max_ticks=2)


class TestDecodeHealthGuard:
    """Non-finite decode logits: quarantine the slot, keep the batch;
    watchdog warm restart on persistence."""

    def test_quarantine_storm_exact_pool_accounting(self, model,
                                                    params):
        """Poison EVERY active slot in one tick: all quarantined as
        `failed`, every freed block returns to the free list exactly
        once (no loss, no double-free), and the engine keeps serving —
        a fresh request admits onto the reclaimed blocks and is
        token-exact."""
        from tiny_deepspeed_tpu.serving import ServingEngine
        eng = ServingEngine(model, params,
                            _serve_config(guard_k_restart=3))
        storm = [eng.submit(_prompt(s, 7), 10) for s in (1, 2, 3)]
        eng.tick()
        assert eng.n_active == 3
        for i in eng.active_slots():
            eng.poison_slot(i)
        eng.tick()
        _assert_accounting(eng)
        assert [r.status for r in storm] == ["failed"] * 3
        assert all(r.finish_reason == "nonfinite_logits" for r in storm)
        free = [b for kind in eng.pool._free for b in kind]
        assert len(free) == len(set(free)) == eng.pool.num_usable, (
            "quarantine leaked or double-freed pool blocks"
        )
        fresh = eng.submit(_prompt(4, 7), 10)
        eng.drain(max_ticks=100)
        assert fresh.status == "ok"
        np.testing.assert_array_equal(
            np.asarray(fresh.tokens),
            _ref_tokens(model, params, fresh.prompt, 10),
            err_msg="post-storm admission corrupted",
        )
        assert eng.restarts == 0  # one poisoned tick < k_restart

    # demoted to slow (ISSUE-12 tier-1 budget): neighbor survival under
    # quarantine stays pinned by the slow chaos soak (every unpoisoned
    # request token-exact under a multi-fault schedule); the quick
    # quarantine-storm test keeps the freed-exactly-once accounting
    @pytest.mark.slow
    def test_neighbor_survives_quarantine_token_exact(self, model,
                                                      params):
        from tiny_deepspeed_tpu.serving import ServingEngine
        eng = ServingEngine(model, params, _serve_config(max_active=2))
        victim = eng.submit(_prompt(1, 7), 10)
        neighbor = eng.submit(_prompt(2, 13), 10)
        eng.tick()
        eng.poison_slot(eng.active_slots()[0])  # victim admitted first
        eng.drain(max_ticks=100)
        assert victim.status == "failed"
        assert neighbor.status == "ok"
        np.testing.assert_array_equal(
            np.asarray(neighbor.tokens),
            _ref_tokens(model, params, neighbor.prompt, 10),
            err_msg="neighbor diverged across a quarantine",
        )

    # demoted to slow (ISSUE-12 tier-1 budget): the watchdog-restart
    # resume path stays quick via test_tick_exception_warm_restart
    # (same restart machinery, one compile cheaper) and the consecutive-
    # poison trip predicate is unit-level in DecodeHealthGuard
    @pytest.mark.slow
    def test_watchdog_restart_after_consecutive_poison(self, model,
                                                       params):
        """k_restart consecutive poisoned ticks trip ONE warm restart;
        the in-flight survivors re-queue and finish token-exact on the
        rebuilt pool (same compiled programs)."""
        from tiny_deepspeed_tpu.resilience import (
            Chaos, ChaosServingEngine,
        )
        from tiny_deepspeed_tpu.serving import ServingEngine
        eng = ServingEngine(model, params,
                            _serve_config(max_active=2,
                                          guard_k_restart=2))
        ce = ChaosServingEngine(eng, Chaos(seed=3,
                                           tick_nan_steps=(1, 2)))
        reqs = [ce.submit(_prompt(s, 7), 12) for s in (1, 2, 3)]
        ce.drain(max_ticks=300)
        assert eng.restarts == 1
        statuses = sorted(r.status for r in reqs)
        assert statuses.count("failed") == 2  # one per poisoned tick
        survivors = [r for r in reqs if r.status == "ok"]
        assert survivors, "someone must survive the restart"
        for r in survivors:
            np.testing.assert_array_equal(
                np.asarray(r.tokens),
                _ref_tokens(model, params, r.prompt, 12),
                err_msg=f"request {r.id} diverged across warm restart",
            )
        _assert_accounting(eng)
        assert eng.pool.blocks_in_use == 0

    def test_tick_exception_warm_restart(self, model, params):
        """A chaos-injected prefill failure trips the watchdog: the
        half-admitted request re-queues and completes token-exact after
        the restart."""
        from tiny_deepspeed_tpu.resilience import (
            Chaos, ChaosServingEngine,
        )
        from tiny_deepspeed_tpu.serving import ServingEngine
        eng = ServingEngine(model, params, _serve_config())
        ce = ChaosServingEngine(eng,
                                Chaos(seed=4, prefill_raise_steps=(0,)))
        r = ce.submit(_prompt(5, 7), 8)
        ce.drain(max_ticks=100)
        assert eng.restarts == 1 and r.status == "ok"
        np.testing.assert_array_equal(
            np.asarray(r.tokens),
            _ref_tokens(model, params, r.prompt, 8),
            err_msg="request diverged across a prefill-failure restart",
        )

    def test_guard_off_propagates_tick_exceptions(self, model, params):
        from tiny_deepspeed_tpu.serving import ServingEngine
        eng = ServingEngine(model, params,
                            _serve_config(health_guard=False))
        eng.submit(_prompt(1, 7), 4)
        eng.arm_prefill_exception(RuntimeError("boom"))
        with pytest.raises(RuntimeError, match="boom"):
            eng.tick()


class TestRequestJournal:
    """Crash-recoverable request journal + ServingEngine.recover."""

    def test_replay_tolerates_torn_tail_only(self, tmp_path):
        from tiny_deepspeed_tpu.serving.journal import RequestJournal
        p = str(tmp_path / "j.jsonl")
        with open(p, "w") as f:
            f.write(json.dumps({"ev": "submit", "id": 0,
                                "prompt": [1, 2], "max_new": 4,
                                "deadline_s": None, "seed": 0}) + "\n")
            f.write(json.dumps({"ev": "tok", "id": 0,
                                "toks": [5]}) + "\n")
            f.write('{"ev": "tok", "id": 0, "to')  # torn by the crash
        pending, done = RequestJournal.replay(p)
        assert done == [] and len(pending) == 1
        assert pending[0]["tokens"] == [5]
        # the SAME torn line mid-file is corruption, not a crash mark
        with open(p, "a") as f:
            f.write("\n" + json.dumps({"ev": "end", "id": 0,
                                       "status": "ok",
                                       "finish": "length"}) + "\n")
        with pytest.raises(ValueError, match="corrupt journal"):
            RequestJournal.replay(p)

    # demoted to slow (ISSUE-12 tier-1 budget): same-engine recover
    # parity is subsumed quick by test_chaos_journal_kill_then_recover
    # (recover after a REAL lost tick) and by the fleet failover pin
    # (tests/test_fleet.py: journal replay onto a sibling, active AND
    # queued requests, token-identical)
    @pytest.mark.slow
    def test_recover_continues_token_exact(self, model, params,
                                           tmp_path):
        """Abandon an engine mid-flight (requests active AND queued);
        a fresh engine recovers from its journal and every interrupted
        request finishes with exactly the sequence an uninterrupted run
        produces."""
        from tiny_deepspeed_tpu.serving import ServingEngine
        jp = str(tmp_path / "journal.jsonl")
        cfg = _serve_config(max_active=2)
        engA = ServingEngine(model, params, cfg, journal=jp)
        specs = [(6, 7, 10), (7, 13, 10), (8, 7, 10)]
        ra = [engA.submit(_prompt(s, n), new) for s, n, new in specs]
        for _ in range(4):
            engA.tick()
        assert any(r.tokens for r in ra) and not all(r.done for r in ra)
        engB = ServingEngine(model, params, cfg, journal=jp)
        rec = engB.recover()
        assert [r.id for r in rec] == [r.id for r in ra]
        engB.drain(max_ticks=200)
        for r, (s, n, new) in zip(rec, specs):
            assert r.status == "ok"
            np.testing.assert_array_equal(
                np.asarray(r.tokens),
                _ref_tokens(model, params, r.prompt, new),
                err_msg=f"recovered request {r.id} diverged",
            )

    def test_recover_closes_eos_finished_request(self, model, params,
                                                 tmp_path):
        """A request whose journaled prefix already ends in eos — but
        whose end line was torn away by the crash — must be CLOSED OUT
        at recovery, not re-queued: re-admitting it would decode past
        its eos and diverge from the uninterrupted run."""
        from tiny_deepspeed_tpu.serving import ServingEngine
        from tiny_deepspeed_tpu.serving.journal import RequestJournal
        jp = str(tmp_path / "journal.jsonl")
        eos = 42
        with open(jp, "w") as f:
            f.write(json.dumps({"ev": "submit", "id": 0,
                                "prompt": [1, 2, 3], "max_new": 8,
                                "deadline_s": None, "seed": 0}) + "\n")
            f.write(json.dumps({"ev": "tok", "id": 0,
                                "toks": [5, 9, eos]}) + "\n")
        eng = ServingEngine(model, params,
                            _serve_config(eos_id=eos), journal=jp)
        rec = eng.recover()
        assert rec == [] and eng.queue_depth == 0
        # the close-out landed an end line: a second replay sees the
        # request finished, so a crash loop cannot resurrect it either
        pending, done = RequestJournal.replay(jp)
        assert pending == [] and done == [0]

    def test_chaos_journal_kill_then_recover(self, model, params,
                                             tmp_path):
        """The chaos kill between journal-append and commit loses that
        tick's token lines; recovery re-decodes them to the same values
        (greedy continuation is position-keyed, not journal-keyed)."""
        from tiny_deepspeed_tpu.resilience import (
            Chaos, ChaosServingEngine,
        )
        from tiny_deepspeed_tpu.serving import ServingEngine, ServingKilled
        jp = str(tmp_path / "journal.jsonl")
        cfg = _serve_config(max_active=2)
        eng = ServingEngine(model, params, cfg, journal=jp)
        ce = ChaosServingEngine(eng, Chaos(seed=5, journal_kill_step=3))
        reqs = [ce.submit(_prompt(s, 7), 10) for s in (1, 2)]
        with pytest.raises(ServingKilled):
            ce.drain(max_ticks=100)
        assert not any(r.done for r in reqs)
        engB = ServingEngine(model, params, cfg, journal=jp)
        rec = engB.recover()
        assert len(rec) == 2
        # the killed tick's tokens are NOT in the journal prefix
        assert all(len(r.tokens) < len(o.tokens)
                   for r, o in zip(rec, reqs))
        engB.drain(max_ticks=200)
        for r in rec:
            np.testing.assert_array_equal(
                np.asarray(r.tokens),
                _ref_tokens(model, params, r.prompt, 10),
                err_msg=f"post-kill recovery diverged for {r.id}",
            )


class TestTemperatureDeterminism:
    # demoted to slow (ISSUE-12 tier-1 budget): the (seed, position)
    # key identity is unit-pinned quick in TestSamplingCore, and the
    # engine-level temp>0 tight-vs-roomy resume determinism stays
    # pinned by the slow spec-decoding determinism tests (both
    # drafters) plus this test in the slow tier
    @pytest.mark.slow
    def test_preemption_resume_deterministic_nongreedy(self, model,
                                                       params):
        """temperature > 0: a preempted-and-resumed request re-samples
        the SAME tokens as an undisturbed run — the sampling key for
        output position i of request r depends only on (r.seed, i),
        never on scheduler state (the ServingEngine docstring's
        guarantee)."""
        from tiny_deepspeed_tpu.serving import ServingEngine
        kw = dict(block_tokens=8, temperature=1.0, top_k=16)
        tight = ServingEngine(
            model, params,
            _serve_config(max_active=3, num_blocks=5, **kw))
        roomy = ServingEngine(
            model, params,
            _serve_config(max_active=3, num_blocks=24, **kw))
        outs = []
        preemptions = []
        for eng in (tight, roomy):
            reqs = [eng.submit(_prompt(s, 10), 14, seed=100 + s)
                    for s in (1, 2, 3)]
            eng.drain(max_ticks=2000)
            outs.append([list(r.tokens) for r in reqs])
            preemptions.append(sum(r.preemptions for r in reqs))
        assert preemptions[0] >= 1, (
            "tight pool was sized to force at least one preemption"
        )
        assert preemptions[1] == 0
        assert outs[0] == outs[1], (
            "temperature>0 resume diverged from the undisturbed run"
        )


class TestRunTraceGuards:
    def test_no_progress_bound_names_state(self, model, params):
        """An engine that can never admit its queue must raise the
        no-progress bound (naming queue/pool state), not spin to
        max_ticks."""
        from tiny_deepspeed_tpu.serving import ServingEngine
        from tiny_deepspeed_tpu.serving.driver import Arrival, run_trace
        eng = ServingEngine(model, params, _serve_config())
        # simulate the post-incident pool shrink: every block vanishes
        # after the admission check, so the queued prompt never admits
        eng.pool._free = [[]]
        with pytest.raises(RuntimeError,
                           match=r"no progress .* queue_depth=1"):
            run_trace(eng, [Arrival(0.0, _prompt(1, 7), 4)],
                      realtime=False, no_progress_ticks=10)


class TestOffPathSafety:
    def test_training_hlo_identical_with_serving_imported(self):
        """The training step's HLO is byte-identical with the serving
        package imported AND a live ServingEngine constructed — in a
        fresh subprocess, so the import order is genuinely
        before/after (an in-process pin would be vacuous once any other
        test imported serving).  The robustness layer rides the same
        pin: serving.guard and serving.journal are imported explicitly
        and the engine is built with the health guard ON (its default),
        so the ISSUE-8 acceptance 'training HLO byte-identical with
        serving.guard imported' is exactly what this asserts."""
        script = r"""
import json
import jax
jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp
import numpy as np
import sys
assert not any("serving" in m for m in sys.modules), "import leaked"
from tiny_deepspeed_tpu import GPTConfig, GPT2Model, SGD, SingleDevice
cfg = GPTConfig(block_size=32, vocab_size=128, n_layer=2, n_head=2,
                n_embd=32, compute_dtype=jnp.float32)
batch = (np.zeros((2, 32), np.int32), np.zeros((2, 32), np.int32))
eng = SingleDevice(GPT2Model(cfg), SGD(lr=0.1))
state = eng.init(jax.random.PRNGKey(0))
before = eng._step.lower(state, batch).as_text()
from tiny_deepspeed_tpu.serving import ServeConfig, ServingEngine
from tiny_deepspeed_tpu.serving import guard as _guard   # noqa: F401
from tiny_deepspeed_tpu.serving import journal as _jrn   # noqa: F401
from tiny_deepspeed_tpu.serving import spec as _spec     # noqa: F401
from tiny_deepspeed_tpu.serving import drafter as _drf   # noqa: F401
model = GPT2Model(cfg)
se = ServingEngine(model, model.init(jax.random.PRNGKey(0)),
                   ServeConfig(max_active=2, num_blocks=4,
                               block_tokens=8, health_guard=True))
# a SPECULATIVE engine constructed too: the spec machinery (drafter +
# verify program) must not perturb the training step's HLO either
se2 = ServingEngine(model, model.init(jax.random.PRNGKey(0)),
                    ServeConfig(max_active=2, num_blocks=4,
                                block_tokens=8, spec_draft="ngram",
                                spec_k=2))
# ...and the live observability plane ON (schema v15): telemetry +
# aggregator + SLO tracker attached, the /metrics exporter serving on a
# loopback port, a request actually served and scraped through it — all
# host-side by contract, so the training HLO must still not move
from tiny_deepspeed_tpu.telemetry import Telemetry
from tiny_deepspeed_tpu.telemetry.live import LiveAggregator, LiveExporter
from tiny_deepspeed_tpu.telemetry.slo import SLOTracker
import urllib.request
se.telemetry = Telemetry()
agg = LiveAggregator()
exp = LiveExporter(agg, slo=SLOTracker(), port=0)
lport = exp.start()
se.attach_live(agg)
se.attach_slo(SLOTracker())
lr = se.submit([1, 2, 3], 2)
se.drain(max_ticks=50)
assert lr.status == "ok", lr.status
scrape = urllib.request.urlopen(
    f"http://127.0.0.1:{lport}/metrics", timeout=10).read().decode()
assert "serve_tokens_total" in scrape, scrape[:200]
exp.stop()
eng2 = SingleDevice(GPT2Model(cfg), SGD(lr=0.1))
state2 = eng2.init(jax.random.PRNGKey(0))
after = eng2._step.lower(state2, batch).as_text()
print(json.dumps({"identical": before == after,
                  "n": len(before)}))
"""
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        env.pop("XLA_FLAGS", None)  # single-device is enough, and faster
        out = subprocess.run(
            [sys.executable, "-c", script],
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(
                __file__))),
            capture_output=True, text=True, timeout=300, env=env,
        )
        assert out.returncode == 0, out.stderr[-2000:]
        rec = json.loads(out.stdout.strip().splitlines()[-1])
        assert rec["identical"], (
            "training HLO changed with serving imported+instantiated"
        )


@pytest.mark.slow
class TestServingSoak:
    """Multi-minute acceptance runs: throughput vs serial, preemption."""

    def test_concurrent_beats_serial_at_greedy_parity(self):
        """>= 4 concurrent requests through the batched engine move more
        aggregate tokens/s than the same trace served one-at-a-time via
        `generate` — at token-exact greedy parity per request (the
        ISSUE's headline acceptance).

        Scale matters on the CPU mesh: below ~6 layers x 256 embd the
        per-TICK costs that batching amortizes (host round-trip, block-
        table gathers) exceed the per-token model compute itself and the
        fully-on-device serial fori_loop wins — measured 0.92x at
        2Lx32D, 0.71x at 4Lx128D, 12.7x at 6Lx256D (PROFILE.md "Decode
        under load").  The production claim is the 6x256 point; real
        serving models are orders of magnitude past the crossover."""
        import dataclasses

        from tiny_deepspeed_tpu.models import ALL_PRESETS, build_model
        from tiny_deepspeed_tpu.serving import ServingEngine
        from tiny_deepspeed_tpu.serving.driver import (
            poisson_trace, run_serial, run_trace,
        )
        cfg_m = dataclasses.replace(
            ALL_PRESETS["tiny"], n_layer=6, n_embd=256, n_head=4)
        model = build_model(cfg_m)
        params = model.init(jax.random.PRNGKey(0))
        trace = poisson_trace(12, rate_rps=None, prompt_lens=[7, 13],
                              max_new_tokens=24, vocab_size=512, seed=0)
        # max_seq_tokens sized to the trace (13 + 24 -> 40): the decode
        # panel reads 40 positions/slot, comparable to generate's cache
        cfg = _serve_config(max_active=4, num_blocks=32,
                            max_seq_tokens=40)
        eng = ServingEngine(model, params, cfg)
        # warm both paths on the SAME engine/jits: compiles out of the
        # measured wall
        run_trace(eng, trace[:4], realtime=False)
        run_serial(model, params, trace[:2])
        res = run_trace(eng, trace, realtime=False)
        ser = run_serial(model, params, trace)
        for rid, toks in enumerate(sorted(res["outputs"])):
            np.testing.assert_array_equal(
                np.asarray(res["outputs"][toks]),
                np.asarray(ser["outputs"][rid]),
                err_msg=f"trace request {rid} diverged from generate()",
            )
        assert res["mean_occupancy"] > 0.5  # truly concurrent
        assert res["tokens_per_s"] > 1.1 * ser["tokens_per_s"], (
            f"continuous batching {res['tokens_per_s']} tok/s did not "
            f"beat serial {ser['tokens_per_s']} tok/s"
        )

    def test_preemption_continues_greedy_exact(self, model, params):
        """Block exhaustion preempts the youngest request; after
        re-admission (re-prefilling prompt + produced tokens) its final
        output is still token-exact with `generate`."""
        from tiny_deepspeed_tpu.serving import ServingEngine
        eng = ServingEngine(
            model, params,
            _serve_config(max_active=3, num_blocks=5, block_tokens=8))
        reqs = [eng.submit(_prompt(s, 10), 14) for s in (1, 2, 3)]
        eng.drain(max_ticks=2000)
        assert sum(r.preemptions for r in reqs) >= 1, (
            "pool was sized to force at least one preemption"
        )
        for r in reqs:
            np.testing.assert_array_equal(
                np.asarray(r.tokens),
                _ref_tokens(model, params, r.prompt, 14),
                err_msg=f"request {r.id} diverged after preemption",
            )


@pytest.mark.slow
class TestServingFaultSoak:
    """ISSUE-8 acceptance runs: real SIGKILL recovery, goodput under a
    sustained fault schedule.  Slow tier from the start — each pays
    fresh compiles in subprocesses or long drains."""

    def test_kill_mid_trace_sigkill_recovery_token_exact(self,
                                                         tmp_path):
        """SIGKILL the serving process from the journal's commit hook
        (a REAL death between journal-append and fsync), recover a
        fresh engine in a new process, and pin that every interrupted
        request's FINAL sequence equals the uninterrupted run's — the
        headline crash-recovery acceptance."""
        here = os.path.dirname(os.path.abspath(__file__))
        jp = str(tmp_path / "journal.jsonl")
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        env.pop("XLA_FLAGS", None)

        def run(mode, check=True):
            out = subprocess.run(
                [sys.executable, os.path.join(here, "serving_worker.py"),
                 mode, jp],
                capture_output=True, text=True, timeout=600, env=env,
            )
            if check:
                assert out.returncode == 0, out.stderr[-2000:]
                return json.loads(out.stdout.strip().splitlines()[-1])
            return out

        straight = run("straight")["outputs"]
        killed = run("serve", check=False)
        assert killed.returncode == -9, (
            f"worker was supposed to die by SIGKILL, got rc="
            f"{killed.returncode}: {killed.stderr[-1000:]}"
        )
        assert os.path.exists(jp), "journal must survive the kill"
        rec = run("recover")
        assert rec["recovered"], "the kill left no in-flight requests?"
        assert all(s == "ok" for s in rec["statuses"].values())
        for rid, toks in rec["outputs"].items():
            assert toks == straight[rid], (
                f"request {rid} diverged across SIGKILL+recover:\n"
                f"  recovered: {toks}\n  straight:  {straight[rid]}"
            )

    def test_chaos_goodput_counts_exact_and_neighbors_unharmed(
            self, model, params):
        """Slot-poison + tick-delay chaos over a 10-request closed-loop
        trace: the poisoned requests fail, EVERY other request finishes
        `ok` AND token-exact with `generate` (no whole-batch failure),
        and the JSONL/summary status counts are exact for the
        deterministic fault schedule."""
        from tiny_deepspeed_tpu.resilience import (
            Chaos, ChaosServingEngine,
        )
        from tiny_deepspeed_tpu.serving import ServingEngine
        from tiny_deepspeed_tpu.serving.driver import (
            poisson_trace, run_trace,
        )
        trace = poisson_trace(10, rate_rps=None, prompt_lens=[7, 13],
                              max_new_tokens=12, vocab_size=128, seed=0)
        eng = ServingEngine(model, params,
                            _serve_config(max_active=4, num_blocks=24))
        # two NON-consecutive poisons (no watchdog restart) + one delay
        chaos = Chaos(seed=7, tick_nan_steps=(4, 8),
                      tick_delay_steps=(6,), delay_s=0.05)
        res = run_trace(ChaosServingEngine(eng, chaos), trace,
                        realtime=False)
        counts = res["status_counts"]
        assert counts == {"ok": 8, "shed": 0, "expired": 0,
                          "failed": 2}, counts
        assert res["restarts"] == 0
        n_nan = sum(1 for f in chaos.injected
                    if f["fault"] == "tick_nan" and f.get("slot", -1)
                    >= 0)
        assert counts["failed"] == n_nan
        assert 0 < res["ok_tokens_per_s"] <= res["tokens_per_s"]
        ok = [r for r in res["requests"] if r.status == "ok"]
        for r in ok:
            np.testing.assert_array_equal(
                np.asarray(r.tokens),
                _ref_tokens(model, params, r.prompt, 12),
                err_msg=f"unpoisoned request {r.id} diverged under "
                        "chaos",
            )
