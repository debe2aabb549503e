# Copyright 2026 tiny-deepspeed-tpu authors
# SPDX-License-Identifier: Apache-2.0

"""MoE GPT + expert parallelism on the 8-device CPU mesh.

The reference has no MoE / expert parallelism (SURVEY §2.20).  Acceptance:
single-device MoE trains; expert-parallel runs match single-device losses;
EP composes with TP and ZeRO; routing respects static capacity."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytestmark = pytest.mark.slow  # multi-minute composition suite (see pytest.ini)

from tiny_deepspeed_tpu import (
    MoEConfig, MoEGPT, AdamW, SingleDevice, DDP, Zero2, Zero3,
)

CFG = MoEConfig(
    block_size=32, vocab_size=128, n_layer=2, n_head=4, n_embd=32,
    n_expert=4, expert_top_k=2, compute_dtype=jnp.float32,
)


def make_batch(key, b=8, t=32, vocab=128):
    k1, k2 = jax.random.split(key)
    return (jax.random.randint(k1, (b, t), 0, vocab),
            jax.random.randint(k2, (b, t), 0, vocab))


def run_steps(engine, n=3):
    state = engine.init(jax.random.PRNGKey(0))
    losses = []
    for i in range(n):
        state, loss = engine.step(state, make_batch(jax.random.PRNGKey(100 + i)))
        losses.append(float(loss))
    return losses, state


@pytest.fixture(scope="module")
def model():
    return MoEGPT(CFG)


@pytest.fixture(scope="module")
def ref_losses(model):
    losses, _ = run_steps(SingleDevice(model, AdamW(lr=1e-3)))
    return losses


class TestMoE:
    def test_single_device_trains(self, model):
        losses, _ = run_steps(SingleDevice(model, AdamW(lr=1e-3)), n=5)
        assert losses[-1] < losses[0] + 0.1  # aux loss adds noise; sanity only
        assert all(np.isfinite(losses))

    @pytest.mark.parametrize("ep", [2, 4])
    def test_expert_parallel_matches_single_device(self, model, ref_losses, ep):
        got, _ = run_steps(DDP(model, AdamW(lr=1e-3), expert_parallel=ep))
        np.testing.assert_allclose(got, ref_losses, rtol=5e-4, atol=5e-4)

    def test_ep_composes_with_tp(self, model, ref_losses):
        got, _ = run_steps(
            DDP(model, AdamW(lr=1e-3), expert_parallel=2, tensor_parallel=2)
        )
        np.testing.assert_allclose(got, ref_losses, rtol=5e-4, atol=5e-4)

    @pytest.mark.parametrize("Engine", [Zero2, Zero3])
    def test_ep_composes_with_zero(self, model, ref_losses, Engine):
        got, _ = run_steps(Engine(model, AdamW(lr=1e-3), expert_parallel=4))
        np.testing.assert_allclose(got, ref_losses, rtol=5e-4, atol=5e-4)

    def test_expert_weights_sharded_over_expert_axis(self, model):
        eng = DDP(model, AdamW(lr=1e-3), expert_parallel=4)
        state = eng.init(jax.random.PRNGKey(0))
        spec = state.params["h.moe.fc.w"].sharding.spec  # (L, E, D, F)
        assert "expert" in spec

    def test_capacity_drops_are_bounded(self, model):
        # with capacity_factor >= k the dispatch keeps every token slot
        cfg = MoEConfig(
            block_size=32, vocab_size=128, n_layer=1, n_head=2, n_embd=16,
            n_expert=2, expert_top_k=1, capacity_factor=2.0,
            compute_dtype=jnp.float32,
        )
        m = MoEGPT(cfg)
        x = jax.random.normal(jax.random.PRNGKey(0), (64, 16))
        w = jax.random.normal(jax.random.PRNGKey(1), (16, 2)) * 0.1
        dispatch, combine, aux = m._route(x, w)
        # every token dispatched exactly once (top-1, ample capacity)
        np.testing.assert_allclose(dispatch.sum(axis=(1, 2)), 1.0)
        # combine weights = renormalized top-1 gate = 1.0 per token
        np.testing.assert_allclose(combine.sum(axis=(1, 2)), 1.0, rtol=1e-5)
        assert np.isfinite(float(aux))

    def test_generation_path(self, model):
        params = model.init(jax.random.PRNGKey(0))
        idx = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, 128)
        logits = model.apply(params, idx)
        assert logits.shape == (2, 1, 128)
        assert np.all(np.isfinite(logits))


class TestSortDispatch:
    """moe_dispatch="sort": gather/scatter dispatch parity vs the einsum
    path (see MoEConfig.moe_dispatch)."""

    def test_matches_einsum_when_nothing_drops(self):
        """With capacity ample enough that no token overflows, the two
        dispatch mechanisms are the same function: identical loss and
        identical gradients for every parameter."""
        import dataclasses
        cfg_e = dataclasses.replace(CFG, capacity_factor=4.0)
        cfg_s = dataclasses.replace(cfg_e, moe_dispatch="sort")
        m_e, m_s = MoEGPT(cfg_e), MoEGPT(cfg_s)
        params = m_e.init(jax.random.PRNGKey(0))
        idx, tgt = make_batch(jax.random.PRNGKey(1))
        l_e, g_e = jax.value_and_grad(lambda p: m_e.apply(p, idx, tgt))(params)
        l_s, g_s = jax.value_and_grad(lambda p: m_s.apply(p, idx, tgt))(params)
        np.testing.assert_allclose(float(l_e), float(l_s), rtol=1e-6)
        for k in g_e:
            np.testing.assert_allclose(
                np.asarray(g_e[k]), np.asarray(g_s[k]),
                rtol=2e-5, atol=1e-6, err_msg=k)

    def test_trains_under_overflow(self):
        """Tight capacity (drops expected): the sort path still trains to
        finite decreasing loss — drop SET may differ from einsum by design."""
        import dataclasses
        cfg = dataclasses.replace(CFG, moe_dispatch="sort",
                                  capacity_factor=0.5)
        eng = SingleDevice(MoEGPT(cfg), AdamW(lr=1e-3))
        losses, _ = run_steps(eng, n=4)
        assert all(np.isfinite(losses))

    def test_ep_falls_back_to_einsum(self):
        """Under expert parallelism the sort knob is inert — the einsum
        contraction IS the all-to-all boundary — so the loss must match
        einsum exactly."""
        import dataclasses
        from tiny_deepspeed_tpu import Zero1
        cfg_s = dataclasses.replace(CFG, moe_dispatch="sort")
        e1 = Zero1(MoEGPT(CFG), AdamW(lr=1e-3), expert_parallel=2)
        e2 = Zero1(MoEGPT(cfg_s), AdamW(lr=1e-3), expert_parallel=2)
        (l1, *_), _ = run_steps(e1, n=1)
        (l2, *_), _ = run_steps(e2, n=1)
        assert abs(l1 - l2) < 1e-5

    def test_pure_dp_runs_shard_local_sort(self):
        """Round 5: under pure data parallelism sort dispatch runs
        SHARD-LOCAL (experts replicated, each device argsorts its own
        token shard) — with ample capacity nothing drops on either path,
        so sort and einsum must agree to float tolerance, and the
        effective_dispatch predicate must say so."""
        import dataclasses
        from tiny_deepspeed_tpu import Zero1
        from tiny_deepspeed_tpu.models.moe import effective_dispatch
        roomy = dataclasses.replace(CFG, capacity_factor=4.0)
        cfg_s = dataclasses.replace(roomy, moe_dispatch="sort")
        e1 = Zero1(MoEGPT(roomy), AdamW(lr=1e-3))
        e2 = Zero1(MoEGPT(cfg_s), AdamW(lr=1e-3))
        assert effective_dispatch(cfg_s, e2.pctx) == "sort"
        (l1, *_), _ = run_steps(e1, n=1)
        (l2, *_), _ = run_steps(e2, n=1)
        assert abs(l1 - l2) < 1e-4, (l1, l2)

    def test_pure_dp_sort_composes_with_fp8_gather(self):
        """The '#scale' companions must cross the shard_map boundary
        with their f8 leaves — without them _bw hands the expert einsums
        raw float8 weights (round-5 review finding).  Loss must stay
        close to the unquantized sort path."""
        import dataclasses
        from tiny_deepspeed_tpu import Zero1
        cfg_q = dataclasses.replace(CFG, moe_dispatch="sort",
                                    capacity_factor=4.0,
                                    gather_quant="fp8")
        cfg_p = dataclasses.replace(CFG, moe_dispatch="sort",
                                    capacity_factor=4.0)
        (lq, *_), _ = run_steps(Zero1(MoEGPT(cfg_q), AdamW(lr=1e-3)), n=1)
        (lp, *_), _ = run_steps(Zero1(MoEGPT(cfg_p), AdamW(lr=1e-3)), n=1)
        assert np.isfinite(lq)
        assert abs(lq - lp) < 0.05 * max(1.0, abs(lp)), (lq, lp)

    def test_effective_dispatch_predicate(self):
        """The single fallback predicate: sort survives
        single-device and pure DP, falls back under ep/tp/sp/pipe."""
        import dataclasses
        from tiny_deepspeed_tpu import Zero1
        from tiny_deepspeed_tpu.models.moe import effective_dispatch
        cfg_s = dataclasses.replace(CFG, moe_dispatch="sort")
        assert effective_dispatch(cfg_s, None) == "sort"
        assert effective_dispatch(CFG, None) == "einsum"
        ep_eng = Zero1(MoEGPT(cfg_s), AdamW(lr=1e-3), expert_parallel=2)
        assert effective_dispatch(cfg_s, ep_eng.pctx) == "einsum"
