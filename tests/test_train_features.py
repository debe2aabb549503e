# Copyright 2026 tiny-deepspeed-tpu authors
# SPDX-License-Identifier: Apache-2.0

"""LR schedules, gradient clipping, and fp16-AMP dynamic loss scaling.

None of these exist in the reference: lr is a hard-coded float
(reference example/ddp/train.py:27), there is no clipping anywhere, and AMP
is an unchecked TODO (reference README.md:68).  They are capabilities a
complete framework needs, built engine-first: clipping/scaling run inside
the jitted step on (possibly ZeRO-sharded) gradients."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tiny_deepspeed_tpu import (
    GPTConfig, GPT2Model, AdamW, SGD, SingleDevice, Zero2, schedule,
)
from tiny_deepspeed_tpu.parallel.engine import TrainState

TINY = GPTConfig(
    block_size=32, vocab_size=128, n_layer=2, n_head=2, n_embd=32,
    compute_dtype=jnp.float32,
)


def make_batch(key, b=8, t=32, vocab=128):
    k1, k2 = jax.random.split(key)
    return (jax.random.randint(k1, (b, t), 0, vocab),
            jax.random.randint(k2, (b, t), 0, vocab))


@pytest.fixture(scope="module")
def model():
    return GPT2Model(TINY)


def _flat_delta(a, b):
    return np.concatenate([
        (np.asarray(x, np.float64) - np.asarray(y, np.float64)).ravel()
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))
    ])


class TestSchedules:
    def test_shapes(self):
        s = schedule.warmup_cosine(1.0, total_steps=100, warmup_steps=10,
                                   min_lr=0.1)
        step = jnp.arange(0, 201, dtype=jnp.int32)
        vals = jax.vmap(s)(step)
        assert float(vals[0]) == 0.0
        assert float(vals[10]) == pytest.approx(1.0)
        # monotone decay after warmup, floor at min_lr
        assert float(vals[100]) == pytest.approx(0.1, abs=1e-6)
        assert float(vals[200]) == pytest.approx(0.1, abs=1e-6)

        lin = schedule.warmup_linear(2.0, total_steps=20, warmup_steps=4)
        assert float(lin(jnp.int32(2))) == pytest.approx(1.0)
        assert float(lin(jnp.int32(12))) == pytest.approx(1.0)
        assert float(lin(jnp.int32(20))) == pytest.approx(0.0, abs=1e-6)

        isq = schedule.inverse_sqrt(1.0, warmup_steps=4)
        assert float(isq(jnp.int32(2))) == pytest.approx(0.5)
        assert float(isq(jnp.int32(16))) == pytest.approx(0.5)

    def test_warmup_linear_rejects_zero_peak(self):
        with pytest.raises(ValueError, match="peak_lr"):
            schedule.warmup_linear(0.0, total_steps=10)

    @pytest.mark.slow  # tier-1 budget: schedule arithmetic is
    # unit-pinned in test_optim; this engine-level identity runs in
    # the full tier
    def test_constant_schedule_matches_float_lr(self, model):
        """A constant(x) schedule and lr=x produce identical training."""
        def run(lr):
            eng = SingleDevice(model, AdamW(lr=lr))
            state = eng.init(jax.random.PRNGKey(0))
            for i in range(3):
                state, loss = eng.step(
                    state, make_batch(jax.random.PRNGKey(100 + i))
                )
            return state, float(loss)

        s1, l1 = run(1e-3)
        s2, l2 = run(schedule.constant(1e-3))
        assert l1 == pytest.approx(l2, rel=1e-6)
        for a, b in zip(jax.tree.leaves(s1.params), jax.tree.leaves(s2.params)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)

    def test_schedule_changes_lr_per_step(self, model):
        """lr=0 after warmup-step cutoff freezes params; the same jitted
        step keeps running (no re-jit per lr value)."""
        # lr: 1e-3 on step 1, 0 afterwards
        def sched(step):
            return jnp.where(step <= 1, 1e-3, 0.0).astype(jnp.float32)

        eng = SingleDevice(model, SGD(lr=sched))
        state = eng.init(jax.random.PRNGKey(0))
        state, _ = eng.step(state, make_batch(jax.random.PRNGKey(100)))
        p_after_1 = jax.tree.map(np.asarray, state.params)
        state, _ = eng.step(state, make_batch(jax.random.PRNGKey(101)))
        for a, b in zip(jax.tree.leaves(p_after_1),
                        jax.tree.leaves(state.params)):
            np.testing.assert_array_equal(a, np.asarray(b))

    def test_fused_adamw_refuses_schedule(self):
        opt = AdamW(lr=schedule.constant(1e-3), fused=True)
        with pytest.warns(UserWarning, match="lr schedule"):
            assert not opt._use_fused(jnp.zeros((256, 256), jnp.float32))


class TestGradClip:
    def test_clip_bounds_update_norm(self, model):
        """SGD(lr=1) without momentum: param delta == -grad, so the delta
        norm equals the grad norm and must be capped at grad_clip."""
        batch = make_batch(jax.random.PRNGKey(100))

        free = SingleDevice(model, SGD(lr=1.0))
        s0 = free.init(jax.random.PRNGKey(0))
        s1, _ = free.step(s0, batch)
        # engine donates its input buffers; rebuild state for reuse
        s0b = free.init(jax.random.PRNGKey(0))
        gnorm = float(np.linalg.norm(_flat_delta(s1.params, s0b.params)))
        clip = gnorm / 4.0

        clipped = SingleDevice(model, SGD(lr=1.0), grad_clip=clip)
        c0 = clipped.init(jax.random.PRNGKey(0))
        c1, _ = clipped.step(c0, batch)
        c0b = clipped.init(jax.random.PRNGKey(0))
        cnorm = float(np.linalg.norm(_flat_delta(c1.params, c0b.params)))
        assert cnorm == pytest.approx(clip, rel=1e-4)

    @pytest.mark.slow  # tier-1 budget: the clip bound + sharded-grad
    # clip pins stay quick; the no-op identity runs in the full tier
    def test_clip_noop_when_under_threshold(self, model):
        batch = make_batch(jax.random.PRNGKey(100))
        a = SingleDevice(model, AdamW(lr=1e-3))
        b = SingleDevice(model, AdamW(lr=1e-3), grad_clip=1e9)
        sa, la = a.step(a.init(jax.random.PRNGKey(0)), batch)
        sb, lb = b.step(b.init(jax.random.PRNGKey(0)), batch)
        assert float(la) == pytest.approx(float(lb), rel=1e-6)
        # the no-op multiply still reassociates XLA fusions: bitwise equality
        # is not expected, 1e-5 is
        for x, y in zip(jax.tree.leaves(sa.params), jax.tree.leaves(sb.params)):
            np.testing.assert_allclose(
                np.asarray(x), np.asarray(y), rtol=1e-5, atol=1e-7
            )

    def test_clip_on_sharded_grads(self, model):
        """Under ZeRO-2 the square-sums run on sharded grads (psum inserted
        by XLA); trajectory must match the single-device clipped run."""
        batch = make_batch(jax.random.PRNGKey(100))
        ref_eng = SingleDevice(model, SGD(lr=0.1), grad_clip=0.5)
        z2_eng = Zero2(model, SGD(lr=0.1), grad_clip=0.5)
        ref, _ = ref_eng.step(ref_eng.init(jax.random.PRNGKey(0)), batch)
        z2, _ = z2_eng.step(z2_eng.init(jax.random.PRNGKey(0)), batch)
        for x, y in zip(jax.tree.leaves(ref.params),
                        jax.tree.leaves(z2.params)):
            np.testing.assert_allclose(
                np.asarray(x), np.asarray(y), rtol=2e-4, atol=2e-5
            )


class TestLossScaling:
    @pytest.mark.slow  # tier-1 budget: the dynamic-scaling parity +
    # overflow-skip pins stay quick; the static identity is the
    # simpler special case — full tier
    def test_static_scale_matches_unscaled(self, model):
        """Static scaling in f32 is exact scale/unscale: identical result."""
        batch = make_batch(jax.random.PRNGKey(100))
        a = SingleDevice(model, SGD(lr=0.1))
        b = SingleDevice(model, SGD(lr=0.1), loss_scale=1024.0)
        sa, la = a.step(a.init(jax.random.PRNGKey(0)), batch)
        sb, lb = b.step(b.init(jax.random.PRNGKey(0)), batch)
        assert float(la) == pytest.approx(float(lb), rel=1e-6)
        for x, y in zip(jax.tree.leaves(sa.params), jax.tree.leaves(sb.params)):
            np.testing.assert_allclose(
                np.asarray(x), np.asarray(y), rtol=1e-5, atol=1e-7
            )

    def test_dynamic_scaler_state_and_growth(self, model):
        eng = SingleDevice(model, AdamW(lr=1e-3), loss_scale="dynamic",
                           loss_scale_growth_interval=2)
        state = eng.init(jax.random.PRNGKey(0))
        assert float(state.scaler["scale"]) == 2.0 ** 15
        assert int(state.scaler["good"]) == 0
        state, l0 = eng.step(state, make_batch(jax.random.PRNGKey(100)))
        assert int(state.scaler["good"]) == 1
        assert float(state.scaler["scale"]) == 2.0 ** 15
        state, _ = eng.step(state, make_batch(jax.random.PRNGKey(101)))
        # second consecutive finite step hits the growth interval
        assert float(state.scaler["scale"]) == 2.0 ** 16
        assert int(state.scaler["good"]) == 0
        # loss reported UNSCALED
        assert 0 < float(l0) < 20

    def test_overflow_skips_step_and_halves_scale(self, model):
        eng = SingleDevice(model, AdamW(lr=1e-3), loss_scale="dynamic")
        state = eng.init(jax.random.PRNGKey(0))
        # snapshot before stepping: the engine donates its input buffers
        before = jax.tree.map(np.asarray, state.params)
        # poison one parameter -> non-finite grads everywhere downstream
        params = dict(state.params)
        name = next(iter(params))
        params[name] = jnp.full_like(params[name], jnp.nan)
        poisoned = TrainState(params=params, opt_state=state.opt_state,
                              scaler=state.scaler)
        new, _ = eng.step(poisoned, make_batch(jax.random.PRNGKey(100)))
        # scale halved, streak reset, and the optimizer step NOT taken
        assert float(new.scaler["scale"]) == 2.0 ** 14
        assert int(new.scaler["good"]) == 0
        assert int(new.opt_state["step"]) == 0
        # un-poisoned params unchanged (update discarded)
        for k in before:
            if k == name:
                continue
            np.testing.assert_array_equal(np.asarray(new.params[k]),
                                          before[k])

    @pytest.mark.slow  # tier-1 budget: dynamic-scale semantics are
    # pinned quick at engine level (overflow skip/grow tests); the
    # zero2 composition runs in the full tier
    def test_dynamic_scaling_under_zero2_matches_single(self, model):
        batch = make_batch(jax.random.PRNGKey(100))
        a = SingleDevice(model, SGD(lr=0.1), loss_scale="dynamic")
        b = Zero2(model, SGD(lr=0.1), loss_scale="dynamic")
        sa, la = a.step(a.init(jax.random.PRNGKey(0)), batch)
        sb, lb = b.step(b.init(jax.random.PRNGKey(0)), batch)
        assert float(la) == pytest.approx(float(lb), rel=1e-4)
        assert float(sb.scaler["scale"]) == 2.0 ** 15
        for x, y in zip(jax.tree.leaves(sa.params), jax.tree.leaves(sb.params)):
            np.testing.assert_allclose(
                np.asarray(x), np.asarray(y), rtol=2e-4, atol=2e-5
            )

    def test_fp16_compute_with_dynamic_scaling_trains(self):
        """The actual AMP capability: float16 compute + dynamic scaling
        converges on the tiny model (fp16 grads without scaling underflow
        readily; the scaler keeps them representable)."""
        cfg = GPTConfig(
            block_size=32, vocab_size=128, n_layer=2, n_head=2, n_embd=32,
            compute_dtype=jnp.float16, attn_impl="standard_attention",
        )
        eng = SingleDevice(GPT2Model(cfg), AdamW(lr=1e-3),
                           loss_scale="dynamic")
        state = eng.init(jax.random.PRNGKey(0))
        losses = []
        for i in range(4):
            state, loss = eng.step(
                state, make_batch(jax.random.PRNGKey(100 + i))
            )
            losses.append(float(loss))
        assert all(np.isfinite(losses))
        assert losses[-1] < losses[0]


def test_checkpoint_roundtrip_with_scaler(tmp_path, model):
    """Dynamic-scaling state checkpoints and restores with the TrainState."""
    from tiny_deepspeed_tpu.utils.checkpoint import (
        load_checkpoint, save_checkpoint,
    )
    eng = SingleDevice(model, AdamW(lr=1e-3), loss_scale="dynamic",
                       loss_scale_growth_interval=1)
    state = eng.init(jax.random.PRNGKey(0))
    state, _ = eng.step(state, make_batch(jax.random.PRNGKey(100)))
    assert float(state.scaler["scale"]) == 2.0 ** 16  # grew after 1 step
    save_checkpoint(str(tmp_path), state, 1)
    restored = load_checkpoint(str(tmp_path), eng, step=1)
    assert float(restored.scaler["scale"]) == 2.0 ** 16
    assert int(restored.opt_state["step"]) == 1


class TestEvalLoss:
    def test_matches_apply_and_is_stateless(self, model):
        from tiny_deepspeed_tpu import Zero3
        eng = Zero3(model, AdamW(lr=1e-3))
        state = eng.init(jax.random.PRNGKey(0))
        batch = make_batch(jax.random.PRNGKey(100))
        direct = float(model.apply(state.params, *batch))
        v1 = float(eng.eval_loss(state, batch))
        v2 = float(eng.eval_loss(state, batch))
        assert v1 == pytest.approx(direct, rel=1e-5)
        assert v1 == v2  # deterministic, no state advanced

    @pytest.mark.slow  # tier-1 budget: eval determinism is implied by
    # eval_loss having no rng plumbed (API-level) and is re-checked
    # here with a dropout engine in the full tier
    def test_no_dropout_at_eval(self):
        cfg = GPTConfig(block_size=32, vocab_size=128, n_layer=2, n_head=2,
                        n_embd=32, compute_dtype=jnp.float32, dropout=0.3)
        m = GPT2Model(cfg)
        eng = SingleDevice(m, AdamW(lr=1e-3))
        state = eng.init(jax.random.PRNGKey(0))
        batch = make_batch(jax.random.PRNGKey(100))
        # train loss (dropout on, step 0 key) differs from eval loss
        _, train_loss = eng.step(state, batch)
        state2 = eng.init(jax.random.PRNGKey(0))
        ev = float(eng.eval_loss(state2, batch))
        # no dropout masks at eval (jit vs eager float reassociation only)
        assert ev == pytest.approx(float(m.apply(state2.params, *batch)),
                                   rel=1e-6)
        assert abs(float(train_loss) - ev) > 1e-4  # train DID use masks

    @pytest.mark.slow  # tier-1 budget: per-seed mask-stream identity
    # is also pinned by test_checkpoint's dropout-base assertions
    def test_dropout_masks_vary_with_init_seed(self):
        """Round-2 advice: the dropout base key was a hard-coded
        PRNGKey(0xD0), so differently-seeded runs replayed identical mask
        sequences.  Now init(key) folds the user key into the base: two
        engines holding the SAME params but different init seeds must see
        different step-0 dropout losses."""
        cfg = GPTConfig(block_size=32, vocab_size=128, n_layer=2, n_head=2,
                        n_embd=32, compute_dtype=jnp.float32, dropout=0.3)
        batch = make_batch(jax.random.PRNGKey(100))

        def step0_loss(seed):
            m = GPT2Model(cfg)
            eng = SingleDevice(m, AdamW(lr=1e-3))
            state = eng.init(jax.random.PRNGKey(seed))
            # overwrite params with a fixed tree so ONLY the mask stream
            # differs between the two runs
            fixed = m.init(jax.random.PRNGKey(7))
            state = dataclasses.replace(state, params=fixed)
            _, loss = eng.step(state, batch)
            return float(loss)

        assert step0_loss(0) != step0_loss(1)


@pytest.mark.slow  # tier-1 budget: generate() itself is covered by the
# (slow) model/example suites; the gather bridge runs in the full tier
def test_gather_params_enables_generate_from_sharded_state(model):
    """ZeRO-3 resting params are axis-sharded; gather_params replicates
    them so model.generate() (a non-mesh-aware jit) consumes the trained
    state directly."""
    from tiny_deepspeed_tpu import Zero3
    eng = Zero3(model, AdamW(lr=1e-3))
    state = eng.init(jax.random.PRNGKey(0))
    state, _ = eng.step(state, make_batch(jax.random.PRNGKey(100)))
    params = eng.gather_params(state)
    for leaf in jax.tree.leaves(params):
        assert leaf.sharding.is_fully_replicated
    idx = jnp.array([[1, 2, 3]], jnp.int32)
    out = model.generate(params, idx, 4, temperature=0.0)
    assert out.shape == (1, 7)
    # values equal the sharded originals
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(state.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
