# Copyright 2026 tiny-deepspeed-tpu authors
# SPDX-License-Identifier: Apache-2.0

"""Pallas kernel numerics, run in interpret mode on the CPU CI mesh.

On real TPU the same kernels are exercised by the benchmark's cells and the
examples; this guards the kernel *logic* (blocking, grid accumulation, stats layout) in CI.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tiny_deepspeed_tpu.ops.layernorm_pallas as LNP
from tiny_deepspeed_tpu.ops.layernorm import _ln_fwd_xla


@pytest.fixture(autouse=True)
def interpret_mode(monkeypatch):
    import tiny_deepspeed_tpu.optim.adamw_pallas as AP
    monkeypatch.setattr(LNP, "INTERPRET", True)
    monkeypatch.setattr(AP, "INTERPRET", True)


def make(rows=64, n=128, dtype=jnp.float32):
    k = jax.random.split(jax.random.PRNGKey(0), 4)
    x = jax.random.normal(k[0], (rows, n), dtype)
    w = jax.random.normal(k[1], (n,), jnp.float32)
    b = jax.random.normal(k[2], (n,), jnp.float32)
    gy = jax.random.normal(k[3], (rows, n), dtype)
    return x, w, b, gy


class TestPallasLayerNorm:
    def test_fwd_matches_xla(self):
        x, w, b, _ = make()
        y0, m0, r0 = _ln_fwd_xla(x, w, b, 1e-5)
        y1, m1, r1 = LNP.ln_fwd_pallas(x, w, b)
        np.testing.assert_allclose(y0, y1, rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(m0, m1, rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(r0, r1, rtol=1e-4, atol=1e-5)

    def test_fwd_3d_input(self):
        x, w, b, _ = make(rows=64, n=128)
        x3 = x.reshape(4, 16, 128)
        y0, m0, r0 = _ln_fwd_xla(x3, w, b, 1e-5)
        y1, m1, r1 = LNP.ln_fwd_pallas(x3, w, b)
        assert y1.shape == x3.shape and m1.shape == (4, 16)
        np.testing.assert_allclose(y0, y1, rtol=1e-5, atol=1e-5)

    def test_dx_matches_closed_form(self):
        x, w, b, gy = make()
        _, mean, rstd = _ln_fwd_xla(x, w, b, 1e-5)
        from tiny_deepspeed_tpu.ops import layernorm as LN
        # closed-form via the XLA formula body (bypassing TPU dispatch)
        n = x.shape[-1]
        xf = x.astype(jnp.float32)
        gyf = gy.astype(jnp.float32)
        xhat = (xf - mean[..., None]) * rstd[..., None]
        dxhat = gyf * w
        c1 = jnp.sum(dxhat, -1, keepdims=True) / n
        c2 = jnp.sum(dxhat * xhat, -1, keepdims=True) / n
        dx_ref = (dxhat - c1 - xhat * c2) * rstd[..., None]
        dx_p = LNP.ln_dx_pallas(gy, x, w, mean, rstd)
        np.testing.assert_allclose(dx_p, dx_ref, rtol=1e-4, atol=1e-5)

    def test_dwdb_grid_accumulation(self):
        # rows > row block forces multi-step grid accumulation
        x, w, b, gy = make(rows=512, n=128)
        _, mean, rstd = _ln_fwd_xla(x, w, b, 1e-5)
        xf = x.astype(jnp.float32)
        gyf = gy.astype(jnp.float32)
        xhat = (xf - mean[..., None]) * rstd[..., None]
        dw_ref = jnp.sum(gyf * xhat, 0)
        db_ref = jnp.sum(gyf, 0)
        dw_p, db_p = LNP.ln_dwdb_pallas(gy, x, mean, rstd)
        np.testing.assert_allclose(dw_p, dw_ref, rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(db_p, db_ref, rtol=1e-4, atol=1e-4)

    def test_row_block_picker(self):
        assert LNP._pick_row_block(8192, 768) == 256
        rb = LNP._pick_row_block(96, 128)
        assert rb is not None and 96 % rb == 0
        assert LNP._pick_row_block(7, 128) is None  # too few rows
        # huge feature dim shrinks the block to fit VMEM
        rb = LNP._pick_row_block(4096, 8192)
        assert rb is not None and rb * 8192 * 16 <= 8 * 1024 * 1024
        # Mosaic tiles (8, 128): a block that is not the whole array must
        # have a multiple-of-8 row count.  300 rows (a generate() prompt)
        # has no such divisor <= 256 -> XLA; the old picker chose 150 and
        # the TPU lowering refused the program on the chip
        assert LNP._pick_row_block(300, 768) is None
        assert LNP._pick_row_block(100, 768) == 100  # whole array: legal
        assert LNP._pick_row_block(12 * 1024, 768) == 256
        for rows in (264, 520, 1000, 3000, 12288):
            rb = LNP._pick_row_block(rows, 768)
            assert rb is None or (rows % rb == 0 and rb % 8 == 0), rows

    def test_pallas_supported_gate(self):
        assert LNP.pallas_supported(jnp.zeros((64, 128)))
        assert not LNP.pallas_supported(jnp.zeros((7, 128)))


class TestFlashGate:
    def test_flash_gate_follows_sequence_length(self, monkeypatch):
        """The attention gate picks the Pallas kernel only on the 128
        grid (Mosaic refuses the backward passes, and FA2's bf16 forward,
        off it — ops/attention.flash_kernel_ok), and notes what it chose
        (ops/dispatch.kernels_noted, what chip_smoke.py prints)."""
        from tiny_deepspeed_tpu.ops import attention, flash_fa2
        from tiny_deepspeed_tpu.ops.dispatch import (
            kernel_target_forced, kernels_noted,
        )
        monkeypatch.setattr(flash_fa2, "_INTERPRET", True)
        q = jnp.ones((1, 2, 128, 64), jnp.float32)
        # gates choose (and note) at TRACE time: eval_shape is enough; the
        # kernels' numerics are tests/test_flash_fa2.py's
        with kernel_target_forced("tpu"):
            kernels_noted(clear=True)
            jax.eval_shape(attention.flash_attention, q, q, q)
            assert kernels_noted(clear=True)["attention"] == [
                "pallas:fa2_q512_k512"]
            q2 = q[:, :, :100]
            jax.eval_shape(attention.flash_attention, q2, q2, q2)
            assert kernels_noted(clear=True)["attention"] == [
                "xla:dot_product_attention"]
        assert [attention.flash_kernel_ok(t)
                for t in (8, 100, 128, 300, 1024)] == [
            False, False, True, False, True]


class TestPallasAdamW:
    """Fused optimizer kernel vs the XLA update (optim/adamw_pallas.py)."""

    def _compare(self, n=9000, **opt_kw):
        import tiny_deepspeed_tpu.optim.adamw_pallas as AP
        from tiny_deepspeed_tpu.optim.adamw import AdamW

        opt = AdamW(lr=3e-3, weight_decay=0.1, fused=False, **opt_kw)
        k = jax.random.split(jax.random.PRNGKey(1), 4)
        p = jax.random.normal(k[0], (n,), jnp.float32)
        g = jax.random.normal(k[1], (n,), jnp.float32) * 0.1
        m = jax.random.normal(k[2], (n,), jnp.float32) * 0.01
        v = jnp.abs(jax.random.normal(k[3], (n,), jnp.float32)) * 0.01
        step = jnp.asarray(7, jnp.int32)

        ref_p, ref_state = opt.update_one(
            "w", p, g, {"m": m, "v": v}, step
        )
        got_p, got_m, got_v = AP.adamw_update_pallas(
            p, g, m, v, step, lr=opt.lr, b1=opt.b1, b2=opt.b2,
            eps=opt.eps, wd=opt.weight_decay, decoupled=opt.decoupled,
            maximize=opt.maximize,
        )
        np.testing.assert_allclose(got_p, ref_p, rtol=1e-6, atol=1e-7)
        np.testing.assert_allclose(got_m, ref_state["m"], rtol=1e-6,
                                   atol=1e-7)
        np.testing.assert_allclose(got_v, ref_state["v"], rtol=1e-6,
                                   atol=1e-7)

    def test_matches_xla(self):
        self._compare()

    def test_matches_xla_decoupled_maximize(self):
        self._compare(decoupled=True, maximize=True)

    def test_padding_inert(self):
        """n not a multiple of the lane width: padded tail must not leak."""
        self._compare(n=8193)

    def test_dispatch_gates(self):
        """Fused path stays off for multi-device and small leaves."""
        from tiny_deepspeed_tpu.optim.adamw import AdamW
        # the autouse fixture sets INTERPRET=True, so the device-count
        # branch is what refuses on the 8-device CPU test mesh — for BOTH
        # auto and forced-True (the GSPMD-unpartitionable custom call must
        # never touch sharded state)
        big = jnp.zeros((100_000,), jnp.float32)
        assert not AdamW(fused="auto")._use_fused(big)
        assert not AdamW(fused=True)._use_fused(big)
        assert not AdamW(fused=False)._use_fused(big)
        small = jnp.zeros((16,), jnp.float32)
        assert not AdamW(fused=True)._use_fused(small)
