# Copyright 2026 tiny-deepspeed-tpu authors
# SPDX-License-Identifier: Apache-2.0

"""Pallas fused LayerNorm kernels: the TPU re-design of the reference's one
hand-written kernel (Triton, reference ops/layernorm.py:158-298).

Three kernels, mirroring the reference's decomposition:

  fwd   — per-row normalize, emitting (y, mean, rstd)
          (reference `_layer_norm_fwd_fused` :158-207)
  dx    — per-row input grad from saved stats
          (reference `_layer_norm_bwd_dx_fused` :210-269)
  dwdb  — (dw, db) reduction over all rows
          (reference `_layer_norm_bwd_dwdb` :272-298)

The reference's dwdb uses a GPU-specific spin-lock + atomics protocol into
GROUP_SIZE_M partial stripes followed by a second reduction kernel
(:257-298).  On TPU the grid is executed *sequentially* per core, so the same
accumulation is just "+=" into the output block across grid steps — no locks,
no atomics, no second kernel.  Rows are processed in (ROW_BLOCK, N) tiles in
VMEM; stats accumulate in float32 (reference keeps an accumulation-dtype
table, ops/utils.py:13-16).
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

ROW_BLOCK = 256

# interpret mode lets the kernels run (slowly) on CPU for unit tests
INTERPRET = bool(os.environ.get("TDS_PALLAS_INTERPRET"))


def _pick_row_block(n_rows: int, n_cols: int):
    """Largest row-block <= ROW_BLOCK that DIVIDES n_rows (so no padding
    rows exist — padding would corrupt the dwdb accumulation), fits
    comfortably in VMEM, and that Mosaic can tile: a block's second-minor
    dim must be a multiple of 8 or the whole array's (300 rows used to
    pick 150-row blocks, which the TPU lowering rejects — found by the
    first chip run of generate() at a 300-token prompt).  Returns None
    when no suitable block exists; the dispatch site falls back to the
    XLA implementation."""
    cap = ROW_BLOCK
    while cap > 8 and cap * n_cols * 4 * 4 > 8 * 1024 * 1024:
        cap //= 2
    if 8 <= n_rows <= cap:
        return n_rows  # one block spanning the array
    for rb in range(cap, 7, -8):
        if n_rows % rb == 0:
            return rb
    return None


def pallas_supported(x) -> bool:
    n = x.shape[-1]
    rows = x.size // n
    return _pick_row_block(rows, n) is not None


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _ln_fwd_kernel(x_ref, w_ref, b_ref, y_ref, mean_ref, rstd_ref, *, eps):
    xf = x_ref[:].astype(jnp.float32)
    mean = jnp.mean(xf, axis=1, keepdims=True)
    var = jnp.mean(jnp.square(xf), axis=1, keepdims=True) - jnp.square(mean)
    rstd = jax.lax.rsqrt(var + eps)
    xhat = (xf - mean) * rstd
    w = w_ref[:].astype(jnp.float32)
    b = b_ref[:].astype(jnp.float32)
    y_ref[:] = (xhat * w + b).astype(y_ref.dtype)
    mean_ref[:] = mean
    rstd_ref[:] = rstd


def ln_fwd_pallas(x, w, b, eps=1e-5):
    """x (..., N) -> (y, mean, rstd); mean/rstd float32, shape x.shape[:-1]."""
    orig_shape = x.shape
    n = orig_shape[-1]
    rows = x.size // n
    x2 = x.reshape(rows, n)
    rb = _pick_row_block(rows, n)
    grid = (pl.cdiv(rows, rb),)

    y, mean, rstd = pl.pallas_call(
        functools.partial(_ln_fwd_kernel, eps=eps),
        grid=grid,
        in_specs=[
            pl.BlockSpec((rb, n), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, n), lambda i: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, n), lambda i: (0, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((rb, n), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((rb, 1), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((rb, 1), lambda i: (i, 0), memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows, n), x.dtype),
            jax.ShapeDtypeStruct((rows, 1), jnp.float32),
            jax.ShapeDtypeStruct((rows, 1), jnp.float32),
        ],
        interpret=INTERPRET,
        name="tds_ln_fwd",
    )(x2, w.reshape(1, n), b.reshape(1, n))
    return (
        y.reshape(orig_shape),
        mean.reshape(orig_shape[:-1]),
        rstd.reshape(orig_shape[:-1]),
    )


# ---------------------------------------------------------------------------
# backward: dx
# ---------------------------------------------------------------------------

def _ln_dx_kernel(gy_ref, x_ref, w_ref, mean_ref, rstd_ref, dx_ref):
    xf = x_ref[:].astype(jnp.float32)
    gyf = gy_ref[:].astype(jnp.float32)
    w = w_ref[:].astype(jnp.float32)
    mean = mean_ref[:]
    rstd = rstd_ref[:]
    n = xf.shape[1]
    xhat = (xf - mean) * rstd
    dxhat = gyf * w
    c1 = jnp.sum(dxhat, axis=1, keepdims=True) / n
    c2 = jnp.sum(dxhat * xhat, axis=1, keepdims=True) / n
    dx_ref[:] = ((dxhat - c1 - xhat * c2) * rstd).astype(dx_ref.dtype)


def ln_dx_pallas(gy, x, w, mean, rstd):
    orig_shape = x.shape
    n = orig_shape[-1]
    rows = x.size // n
    rb = _pick_row_block(rows, n)
    grid = (pl.cdiv(rows, rb),)

    dx = pl.pallas_call(
        _ln_dx_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((rb, n), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((rb, n), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, n), lambda i: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((rb, 1), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((rb, 1), lambda i: (i, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec(
            (rb, n), lambda i: (i, 0), memory_space=pltpu.VMEM
        ),
        out_shape=jax.ShapeDtypeStruct((rows, n), x.dtype),
        interpret=INTERPRET,
        name="tds_ln_dx",
    )(
        gy.reshape(rows, n),
        x.reshape(rows, n),
        w.reshape(1, n),
        mean.reshape(rows, 1),
        rstd.reshape(rows, 1),
    )
    return dx.reshape(orig_shape)


# ---------------------------------------------------------------------------
# backward: dw/db reduction
# ---------------------------------------------------------------------------

def _ln_dwdb_kernel(gy_ref, x_ref, mean_ref, rstd_ref, dw_ref, db_ref):
    # Sequential TPU grid: accumulate into the (1, N) outputs across steps —
    # replaces the reference's lock/atomics two-stage protocol (:257-298).
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        dw_ref[:] = jnp.zeros_like(dw_ref)
        db_ref[:] = jnp.zeros_like(db_ref)

    xf = x_ref[:].astype(jnp.float32)
    gyf = gy_ref[:].astype(jnp.float32)
    xhat = (xf - mean_ref[:]) * rstd_ref[:]
    dw_ref[:] += jnp.sum(gyf * xhat, axis=0, keepdims=True)
    db_ref[:] += jnp.sum(gyf, axis=0, keepdims=True)


def ln_dwdb_pallas(gy, x, mean, rstd):
    n = x.shape[-1]
    rows = x.size // n
    rb = _pick_row_block(rows, n)
    grid = (pl.cdiv(rows, rb),)

    dw, db = pl.pallas_call(
        _ln_dwdb_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((rb, n), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((rb, n), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((rb, 1), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((rb, 1), lambda i: (i, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, n), lambda i: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, n), lambda i: (0, 0), memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, n), jnp.float32),
            jax.ShapeDtypeStruct((1, n), jnp.float32),
        ],
        interpret=INTERPRET,
        name="tds_ln_dwdb",
    )(
        gy.reshape(rows, n),
        x.reshape(rows, n),
        mean.reshape(rows, 1),
        rstd.reshape(rows, 1),
    )
    return dw.reshape(n).astype(x.dtype), db.reshape(n).astype(x.dtype)


def ln_fwd_pallas_dispatch(x, w, b, eps):
    """Signature-compatible candidate for layernorm_fwd's dispatch table."""
    return ln_fwd_pallas(x, w, b, eps)
