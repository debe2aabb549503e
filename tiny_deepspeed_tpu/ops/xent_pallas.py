# Copyright 2026 tiny-deepspeed-tpu authors
# SPDX-License-Identifier: Apache-2.0

"""Pallas fused lm_head + softmax cross-entropy (TPU).

The remaining non-attention headroom at the flagship size after round 4's
FA2 kernel: the vocab-head bucket (lm_head matmul + logsumexp + gold
gather + their backward) measured 20.7 ms of a 95 ms gpt2-124m step, and
the chunked-recompute XLA formulation (`softmax_xent.fused_linear_xent`)
LOSES end-to-end at 124M because its ladder of (B, chunk, V) slabs still
round-trips every logit through HBM (PROFILE.md "chip profile" item 2).

This kernel is the flash-attention treatment applied to the loss head:

  * forward: grid (token-blocks, vocab-blocks); each (bs, bv) logit tile
    is computed on the MXU and consumed IN VMEM — online max/sumexp
    scratch accumulates the logsumexp across vocab tiles, the gold logit
    is picked out by a column-iota match, and only per-token `loss` and
    `lse` vectors (S f32 each) ever reach HBM.  The full (S, V) logits
    never exist anywhere.
  * backward: recomputes the same tiles from the stashed lse
    (`p = exp(z - lse)`, `dz = (p - onehot) * g/n`) in two passes — dx
    accumulates over vocab tiles (row-parallel), dW over token tiles
    (column-parallel) — mirroring the FA2 dq/dkv split (no cross-program
    atomics on TPU).
  * the vocab tail (50304 = 128 x 3 x 131 rarely divides a nice bv) is
    handled by masking the out-of-range columns of the LAST tile to -inf
    before any reduction — garbage from the padded block read never
    survives a `where`.

Reference counterpart: F.cross_entropy(logits.view(-1, V), ...) on fully
materialized logits (reference example/model.py:154-156).

Numerics: matmuls accumulate f32 on the MXU, stats are f32, dx returns in
x.dtype, dW in f32 (cast at the call site like the XLA path).  Parity vs
`softmax_cross_entropy` on materialized logits is pinned in
tests/test_xent_pallas.py (interpret mode); Mosaic acceptance via the v5e
AOT compile in tests/test_aot_topology.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

_INTERPRET = False  # tests flip this on CPU (no Mosaic backend there)


def _pick_bs(s: int, want: int = 256) -> int:
    """Largest token-block <= want dividing S, stepping by 8 (sublane);
    S itself when nothing fits (tiny test shapes)."""
    b = min(want, s)
    while b >= 8 and s % b:
        b -= 8
    return b if b >= 8 and s % b == 0 else s


def viable_token_block(s: int, want: int = 256) -> bool:
    """Whether the kernel has a sane token-block for S tokens: an
    8-aligned divisor <= want, or S small enough that one (S, d) block is
    itself VMEM-resident.  When this is False (e.g. a prime S > 256),
    `pallas_fused_xent` falls back to the chunked XLA path instead of
    attempting a single full-size VMEM block — also consulted by the
    shared head-impl predicate (models/gpt2.effective_xent_impl) so
    bench A/B labels can't drift from what actually ran."""
    return _pick_bs(s, want) != s or s <= want


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _xent_fwd_kernel(x_ref, w_ref, t_ref, loss_ref, lse_ref,
                     m_acc, l_acc, g_acc, *, bv, v):
    j = pl.program_id(1)
    nv = pl.num_programs(1)
    x = x_ref[...].astype(jnp.float32)          # (bs, d)
    w = w_ref[...].astype(jnp.float32)          # (d, bv)
    z = jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)     # (bs, bv)
    bs = z.shape[0]
    cols = jax.lax.broadcasted_iota(jnp.int32, (bs, bv), 1) + j * bv
    z = jnp.where(cols < v, z, NEG_INF)         # mask the vocab tail

    @pl.when(j == 0)
    def _init():
        m_acc[...] = jnp.full_like(m_acc, NEG_INF)
        l_acc[...] = jnp.zeros_like(l_acc)
        g_acc[...] = jnp.zeros_like(g_acc)

    m_prev = m_acc[...]                          # (bs, 1)
    m_new = jnp.maximum(m_prev, jnp.max(z, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    l_acc[...] = l_acc[...] * alpha + jnp.sum(
        jnp.exp(z - m_new), axis=1, keepdims=True)
    m_acc[...] = m_new
    hit = cols == t_ref[...]                     # (bs, bv) vs (bs, 1)
    g_acc[...] += jnp.sum(jnp.where(hit, z, 0.0), axis=1, keepdims=True)

    @pl.when(j == nv - 1)
    def _emit():
        lse = m_acc[...] + jnp.log(l_acc[...])
        lse_ref[...] = lse
        loss_ref[...] = lse - g_acc[...]


def _fwd(x, w, targets, *, bs, bv):
    s, d = x.shape
    v = w.shape[1]
    nv = pl.cdiv(v, bv)
    t2 = targets.reshape(s, 1).astype(jnp.int32)
    loss, lse = pl.pallas_call(
        functools.partial(_xent_fwd_kernel, bv=bv, v=v),
        grid=(s // bs, nv),
        in_specs=[
            pl.BlockSpec((bs, d), lambda i, j: (i, 0)),    # x
            pl.BlockSpec((d, bv), lambda i, j: (0, j)),    # w
            pl.BlockSpec((bs, 1), lambda i, j: (i, 0)),    # targets
        ],
        out_specs=[
            pl.BlockSpec((bs, 1), lambda i, j: (i, 0)),    # loss
            pl.BlockSpec((bs, 1), lambda i, j: (i, 0)),    # lse
        ],
        out_shape=[
            jax.ShapeDtypeStruct((s, 1), jnp.float32),
            jax.ShapeDtypeStruct((s, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bs, 1), jnp.float32),   # m
            pltpu.VMEM((bs, 1), jnp.float32),   # l
            pltpu.VMEM((bs, 1), jnp.float32),   # gold
        ],
        interpret=_INTERPRET,
        name="tds_xent_fwd",
    )(x, w, t2)
    return loss[:, 0], lse


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

def _tile_dz(x_ref, w_ref, t_ref, lse_ref, gs_ref, j, *, bv, v):
    """Recompute one (bs, bv) tile's dz = (softmax - onehot) * g/n."""
    x = x_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)
    d = w.shape[0]
    # zero the vocab-tail overhang IN W, not just in dz: the padded block
    # columns are uninitialized memory, and 0 * NaN = NaN would poison the
    # dz @ w^T contraction even though dz is 0 there
    wcols = jax.lax.broadcasted_iota(jnp.int32, (d, bv), 1) + j * bv
    w = jnp.where(wcols < v, w, 0.0)
    z = jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    bs = z.shape[0]
    cols = jax.lax.broadcasted_iota(jnp.int32, (bs, bv), 1) + j * bv
    z = jnp.where(cols < v, z, NEG_INF)
    p = jnp.exp(z - lse_ref[...])               # masked cols -> exp(-inf)=0
    dz = jnp.where(cols == t_ref[...], p - 1.0, p)
    return dz * gs_ref[0, 0], x, w


def _xent_dx_kernel(x_ref, w_ref, t_ref, lse_ref, gs_ref, dx_ref,
                    dx_acc, *, bv, v):
    j = pl.program_id(1)
    nv = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        dx_acc[...] = jnp.zeros_like(dx_acc)

    dz, _, w = _tile_dz(x_ref, w_ref, t_ref, lse_ref, gs_ref, j, bv=bv, v=v)
    dx_acc[...] += jax.lax.dot_general(
        dz, w, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)     # (bs, d)

    @pl.when(j == nv - 1)
    def _emit():
        dx_ref[...] = dx_acc[...].astype(dx_ref.dtype)


def _xent_dw_kernel(x_ref, w_ref, t_ref, lse_ref, gs_ref, dw_ref,
                    dw_acc, *, bv, v):
    # grid is (vocab-blocks, token-blocks): the dw tile stays resident
    # while token blocks stream through
    j = pl.program_id(0)
    i = pl.program_id(1)
    ns = pl.num_programs(1)

    @pl.when(i == 0)
    def _init():
        dw_acc[...] = jnp.zeros_like(dw_acc)

    dz, x, _ = _tile_dz(x_ref, w_ref, t_ref, lse_ref, gs_ref, j, bv=bv, v=v)
    dw_acc[...] += jax.lax.dot_general(
        x, dz, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)     # (d, bv)

    @pl.when(i == ns - 1)
    def _emit():
        dw_ref[...] = dw_acc[...]


def _bwd(x, w, targets, lse, gscale, *, bs, bv_dx, bv_dw):
    s, d = x.shape
    v = w.shape[1]
    t2 = targets.reshape(s, 1).astype(jnp.int32)
    gs = gscale.reshape(1, 1).astype(jnp.float32)
    stat = lambda i, j: (i, 0)
    dx = pl.pallas_call(
        functools.partial(_xent_dx_kernel, bv=bv_dx, v=v),
        grid=(s // bs, pl.cdiv(v, bv_dx)),
        in_specs=[
            pl.BlockSpec((bs, d), lambda i, j: (i, 0)),      # x
            pl.BlockSpec((d, bv_dx), lambda i, j: (0, j)),   # w
            pl.BlockSpec((bs, 1), stat),                     # targets
            pl.BlockSpec((bs, 1), stat),                     # lse
            pl.BlockSpec((1, 1), lambda i, j: (0, 0)),       # g/n
        ],
        out_specs=pl.BlockSpec((bs, d), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((s, d), x.dtype),
        scratch_shapes=[pltpu.VMEM((bs, d), jnp.float32)],
        interpret=_INTERPRET,
        name="tds_xent_dx",
    )(x, w, t2, lse, gs)

    tok = lambda j, i: (i, 0)
    dw = pl.pallas_call(
        functools.partial(_xent_dw_kernel, bv=bv_dw, v=v),
        grid=(pl.cdiv(v, bv_dw), s // bs),
        in_specs=[
            pl.BlockSpec((bs, d), tok),                      # x
            pl.BlockSpec((d, bv_dw), lambda j, i: (0, j)),   # w
            pl.BlockSpec((bs, 1), tok),                      # targets
            pl.BlockSpec((bs, 1), tok),                      # lse
            pl.BlockSpec((1, 1), lambda j, i: (0, 0)),       # g/n
        ],
        out_specs=pl.BlockSpec((d, bv_dw), lambda j, i: (0, j)),
        out_shape=jax.ShapeDtypeStruct((d, v), jnp.float32),
        scratch_shapes=[pltpu.VMEM((d, bv_dw), jnp.float32)],
        interpret=_INTERPRET,
        name="tds_xent_dw",
    )(x, w, t2, lse, gs)
    return dx, dw


# ---------------------------------------------------------------------------
# public entry
# ---------------------------------------------------------------------------

# vocab-tile widths: each pass holds one (d, bv) weight panel (double-
# buffered by the pipeline) + a (bs, bv) f32 logit tile; dx adds a
# (bs, d) f32 accumulator and dw a (d, bv) one.  1024-wide dx measured
# 0.5 MB over the 16 MB scoped-vmem limit at d=1600 (v5e AOT compile),
# so the backward passes run at 512.
_BV_FWD = 1024
_BV_DX = 512
_BV_DW = 512


def pallas_fused_xent(x, w, targets):
    """Mean NLL of logits = x @ w, logits never materialized.

    x (B, T, D) or (S, D); w (D, V); targets matching x's leading dims.
    Falls back to the chunked XLA `fused_linear_xent` when no viable
    token-block exists for this S (`viable_token_block`): without the
    guard an awkward S would run as a single (S, d) VMEM-resident block
    and blow the scoped-vmem limit at real sizes."""
    s = 1
    for dim in x.shape[:-1]:
        s *= dim
    if not viable_token_block(s):
        from .softmax_xent import fused_linear_xent
        return fused_linear_xent(x, w, targets)
    return _pallas_fused_xent(x, w, targets)


@jax.custom_vjp
def _pallas_fused_xent(x, w, targets):
    loss, _ = _pfx_fwd(x, w, targets)
    return loss


def _pfx_fwd(x, w, targets):
    d = x.shape[-1]
    xf = x.reshape(-1, d)
    tf = targets.reshape(-1)
    s = xf.shape[0]
    bs = _pick_bs(s)
    loss_vec, lse = _fwd(xf, w, tf, bs=bs, bv=_BV_FWD)
    return jnp.sum(loss_vec) / s, (x, w, targets, lse)


def _pfx_bwd(res, g):
    x, w, targets, lse = res
    lead = x.shape[:-1]
    d = x.shape[-1]
    xf = x.reshape(-1, d)
    tf = targets.reshape(-1)
    s = xf.shape[0]
    bs = _pick_bs(s)
    gscale = (g / s).astype(jnp.float32)
    dx, dw = _bwd(xf, w, tf, lse, gscale, bs=bs, bv_dx=_BV_DX,
                  bv_dw=_BV_DW)
    zero = np.zeros(targets.shape, dtype=jax.dtypes.float0)
    return dx.reshape(*lead, d), dw.astype(w.dtype), zero


_pallas_fused_xent.defvjp(_pfx_fwd, _pfx_bwd)
