# Copyright 2026 tiny-deepspeed-tpu authors
# SPDX-License-Identifier: Apache-2.0

"""Pallas decode kernel for EVA attention over the paged pool: one query a
slot against TWO valid ranges of its block-table row.

The pool and the way it is read are `ops/paged_attn_pallas.py`'s: the
arrays as they rest, (blocks, bt, L * KVH * Dh), a (block, layer) one
(bt, KVH * Dh) window, the queries block-diagonal over the merged
dimension so that one product gives every head's scores, an online
softmax in float32 carried across the grid's sequential dimension.  What
differs is what a slot's table row holds (models/evabyte.EvaLayout): the
window's blocks first, of which rows [0, n_win) are live, then the
summary blocks, of which rows [0, n_sum) are visible.  Both bounds ride
the scalar prefetch, and the grid visits the window's steps, then the
summaries', then one last step for the position's own key and value.

Only live blocks cross HBM.  A table entry past a range's bound names
the range's last live block again: Pallas fetches a block only when its
index changes from one step to the next, so a dead step brings nothing,
and its arithmetic is skipped.  At 32k of context a slot holds 4096 rows
of table and attends 1-3 thousand of them; the dead ones cost a grid
step (a third of a microsecond), not their bytes.

The products take the pool's bf16 as it is, with float32 accumulation:
a product of two bf16 numbers is exact in float32, so q . k is what
float32 arithmetic on the same operands gives; the scale is applied to
the float32 scores.  The softmax weights are rounded to the pool's dtype
before they multiply V (a relative 2^-9 on each of hundreds of weights,
which averages out an order under the rounding of the result itself);
the running sums and the rescaling stay float32.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import paged_attn_pallas as _paged

_MASKED = -1e30
# pool rows a grid step folds (paged_attn_pallas._STEP_TOKENS: a step
# costs a third of a microsecond whatever it brings)
_STEP_TOKENS = 256


def _eva_kernel(
    # scalar prefetch
    tables_ref, nwin_ref, nsum_ref, l_ref,
    # inputs, outputs, scratch
    *refs,
    bt: int, nb: int, npw: int, nps: int, scale: float,
):
    q_ref = refs[0]
    k_refs, v_refs = refs[1:1 + nb], refs[1 + nb:1 + 2 * nb]
    sk_ref, sv_ref, o_ref, acc, m, ll = refs[1 + 2 * nb:]
    s = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc[...] = jnp.zeros(acc.shape, jnp.float32)
        m[...] = jnp.full(m.shape, _MASKED, jnp.float32)
        ll[...] = jnp.zeros(ll.shape, jnp.float32)

    q = q_ref[0]  # (R, C), the pool's dtype

    def dot_nt(a, b):  # a @ b^T over the lanes of both
        return jax.lax.dot_general(
            a, b, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)

    def fold(scores, values):
        """Online-softmax update: scores (R, T') float32; values(p) the
        weighted rows (R, C)."""
        m_new = jnp.maximum(m[...], jnp.max(scores, axis=-1, keepdims=True))
        alpha = jnp.exp(m[...] - m_new)
        p = jnp.exp(scores - m_new)
        ll[...] = ll[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc[...] = acc[...] * alpha + values(p)
        m[...] = m_new

    def rows(block_refs):  # the step's nb blocks, one under the other
        return jnp.concatenate([r[0] for r in block_refs], axis=0)

    def pool_step(first_row, bound):
        scores = dot_nt(q, rows(k_refs)) * scale          # (R, nb * bt)
        at = first_row + jax.lax.broadcasted_iota(
            jnp.int32, scores.shape, 1)
        fold(jnp.where(at < bound, scores, _MASKED),
             lambda p: jnp.dot(p.astype(q.dtype), rows(v_refs),
                               preferred_element_type=jnp.float32))

    n_win, n_sum = nwin_ref[s], nsum_ref[s]
    step = nb * bt

    @pl.when(jnp.logical_and(j < npw, j * step < n_win))
    def _window():
        pool_step(j * step, n_win)

    @pl.when(jnp.logical_and(
        jnp.logical_and(j >= npw, j < npw + nps), (j - npw) * step < n_sum))
    def _summaries():
        pool_step((j - npw) * step, n_sum)

    @pl.when(j == npw + nps)
    def _self_and_emit():
        # one key, one value: a row sum and a scaled row, no product
        sk = sk_ref[0].astype(jnp.float32)                 # (1, C)
        sv = sv_ref[0].astype(jnp.float32)
        own = jnp.sum(q.astype(jnp.float32) * sk, axis=-1, keepdims=True)
        fold(own * scale, lambda p: p * sv)
        o_ref[0] = (acc[...] / ll[...]).astype(o_ref.dtype)


def eva_paged_attention_kernel(q, view, tables, n_win, n_sum, l, self_kv,
                               *, window_blocks: int):
    """q (S, H, 1, Dh); view: serving.pool.KVPoolView, unquantized, as it
    rests; tables (S, window_blocks + summary blocks) int32; n_win, n_sum
    (S,): live window rows and visible summary rows a slot; l: the layer
    (traced); self_kv = (k, v), each (S, H, 1, Dh).  -> (S, H, 1, Dh) in
    q's dtype."""
    if view.k_scale is not None:
        raise ValueError("the EVA decode kernel reads no quantized pool")
    s, h, _, dh = q.shape
    c = h * dh
    bt = view.k.shape[1]
    pdt = view.k.dtype
    nw_e = window_blocks
    ns_e = tables.shape[1] - nw_e
    nb = max(1, min(nw_e, ns_e, _STEP_TOKENS // bt))
    npw, nps = -(-nw_e // nb), -(-ns_e // nb)
    rpad = -h % 8

    # block-diagonal queries: row h carries its Dh numbers in head h's
    # columns (paged_attn_pallas.py)
    eye = jnp.eye(h, dtype=pdt)
    qbd = (q[:, :, 0].astype(pdt)[:, :, None, :]
           * eye[None, :, :, None]).reshape(s, h, c)
    qbd = jnp.pad(qbd, ((0, 0), (0, rpad), (0, 0)))

    def entry_spec(i):
        """Block i of a step.  Window steps [0, npw) take table entry
        j * nb + i, summary steps the same past the window's entries;
        an entry beyond a range's live rows names the range's last live
        block (entry 0 of it where none is), and the last step those of
        the step before it: nothing new is fetched."""
        def index(si, j, tr, wr, sr, lr):
            last_w = jnp.maximum(-(-wr[si] // bt), 1) - 1
            last_s = jnp.maximum(-(-sr[si] // bt), 1) - 1
            e_w = jnp.minimum(j * nb + i, jnp.minimum(last_w, nw_e - 1))
            js = jnp.minimum(j, npw + nps - 1) - npw
            e_s = nw_e + jnp.minimum(js * nb + i,
                                     jnp.minimum(last_s, ns_e - 1))
            return (tr[si, jnp.where(j < npw, e_w, e_s)], 0, lr[0])
        return pl.BlockSpec((1, bt, c), index)

    pool_specs = [entry_spec(i) for i in range(nb)]
    row_spec = pl.BlockSpec((1, h + rpad, c),
                            lambda si, j, tr, wr, sr, lr: (si, 0, 0))
    self_spec = pl.BlockSpec((1, 1, c),
                             lambda si, j, tr, wr, sr, lr: (si, 0, 0))
    sk, sv = (a.swapaxes(1, 2).reshape(s, 1, c).astype(pdt)
              for a in self_kv)
    out = pl.pallas_call(
        functools.partial(_eva_kernel, bt=bt, nb=nb, npw=npw, nps=nps,
                          scale=1.0 / math.sqrt(dh)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(s, npw + nps + 1),
            in_specs=[row_spec] + 2 * pool_specs + [self_spec, self_spec],
            out_specs=row_spec,
            scratch_shapes=[
                pltpu.VMEM((h + rpad, c), jnp.float32),
                pltpu.VMEM((h + rpad, 1), jnp.float32),
                pltpu.VMEM((h + rpad, 1), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((s, h + rpad, c), q.dtype),
        interpret=_paged.INTERPRET,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=64 * 1024 * 1024,
        ),
        name="tds_eva_paged_attn",
    )(tables.astype(jnp.int32), n_win.astype(jnp.int32),
      n_sum.astype(jnp.int32), jnp.reshape(jnp.asarray(l, jnp.int32), (1,)),
      qbd, *(nb * [view.k]), *(nb * [view.v]), sk, sv)
    # a row's own head out of its C columns
    hsel = jnp.arange(h)
    out = out[:, :h].reshape(s, h, h, dh)[:, hsel, hsel]   # (S, H, Dh)
    return out[:, :, None, :]
