# Copyright 2026 tiny-deepspeed-tpu authors
# SPDX-License-Identifier: Apache-2.0

"""Pallas decode kernel for EVA attention over the paged pool: one query a
slot against TWO valid ranges of its block-table row.

The pool and the way it is read are `ops/paged_attn_pallas.py`'s: the
arrays as they rest, (blocks, bt, L * KVH * Dh), a (block, layer) one
(bt, KVH * Dh) window, the queries block-diagonal over the merged
dimension so that one product gives every head's scores, an online
softmax in float32.  What differs is what a slot's table row holds
(models/evabyte.EvaLayout): the window's blocks first, of which rows
[0, n_win) are live, then the summary blocks, of which rows [0, n_sum)
are visible.  Both bounds ride the scalar prefetch beside the table.

Grid: (S,), one grid step a slot, parallel.  Only what is live is worked
on.  The pool arrays stay in HBM (`memory_space=HBM`: no BlockSpec over
the table, no index map, no pipeline of Pallas's), and the step walks
the slot's live chunks itself: the window range's, then the summary
range's, as ONE loop of ceil(n_win / 256) + ceil(n_sum / 256) passes.  A
chunk is 256 rows of one range, `nb` blocks (`eva_steps`); the step
copies a chunk's live blocks into one half of a two-deep VMEM buffer
while it folds the other half, across the boundary of the two ranges as
well.  A block whose first row lies at or past its range's bound is
neither looked up in the table nor copied, and what its plane of the
buffer still holds is masked out of the scores and zeroed out of V.
Last comes the position's own key and value, not yet in the pool, then
the result.  A slot with both ranges empty (position 0, or an empty slot
whose table is scratch) copies nothing and goes straight there.

The products take the pool's bf16 as it is, with float32 accumulation:
a product of two bf16 numbers is exact in float32, so q . k is what
float32 arithmetic on the same operands gives; the scale is applied to
the float32 scores.  The softmax weights are rounded to the pool's dtype
before they multiply V (a relative 2^-9 on each of hundreds of weights,
which averages out an order under the rounding of the result itself);
the running sums and the rescaling stay float32.

Measured on a v5e at evabyte-6.5b's serving sizes (16 slots, 256 table
entries of 16 rows x 4096 columns, one block 128 KiB, the kernel once a
layer for 6 layers: a decode tick; PERF.md section 6, PR 33), ms a tick
with every slot empty / 7 slots live at 2.5k-18k positions (10,287 rows)
/ all 16 at 18k (30,864) / all 16 full (63,472): 0.23 / 1.62 / 4.56 /
8.63, at 623, 666 and 723 GB/s of the live rows' K and V (819 is the
chip's).  The kernel this one replaced gave every slot a grid step for
each chunk of its table row, live or not, with a BlockSpec for each of
the chunk's 16 K and 16 V blocks, and took 6.27 / 6.74 / 7.82 / 9.07.
At 256 rows a chunk the two give the same bits; 128 read 0.25 / 1.61 /
4.45 / 8.57 and 512 0.23 / 1.71 / 4.59 / 8.78.
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
# pool rows a chunk holds, of either range: the VMEM buffer is two of
# them for K and two for V, and a chunk is what one pass of the loop
# copies and folds (paged_attn_pallas._STEP_TOKENS).  The slot layout
# counts a tick's chunks by the same rule (`eva_steps`)
_STEP_TOKENS = 256


def eva_steps(window_blocks: int, summary_blocks: int,
              bt: int) -> tuple[int, int, int]:
    """(nb, npw, nps): the table entries a chunk holds, and the chunks
    that cover a table row's `window_blocks` entries and its
    `summary_blocks` entries, of `bt` rows each."""
    nb = max(1, _STEP_TOKENS // bt)
    return nb, -(-window_blocks // nb), -(-summary_blocks // nb)


def _eva_kernel(
    # scalar prefetch
    tables_ref, nwin_ref, nsum_ref, l_ref,
    # inputs, output, scratch
    q_ref, k_pool, v_pool, sk_ref, sv_ref, o_ref, kbuf, vbuf, sem, acc, m, ll,
    *, bt: int, nb: int, nw_e: int, scale: float,
):
    """One slot a grid step: fold the live chunks of the slot's window
    range, then of its summary range, `nb` blocks a chunk, then the
    position's own key and value, into the online softmax of its R query
    rows.  The pool arrays stay in HBM; the kernel copies the blocks it
    folds into a two-deep VMEM buffer itself, the next chunk's while it
    folds this one's, across the boundary of the two ranges as well."""
    s = pl.program_id(0)
    c_lanes = kbuf.shape[-1]
    col = pl.ds(pl.multiple_of(l_ref[0] * c_lanes, c_lanes), c_lanes)
    step = nb * bt
    n_win, n_sum = nwin_ref[s], nsum_ref[s]
    cw = (n_win + step - 1) // step   # the window's live chunks
    total = cw + (n_sum + step - 1) // step

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

    def place(c):
        """Chunk c of the walk -> (the table entry of its first block,
        its first row in its range, the range's bound)."""
        own = c < cw
        j = jnp.where(own, c, c - cw)
        return (jnp.where(own, 0, nw_e) + j * nb, j * step,
                jnp.where(own, n_win, n_sum))

    def copies(at, half, act):
        """Start, or wait for, the copies of the live blocks of the chunk
        at `place(c)` into buffer `half`, a block a plane.  A block whose
        first row lies at or past its range's bound is not read from the
        table and not copied: its plane keeps what it held."""
        entry, row0, bound = at
        for i in range(nb):

            @pl.when(row0 + i * bt < bound)
            def _(i=i):
                blk = tables_ref[s, entry + i]
                for n, (pool, buf) in enumerate(
                        ((k_pool, kbuf), (v_pool, vbuf))):
                    act(pltpu.make_async_copy(
                        pool.at[blk, :, col], buf.at[half, i],
                        sem.at[half, n]))

    def rows(buf, half):  # the chunk's nb planes, one under the other
        return jnp.concatenate([buf[half, i] for i in range(nb)], axis=0)

    @pl.when(total > 0)
    def _first():
        copies(place(0), 0, lambda cp: cp.start())

    def chunk(c):
        half = c % 2
        here = _, row0, bound = place(c)

        @pl.when(c + 1 < total)
        def _next():
            copies(place(c + 1), 1 - half, lambda cp: cp.start())

        copies(here, half, lambda cp: cp.wait())
        scores = dot_nt(q, rows(kbuf, half)) * scale       # (R, nb * bt)
        at = row0 + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
        # a plane that was not copied, and the last block's rows past
        # the bound, hold whatever they held: K's scores are masked, and
        # V's rows zeroed, since p = 0 times a stray NaN is no 0
        vblk = rows(vbuf, half)
        vrow = row0 + jax.lax.broadcasted_iota(jnp.int32, vblk.shape, 0)
        vblk = jnp.where(vrow < bound, vblk, jnp.zeros_like(vblk))
        fold(jnp.where(at < bound, scores, _MASKED),
             lambda p: jnp.dot(p.astype(q.dtype), vblk,
                               preferred_element_type=jnp.float32))
        return c + 1

    jax.lax.while_loop(lambda c: c < total, chunk, jnp.int32(0))

    # one key, one value: a row sum and a scaled row, no product
    sk = sk_ref[0].astype(jnp.float32)                     # (1, C)
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
    nb, _, _ = eva_steps(window_blocks, tables.shape[1] - window_blocks, bt)
    rpad = -h % 8

    # block-diagonal queries: row h carries its Dh numbers in head h's
    # columns (paged_attn_pallas.py)
    eye = jnp.eye(h, dtype=pdt)
    qbd = (q[:, :, 0].astype(pdt)[:, :, None, :]
           * eye[None, :, :, None]).reshape(s, h, c)
    qbd = jnp.pad(qbd, ((0, 0), (0, rpad), (0, 0)))

    in_hbm = pl.BlockSpec(memory_space=pltpu.HBM)
    row_spec = pl.BlockSpec((1, h + rpad, c),
                            lambda si, tr, wr, sr, lr: (si, 0, 0))
    self_spec = pl.BlockSpec((1, 1, c),
                             lambda si, tr, wr, sr, lr: (si, 0, 0))
    sk, sv = (a.swapaxes(1, 2).reshape(s, 1, c).astype(pdt)
              for a in self_kv)
    out = pl.pallas_call(
        functools.partial(_eva_kernel, bt=bt, nb=nb, nw_e=window_blocks,
                          scale=1.0 / math.sqrt(dh)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(s,),
            in_specs=[row_spec, in_hbm, in_hbm, self_spec, self_spec],
            out_specs=row_spec,
            scratch_shapes=[
                # the two-deep buffer: nb planes of one block's (bt, C)
                # a half, for K and for V
                pltpu.VMEM((2, nb, bt, c), pdt),
                pltpu.VMEM((2, nb, bt, c), pdt),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.VMEM((h + rpad, c), jnp.float32),
                pltpu.VMEM((h + rpad, 1), jnp.float32),
                pltpu.VMEM((h + rpad, 1), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((s, h + rpad, c), q.dtype),
        interpret=_paged.INTERPRET,
        # a grid step starts and waits for its own copies and resets its
        # own softmax state, so slots may split across Mosaic cores
        # the buffer is 2 x 4 MiB at 16 blocks of (16, 4096) bf16 a
        # chunk, and a chunk's K and V rows pass through as values too
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=64 * 1024 * 1024,
        ),
        name="tds_eva_paged_attn",
    )(tables.astype(jnp.int32), n_win.astype(jnp.int32),
      n_sum.astype(jnp.int32), jnp.reshape(jnp.asarray(l, jnp.int32), (1,)),
      qbd, view.k, view.v, sk, sv)
    # a row's own head out of its C columns
    hsel = jnp.arange(h)
    out = out[:, :h].reshape(s, h, h, dh)[:, hsel, hsel]   # (S, H, Dh)
    return out[:, :, None, :]
