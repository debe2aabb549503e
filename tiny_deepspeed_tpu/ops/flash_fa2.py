# Copyright 2026 tiny-deepspeed-tpu authors
# SPDX-License-Identifier: Apache-2.0

"""Hand-written FA2-style causal flash attention for TPU (Pallas).

Why another kernel when `ops/attention_pallas.py` already wraps JAX's
bundled one: the round-4 chip profile (PROFILE.md "chip profile") showed
the bundled kernel's XLA-side residual plumbing materializing ~9 ms/step
of f32 broadcasts on gpt2-124m — it stashes softmax stats as separate
running-max `m` and running-sum `l`, each expanded to `[B, H, T, 128]`
(its MIN_BLOCK_SIZE), and its backward additionally expands the
`di = rowsum(do*o)` contraction the same way.  This kernel is the
FlashAttention-2 formulation (Dao, arXiv:2307.08691) built TPU-first:

  * ONE fused stat: the forward emits `lse = m + log(l)` of shape
    (B*H, T) — 128x fewer residual bytes than m+l at [.,128] each; the
    backward consumes it directly (`p = exp(s - lse)`), no rescaling
    pass, no broadcast materialization in HBM.
  * K/V (and in the backward, Q/dO) ride VMEM whole per (batch, head):
    at GPT-2 shapes a (T, 64) bf16 panel is 128 KB, so the inner
    k-block loop is VMEM-resident with zero HBM refetch; the grid walks
    only (B*H, T/block).  Causality is exact loop bounds (`fori_loop` to
    the diagonal), not masked wasted blocks — plus one iota mask on the
    diagonal block itself.
  * dq and dkv stay two separate passes (dq is row-parallel, dkv is
    column-parallel; TPU has no cross-program atomics to fuse them), the
    same decomposition as the bundled kernel — the win is the stat diet
    and the VMEM residency, not the pass count.

Numerics: all matmuls accumulate f32 on the MXU
(`preferred_element_type`), softmax/statistics math is f32, outputs cast
back to the input dtype.  Parity vs the bundled kernel and vs plain
softmax(QK^T)V autodiff is pinned in tests/test_flash_fa2.py (CPU
`interpret=True` and the real chip).

The reference has no kernel of its own at this layer — its
"flash_attention" calls torch's F.scaled_dot_product_attention
(reference example/model.py:44-51); this file is the TPU-native
counterpart of what that call delegates to cuDNN.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .attention_pallas import _pick_block as _pick  # shared block picker

NEG_INF = -1e30


def _causal_mask(s, iq, jk, bq, bk):
    """Mask (bq, bk) scores for q-block iq vs k-block jk (additive)."""
    rows = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) + iq * bq
    cols = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1) + jk * bk
    return jnp.where(rows >= cols, s, NEG_INF)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref,
                *, scale, bq, bk, causal=True):
    iq = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32)  # (bq, d)

    acc_ref[:] = jnp.zeros_like(acc_ref)

    # k-blocks [0, nfull) lie entirely below the diagonal (no mask);
    # [nfull, ndiag) straddle it (iota mask); ndiag is one past the last
    # block any row of this q-block may see.  causal=False (a ring
    # attention off-diagonal chunk: every key is strictly behind every
    # local query) visits ALL k-blocks unmasked.
    if causal:
        nfull = iq * bq // bk
        ndiag = pl.cdiv((iq + 1) * bq, bk)
    else:
        nfull = ndiag = k_ref.shape[1] // bk

    def step(jk, m, l, masked):
        k = k_ref[0, pl.ds(jk * bk, bk), :].astype(jnp.float32)
        v = v_ref[0, pl.ds(jk * bk, bk), :].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if masked:
            s = _causal_mask(s, iq, jk, bq, bk)
        m_cur = jnp.maximum(m, jnp.max(s, axis=1))
        alpha = jnp.exp(m - m_cur)                      # (bq,)
        p = jnp.exp(s - m_cur[:, None])                 # (bq, bk)
        l = l * alpha + jnp.sum(p, axis=1)
        acc_ref[:] = acc_ref[:] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_cur, l

    m0 = jnp.full((bq,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq,), jnp.float32)
    m, l = jax.lax.fori_loop(
        0, nfull, lambda jk, c: step(jk, *c, masked=False), (m0, l0))
    m, l = jax.lax.fori_loop(
        nfull, ndiag, lambda jk, c: step(jk, *c, masked=True), (m, l))

    o_ref[0] = (acc_ref[:] / l[:, None]).astype(o_ref.dtype)
    lse_ref[0, 0] = m + jnp.log(l)


def _specs(*, t, d, size, group=1):
    """BlockSpec for one (bh, t, d) q/k/v/o/grad panel operand: block
    (1, size, d); `size` None means the full-T panel (index pinned 0).
    `group` > 1 (GQA) maps the grid's per-QUERY-head index onto the
    operand's KV-head panels: query head b reads kv panel b // group
    (query heads of one group are adjacent — llama.py packs them so)."""
    if size is None:
        return pl.BlockSpec((1, t, d), lambda b, i: (b // group, 0, 0))
    return pl.BlockSpec((1, size, d), lambda b, i: (b // group, i, 0))


def _fwd(q, k, v, *, scale, bq, bk, group=1, causal=True):
    bh, t, d = q.shape
    oshape = (bh, t, d)
    sp = functools.partial(_specs, t=t, d=d)
    o, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, bq=bq, bk=bk,
                          causal=causal),
        grid=(bh, t // bq),
        in_specs=[sp(size=bq),
                  sp(size=None, group=group), sp(size=None, group=group)],
        out_specs=[
            sp(size=bq),
            pl.BlockSpec((1, 1, bq), lambda b, i: (b, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(oshape, q.dtype),
            jax.ShapeDtypeStruct((bh, 1, t), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),    # acc
        ],
        interpret=_INTERPRET,
        name="tds_fa2_fwd",
    )(q, k, v)
    return o, lse


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref,
                    dk_ref, dv_ref, dk_acc, dv_acc, *, scale, bq, bk,
                    group=1, causal=True):
    """Grid is (n_KV_heads * B, t // bk); with GQA (group > 1) the q/do/
    lse/di blocks carry this kv head's `group` adjacent query heads in
    their leading dim, statically looped — dk/dv accumulate the sum over
    the group, which IS d(k)/d(v) under grouped-query sharing.
    causal=False (ring off-diagonal chunk): every q-block touches this
    k-block, none masked."""
    jk = pl.program_id(1)
    k = k_ref[0].astype(jnp.float32)   # (bk, d)
    v = v_ref[0].astype(jnp.float32)

    dk_acc[:] = jnp.zeros_like(dk_acc)
    dv_acc[:] = jnp.zeros_like(dv_acc)

    nq = q_ref.shape[1] // bq           # q-blocks total (t // bq)
    if causal:
        first = jk * bk // bq           # first q-block touching this k-block
        idiag_end = pl.cdiv((jk + 1) * bk, bq)  # first FULLY-unmasked q-blk
    else:
        first = idiag_end = 0

    for g in range(group):  # static unroll over the query heads sharing k/v
        def body(iq, masked):
            q = q_ref[g, pl.ds(iq * bq, bq), :].astype(jnp.float32)
            do = do_ref[g, pl.ds(iq * bq, bq), :].astype(jnp.float32)
            lse = lse_ref[g, 0, pl.ds(iq * bq, bq)]
            di = di_ref[g, 0, pl.ds(iq * bq, bq)]
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            if masked:
                s = _causal_mask(s, iq, jk, bq, bk)
            p = jnp.exp(s - lse[:, None])                    # (bq, bk)
            dv_acc[:] += jax.lax.dot_general(
                p, do, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            dp = jax.lax.dot_general(
                do, v, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)          # (bq, bk)
            ds = p * (dp - di[:, None]) * scale
            dk_acc[:] += jax.lax.dot_general(
                ds, q, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            return 0

        jax.lax.fori_loop(first, idiag_end,
                          lambda i, c: body(i, masked=True), 0)
        jax.lax.fori_loop(idiag_end, nq,
                          lambda i, c: body(i, masked=False), 0)
    dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
    dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref,
                   dq_ref, dq_acc, *, scale, bq, bk, causal=True):
    iq = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32)
    do = do_ref[0].astype(jnp.float32)
    lse = lse_ref[0, 0]
    di = di_ref[0, 0]

    dq_acc[:] = jnp.zeros_like(dq_acc)
    if causal:
        nfull = iq * bq // bk
        ndiag = pl.cdiv((iq + 1) * bq, bk)
    else:
        nfull = ndiag = k_ref.shape[1] // bk

    def body(jk, masked):
        k = k_ref[0, pl.ds(jk * bk, bk), :].astype(jnp.float32)
        v = v_ref[0, pl.ds(jk * bk, bk), :].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if masked:
            s = _causal_mask(s, iq, jk, bq, bk)
        p = jnp.exp(s - lse[:, None])
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - di[:, None]) * scale
        dq_acc[:] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return 0

    jax.lax.fori_loop(0, nfull, lambda j, c: body(j, masked=False), 0)
    jax.lax.fori_loop(nfull, ndiag, lambda j, c: body(j, masked=True), 0)
    dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _dkv_call(q, k, v, do, lse, di, *, scale, bq, bk, group=1, causal=True):
    """dk/dv pass: grid walks KV-head panels of k; q/do/lse/di blocks
    carry the whole query-head group in their leading dim (block index j
    on a group-leading block addresses rows [j*group, (j+1)*group) —
    exactly kv panel j's query heads)."""
    bh, t, d = q.shape
    bkvh = k.shape[0]  # bh // group KV-head panels under GQA
    sp = functools.partial(_specs, t=t, d=d)
    gq_full = pl.BlockSpec((group, t, d), lambda j, i: (j, 0, 0))
    stat_full = pl.BlockSpec((group, 1, t), lambda b, j: (b, 0, 0))
    return pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, bq=bq, bk=bk,
                          group=group, causal=causal),
        grid=(bkvh, t // bk),
        in_specs=[gq_full,         # q (full, whole group)
                  sp(size=bk),     # k (block)
                  sp(size=bk),     # v (block)
                  gq_full,         # do (full, whole group)
                  stat_full,             # lse (full, whole group)
                  stat_full],            # di (full, whole group)
        out_specs=[sp(size=bk), sp(size=bk)],
        out_shape=[
            jax.ShapeDtypeStruct((bkvh, t, d), k.dtype),
            jax.ShapeDtypeStruct((bkvh, t, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, d), jnp.float32),
            pltpu.VMEM((bk, d), jnp.float32),
        ],
        interpret=_INTERPRET,
        name="tds_fa2_dkv",
    )(q, k, v, do, lse, di)


def _dq_call(q, k, v, do, lse, di, *, scale, bq, bk, group=1, causal=True):
    bh, t, d = q.shape
    sp = functools.partial(_specs, t=t, d=d)
    stat_blk = pl.BlockSpec((1, 1, bq), lambda b, i: (b, 0, i))
    return pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, bq=bq, bk=bk,
                          causal=causal),
        grid=(bh, t // bq),
        in_specs=[sp(size=bq),     # q (block)
                  sp(size=None, group=group),   # k (full, kv-indexed)
                  sp(size=None, group=group),   # v (full, kv-indexed)
                  sp(size=bq),     # do (block)
                  stat_blk,              # lse (block)
                  stat_blk],             # di (block)
        out_specs=sp(size=bq),
        out_shape=jax.ShapeDtypeStruct((bh, t, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        interpret=_INTERPRET,
        name="tds_fa2_dq",
    )(q, k, v, do, lse, di)


def _bwd(res, g, *, scale, bq, bk, group=1):
    q, k, v, o, lse = res
    do = g
    # di = rowsum(do * o): one fused elementwise+reduce in XLA, (bh, 1, t)
    # f32 — consumed directly by both kernels, never broadcast to block
    # width
    di = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                 axis=-1)[:, None, :]
    dk, dv = _dkv_call(q, k, v, do, lse, di, scale=scale, bq=bq, bk=bk,
                       group=group)
    dq = _dq_call(q, k, v, do, lse, di, scale=scale, bq=bq, bk=bk,
                  group=group)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# chunk-level raw entries for ring attention (parallel/ring_attention.py)
#
# The ring's per-device step is chunk-local attention between the resident
# q block and a rotating K/V chunk: the DIAGONAL chunk (global offsets
# equal) is ordinary causal attention, every other contributing chunk is
# FULLY unmasked (all its keys are strictly behind all local queries).
# These entries expose the same kernels with a static `causal` switch and
# hand back the raw (o, lse) pair / consume the global (lse, di) stats the
# ring's custom_vjp merges across chunks — no custom_vjp of their own.
# ---------------------------------------------------------------------------


def fa2_chunk_fwd(q, k, v, *, causal: bool, block: int = 512,
                  group: int = 1):
    """(BH, T, D) panels -> (o normalized within the chunk, lse (BH,1,T)).
    `group` > 1: k/v carry BH//group KV-head panels (GQA — the ring
    rotates them at kv_heads, cutting its dominant wire term)."""
    bh, t, d = q.shape
    bq, bk = _pick(t, block), _pick(t, block)
    return _fwd(q, k, v, scale=1.0 / math.sqrt(d), bq=bq, bk=bk,
                causal=causal, group=group)


def fa2_chunk_dq(q, k, v, do, lse, di, *, causal: bool, block: int = 512,
                 group: int = 1):
    """dq of one chunk given the GLOBAL (merged) lse and di stats."""
    bh, t, d = q.shape
    bq, bk = _pick(t, block), _pick(t, block)
    return _dq_call(q, k, v, do, lse, di, scale=1.0 / math.sqrt(d),
                    bq=bq, bk=bk, causal=causal, group=group)


def fa2_chunk_dkv(q, k, v, do, lse, di, *, causal: bool, block: int = 512,
                  group: int = 1):
    """(dk, dv) of one chunk given the GLOBAL (merged) lse and di stats;
    dk/dv return at the k/v (KV-head) panel count."""
    bh, t, d = q.shape
    bq, bk = _pick(t, block), _pick(t, block)
    return _dkv_call(q, k, v, do, lse, di, scale=1.0 / math.sqrt(d),
                     bq=bq, bk=bk, causal=causal, group=group)


# ---------------------------------------------------------------------------
# public entry (custom_vjp over (B, H, T, Dh))
# ---------------------------------------------------------------------------

_INTERPRET = False  # tests flip this on CPU (no Mosaic backend there)


# GQA VMEM bound: the dkv pass holds the kv head's whole query-head
# group of Q and dO panels VMEM-resident — group * t * d elements each
# (bf16).  1M elements = 2 MB/panel, 4 MB for the pair, matching the
# per-panel envelope the MHA dispatch bound was tuned to (at group=1
# this is exactly FA2_MAX_T=16384 at d=64: 16384*64 = 1,048,576).
_GQA_MAX_PANEL = 1024 * 1024


def fa2_gqa_supported(t: int, d: int, group: int) -> bool:
    """True when the GQA kernel's dkv VMEM panels fit (trace-time check)."""
    return group * t * d <= _GQA_MAX_PANEL


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def fa2_flash_attention(q, k, v, block_q: int = 512, block_k: int = 512):
    """Causal FA2 attention; returns (B, H, T, Dh).

    q is (B, H, T, Dh); k/v may be (B, KVH, T, Dh) with KVH | H —
    grouped-query attention runs NATIVELY: K/V stay at KVH heads in HBM
    and VMEM (the kernels index kv panels by query_head // group), and
    dk/dv come back at KVH heads (the in-kernel group sum IS the
    repeat's vjp).  The query heads of one group must be adjacent —
    the jnp.repeat(k, H//KVH, axis=1) ordering, which is how llama.py
    lays them out (ref example/model.py:44-51 is the MHA-only
    counterpart this generalizes)."""
    out, _ = _fa2_fwd(q, k, v, block_q, block_k)
    return out


def _fa2_fwd(q, k, v, block_q, block_k):
    b, h, t, d = q.shape
    kvh = k.shape[1]
    assert h % kvh == 0, f"query heads {h} not a multiple of kv heads {kvh}"
    group = h // kvh
    bq, bk = _pick(t, block_q), _pick(t, block_k)
    scale = 1.0 / math.sqrt(d)
    o, lse = _fwd(q.reshape(b * h, t, d),
                  k.reshape(b * kvh, t, d), v.reshape(b * kvh, t, d),
                  scale=scale, bq=bq, bk=bk, group=group)
    o = o.reshape(b, h, t, d)
    return o, (q, k, v, o, lse)


def _fa2_bwd(block_q, block_k, res, g):
    q, k, v, o, lse = res
    b, h, t, d = q.shape
    kvh = k.shape[1]
    group = h // kvh
    bq, bk = _pick(t, block_q), _pick(t, block_k)
    scale = 1.0 / math.sqrt(d)
    flat = lambda x: x.reshape(b * h, t, d)
    dq, dk, dv = _bwd(
        (flat(q), k.reshape(b * kvh, t, d), v.reshape(b * kvh, t, d),
         flat(o), lse), flat(g),
        scale=scale, bq=bq, bk=bk, group=group)
    return (dq.reshape(b, h, t, d),
            dk.reshape(b, kvh, t, d), dv.reshape(b, kvh, t, d))


fa2_flash_attention.defvjp(_fa2_fwd, _fa2_bwd)


# ---------------------------------------------------------------------------
# heads-last entry (B, T, H, Dh) — EXPERIMENTAL, not wired into dispatch
# ---------------------------------------------------------------------------
#
# Motivation: the round-4 chip profile priced the per-layer
# (B,T,H,Dh)->(B,H,T,Dh) copies around the attention kernel at ~8.4 ms of
# the 95 ms gpt2-124m step.  A first attempt addressed the head axis in
# per-head BlockSpec index maps — REJECTED by Mosaic's tiling rule (the
# size-1 head block lands in the sublane position, which must be
# divisible by 8 or the full dim; caught by the local v5e AOT compile).
# This implementation instead reads the WHOLE (T, H*Dh) panel per batch
# element — minor dim H*Dh is the full array dim, so the rule is
# satisfied — and loops the heads statically inside the kernel, slicing
# 64-lane head columns in VMEM.  Zero XLA transposes; the open question
# (chip A/B, scripts/fa2_bthd_ab.py) is whether the in-kernel sub-128
# lane slices cost more relayout than the deleted copies.
#
# VMEM: panels are (T, H*Dh) bf16 — 1.5 MB at the 124M shape; the bwd
# holds four of them plus f32 scratch, so the entry transposes over to
# the standard kernels past _AH_MAX_T_HD elements.

_AH_MAX_T_HD = 4 * 1024 * 1024  # t * h * d bound for the all-heads path


def _fwd_kernel_ah(q_ref, k_ref, v_ref, o_ref, lse_ref, o_acc,
                   *, scale, bq, bk, h):
    iq = pl.program_id(1)
    hd = q_ref.shape[-1]
    d = hd // h
    nfull = iq * bq // bk
    ndiag = pl.cdiv((iq + 1) * bq, bk)

    for hh in range(h):  # static unroll over heads
        sl = slice(hh * d, (hh + 1) * d)
        q = q_ref[0, :, sl].astype(jnp.float32)      # (bq, d)

        def step(jk, carry, masked):
            m, l, acc = carry
            k = k_ref[0, pl.ds(jk * bk, bk), sl].astype(jnp.float32)
            v = v_ref[0, pl.ds(jk * bk, bk), sl].astype(jnp.float32)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            if masked:
                s = _causal_mask(s, iq, jk, bq, bk)
            m_cur = jnp.maximum(m, jnp.max(s, axis=1))
            alpha = jnp.exp(m - m_cur)
            p = jnp.exp(s - m_cur[:, None])
            l = l * alpha + jnp.sum(p, axis=1)
            acc = acc * alpha[:, None] + jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            return m_cur, l, acc

        m0 = jnp.full((bq,), NEG_INF, jnp.float32)
        l0 = jnp.zeros((bq,), jnp.float32)
        a0 = jnp.zeros((bq, d), jnp.float32)
        m, l, acc = jax.lax.fori_loop(
            0, nfull, lambda jk, c: step(jk, c, masked=False), (m0, l0, a0))
        m, l, acc = jax.lax.fori_loop(
            nfull, ndiag, lambda jk, c: step(jk, c, masked=True), (m, l, acc))
        o_acc[:, sl] = acc / l[:, None]
        lse_ref[0, hh] = m + jnp.log(l)

    o_ref[0] = o_acc[:].astype(o_ref.dtype)


def _bwd_dkv_kernel_ah(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref,
                       dk_ref, dv_ref, dk_acc, dv_acc, *, scale, bq, bk, h):
    jk = pl.program_id(1)
    hd = q_ref.shape[-1]
    d = hd // h
    nq = q_ref.shape[1] // bq
    first = jk * bk // bq
    idiag_end = pl.cdiv((jk + 1) * bk, bq)

    for hh in range(h):
        sl = slice(hh * d, (hh + 1) * d)
        k = k_ref[0, :, sl].astype(jnp.float32)      # (bk, d)
        v = v_ref[0, :, sl].astype(jnp.float32)

        def body(iq, carry, masked):
            dk_c, dv_c = carry
            q = q_ref[0, pl.ds(iq * bq, bq), sl].astype(jnp.float32)
            do = do_ref[0, pl.ds(iq * bq, bq), sl].astype(jnp.float32)
            lse = lse_ref[0, hh, pl.ds(iq * bq, bq)]
            di = di_ref[0, hh, pl.ds(iq * bq, bq)]
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            if masked:
                s = _causal_mask(s, iq, jk, bq, bk)
            p = jnp.exp(s - lse[:, None])
            dv_c = dv_c + jax.lax.dot_general(
                p, do, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            dp = jax.lax.dot_general(
                do, v, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            ds = p * (dp - di[:, None]) * scale
            dk_c = dk_c + jax.lax.dot_general(
                ds, q, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            return dk_c, dv_c

        z = jnp.zeros((bk, d), jnp.float32)
        dk_c, dv_c = jax.lax.fori_loop(
            first, idiag_end, lambda i, c: body(i, c, masked=True), (z, z))
        dk_c, dv_c = jax.lax.fori_loop(
            idiag_end, nq, lambda i, c: body(i, c, masked=False),
            (dk_c, dv_c))
        dk_acc[:, sl] = dk_c
        dv_acc[:, sl] = dv_c

    dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
    dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _bwd_dq_kernel_ah(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref,
                      dq_ref, dq_acc, *, scale, bq, bk, h):
    iq = pl.program_id(1)
    hd = q_ref.shape[-1]
    d = hd // h
    nfull = iq * bq // bk
    ndiag = pl.cdiv((iq + 1) * bq, bk)

    for hh in range(h):
        sl = slice(hh * d, (hh + 1) * d)
        q = q_ref[0, :, sl].astype(jnp.float32)
        do = do_ref[0, :, sl].astype(jnp.float32)
        lse = lse_ref[0, hh]
        di = di_ref[0, hh]

        def body(jk, dq_c, masked):
            k = k_ref[0, pl.ds(jk * bk, bk), sl].astype(jnp.float32)
            v = v_ref[0, pl.ds(jk * bk, bk), sl].astype(jnp.float32)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            if masked:
                s = _causal_mask(s, iq, jk, bq, bk)
            p = jnp.exp(s - lse[:, None])
            dp = jax.lax.dot_general(
                do, v, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            ds = p * (dp - di[:, None]) * scale
            return dq_c + jax.lax.dot_general(
                ds, k, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

        dq_c = jax.lax.fori_loop(
            0, nfull, lambda j, c: body(j, c, masked=False),
            jnp.zeros((bq, d), jnp.float32))
        dq_c = jax.lax.fori_loop(
            nfull, ndiag, lambda j, c: body(j, c, masked=True), dq_c)
        dq_acc[:, sl] = dq_c

    dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _ah_specs(t, hd, size):
    if size is None:
        return pl.BlockSpec((1, t, hd), lambda b, i: (b, 0, 0))
    return pl.BlockSpec((1, size, hd), lambda b, i: (b, i, 0))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def fa2_flash_attention_bthd(q, k, v, block_q: int = 512,
                             block_k: int = 512):
    """Causal FA2 on (B, T, H, Dh) tensors — the layout the QKV matmul
    produces — with the heads looped statically INSIDE the kernel over
    whole (T, H*Dh) panels, so no (B,T,H,Dh)->(B,H,T,Dh) XLA transpose
    ever materializes (see the section comment above for why per-head
    blocks cannot lower).  Semantics parity with `fa2_flash_attention`
    is pinned in tests/test_flash_fa2.py; chip timing pending
    (scripts/fa2_bthd_ab.py).  Falls back to
    transpose + the standard kernels when the panel exceeds the VMEM
    budget."""
    out, _ = _fa2_bthd_fwd(q, k, v, block_q, block_k)
    return out


def _use_ah(q):
    b, t, h, d = q.shape
    return t * h * d <= _AH_MAX_T_HD


def _fa2_bthd_fwd(q, k, v, block_q, block_k):
    b, t, h, d = q.shape
    if not _use_ah(q):
        # residuals stay (B, T, H, Dh) so the bwd fallback's transposes
        # are unconditional; only lse keeps the standard (B*H, 1, T) form
        tr = lambda x: x.swapaxes(1, 2)
        o, (*_, lse) = _fa2_fwd(tr(q), tr(k), tr(v), block_q, block_k)
        o_t = tr(o)
        return o_t, (q, k, v, o_t, lse)
    bq, bk = _pick(t, block_q), _pick(t, block_k)
    scale = 1.0 / math.sqrt(d)
    hd = h * d
    flat = lambda x: x.reshape(b, t, hd)
    sp = functools.partial(_ah_specs, t, hd)
    o, lse = pl.pallas_call(
        functools.partial(_fwd_kernel_ah, scale=scale, bq=bq, bk=bk, h=h),
        grid=(b, t // bq),
        in_specs=[sp(bq), sp(None), sp(None)],
        out_specs=[
            sp(bq),
            pl.BlockSpec((1, h, bq), lambda b_, i: (b_, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, t, hd), q.dtype),
            jax.ShapeDtypeStruct((b, h, t), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((bq, hd), jnp.float32)],
        interpret=_INTERPRET,
        name="tds_fa2_fwd_packed",
    )(flat(q), flat(k), flat(v))
    o = o.reshape(b, t, h, d)
    return o, (q, k, v, o, lse)


def _fa2_bthd_bwd(block_q, block_k, res, g):
    q, k, v, o, lse = res
    if not _use_ah(q):
        tr = lambda x: x.swapaxes(1, 2)
        dq, dk, dv = _fa2_bwd(block_q, block_k,
                              (tr(q), tr(k), tr(v), tr(o), lse), tr(g))
        return tr(dq), tr(dk), tr(dv)
    b, t, h, d = q.shape
    bq, bk = _pick(t, block_q), _pick(t, block_k)
    scale = 1.0 / math.sqrt(d)
    hd = h * d
    flat = lambda x: x.reshape(b, t, hd)
    do = flat(g)
    # di = rowsum(do * o) per head: (B, T, H) -> (B, H, T), f32 — tiny
    # next to the bf16 panel transposes this path exists to delete
    di = jnp.sum(g.astype(jnp.float32) * o.astype(jnp.float32),
                 axis=-1).transpose(0, 2, 1)
    sp = functools.partial(_ah_specs, t, hd)
    stat_full = pl.BlockSpec((1, h, t), lambda b_, j: (b_, 0, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel_ah, scale=scale, bq=bq, bk=bk,
                          h=h),
        grid=(b, t // bk),
        in_specs=[sp(None), sp(bk), sp(bk), sp(None), stat_full, stat_full],
        out_specs=[sp(bk), sp(bk)],
        out_shape=[
            jax.ShapeDtypeStruct((b, t, hd), k.dtype),
            jax.ShapeDtypeStruct((b, t, hd), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, hd), jnp.float32),
            pltpu.VMEM((bk, hd), jnp.float32),
        ],
        interpret=_INTERPRET,
        name="tds_fa2_dkv_packed",
    )(flat(q), flat(k), flat(v), do, lse, di)
    stat_blk = pl.BlockSpec((1, h, bq), lambda b_, i: (b_, 0, i))
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel_ah, scale=scale, bq=bq, bk=bk,
                          h=h),
        grid=(b, t // bq),
        in_specs=[sp(bq), sp(None), sp(None), sp(bq), stat_blk, stat_blk],
        out_specs=sp(bq),
        out_shape=jax.ShapeDtypeStruct((b, t, hd), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, hd), jnp.float32)],
        interpret=_INTERPRET,
        name="tds_fa2_dq_packed",
    )(flat(q), flat(k), flat(v), do, lse, di)
    unflat = lambda x: x.reshape(b, t, h, d)
    return unflat(dq), unflat(dk), unflat(dv)


fa2_flash_attention_bthd.defvjp(_fa2_bthd_fwd, _fa2_bthd_bwd)
