# Copyright 2026 tiny-deepspeed-tpu authors
# SPDX-License-Identifier: Apache-2.0

"""EVA chunked linear attention (arXiv:2302.04542; models/evabyte.py states
the equations): chunk pooling, attention over whole sequences, and
attention of one position a slot over the paged pool.

Over whole sequences the two terms of the one softmax are computed apart
and merged by their log-sum-exp, as the ring merges chunks
(parallel/ring_attention.py): the window term is causal attention over W
positions with the windows folded into the batch -- the FA2 forward kernel
where no gradient is taken (`ops/flash_fa2.fa2_chunk_fwd` hands back its
log-sum-exp), plain XLA otherwise -- and the summary term is a masked
product against one key a chunk, window by window so that its scores stay
(H, W, T / C).  Scores, softmax and the merge are float32.

Over the pool (`eva_paged_attention`) a slot's block-table row is two
tables side by side (models/evabyte.EvaLayout): the window's blocks, of
which rows [0, n - w(n) W) are live, and the summary blocks, of which
rows [0, w(n) W / C) are visible.  The Pallas kernel
(ops/eva_attn_pallas.py) reads only those; elsewhere the two panels are
gathered and masked in XLA.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

# masked scores: finite, so that a window with no summary yet merges with
# weight exp(-1e30 - m) = 0 and not NaN
_MASKED = -1e30


def eva_bounds(pos, window: int, chunk: int):
    """What the query at position `pos` sees beside itself: (rows of its
    own window before it, chunk summaries of the windows before that
    one).  A chunk counts only once its whole window is past."""
    return pos % window, (pos // window) * (window // chunk)


def _pool_weights(scores):
    """A chunk's pooling weights from its C logits: their softmax."""
    return jax.nn.softmax(scores, axis=-1)


def eva_summaries(k, v, mu, phi, chunk: int):
    """k, v (..., H, T, Dh) with T a multiple of `chunk`; mu, phi
    (..., H, Dh) -> kbar, vbar (..., H, T / chunk, Dh): each chunk's keys
    pooled by softmax_m(mu . k_m), its values by softmax_m(phi . k_m), in
    float32, handed back in k's dtype."""
    *lead, t, dh = k.shape
    kc = k.reshape(*lead, t // chunk, chunk, dh).astype(jnp.float32)
    vc = v.reshape(*lead, t // chunk, chunk, dh).astype(jnp.float32)

    def weights(vec):
        s = jnp.einsum("...ncd,...d->...nc", kc, vec.astype(jnp.float32))
        return _pool_weights(s)

    kbar = jnp.einsum("...nc,...ncd->...nd", weights(mu), kc)
    vbar = jnp.einsum("...nc,...ncd->...nd", weights(phi), vc)
    return kbar.astype(k.dtype), vbar.astype(v.dtype)


def _window_xla(q, k, v):
    """Causal attention, (N, T, Dh) panels -> (out float32, lse (N, T))."""
    t, dh = q.shape[1:]
    s = jnp.einsum("nqd,nkd->nqk", q, k,
                   preferred_element_type=jnp.float32) / math.sqrt(dh)
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, _MASKED)
    lse = jax.nn.logsumexp(s, axis=-1)
    p = jnp.exp(s - lse[..., None])
    out = jnp.einsum("nqk,nkd->nqd", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out, lse


def _window_kernel(q, k, v):
    """The same through the FA2 forward kernel (forward only)."""
    from .flash_fa2 import fa2_chunk_fwd
    out, lse = fa2_chunk_fwd(q, k, v, causal=True)
    return out.astype(jnp.float32), lse[:, 0]


def _use_window_kernel(wp: int, dh: int) -> bool:
    from .dispatch import in_gspmd_auto_region, kernel_target
    return (kernel_target() == "tpu" and not in_gspmd_auto_region()
            and wp % 128 == 0 and dh % 64 == 0)


def eva_attention(q, k, v, kbar, vbar, window: int, chunk: int, *,
                  kernel_ok: bool = False):
    """q, k, v (B, H, T, Dh), kbar, vbar (B, H, T / chunk, Dh) from
    `eva_summaries` -> (B, H, T, Dh) in q's dtype.  T is a multiple of
    `chunk`, and of `window` where it is longer than one (`eva_pad_len`;
    the caller pads).  `kernel_ok`: no gradient will be asked for, so the
    window term may run in the FA2 forward kernel."""
    from .dispatch import note_kernel
    b, h, t, dh = q.shape
    if t != eva_pad_len(t, window, chunk):
        raise ValueError(f"{t} positions are no whole number of windows "
                         f"({window}) or chunks ({chunk}): pad them")
    wp = min(window, t)
    nw = t // wp

    def fold(z):  # (B, H, T, Dh) -> (B * H * nw, wp, Dh)
        return z.reshape(b * h * nw, wp, dh)

    if kernel_ok and _use_window_kernel(wp, dh):
        note_kernel("eva_window", "pallas:fa2_chunk_fwd")
        ow, lw = _window_kernel(fold(q), fold(k), fold(v))
    else:
        note_kernel("eva_window", "xla:window")
        ow, lw = _window_xla(fold(q), fold(k), fold(v))
    ow, lw = ow.reshape(b, h, nw, wp, dh), lw.reshape(b, h, nw, wp)
    if nw == 1:
        return ow.reshape(b, h, t, dh).astype(q.dtype)

    # the summary term, one window of queries at a time: window w sees
    # the chunks of the windows before it, columns [0, w * W / C)
    qw = q.reshape(b, h, nw, wp, dh).transpose(2, 0, 1, 3, 4)
    col = jnp.arange(kbar.shape[2])

    def one(args):
        w, qq = args
        s = jnp.einsum("bhqd,bhnd->bhqn", qq, kbar,
                       preferred_element_type=jnp.float32) / math.sqrt(dh)
        s = jnp.where(col < eva_bounds(w * wp, wp, chunk)[1], s, _MASKED)
        lse = jax.nn.logsumexp(s, axis=-1)
        p = jnp.exp(s - lse[..., None])
        out = jnp.einsum("bhqn,bhnd->bhqd", p.astype(vbar.dtype), vbar,
                         preferred_element_type=jnp.float32)
        return out, lse

    os_, ls = jax.lax.map(one, (jnp.arange(nw), qw))
    os_, ls = os_.transpose(1, 2, 0, 3, 4), ls.transpose(1, 2, 0, 3)
    # window 0's summary scores are all masked: its lse is about -1e30
    # and its weight in the merge exactly 0
    m = jnp.maximum(lw, ls)
    aw, as_ = jnp.exp(lw - m), jnp.exp(ls - m)
    out = (ow * aw[..., None] + os_ * as_[..., None]) / (aw + as_)[..., None]
    return out.reshape(b, h, t, dh).astype(q.dtype)


def eva_pad_len(t: int, window: int, chunk: int) -> int:
    """The length `eva_attention` takes a sequence of t positions at:
    whole windows, or whole chunks where t is under one window.  Padding
    lies behind every real position, and a chunk that holds any of it
    lies in the last window, which no query sees summarised."""
    return -(-t // window) * window if t > window else -(-t // chunk) * chunk


def eva_paged_attention(q, view, page, l, self_kv, layout):
    """One query a slot over the pool: q (S, H, 1, Dh) at positions
    page.pos; `page.tables` (S, window + summary) as `layout`
    (models/evabyte.EvaLayout) splits it; self_kv = (k, v), each
    (S, H, 1, Dh), the position's own key and value, not yet in the
    pool.  A query at n sees window rows [0, n - w(n) W), summary rows
    [0, w(n) W / C) and itself, under one float32 softmax.
    -> (S, H, 1, Dh) in q's dtype."""
    from .dispatch import note_kernel
    from .paged_attn_pallas import use_paged_kernel
    n_win, n_sum = eva_bounds(page.pos, layout.window_size,
                              layout.chunk_size)
    if use_paged_kernel():
        from .eva_attn_pallas import eva_paged_attention_kernel
        note_kernel("eva_paged_attention", "pallas:eva_paged_attention")
        return eva_paged_attention_kernel(
            q, view, page.tables, n_win, n_sum, l, self_kv,
            window_blocks=layout.window)
    note_kernel("eva_paged_attention", "xla:eva_panels")
    from ..serving.pool import PageRef, paged_panel
    s, h, _, dh = q.shape

    def panel(tables):
        ref = PageRef(tables, page.blk, page.off, page.pos)
        return paged_panel(view, l, ref, h, dh, q.dtype)

    kw, vw = panel(page.tables[:, :layout.window])
    ks, vs = panel(page.tables[:, layout.window:])
    sk, sv = self_kv
    kf = jnp.concatenate([ks, kw, sk.astype(kw.dtype)], axis=2)
    vf = jnp.concatenate([vs, vw, sv.astype(vw.dtype)], axis=2)
    live = jnp.concatenate([
        jnp.arange(ks.shape[2])[None] < n_sum[:, None],
        jnp.arange(kw.shape[2])[None] < n_win[:, None],
        jnp.ones((s, 1), bool)], axis=1)
    att = jnp.einsum("shqd,shtd->shqt", q.astype(kf.dtype), kf,
                     preferred_element_type=jnp.float32) / math.sqrt(dh)
    att = jax.nn.softmax(
        jnp.where(live[:, None, None], att, _MASKED), axis=-1)
    y = jnp.einsum("shqt,shtd->shqd", att.astype(vf.dtype), vf,
                   preferred_element_type=jnp.float32)
    return y.astype(q.dtype)
