# Copyright 2026 tiny-deepspeed-tpu authors
# SPDX-License-Identifier: Apache-2.0

"""Softmax cross-entropy with integer targets, computed in float32.

The reference computes loss inside the model forward with
F.cross_entropy(logits.view(-1, V), targets.view(-1)) (reference
example/model.py:154-156).  This is the TPU equivalent: a numerically stable
log-softmax gather, mean-reduced over all positions.  Kept as a standalone op
so the lm_head matmul + loss can later be fused/blocked (the (B*T, 50304)
logits tensor dominates HBM traffic at small batch).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def softmax_cross_entropy(logits, targets):
    """Mean NLL.  logits (..., V) any float dtype; targets (...) int."""
    logits = logits.astype(jnp.float32)
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(
        logits, targets[..., None], axis=-1
    ).squeeze(-1)
    return jnp.mean(logz - gold)


def softmax_cross_entropy_onehot(logits, targets):
    """Same mean NLL via a one-hot contraction instead of take_along_axis.

    The gather in the standard path trips XLA's SPMD partitioner when it
    runs on vocab-sharded logits INSIDE a partial-manual shard_map region
    (CHECK failure in PartitionGather/ExpandDeviceGroupsWithIota on a
    3-axis mesh) — the 1F1B pipeline computes the loss per microbatch at
    the last stage, exactly that situation.  One-hot multiply + sum
    partitions as elementwise + psum over the vocab shards, which GSPMD
    handles everywhere."""
    logits = logits.astype(jnp.float32)
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    onehot = jax.nn.one_hot(targets, logits.shape[-1], dtype=jnp.float32)
    gold = jnp.sum(onehot * logits, axis=-1)
    return jnp.mean(logz - gold)


# ---------------------------------------------------------------------------
# fused lm_head matmul + cross-entropy (chunked over the sequence)
# ---------------------------------------------------------------------------

def _pick_chunk(t: int, want: int) -> int:
    """Largest chunk <= want that divides t; t itself when the only such
    divisor would be degenerate (< 32 rows per chunk wastes the MXU on
    (B, tiny, V) matmuls — better to take one full-size chunk).  The
    full-size fallback defeats the memory bound this op exists for, so it
    warns (once per T — trace-time, not per step; ADVICE r1)."""
    for c in range(min(want, t), 31, -1):
        if t % c == 0:
            return c
    if t > want:
        import warnings
        warnings.warn(
            f"fused_linear_xent: sequence length {t} has no chunk divisor in "
            f"[32, {want}]; materializing full (B, {t}, V) logits — pad T to "
            "a multiple of a power of two to keep the chunked path",
            stacklevel=3,
        )
    return t


def _head_logits(xc, w):
    """One chunk's lm_head matmul in f32 — or the e4m3 fp8 matmul when
    ops/matmul_fp8 is forced "on" (the mode covers the fused head
    too; trace-time gate, so "off" stays byte-identical)."""
    from .matmul_fp8 import fp8_matmul, fp8_matmul_mode
    if fp8_matmul_mode() == "on":
        return fp8_matmul(xc, w)
    return jnp.einsum(
        "btd,dv->btv", xc, w, preferred_element_type=jnp.float32
    )


def _chunk_iter_fwd(x, w, targets, chunk):
    """Scan over sequence chunks: returns (loss_sum f32 scalar, logz (B,T))."""
    b, t, _ = x.shape
    nc = t // chunk

    def body(acc, ci):
        start = ci * chunk
        xc = jax.lax.dynamic_slice_in_dim(x, start, chunk, axis=1)
        tc = jax.lax.dynamic_slice_in_dim(targets, start, chunk, axis=1)
        logits = _head_logits(xc, w)
        logz = jax.scipy.special.logsumexp(logits, axis=-1)  # (B, chunk)
        gold = jnp.take_along_axis(
            logits, tc[..., None], axis=-1
        ).squeeze(-1)
        return acc + jnp.sum(logz - gold), logz

    acc, logz = jax.lax.scan(body, jnp.zeros((), jnp.float32),
                             jnp.arange(nc))
    # logz stacked (nc, B, chunk) -> (B, T)
    return acc, jnp.moveaxis(logz, 0, 1).reshape(b, t)


def _make_flx_variant(want: int, name: str):
    """One custom_vjp fused lm_head/xent with a fixed target chunk size.

    Each chunk size is its own module-level function so the runtime
    autotuner can identify winners by module+name in its AOT cache."""

    @jax.custom_vjp
    def flx(x, w, targets):
        chunk = _pick_chunk(x.shape[1], want)
        loss_sum, _ = _chunk_iter_fwd(x, w, targets, chunk)
        return loss_sum / (x.shape[0] * x.shape[1])

    def fwd_rule(x, w, targets):
        chunk = _pick_chunk(x.shape[1], want)
        loss_sum, logz = _chunk_iter_fwd(x, w, targets, chunk)
        n = x.shape[0] * x.shape[1]
        return loss_sum / n, (x, w, targets, logz)

    def bwd_rule(res, g):
        x, w, targets, logz = res
        b, t, d = x.shape
        v = w.shape[1]
        chunk = _pick_chunk(t, want)
        nc = t // chunk
        scale = g / (b * t)

        def body(dw_acc, ci):
            start = ci * chunk
            xc = jax.lax.dynamic_slice_in_dim(x, start, chunk, axis=1)
            tc = jax.lax.dynamic_slice_in_dim(targets, start, chunk, axis=1)
            lzc = jax.lax.dynamic_slice_in_dim(logz, start, chunk, axis=1)
            # backward recompute must use the SAME logits the forward
            # saw — including the fp8 arm's quantization
            logits = _head_logits(xc, w)
            p = jnp.exp(logits - lzc[..., None])
            vocab = jax.lax.broadcasted_iota(jnp.int32, p.shape, 2)
            p = jnp.where(vocab == tc[..., None], p - 1.0, p) * scale
            pc = p.astype(x.dtype)  # grads flow at compute precision
            dxc = jnp.einsum(
                "btv,dv->btd", pc, w, preferred_element_type=jnp.float32
            ).astype(x.dtype)
            dw_acc = dw_acc + jnp.einsum(
                "btd,btv->dv", xc, pc, preferred_element_type=jnp.float32
            )
            return dw_acc, dxc

        dw, dx = jax.lax.scan(body, jnp.zeros((d, v), jnp.float32),
                              jnp.arange(nc))
        dx = jnp.moveaxis(dx, 0, 1).reshape(b, t, d)
        import numpy as np
        zero = np.zeros(targets.shape, dtype=jax.dtypes.float0)
        return dx, dw.astype(w.dtype), zero

    flx.defvjp(fwd_rule, bwd_rule)
    flx.__name__ = name
    flx.__qualname__ = name
    return flx


# chunk ladder: bigger chunks amortize the (chunk, V) matmul better on the
# MXU, smaller ones cap live logits lower — a real tradeoff the tuner
# measures per shape (round-2 note: the fixed 128 cost ~8% at 774M/1.5B).
# The ladder deliberately stops at 256: the tuner times candidates as
# standalone jits on an otherwise-empty device, which is blind to the live
# logits slab (B, chunk, V) competing with model state in the real step —
# 256 bounds that slab at 2x the long-standing default, a measured-safe
# envelope, where a 512 winner could OOM the training step it never saw.
# (Winner identity for the AOT cache is each variant's stable
# __module__ + __name__, matched against the live candidate list.)
_FLX_VARIANTS = {
    want: _make_flx_variant(want, f"fused_linear_xent_c{want}")
    for want in (64, 128, 256)
}


def fused_linear_xent(x, w, targets, tuner=None):
    """mean NLL of logits = x @ w without materializing the full (B, T, V)
    logits tensor: forward and backward both stream (B, chunk, V) slabs.

    x (B, T, D); w (D, V); targets (B, T) int.  At GPT-2 vocab (50304) the
    full logits are ~25x the activations they come from — this op caps the
    live logits footprint at T/chunk of that and recomputes them in the
    backward (flash-attention-style recompute-over-materialize, applied to
    the loss head).  Replaces the reference's full-logits
    F.cross_entropy(logits.view(-1, V), ...) (reference example/model.py:
    154-156).

    The target chunk size is an autotuner site (chunk ladder above;
    default 128 without a tuner).  Caveat shared with the other sites
    (runtime_tuner.py): candidates are timed forward-only standalone jits,
    a proxy for the fwd+bwd in-graph cost."""
    if tuner is None:
        from ..autotuner import get_default_tuner
        tuner = get_default_tuner()
    # dedupe by EFFECTIVE chunk (short / divisor-poor T collapses several
    # wants onto one chunk — no point compiling identical programs), with
    # the long-standing default first
    cands, seen = [], set()
    for want in (128, 64, 256):
        eff = _pick_chunk(x.shape[1], want)
        if eff not in seen:
            seen.add(eff)
            cands.append(_FLX_VARIANTS[want])
    impl = tuner.choose(cands, (x, w, targets)) if tuner else cands[0]
    return impl(x, w, targets)
