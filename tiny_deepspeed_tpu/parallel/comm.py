# Copyright 2026 tiny-deepspeed-tpu authors
# SPDX-License-Identifier: Apache-2.0

"""Collective CODECS and wire geometry: quantized gradient collectives
(blockwise int8/fp8 reduce-scatter with error feedback, hierarchical
2-hop all-reduce), the bucket layout table, and the ring wire models.

The scan-tap machinery that used to live here (the bucketed grad-release
tap, the prefetched weight-gather scan, the per-layer health probe) is
now owned by parallel/schedule.py — the ONE composable in-scan
collective scheduler; this module keeps only the quantization primitives
and schedules it calls.  The repo-hygiene guard
(tests/test_repo_hygiene.py) pins that no jax.custom_vjp scan-tap grows
back here.

The gradient reduce-scatter/all-reduce is the dominant per-step wire cost
in every ZeRO stage (utils/hlo_comm.py ring model, PROFILE.md), and until
this module it always ran at full precision — only the ZeRO-3 weight
gather was quantized (gather_quant="fp8", models/gpt2.py).  ZeRO++ (qgZ,
arxiv 2306.10209) and EQuARX show the other half: blockwise-quantized,
hierarchically-scheduled gradient collectives cut cross-replica gradient
traffic ~4x with negligible convergence impact.

Under GSPMD the gradient reduction is IMPLICIT — XLA emits the
all-reduce/reduce-scatter from sharding constraints, so there is no
program point where "the bytes on the wire" can be re-typed.  The engine
therefore computes LOCAL grads inside a `jax.shard_map` over the data
axis (params replicated, model applied with pctx=None — the same
manual-region pattern as the MoE pure-DP sort dispatch) and calls the
explicit schedule here:

  1. error feedback: e = g_local + residual; the residual is what the
     quantizer dropped LAST step, re-injected so quantization error
     accumulates to zero instead of biasing the trajectory (EF-SGD /
     1-bit Adam lineage).
  2. blockwise quantize e: per-block (default 256 elems) absmax scale,
     int8 with STOCHASTIC rounding (unbiased: E[Q(x)] = x) or fp8 e4m3
     round-to-nearest; new residual = e - dequant(Q(e)).
  3. reduce-scatter as an all-to-all of the quantized blocks + local
     dequant-sum — one hop on a flat axis, or TWO hops when
     `inner` factors the axis (ZeRO++/EQuARX hierarchical schedule):
     intra-group all-to-all at low precision, inter-group at bf16 so the
     second hop adds no second quantization error to the partial sums.
  4. all-gather of the (re-quantized) reduced chunks back to replicated
     full gradients — the all-reduce completion, also 1-byte wire.

Wire bytes per device (E gradient elements, n devices, ring model):
    fp32 all-reduce          8 E (n-1)/n
    int8 flat schedule       ~2 E (n-1)/n  + scales (4/block per elem)
so ~3.9x less at block=256 — the measured ledger (utils/hlo_comm.py)
pins >= 3.5x in tests/test_grad_comm.py.

Everything here runs INSIDE a shard_map manual region over the data axis;
the public entry is `quantized_grad_sync`.  The quant/dequant primitives
are XLA everywhere (they fuse into the surrounding code); a Pallas kernel
behind the existing dispatch gate (ops/dispatch.kernel_target) can slot
into `quantize_blockwise` later without touching the schedule.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

GRAD_COMM_MODES = ("fp32", "int8", "fp8")
DEFAULT_BLOCK = 256

_QMAX = {"int8": 127.0, "fp8": 448.0}  # e4m3 max normal = 448
_QDTYPE = {"int8": jnp.int8, "fp8": jnp.float8_e4m3fn}


def padded_size(n_elems: int, n_dev: int, block: int = DEFAULT_BLOCK) -> int:
    """Flat gradient length after padding: the smallest multiple of
    n_dev * block >= n_elems, so every hop's split is block-aligned
    (E = n*block*t => E/m divisible by both block and G for any
    factorization n = m*G, and the final 1/n chunk is block-aligned)."""
    unit = n_dev * block
    return max(unit, ((n_elems + unit - 1) // unit) * unit)


# ---------------------------------------------------------------------------
# blockwise quant/dequant primitives
# ---------------------------------------------------------------------------

def quantize_blockwise(x, mode: str, block: int = DEFAULT_BLOCK, rng=None):
    """Flat f32 (len % block == 0) -> (q, scale).

    q: int8 or float8_e4m3fn, same length; scale: (len/block, 1) f32
    per-block absmax scales.  int8 + rng uses stochastic rounding
    (additive U(-1/2, 1/2) dither before round — unbiased, the property
    tests/test_grad_comm.py pins); rng=None rounds to nearest.  fp8
    casts round-to-nearest-even (the e4m3 cast is already fine-grained
    enough that dithering buys nothing).

    On a TPU kernel target the fused Pallas quantizer takes over
    (ops/quant_pallas.py — one VMEM pass for absmax/scale/round/cast,
    behind the standard ops.dispatch gate); the XLA formulation below is
    the everywhere-fallback and the parity reference.  Both consume the
    same dither draw, so the paths are directly comparable."""
    if mode not in _QMAX:
        raise ValueError(f"quantize_blockwise mode must be int8/fp8, "
                         f"got {mode!r}")
    dither = None
    if mode == "int8" and rng is not None:
        dither = jax.random.uniform(rng, x.shape, jnp.float32, -0.5, 0.5)
    from ..ops.dispatch import kernel_target
    if kernel_target() == "tpu":
        from ..ops.quant_pallas import pallas_quantize_blockwise
        return pallas_quantize_blockwise(x, mode, block, dither)
    nb = x.shape[0] // block
    xb = x.reshape(nb, block)
    s = jnp.max(jnp.abs(xb), axis=1, keepdims=True) / _QMAX[mode] + 1e-12
    y = xb / s
    if dither is not None:
        y = y + dither.reshape(nb, block)
    if mode == "int8":
        q = jnp.clip(jnp.round(y), -127.0, 127.0).astype(jnp.int8)
    else:
        q = y.astype(jnp.float8_e4m3fn)
    return q.reshape(-1), s


def dequantize_blockwise(q, scale):
    """(q, (nb, 1) scale) -> flat f32."""
    nb = scale.shape[0]
    return (q.astype(jnp.float32).reshape(nb, -1) * scale).reshape(-1)


def _quant_rows(parts, mode, block, rng):
    """(k, r) f32 rows (r % block == 0) -> (q (k, r), scales (k, r/block)).
    Blocks never straddle rows, so row-wise quantization == flat
    quantization of the concatenation (what the error-feedback residual
    relies on)."""
    k, r = parts.shape
    q, s = quantize_blockwise(parts.reshape(-1), mode, block, rng)
    return q.reshape(k, r), s.reshape(k, r // block)


def _dequant_rows(q, s):
    k, r = q.shape
    nb = s.shape[1]
    return (
        q.astype(jnp.float32).reshape(k, nb, r // nb) * s[:, :, None]
    ).reshape(k, r)


def as_wire(q):
    """Bitcast an fp8 payload to u8 for the collective: backends without
    native f8 collectives (XLA:CPU here) otherwise CONVERT the operand
    to f16 — doubling the one wire the codec exists to shrink.  u8 moves
    1 byte/elem everywhere; int8 payloads pass through untouched (their
    collectives are already native), keeping the int8 HLO byte-identical."""
    if q.dtype == jnp.float8_e4m3fn:
        return jax.lax.bitcast_convert_type(q, jnp.uint8)
    return q


def from_wire(q, mode: str):
    """Undo `as_wire` after the collective."""
    if mode == "fp8" and q.dtype == jnp.uint8:
        return jax.lax.bitcast_convert_type(q, jnp.float8_e4m3fn)
    return q


# ---------------------------------------------------------------------------
# the schedule (inside a shard_map manual region over `axis`)
# ---------------------------------------------------------------------------

def _hier_groups(n: int, inner: int):
    """(intra, inter) axis_index_groups for n = G*inner consecutive-rank
    groups: intra = the inner-sized groups (hop 1, low precision), inter =
    same-local-rank members across groups (hop 2, bf16).  `inner` must be
    a divisor of n — the engine validates its knob, but the schedule
    helpers validate too so a direct caller cannot silently build groups
    that drop ranks."""
    if inner < 1 or n % inner:
        raise ValueError(
            f"hierarchical inner group size {inner} must divide the "
            f"axis size {n}"
        )
    g_outer = n // inner
    intra = [[g * inner + j for j in range(inner)] for g in range(g_outer)]
    inter = [[g * inner + j for g in range(g_outer)] for j in range(inner)]
    return intra, inter


def piece_owner(n: int, inner: Optional[int]) -> np.ndarray:
    """owner[p] = rank holding canonical piece p after the reduce-scatter.

    Flat schedule: owner[p] = p.  2-hop: rank r = (gid, lid) ends with
    sub-piece gid of part lid, i.e. piece p = lid*G + gid lives on rank
    gid*inner + lid."""
    if not inner or inner in (1, n):
        return np.arange(n)
    if n % inner:
        raise ValueError(
            f"hierarchical inner group size {inner} must divide the "
            f"axis size {n}"
        )
    g_outer = n // inner
    p = np.arange(n)
    gid, lid = p % g_outer, p // g_outer
    return gid * inner + lid


def quantized_reduce_scatter(flat, axis: str, n: int, mode: str, *,
                             block: int = DEFAULT_BLOCK, rng=None,
                             inner: Optional[int] = None,
                             pre_q: Optional[Tuple] = None):
    """Sum `flat` ((E,) f32 local, E % (n*block) == 0) across the manual
    axis; returns this rank's 1/n chunk of the sum, in canonical-piece
    order given by `piece_owner(n, inner)`.

    `pre_q=(q, s)` supplies an already-quantized copy of `flat` (the
    error-feedback path quantizes once up front to compute the residual);
    otherwise quantizes here.  One hop when `inner` is None/1/n; else the
    2-hop hierarchical schedule: intra-group all-to-all at `mode`
    precision, inter-group all-to-all of the partial sums at bf16 (per
    ZeRO++/EQuARX: re-quantizing partial sums to int8 would compound two
    quantization errors; bf16 costs 2 bytes on 1/inner of the volume)."""
    e = flat.shape[0]
    if pre_q is None:
        pre_q = quantize_blockwise(flat, mode, block, rng)
    q, s = pre_q
    if not inner or inner in (1, n):
        parts = as_wire(q).reshape(n, e // n)
        srows = s.reshape(n, -1)
        parts = jax.lax.all_to_all(parts, axis, 0, 0, tiled=True)
        srows = jax.lax.all_to_all(srows, axis, 0, 0, tiled=True)
        return jnp.sum(_dequant_rows(from_wire(parts, mode), srows),
                       axis=0)
    intra, inter = _hier_groups(n, inner)
    # hop 1: low-precision reduce-scatter within the inner group
    parts = as_wire(q).reshape(inner, e // inner)
    srows = s.reshape(inner, -1)
    parts = jax.lax.all_to_all(parts, axis, 0, 0,
                               axis_index_groups=intra, tiled=True)
    parts = from_wire(parts, mode)
    srows = jax.lax.all_to_all(srows, axis, 0, 0,
                               axis_index_groups=intra, tiled=True)
    part = jnp.sum(_dequant_rows(parts, srows), axis=0)   # (E/inner,)
    # hop 2: bf16 reduce-scatter of the partial sums across groups
    g_outer = n // inner
    sub = part.reshape(g_outer, -1).astype(jnp.bfloat16)
    sub = jax.lax.all_to_all(sub, axis, 0, 0,
                             axis_index_groups=inter, tiled=True)
    return jnp.sum(sub.astype(jnp.float32), axis=0)       # (E/n,)


def quantized_all_gather(chunk, axis: str, n: int, mode: str, *,
                         block: int = DEFAULT_BLOCK, rng=None,
                         inner: Optional[int] = None):
    """All-gather the reduced chunks back to the full flat vector at
    `mode` precision (the all-reduce completion).  Rows come back in rank
    order; the hierarchical schedule leaves pieces rank-permuted, so they
    are re-ordered by the static `piece_owner` table."""
    q, s = quantize_blockwise(chunk, mode, block, rng)
    rows = jax.lax.all_gather(as_wire(q), axis, axis=0, tiled=False)
    srows = jax.lax.all_gather(s.reshape(-1), axis, axis=0, tiled=False)
    vals = _dequant_rows(from_wire(rows, mode), srows)    # (n, E/n)
    owner = piece_owner(n, inner)
    if not np.array_equal(owner, np.arange(n)):
        vals = vals[owner]
    return vals.reshape(-1)


@jax.named_scope("tds.grad_sync")
def quantized_grad_sync(grads, residual, axis: str, n: int, mode: str, *,
                        block: int = DEFAULT_BLOCK, rng=None,
                        inner: Optional[int] = None, mean: bool = True):
    """Error-feedback quantized all-reduce of a local gradient tree.

    Called INSIDE the engine's shard_map over the data axis.  `grads` is
    this device's local gradient tree (any float leaf dtypes); `residual`
    is the flat (padded_size,) f32 error carried from last step, or None
    (error feedback off).  Returns (reduced tree in the original leaf
    dtypes, new flat residual or None).

    The residual is computed against what hop 1 actually transmits
    (residual = e - dequant(Q(e)), with Q(e) quantized ONCE and reused
    by the reduce-scatter), so the compensation is exact for the flat
    schedule.  The hop-2 bf16 rounding and the all-gather re-quantization
    are NOT error-fed — they act on cross-device partial/final sums no
    single rank can compensate locally; stochastic rounding keeps the
    gather hop unbiased, and bf16 partial sums are below gradient noise
    (the ZeRO++/EQuARX position, convergence-pinned in
    tests/test_grad_comm.py)."""
    leaves = jax.tree.leaves(grads)
    treedef = jax.tree.structure(grads)
    sizes = [int(np.prod(l.shape)) for l in leaves]
    total = sum(sizes)
    e_pad = padded_size(total, n, block)
    flat = jnp.concatenate(
        [l.reshape(-1).astype(jnp.float32) for l in leaves]
    )
    if e_pad > total:
        flat = jnp.concatenate(
            [flat, jnp.zeros((e_pad - total,), jnp.float32)]
        )
    rng_rs = rng_ag = None
    if rng is not None:
        rng_rs, rng_ag = jax.random.split(rng)
    if residual is not None:
        err = flat + residual
        q, s = quantize_blockwise(err, mode, block, rng_rs)
        new_residual = err - dequantize_blockwise(q, s)
        # a non-finite local grad (fp16 overflow step) must not poison the
        # carried error forever — the bad values still reach the wire and
        # trip the engine's finite check; only the residual is scrubbed
        new_residual = jnp.where(
            jnp.isfinite(new_residual), new_residual, 0.0
        )
        pre_q = (q, s)
    else:
        new_residual = None
        pre_q = quantize_blockwise(flat, mode, block, rng_rs)
    chunk = quantized_reduce_scatter(
        flat, axis, n, mode, block=block, inner=inner, pre_q=pre_q
    )
    if mean:
        chunk = chunk / n
    out_flat = quantized_all_gather(
        chunk, axis, n, mode, block=block, rng=rng_ag, inner=inner
    )
    out_leaves, off = [], 0
    for leaf, sz in zip(leaves, sizes):
        out_leaves.append(
            out_flat[off:off + sz].reshape(leaf.shape).astype(leaf.dtype)
        )
        off += sz
    return jax.tree.unflatten(treedef, out_leaves), new_residual


# ---------------------------------------------------------------------------
# bucketed backward-overlapped release (engine grad_buckets=, ISSUE 3)
# ---------------------------------------------------------------------------

def bucket_layout(shapes, n_layer: int, n_buckets: int, n_dev: int,
                  block: int = DEFAULT_BLOCK) -> dict:
    """Static geometry of the bucketed gradient release.

    The stacked "h.*" leaves are chunked into `n_buckets` groups of
    n_layer/n_buckets consecutive layers (every layer carries the same
    per-layer parameter count, so equal layer counts ARE size-balanced
    buckets), and the non-block leaves (wte/wpe/ln_f/lm_head) form the
    tail bucket — their grads finalize only once the whole backward is
    done (wte last of all), so there is no overlap window to chase for
    them.  `bucket_pad`/`tail_pad` are the per-bucket padded flat sizes
    the quantized schedule and the error-feedback residual slices use;
    the residual row is laid out [bucket 0 | ... | bucket K-1 | tail]."""
    if n_buckets < 1:
        raise ValueError(f"grad_buckets must be >= 1, got {n_buckets}")
    if n_layer % n_buckets:
        raise ValueError(
            f"grad_buckets={n_buckets} must divide n_layer={n_layer} "
            "(equal layers per bucket is what keeps the buckets "
            "size-balanced and the scan body uniform)"
        )
    block_elems = sum(
        int(np.prod(s.shape)) for n, s in shapes.items()
        if n.startswith("h.")
    )
    tail_elems = sum(
        int(np.prod(s.shape)) for n, s in shapes.items()
        if not n.startswith("h.")
    )
    per_bucket = block_elems // n_buckets
    bucket_pad = padded_size(per_bucket, n_dev, block)
    tail_pad = padded_size(tail_elems, n_dev, block) if tail_elems else 0
    return {
        "n_buckets": n_buckets,
        "layers_per_bucket": n_layer // n_buckets,
        "bucket_elems": per_bucket,
        "bucket_pad": bucket_pad,
        "tail_elems": tail_elems,
        "tail_pad": tail_pad,
        "tail_names": sorted(
            n for n in shapes if not n.startswith("h.")
        ),
        "residual_len": n_buckets * bucket_pad + tail_pad,
    }


def modeled_gather_wire_bytes(block_rest_bytes: int, block_cd_bytes: int,
                              n: int, inner: Optional[int] = None) -> float:
    """Ring-model per-device wire bytes of ONE full-stack weight gather
    (all layers, one pass) — the comm_report pricing hook for the
    prefetched schedule.  Flat: resting-precision payload * (n-1)/n.
    2-hop (`inner` ranks per group): hop 1's OUTPUT is only the group's
    inner/n chunk of the tensor, so its wire is rest * (inner-1)/n; hop 2
    all-gathers the full tensor across g = n/inner groups at compute
    dtype (dequantized), cd * (g-1)/g.  With rest == cd the two hops sum
    to exactly the flat (n-1)/n — an all-gather's ring wire is
    output-minus-input bytes however it is staged (CPU-mesh ledger check:
    the fp32 2-hop program measures byte-identical gather wire to flat;
    only a dtype change between hops moves the total)."""
    if n <= 1:
        return 0.0
    if not inner or inner in (1, n):
        return block_rest_bytes * (n - 1) / n
    g_outer = n // inner
    return (block_rest_bytes * (inner - 1) / n
            + block_cd_bytes * (g_outer - 1) / g_outer)


# ---------------------------------------------------------------------------
# wire model (the comm_report / ledger_summary honest-bytes counterpart)
# ---------------------------------------------------------------------------

def modeled_wire_bytes(n_elems: int, n: int, mode: str, *,
                       block: int = DEFAULT_BLOCK,
                       inner: Optional[int] = None) -> dict:
    """Ring-model per-device wire bytes of one quantized grad sync, the
    same accounting conventions as utils/profiling.comm_report /
    utils/hlo_comm.py (all-to-all and all-gather both move payload *
    (n-1)/n).  Returns the quantized total next to the fp32 all-reduce
    baseline so callers (comm_report, telemetry gauges) can report bytes
    saved without re-deriving the schedule."""
    e = padded_size(n_elems, n, block)
    scale_b = e // block * 4
    qpay = e * 1 + scale_b                      # int8 and e4m3 are 1 byte
    if not inner or inner in (1, n):
        rs = qpay * (n - 1) / n
    else:
        g_outer = n // inner
        rs = (qpay * (inner - 1) / inner
              + 2 * (e // inner) * (g_outer - 1) / g_outer)
    ag = qpay * (n - 1) / n
    return {
        "mode": mode,
        "elems_padded": e,
        "quant_wire_bytes": float(rs + ag),
        "fp32_allreduce_wire_bytes": float(2 * 4 * n_elems * (n - 1) / n)
        if n > 1 else 0.0,
    }


def modeled_hpz_rebuild_bytes(shard_bytes: int, shard_elems: int,
                              n_gran: int, mode: str, *,
                              block: int = DEFAULT_BLOCK) -> float:
    """Ring-model per-device wire of the once-per-step hpZ secondary
    rebuild: each rank's global 1/n shard of the sharded stacked leaves
    all-gathers over the `n_gran` inter-slice group (parallel/schedule
    build_sec).  Passthrough mode gathers the leaves at their stacked
    dtype (`shard_bytes`); a quantized mode (qwZ-style, ZeRO++
    arXiv:2306.10209) gathers ONE concatenated blockwise-quantized
    payload (1 byte/elem after padding `shard_elems` to a block
    multiple) plus its f32 scales.  Same convention as the ledger:
    all-gather wire = result bytes * (n_gran - 1) / n_gran."""
    if n_gran <= 1:
        return 0.0
    if mode == "fp32":
        return float(shard_bytes * (n_gran - 1))
    e = shard_elems + (-shard_elems % block)
    return float((e + e // block * 4) * (n_gran - 1))
