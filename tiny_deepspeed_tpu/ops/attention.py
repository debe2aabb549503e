# Copyright 2026 tiny-deepspeed-tpu authors
# SPDX-License-Identifier: Apache-2.0

"""Causal self-attention compute: standard (materialized mask) and flash.

Capability parity with the reference attention switch
(example/model.py:25,78-81): `GPTConfig.attn_impl` selects between
`standard_attention` (explicit QK^T + causal mask + softmax, reference
model.py:29-42) and `flash_attention` (reference wraps
F.scaled_dot_product_attention, model.py:44-51).

TPU-first expression:
  * `standard_attention` is plain jnp — XLA fuses mask+softmax into the
    attention matmuls; logits accumulate in float32.
  * `flash_attention` prefers the Pallas blockwise kernel
    (ops/attention_pallas.py) on TPU backends and falls back to
    `jax.nn.dot_product_attention` / the standard path elsewhere (e.g. the
    virtual CPU mesh used in tests).

Both take (B, H, T, Dh) tensors, matching the reference's post-split layout
(reference model.py:72-76).
"""

from __future__ import annotations

import math
import os

import jax
import jax.numpy as jnp

from .dispatch import impl_label, kernel_target, note_kernel


def standard_attention(q, k, v):
    """Causal softmax(QK^T/sqrt(d))V with an explicit mask (reference :29-42)."""
    *_, t, dh = q.shape
    note_kernel("attention", "xla:standard_attention")
    scale = 1.0 / math.sqrt(dh)
    logits = jnp.einsum(
        "bhqd,bhkd->bhqk", q, k, preferred_element_type=jnp.float32
    ) * scale
    mask = jnp.tril(jnp.ones((t, t), dtype=bool))
    logits = jnp.where(mask, logits, jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v)


def _sdpa_or_standard(q, k, v):
    """XLA-fused causal SDPA, falling back to the explicit-mask path."""
    try:
        out = jax.nn.dot_product_attention(
            q.swapaxes(1, 2), k.swapaxes(1, 2), v.swapaxes(1, 2), is_causal=True
        ).swapaxes(1, 2)
    except Exception:
        return standard_attention(q, k, v)
    note_kernel("attention", "xla:dot_product_attention")
    return out


def _tuned_pallas_flash(q, k, v):
    """Pallas flash kernel, block sizes chosen by the runtime autotuner when
    one is installed (request recorded at trace time, winner baked on
    retune — the real multi-candidate site the reference's tuner never had,
    reference ops/linear.py:12 'Add more functions here')."""
    from .attention_pallas import FLASH_VARIANTS
    from ..autotuner import get_default_tuner

    tuner = get_default_tuner()
    # no tuner: candidates[0] is the measured default — round 4: the
    # hand-written FA2 kernel (ops/flash_fa2.py, fused-lse residuals, no
    # [B,H,T,block] stat broadcasts; every bench row +6-23% vs the bundled
    # kernel), T-guarded to fall back to the bundled kernel past FA2_MAX_T.
    # ONE list defines the dispatch for both the tuned and untuned paths.
    impl = (tuner.choose(FLASH_VARIANTS, (q, k, v)) if tuner is not None
            else FLASH_VARIANTS[0])
    note_kernel("attention", impl_label(impl))
    return impl(q, k, v)


def flash_kernel_ok(t: int) -> bool:
    """Sequence lengths Mosaic accepts the flash kernels at.  Compiled for
    v5e at T = 8..1024 (PR 21): the backward passes of BOTH kernels (FA2
    and the bundled one) lower only at T % 128 == 0, and FA2's bf16 forward
    only at T % 8 == 0 — a generate() prompt of 100 or 300 tokens failed to
    compile on the chip.  One predicate for every dispatch site: lengths off
    the 128 grid take the XLA path."""
    return t % 128 == 0


def flash_attention(q, k, v):
    """Blockwise causal attention; Pallas kernel on TPU, fused XLA elsewhere."""
    # Static (trace-time) backend choice: tracers carry no device, and the
    # kernel choice must be baked into the jitted program anyway.
    if kernel_target() == "tpu" and flash_kernel_ok(q.shape[2]):
        return _tuned_pallas_flash(q, k, v)
    return _sdpa_or_standard(q, k, v)


def gqa_flash_attention(q, k, v):
    """Grouped-query flash attention: q (B, H, T, Dh), k/v (B, KVH, T, Dh).

    On TPU, within the FA2 kernel's VMEM bound, K/V stay at KVH heads all
    the way into the kernel (ops/flash_fa2.py indexes kv panels by
    query_head // group) — the K/V HBM-traffic saving GQA exists for,
    which the reference's SDPA call gets from cuDNN (ref
    example/model.py:44-51) and a jnp.repeat forfeits.  Outside the
    bound, or off-TPU, falls back to repeat + the normal dispatch.  Not
    autotuned: the GQA site has one kernel candidate."""
    group = q.shape[1] // k.shape[1]
    t, d = q.shape[2], q.shape[3]
    if kernel_target() == "tpu" and flash_kernel_ok(t):
        from .flash_fa2 import fa2_flash_attention, fa2_gqa_supported
        if fa2_gqa_supported(t, d, group):
            note_kernel("attention", impl_label(fa2_flash_attention))
            return fa2_flash_attention(q, k, v)
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    return flash_attention(q, k, v)


def sharded_attention(q, k, v, impl: str, pctx=None):
    """Mesh-aware attention dispatch on (B, H, T, Dh) tensors; k/v may
    carry fewer (grouped-query) heads — (B, KVH, T, Dh) with KVH | H.

    * no mesh / 1 device       -> plain `flash_attention`/`standard_attention`
    * sequence-parallel mesh   -> ring attention over the "seq" axis
      (ppermute ring, O(T/n) memory — the long-context path the reference
      lacks entirely, SURVEY §5.7)
    * data-parallel mesh + TPU -> the Pallas flash kernel per batch shard
      under shard_map (XLA cannot auto-partition a custom call; without this
      the kernel would force an all-gather of the batch)
    * otherwise                -> jnp path, GSPMD partitions the einsums
    """
    base_fn = (flash_attention if impl == "flash_attention"
               else standard_attention)
    # non-Pallas fallback for partial-manual regions where the custom call
    # cannot be auto-partitioned over the remaining GSPMD axes
    local_fn = (_sdpa_or_standard if impl == "flash_attention"
                else standard_attention)

    # GQA: k/v arrive at KVH <= H heads (llama.py passes them UNREPEATED).
    # The flash paths below keep them grouped all the way into the FA2
    # kernel; every other path expands here — under GSPMD head sharding
    # the repeat is free, which is exactly what it replaced in llama.py.
    # TINY_DS_GQA=repeat is the chip A/B knob: it forces
    # the round-4 repeat-then-MHA-kernel path so the GQA-native win is
    # measured against the exact program it replaced.
    rep = q.shape[1] // k.shape[1]
    if rep > 1 and os.environ.get("TINY_DS_GQA") == "repeat":
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
        rep = 1

    def _expand(k, v):
        if rep == 1:
            return k, v
        return jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)

    if pctx is None or not pctx.is_multi_device:
        if rep > 1 and impl == "flash_attention":
            return gqa_flash_attention(q, k, v)
        k, v = _expand(k, v)
        return base_fn(q, k, v)

    from ..parallel.ring_attention import ring_attention
    from jax.sharding import NamedSharding, PartitionSpec as P

    # tensor parallelism: heads split over the "model" axis; attention is
    # embarrassingly parallel over heads so every path below just carries
    # the head axis in its specs.
    head_axis = pctx.model_axis if pctx.tensor_parallel else None

    if pctx.seq_parallel:
        ulysses = getattr(pctx, "seq_impl", "ring") == "ulysses"
        # GQA x Ulysses (round 5): the head/seq all-to-all can carry K/V
        # at kv_heads — the K/V reshard bytes drop by the group factor —
        # because splitting H and KVH into the same n contiguous blocks
        # preserves the group adjacency exactly when n | kv_heads
        # (local q block [r*H/n,...) maps onto local kv block
        # [r*KVH/n,...) with local index h' // group).  The ring and the
        # partial-manual paths assume matching head counts — expand there
        # (the repeat is sharded over the head/model axes, so it moves no
        # extra bytes across the mesh).
        tp_size = (pctx.mesh.shape[pctx.model_axis]
                   if pctx.tensor_parallel else 1)
        gqa_ulysses = (
            rep > 1 and ulysses and not pctx.pipe_parallel
            and impl == "flash_attention"
            and (k.shape[1] // tp_size)
            % pctx.mesh.shape[pctx.seq_axis] == 0
        )
        # the ring takes grouped K/V everywhere (round 5): both its
        # bodies are GQA-aware (kernel: kv-indexed panels; jnp: grouped
        # einsum), so the rotating K/V — the ring's dominant wire term —
        # and the backward's dk/dv accumulators move at kv_heads.
        gqa_ring = rep > 1 and not ulysses
        if not (gqa_ulysses or gqa_ring):
            k, v = _expand(k, v)
        if pctx.pipe_parallel:
            # inside the pipeline's shard_map, which is manual over BOTH
            # {pipe, seq} (parallel/pipeline.py): q/k/v are already local
            # (T/n) shards and the seq axis is manual, so the per-shard
            # bodies are called directly — wrapping another shard_map
            # would fail
            if ulysses:
                # data/TP axes are still GSPMD-auto in this region: the
                # Pallas custom call cannot be auto-partitioned over them
                # (it would all-gather the batch), so the local kernel is
                # the XLA path — same reason as the plain-pipeline branch
                from ..parallel.ulysses import ulysses_attention_local
                return ulysses_attention_local(
                    q, k, v, axis_name=pctx.seq_axis, attn_fn=local_fn,
                )
            from ..parallel.ring_attention import ring_attention_local
            return ring_attention_local(
                q, k, v, axis_name=pctx.seq_axis,
                axis_size=pctx.mesh.shape[pctx.seq_axis],
                allow_kernel=False,  # data axis is GSPMD-auto here
            )
        if ulysses:
            # ulysses_attention's shard_map is FULLY manual (all axes in
            # its specs), so the Pallas kernel runs per-shard safely;
            # with gqa_ulysses the local kernel consumes grouped K/V
            # (gqa_flash_attention handles the off-TPU/oversize fallback)
            from ..parallel.ulysses import ulysses_attention
            return ulysses_attention(
                q, k, v, pctx.mesh, seq_axis=pctx.seq_axis,
                batch_axis=pctx.data_axis, head_axis=head_axis,
                attn_fn=gqa_flash_attention if gqa_ulysses else base_fn,
            )
        # attn_impl="standard_attention" keeps its kernel-free meaning
        # under the ring too: the jnp body runs, not the FA2 chunks
        return ring_attention(
            q, k, v, pctx.mesh, seq_axis=pctx.seq_axis,
            batch_axis=pctx.data_axis, head_axis=head_axis,
            allow_kernel=impl == "flash_attention",
        )

    if pctx.pipe_parallel:
        # Inside the pipeline's manual-over-"pipe" region a nested full
        # shard_map (the Pallas flash path below) would re-manualize the
        # already-manual pipe axis and fail at trace time; use the GSPMD
        # jnp path, which auto-partitions over the remaining axes.
        k, v = _expand(k, v)
        if head_axis is not None:
            sh = NamedSharding(
                pctx.mesh, P(pctx.data_axis, head_axis, None, None)
            )
            q, k, v = (
                jax.lax.with_sharding_constraint(z, sh) for z in (q, k, v)
            )
        return local_fn(q, k, v)

    if (impl == "flash_attention" and kernel_target() == "tpu"
            and flash_kernel_ok(q.shape[2])):
        # GQA rides through: per-shard head counts keep the same group
        # ratio (tp must divide kv_heads — models/llama.py tp_rules), so
        # the local gqa path sees a consistent (H/tp, KVH/tp) pair
        spec = P(pctx.data_axis, head_axis, None, None)
        local = gqa_flash_attention if rep > 1 else _tuned_pallas_flash
        return jax.shard_map(
            local, mesh=pctx.mesh,
            in_specs=(spec, spec, spec), out_specs=spec, check_vma=False,
        )(q, k, v)

    k, v = _expand(k, v)
    if head_axis is not None:
        # pin the head-sharded layout so GSPMD partitions the attention
        # einsums over heads instead of gathering them
        sh = NamedSharding(pctx.mesh, P(pctx.data_axis, head_axis, None, None))
        q, k, v = (jax.lax.with_sharding_constraint(z, sh) for z in (q, k, v))

    return base_fn(q, k, v)
