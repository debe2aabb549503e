# Copyright 2026 tiny-deepspeed-tpu authors
# SPDX-License-Identifier: Apache-2.0

"""GPT-2, pure-JAX and TPU-first.

Capability parity with the reference model (example/model.py): GPTConfig
(:15-25), token+position embeddings, pre-LN transformer blocks with fused-QKV
causal attention (:53-85), GELU MLP (:89-101), final layernorm, weight-untied
lm_head, and cross-entropy loss computed inside forward when targets are given
(:139-157).  The `attn_impl` switch ("standard_attention" | "flash_attention")
mirrors reference model.py:25,78-81.

Deliberate TPU-first design deltas (this is a re-design, not a port):

  * Parameters are a FLAT, NAME-KEYED dict (ordered), not nn.Module
    attributes.  Names are stable and sorted insertion order — this is what
    the partitioner ("cache rank map") and the name-keyed optimizers consume,
    replacing torch's named_parameters() iteration.
  * The L transformer blocks are STACKED: each block tensor carries a leading
    (n_layer,) axis and the forward runs `jax.lax.scan` over it.  One traced
    block → O(1) compile time in depth (a 48-layer 1.5B model compiles as
    fast as a 1-layer one), and the stacked axis is a natural target for
    pipeline/ZeRO sharding.
  * Linear weights are (in, out) — see ops/linear.py.
  * Mixed precision is a first-class policy: params live in `param_dtype`
    (float32) and compute runs in `compute_dtype` (bfloat16 on TPU).  The
    reference's AMP is an unchecked TODO (reference README.md:68).
  * Each block is wrapped in `jax.checkpoint` (remat) so the backward
    re-materializes activations instead of storing 2L of them — the TPU way
    to trade MXU FLOPs for HBM.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from ..ops import (
    linear,
    layernorm,
    embedding,
    softmax_cross_entropy,
)
from ..ops.attention import sharded_attention


@dataclasses.dataclass(frozen=True)
class GPTConfig:
    """Model hyperparameters (parity: reference example/model.py:15-25)."""

    block_size: int = 1024
    vocab_size: int = 50304  # padded to a multiple of 128 for the MXU
    n_layer: int = 12
    n_head: int = 12
    n_embd: int = 768
    attn_impl: str = "flash_attention"  # or "standard_attention"
    # Reference-parity knobs (reference example/model.py:23-24):
    #  * `bias` gates the four projection biases (attn qkv/proj, mlp fc/
    #    proj — reference nn.Linear(bias=config.bias)); layernorms keep
    #    theirs (the reference uses stock nn.LayerNorm) and lm_head is
    #    always bias-free (reference model.py:137).  The reference DEFAULTS
    #    bias=False; default True here = the actual GPT-2 architecture.
    #  * `dropout` in the reference is a dead knob: config.dropout is never
    #    read, and its attention calls hard-code `dropout_p=False` == 0.0
    #    (reference model.py:79-81) so dropout never fires even in
    #    training.  Implemented CORRECTLY here (embedding + post-attention
    #    + post-MLP residual dropout, inverted scaling); active only when
    #    a PRNG key is passed to `apply(rng=...)` — the engine does so
    #    automatically when dropout > 0, deriving a fresh key from the
    #    optimizer step counter, so eval/generate stay deterministic.
    bias: bool = True
    dropout: float = 0.0
    # wte/lm_head weight tying.  The ACTUAL GPT-2 ties them; the reference
    # unties (model.py:136-138 creates an independent lm_head), so False is
    # the parity default.  Tied drops the (vocab, d) lm_head table —
    # 38.6M params on gpt2-124m — and the gradient flows through both the
    # gather and the projection use of wte.
    tie_weights: bool = False
    # ZeRO++-style quantized weight gather (qwZ, arxiv 2306.10209), the
    # float8 variant: "fp8" stacks the block matmul weights as
    # float8_e4m3 + per-output-channel f32 scales instead of compute-dtype
    # values, so the per-layer all-gather inside the ZeRO-3 scan moves 2x
    # fewer bytes than bf16 (4x vs f32); each block dequantizes after the
    # gather (one multiply, fused by XLA into the matmul).  Scaling/cast
    # runs ONCE per step from the float32 masters, outside the scan and
    # outside remat.  fp8 rather than int8 deliberately: the e4m3 cast is
    # differentiable (FP8-training style), so no straight-through
    # custom-vjp machinery — the cost is that the per-layer dW cotangent
    # crosses the same edge in e4m3 (scaled by the same per-channel
    # absmax), the standard FP8-comm tradeoff — convergence validated vs
    # the unquantized path in tests/test_fp8_gather.py (30-step loss
    # curves within 5%).  EXPERIMENTAL; the byte win is backend-dependent
    # and on XLA CPU it is NEGATIVE (round-3 measurement, PROFILE.md):
    # the collective upcasts f8 to f16 and several remat-backward gathers
    # stay full precision, so the quantized config moves ~1.34x MORE wire
    # bytes than plain compute dtype (collective ledger pinned in
    # tests/test_profiling.py).  Profile on the target backend before
    # relying on it.  None (default) keeps the exact compute-dtype path.
    gather_quant: Optional[str] = None
    param_dtype: Any = jnp.float32
    compute_dtype: Any = jnp.bfloat16
    remat: bool = True
    # which intermediates the block remat may keep instead of recomputing:
    # "nothing" | "dots" | "dots_no_batch" | "all"  (measured on v5e-1,
    # GPT-2 124M B=8: dots_no_batch ~84.0k tok/s vs nothing ~80.3k;
    # "nothing" still minimizes HBM)
    remat_policy: str = "dots_no_batch"
    # token-embedding row-norm cap: each USED row of wte is rescaled to
    # ||row|| <= wte_max_norm before the gather (reference nn.Embedding
    # max_norm, wired through reference ops/embedding.py:67-68; the
    # reference's GPT-2 never sets it, so None is parity).  Functional:
    # the stored table is untouched, grads flow through the rescale.
    wte_max_norm: Optional[float] = None
    # chunked lm_head+loss (never materializes full (B, T, V) logits;
    # ops/softmax_xent.fused_linear_xent).  A MEMORY knob, not a speed knob:
    # measured v5e-1 gpt2-124m B=8 T=1024 it costs ~8% (77.0k vs 83.8k
    # tok/s — backward recomputes the lm_head matmul) while capping live
    # logits at chunk/T of full; enable for long-T / tight-HBM configs
    # where full (B, T, V) logits would not fit.  Falls back automatically
    # under sequence parallelism (chunking would slice the sharded T axis).
    fused_xent: bool = False
    # which fused implementation: "chunked" (the XLA scan above) or
    # "pallas" (ops/xent_pallas.py — logit tiles live only in VMEM,
    # online logsumexp + in-kernel gold gather, FA2-style recompute
    # backward; round 5).  "pallas" is TPU-gated and falls back to the
    # chunked path elsewhere; adoption as default awaits the chip A/B
    # (measure standalone first, adopt only on an end-to-end win).
    fused_xent_impl: str = "chunked"
    # resting dtype of the decode KV cache (generate(use_cache=True) and
    # the serving tier's contiguous prefill).  None keeps compute_dtype;
    # "bf16"/jnp.bfloat16 halves cache HBM on an f32-compute config —
    # decode is cache-bandwidth bound, and `_decode_attention` already
    # consumes the cache in its resting dtype with f32 MXU accumulation.
    # Greedy parity vs the full-forward path is seed-pinned in
    # tests/test_serving.py.  int8/fp8 cache compression lives in the
    # PAGED pool only (serving/pool.py), where the per-vector scales have
    # a place to rest.
    cache_dtype: Any = None
    # lax.scan unroll factor for the layer stack (True/n_layer = fully
    # unrolled).  Unrolling deletes the scan's stacked activation-stash
    # dynamic-slice traffic — the round-4 TPU profile priced that IO plus
    # the slice/update fusions at ~16 ms of a 132 ms gpt2-124m step — and
    # lets XLA schedule across layer boundaries: measured v5e-1 124M
    # B=12 106.5k tok/s / 0.463 matmul MFU vs 92.0k / 0.401 scanned
    # (+16%).  Default stays scanned: one traced block keeps compile time
    # O(1) in depth (SURVEY §3.1 rationale), and under ZeRO-3 the scan is
    # what bounds live gathered weights to one layer — unrolling there
    # lets XLA hoist gathers and regrow full-model HBM.  Engines leave
    # this to the user/bench config; pipeline ignores it (stages scan).
    scan_unroll: Any = 1

    @property
    def head_dim(self) -> int:
        return self.n_embd // self.n_head


# cache_dtype knob spellings -> jnp dtypes (dtype objects pass through)
_CACHE_DTYPES = {
    "bf16": jnp.bfloat16, "bfloat16": jnp.bfloat16,
    "f16": jnp.float16, "fp16": jnp.float16, "float16": jnp.float16,
    "f32": jnp.float32, "fp32": jnp.float32, "float32": jnp.float32,
}


def resolved_cache_dtype(cfg) -> Any:
    """The decode KV cache's resting dtype: config.cache_dtype (string
    spelling or dtype), defaulting to compute_dtype.  Shared by the
    in-scan decode cache (`_prefill`) and the serving tier's paged pool
    (serving/pool.py) so the knob means the same thing on both."""
    cd = getattr(cfg, "cache_dtype", None)
    if cd is None:
        return cfg.compute_dtype
    if isinstance(cd, str):
        try:
            return _CACHE_DTYPES[cd]
        except KeyError:
            raise ValueError(
                f"cache_dtype {cd!r} not understood; use one of "
                f"{sorted(_CACHE_DTYPES)} or a jnp dtype (int8/fp8 cache "
                f"compression lives in the paged pool: serving/pool.py)"
            ) from None
    return cd


# Named presets covering the BASELINE.md workloads.  "tiny" exists so every
# example entry point smoke-tests in seconds on the virtual CPU mesh
# (`--cpu-devices 8`): XLA-CPU compile of a full 124M step takes minutes
# (round-1 verdict weak #7); float32 compute because CPU bf16 is emulated.
GPT2_PRESETS: Dict[str, GPTConfig] = {
    "tiny": GPTConfig(block_size=256, vocab_size=512, n_layer=2, n_head=2,
                      n_embd=64, compute_dtype=jnp.float32),
    "gpt2-124m": GPTConfig(n_layer=12, n_head=12, n_embd=768),
    "gpt2-350m": GPTConfig(n_layer=24, n_head=16, n_embd=1024),
    "gpt2-774m": GPTConfig(n_layer=36, n_head=20, n_embd=1280),
    "gpt2-1.5b": GPTConfig(n_layer=48, n_head=25, n_embd=1600),
}


def effective_xent_impl(cfg, multi_device: bool = False,
                        seq_sharded: bool = False,
                        tokens: Optional[int] = None) -> str:
    """The loss-head implementation a step with this config/mesh actually
    runs — ONE predicate, the one `GPT2Model.head` gates on (mirroring
    moe.effective_dispatch), so a measurement can never be labeled with a
    knob value that fell back.

    Returns "unfused" (materialized logits), "chunked" (XLA
    fused_linear_xent ladder), or "pallas" (ops/xent_pallas.py — only on
    a single-device TPU kernel target, and only when `tokens` (= B*T, if
    known) admits a viable VMEM token-block)."""
    if not getattr(cfg, "fused_xent", False) or seq_sharded:
        return "unfused"
    if getattr(cfg, "fused_xent_impl", "chunked") == "pallas":
        from ..ops.dispatch import kernel_target
        from ..ops.xent_pallas import viable_token_block
        if (kernel_target() == "tpu" and not multi_device
                and (tokens is None or viable_token_block(tokens))):
            return "pallas"
    return "chunked"


def _dropout(x, key, rate: float):
    """Inverted dropout: zero with prob `rate`, survivors scaled 1/(1-rate)
    so eval needs no rescaling.  `key` may be a raw (2,) uint32 key row
    (what a stacked `jax.random.split` yields per layer)."""
    keep = 1.0 - rate
    mask = jax.random.bernoulli(key, keep, x.shape)
    return jnp.where(mask, x / keep, 0.0).astype(x.dtype)


class GPT2Model:
    """Functional GPT-2: `init(key) -> params`, `apply(params, idx, targets)`.

    Replaces the reference's nn.Module (example/model.py:125-157).  There is
    no layer-swap wrapping step (reference zero/utils/wrapper.py:9-36):
    parallel modes change *shardings and the train step*, never the model
    code.
    """

    # apply() implements the GPipe pipeline path (pctx.pipe_parallel);
    # subclasses that override apply() without it must reset this flag
    pipeline_capable = True
    # apply() threads the scheduler seam (parallel/schedule.py sched=)
    # through the layer scan — the grad slot's bucketed release tap,
    # and the composed lowering drives this family's block_fn/embed/head
    # directly; subclasses that override apply() without the sched
    # branch must reset these (MoEGPT does — its scan carries the
    # aux-loss accumulator the scheduler's scan bodies do not thread)
    grad_bucket_capable = True
    # the gather slot (ZeRO-3 prefetched / hpZ weight-gather scan)
    gather_prefetch_capable = True
    # the probe slot (per-layer health: schedule.layer_health_tap rides
    # the stacked scan tree when a "health_probe" row is present)
    layer_health_capable = True
    # paged_prefill/paged_decode read and write the serving tier's paged
    # KV pool (serving/pool.py block tables); families whose decode step
    # cannot batch rows at different positions (MoE's capacity-routed
    # dispatch) must reset this — serving.ServingEngine refuses them
    paged_decode_capable = True

    def __init__(self, config: GPTConfig):
        self.config = config
        self._generate_cache = {}  # (shape, sampling) -> jitted decode

    # -- initialization ----------------------------------------------------

    def param_shapes(self) -> Dict[str, jax.ShapeDtypeStruct]:
        """Shape/dtype pytree without allocating — the TPU equivalent of the
        reference's meta-device init (reference zero1/train.py:25-27)."""
        return jax.eval_shape(lambda: self.init(jax.random.PRNGKey(0)))

    def init(self, key) -> Dict[str, jax.Array]:
        c = self.config
        d, l, v, t = c.n_embd, c.n_layer, c.vocab_size, c.block_size
        std = 0.02
        # GPT-2 init: N(0, 0.02), residual-projection std scaled by 1/sqrt(2L)
        pstd = std / math.sqrt(2 * l)
        keys = iter(jax.random.split(key, 16))

        def nrm(k, shape, s):
            return (jax.random.normal(k, shape, jnp.float32) * s).astype(
                c.param_dtype
            )

        def zeros(shape):
            return jnp.zeros(shape, c.param_dtype)

        params = {
            "wte": nrm(next(keys), (v, d), std),
            "wpe": nrm(next(keys), (t, d), std),
            "h.ln_1.w": jnp.ones((l, d), c.param_dtype),
            "h.ln_1.b": zeros((l, d)),
            "h.attn.qkv.w": nrm(next(keys), (l, d, 3 * d), std),
            "h.attn.qkv.b": zeros((l, 3 * d)),
            "h.attn.proj.w": nrm(next(keys), (l, d, d), pstd),
            "h.attn.proj.b": zeros((l, d)),
            "h.ln_2.w": jnp.ones((l, d), c.param_dtype),
            "h.ln_2.b": zeros((l, d)),
            "h.mlp.fc.w": nrm(next(keys), (l, d, 4 * d), std),
            "h.mlp.fc.b": zeros((l, 4 * d)),
            "h.mlp.proj.w": nrm(next(keys), (l, 4 * d, d), pstd),
            "h.mlp.proj.b": zeros((l, d)),
            "ln_f.w": jnp.ones((d,), c.param_dtype),
            "ln_f.b": zeros((d,)),
            # weight-untied lm_head, like the reference (model.py:136-138)
            "lm_head.w": nrm(next(keys), (d, v), std),
        }
        if not c.bias:
            # reference bias=False scope: projection linears only
            for name in ("h.attn.qkv.b", "h.attn.proj.b",
                         "h.mlp.fc.b", "h.mlp.proj.b"):
                del params[name]
        if c.tie_weights:
            del params["lm_head.w"]  # head projects through wte.T
        return params

    def tp_rules(self) -> Dict[str, int]:
        """Megatron-style tensor-parallel placement: {param name: dim index
        to shard over the "model" mesh axis}.  Column-parallel qkv/fc (output
        dim), row-parallel attn/mlp proj (input dim — GSPMD inserts the psum
        the row-parallel matmul needs), vocab-parallel lm_head.  Consumed by
        the engine when tensor_parallel > 1; absent entirely from the
        reference (SURVEY §2.20: no TP of any kind)."""
        return {
            "h.attn.qkv.w": 2,
            "h.attn.qkv.b": 1,
            "h.attn.proj.w": 1,
            "h.mlp.fc.w": 2,
            "h.mlp.fc.b": 1,
            "h.mlp.proj.w": 1,
            "lm_head.w": 1,
        }

    def num_params(self, params=None) -> int:
        shapes = params if params is not None else self.param_shapes()
        return sum(int(math.prod(x.shape)) for x in shapes.values())

    # -- forward -----------------------------------------------------------

    def _block(self, x, bp, pctx=None, return_kv=False):
        """One pre-LN transformer block. x: (B, T, D) in compute_dtype;
        bp: this block's params, already in compute_dtype (pre-cast once in
        `apply` — casting per-layer inside the scan re-reads the float32
        master params three times per step: fwd, remat re-fwd, bwd).
        return_kv additionally returns this layer's (k, v) head tensors —
        the KV-cache prefill hook (`_prefill`)."""
        c = self.config
        b, t, d = x.shape
        # dropout rides the stacked tree as a per-layer PRNG key; its
        # presence (static at trace time) is the train/eval switch
        dkey = bp.get("dropout_rng")
        # named scopes: what a device trace's operations are told apart
        # by (utils/profiling.TABLE); metadata only, the program is the same
        scope = jax.named_scope

        with scope("tds.block"):
            with scope("tds.ln"):
                h = layernorm(x, bp["ln_1.w"], bp["ln_1.b"])
            with scope("tds.attn.qkv"):
                qkv = linear(h, self._bw(bp, "attn.qkv.w", pctx),
                             bp.get("attn.qkv.b"))
                q, k, v = jnp.split(qkv, 3, axis=-1)

                def heads(z):  # (B, T, D) -> (B, H, T, Dh)
                    return z.reshape(
                        b, t, c.n_head, c.head_dim).swapaxes(1, 2)

                qh, kh, vh = heads(q), heads(k), heads(v)
            with scope("tds.attn.kernel"):
                y = sharded_attention(qh, kh, vh, c.attn_impl, pctx)
            with scope("tds.attn.proj"):
                y = y.swapaxes(1, 2).reshape(b, t, d)
                y = linear(y, self._bw(bp, "attn.proj.w", pctx),
                           bp.get("attn.proj.b"))
                if dkey is not None:
                    y = _dropout(y, jax.random.fold_in(dkey, 0), c.dropout)
                x = x + y

            with scope("tds.ln"):
                h = layernorm(x, bp["ln_2.w"], bp["ln_2.b"])
            with scope("tds.mlp"):
                h = linear(h, self._bw(bp, "mlp.fc.w", pctx),
                           bp.get("mlp.fc.b"))
                h = jax.nn.gelu(h, approximate=True)
                h = linear(h, self._bw(bp, "mlp.proj.w", pctx),
                           bp.get("mlp.proj.b"))
                if dkey is not None:
                    h = _dropout(h, jax.random.fold_in(dkey, 1), c.dropout)
                x = x + h
        return (x, (kh, vh)) if return_kv else x

    # -- KV-cache decode ---------------------------------------------------
    #
    # generate(use_cache=False) re-runs the FULL (B, block_size) forward per
    # sampled token: O(L * T^2) attention per token.  The cached path runs
    # the prompt once ("prefill", which also emits every layer's K/V head
    # tensors), then each new token is one (B, 1, D) pass attending to the
    # cache — O(L * T) per token, the standard inference structure.  The
    # reference never needed either: its model only trains (SURVEY §2.1).

    def _decode_attention(self, q, ck, cv, pos):
        """q: (B, Hq, 1, Dh); ck/cv: (B, Hkv, T, Dh) caches; pos: the
        query's position (cache filled through pos) — a scalar, or a (B,)
        vector when each row sits at its own position (the serving tier's
        paged decode batches requests of different lengths).  Full-length
        masked attention — slots past pos are padding, masked out.  GQA
        (Hq > Hkv) groups query heads per KV head instead of materializing
        a repeated cache.

        Decode is HBM-bandwidth bound, so the dots consume the cache in
        its RESTING dtype (config.cache_dtype, default compute_dtype)
        with f32 MXU accumulation — the previous `.astype(f32)` on ck/cv
        materialized two full f32 cache copies per token (~2x the cache
        bytes; round-5 decode pass).  Scores, mask and softmax stay f32."""
        b, hq, _, dh = q.shape
        hkv = ck.shape[1]
        scale = 1.0 / math.sqrt(dh)
        out_dtype = q.dtype  # restore the ACTIVATION dtype on return,
        q = q.astype(ck.dtype)  # not the resting cache dtype
        pos = jnp.asarray(pos)
        mask = jnp.arange(ck.shape[2]) <= (
            pos[:, None] if pos.ndim else pos
        )  # (B, T) per-row, or (T,) shared
        m4 = (mask[None, None, None] if mask.ndim == 1
              else mask[:, None, None, :])
        if hq != hkv:
            g = hq // hkv
            att = jnp.einsum(
                "bkgd,bktd->bkgt", q.reshape(b, hkv, g, dh), ck,
                preferred_element_type=jnp.float32) * scale
            att = jnp.where(m4, att, -jnp.inf)
            att = jax.nn.softmax(att, axis=-1)
            y = jnp.einsum("bkgt,bktd->bkgd", att.astype(cv.dtype), cv,
                           preferred_element_type=jnp.float32)
            y = y.reshape(b, hq, 1, dh)
        else:
            att = jnp.einsum("bhqd,bhtd->bhqt", q, ck,
                             preferred_element_type=jnp.float32) * scale
            att = jnp.where(m4, att, -jnp.inf)
            att = jax.nn.softmax(att, axis=-1)
            y = jnp.einsum("bhqt,bhtd->bhqd", att.astype(cv.dtype), cv,
                           preferred_element_type=jnp.float32)
        return y.astype(out_dtype)

    def _attn_decode(self, x, bp, ks, vs, l, pos):
        """Attention half of one decode step on the STACKED (L, B, Hkv,
        T, Dh) caches: write this position's K/V — a (1, B, Hkv, 1, Dh)
        sliver — in place at (l, pos), read layer l's panel, attend,
        residual-add.  x: (B, 1, D).  The caches ride the layer scan's
        CARRY (not xs/ys — see _decode_blocks), so the write aliases the
        buffer instead of restacking it."""
        c = self.config
        b = x.shape[0]
        h = layernorm(x, bp["ln_1.w"], bp["ln_1.b"])
        qkv = linear(h, self._bw(bp, "attn.qkv.w"), bp.get("attn.qkv.b"))
        q, k, v = jnp.split(qkv, 3, axis=-1)

        def heads1(z):
            return z.reshape(b, 1, c.n_head, c.head_dim).swapaxes(1, 2)

        ks = jax.lax.dynamic_update_slice(
            ks, heads1(k).astype(ks.dtype)[None], (l, 0, 0, pos, 0)
        )
        vs = jax.lax.dynamic_update_slice(
            vs, heads1(v).astype(vs.dtype)[None], (l, 0, 0, pos, 0)
        )
        ck = jax.lax.dynamic_index_in_dim(ks, l, 0, keepdims=False)
        cv = jax.lax.dynamic_index_in_dim(vs, l, 0, keepdims=False)
        y = self._decode_attention(heads1(q), ck, cv, pos)
        y = y.swapaxes(1, 2).reshape(b, 1, c.n_embd)
        y = linear(y, self._bw(bp, "attn.proj.w"), bp.get("attn.proj.b"))
        return x + y, ks, vs

    def _mlp_decode(self, x, bp):
        """MLP half of one decode step (norm + MLP + residual) — shared
        between the contiguous-cache and paged decode paths."""
        h = layernorm(x, bp["ln_2.w"], bp["ln_2.b"])
        h = linear(h, self._bw(bp, "mlp.fc.w"), bp.get("mlp.fc.b"))
        h = jax.nn.gelu(h, approximate=True)
        h = linear(h, self._bw(bp, "mlp.proj.w"), bp.get("mlp.proj.b"))
        return x + h

    def _block_decode(self, x, bp, ks, vs, l, pos):
        """One block, one token: cached attention + MLP."""
        x, ks, vs = self._attn_decode(x, bp, ks, vs, l, pos)
        return self._mlp_decode(x, bp), ks, vs

    def _prefill_body(self, x, bp):
        """Scan body for the prompt pass: (x, (k, v)).  Families whose
        _block returns extra values (MoE aux) override this to discard
        them."""
        return self._block(x, bp, None, return_kv=True)

    def _prefill(self, params, idx, cache_len, stacked=None):
        """Run the prompt, returning final-position logits (B, V) float32
        plus (L, B, Hkv, cache_len, Dh) K/V caches (prompt prefix filled,
        rest zeros).  The caches REST in resolved_cache_dtype(config) —
        compute_dtype unless the cache_dtype knob narrows it (decode is
        cache-bandwidth bound; `_decode_attention` consumes the resting
        dtype directly with f32 accumulation, so a narrower cache halves
        HBM traffic without touching activation dtypes)."""
        x = self.embed(params, idx)
        if stacked is None:
            stacked = self.stacked_compute_params(params)
        x, (ks, vs) = jax.lax.scan(self._prefill_body, x, stacked,
                                   unroll=self.config.scan_unroll)
        cdt = resolved_cache_dtype(self.config)
        ks, vs = ks.astype(cdt), vs.astype(cdt)
        pad = ((0, 0), (0, 0), (0, 0), (0, cache_len - idx.shape[1]), (0, 0))
        return self.head(params, x)[:, 0], jnp.pad(ks, pad), jnp.pad(vs, pad)

    def _decode_blocks(self, stacked, x, ks, vs, pos):
        """Layer loop for one decode token.  The caches ride the CARRY
        and each layer writes its (1, B, H, 1, Dh) sliver in place —
        the previous formulation passed them as scan xs/ys, which
        restacked (read + wrote) the ENTIRE (L, B, H, T, Dh) cache pair
        every token (~226 MB/token at the 124M decode bench shape, pure
        copy; round-5 decode pass)."""
        n_layer = jax.tree.leaves(stacked)[0].shape[0]

        def body(carry, l):
            x, ks, vs = carry
            bp = jax.tree.map(
                lambda s: jax.lax.dynamic_index_in_dim(
                    s, l, 0, keepdims=False), stacked)
            x, ks, vs = self._block_decode(x, bp, ks, vs, l, pos)
            return (x, ks, vs), None

        (x, ks, vs), _ = jax.lax.scan(
            body, (x, ks, vs), jnp.arange(n_layer),
            unroll=self.config.scan_unroll)
        return x, ks, vs

    @jax.named_scope("tds.embed")
    def _embed_decode(self, params, tok, pos):
        """One token per row -> (B, 1, D).  tok: (B,) ints; pos: scalar
        (every row at the same position — `generate`) or (B,) vector
        (each row at its own position — the serving tier's paged decode,
        where concurrent requests sit at different lengths)."""
        x = self.embed_tokens(params, tok[:, None])
        if jnp.ndim(pos) == 0:
            wp = jax.lax.dynamic_slice_in_dim(params["wpe"], pos, 1, 0)[None]
        else:
            wp = params["wpe"][pos][:, None]
        return x + wp.astype(x.dtype)

    @staticmethod
    def _sample(logit, key, temperature, top_k):
        """(B, V) float32 logits -> (B,) int32 next tokens — delegates to
        the ONE sampling core (models/sampling.py) shared with the
        serving tier, so a sampling change lands in every decode surface
        at once."""
        from .sampling import sample_logits
        return sample_logits(logit, key, temperature, top_k)

    def _generate_impl_cached(self, params, idx, key, *, t0, max_new_tokens,
                              temperature, top_k):
        total = t0 + max_new_tokens
        b = idx.shape[0]
        buf = jnp.zeros((b, total), jnp.int32)
        buf = jax.lax.dynamic_update_slice(buf, idx.astype(jnp.int32), (0, 0))
        if max_new_tokens == 0:
            return buf
        stacked = self.stacked_compute_params(params)
        logits, ks, vs = self._prefill(params, idx, total, stacked)

        def body(i, carry):
            buf, ks, vs, logits, key = carry
            key, sub = jax.random.split(key)
            nxt = self._sample(logits, sub, temperature, top_k)
            buf = jax.lax.dynamic_update_slice(buf, nxt[:, None], (0, i))
            x = self._embed_decode(params, nxt, i)
            x, ks, vs = self._decode_blocks(stacked, x, ks, vs, i)
            logits = self.head(params, x)[:, 0]
            return buf, ks, vs, logits, key

        # N-1 decode iterations; the final token needs only a sample, not
        # another L-layer pass whose logits nobody reads
        buf, ks, vs, logits, key = jax.lax.fori_loop(
            t0, total - 1, body, (buf, ks, vs, logits, key)
        )
        key, sub = jax.random.split(key)
        last = self._sample(logits, sub, temperature, top_k)
        return jax.lax.dynamic_update_slice(buf, last[:, None], (0, total - 1))

    # -- paged KV-cache decode (the serving tier) --------------------------
    #
    # Same math as the contiguous decode above, but the cache lives in a
    # SHARED preallocated block pool (serving/pool.py): each slot's K/V
    # panel is gathered through its block table instead of sliced from a
    # per-request max-length buffer, and every slot sits at its OWN
    # position (vector `pos`).  ONE layer loop serves every program that
    # reads the pool (`paged_verify`, below): it scores a span of tokens
    # per slot against the committed prefix plus the span itself and
    # never writes; the caller commits the span's K/V afterwards.

    def _paged_attention(self, q, view, l, page, span_kv):
        """ONE dispatch seam for attention over the pool, shared by the
        decode, spec-verify and suffix-prefill programs of every family:
        the Pallas fused gather+attention kernel when the gate says so
        (ops/paged_attn_pallas.use_paged_kernel — TPU targets, or forced
        via ServeConfig.paged_kernel), else the XLA reference
        (materialized `paged_panel` + `_span_attention`).  q
        (S, Hq, K1, Dh) span queries; span_kv = (sk, sv) the span's own
        K/V, each (S, KVH, K1, Dh): the pool gives the committed prefix,
        positions < page.pos."""
        from ..ops.dispatch import note_kernel
        from ..ops.paged_attn_pallas import paged_attention, use_paged_kernel
        # the pool's merged minor dimension shows neither size
        kvh = getattr(self.config, "kv_heads", self.config.n_head)
        if use_paged_kernel():
            note_kernel("paged_attention", "pallas:paged_attention")
            return paged_attention(q, view, page, l, span_kv, kv_heads=kvh)
        note_kernel("paged_attention", "xla:paged_panel")
        from ..serving.pool import paged_panel
        ck, cv = paged_panel(view, l, page, kvh, q.shape[-1],
                             self.config.compute_dtype)
        return self._span_attention(q, ck, cv, *span_kv, page.pos)

    def paged_decode(self, stacked, x, view, page):
        """One paged decode token per slot, x (S, 1, D): the verify pass
        below over a span of ONE — every layer reads the committed
        prefix through the block tables and the token's own K/V from
        the span — then ONE write of the token's K/V rows of all layers
        at (page.blk, page.off).  A write per layer would touch the
        pool L times a token; the token's rows of every layer lie side
        by side in the pool's minor dimension, so one scatter of whole
        rows places them."""
        from ..serving.pool import paged_append
        x, ks, vs = self.paged_verify(stacked, x, view, page)
        with jax.named_scope("tds.kv_write"):
            view = paged_append(view, ks[:, :, :, 0], vs[:, :, :, 0], page)
        return x, view

    # -- speculative verification (serving/spec.py) ------------------------
    #
    # One target pass scores a whole DRAFT SPAN per slot — the committed
    # head token plus up to K drafter proposals at positions
    # pos..pos+K — instead of one token per tick.  The span's K/V never
    # touch the pool here: the committed prefix is read through the
    # block tables (positions < pos), the span attends to itself through
    # a windowed causal mask, and serving/pool.paged_append_span commits
    # only the ACCEPTED prefix afterwards (rejected-draft K/V route to
    # scratch).  The attention math is `_decode_attention` extended to
    # K1 query positions; everything else reuses the paged machinery.

    @jax.named_scope("tds.embed")
    def _embed_decode_span(self, params, toks, positions):
        """(S, K1) tokens at (S, K1) absolute positions -> (S, K1, D)
        compute-dtype activations (the span analogue of
        `_embed_decode`'s vector-position path)."""
        x = self.embed_tokens(params, toks)
        wp = params["wpe"][positions]  # (S, K1, D), OOB rows clamped
        return x + wp.astype(x.dtype)

    def _span_attention(self, q, ck, cv, sk, sv, pos0):
        """Windowed-causal attention over committed cache + draft span.
        q: (S, Hq, K1, Dh) span queries; ck/cv: (S, KVH, T, Dh) pool
        panels holding the COMMITTED prefix (positions < pos0 valid);
        sk/sv: (S, KVH, K1, Dh) the span's own K/V (offset j at absolute
        position pos0+j).  Query j sees pool positions < pos0[s] plus
        span offsets <= j — exactly the causal mask of positions
        <= pos0+j, split across the two sources.  GQA groups query heads
        per KV head like `_decode_attention`; scores/softmax in f32."""
        s, hq, k1, dh = q.shape
        hkv = ck.shape[1]
        t = ck.shape[2]
        scale = 1.0 / math.sqrt(dh)
        out_dtype = q.dtype
        q = q.astype(ck.dtype)
        kf = jnp.concatenate([ck, sk.astype(ck.dtype)], axis=2)
        vf = jnp.concatenate([cv, sv.astype(cv.dtype)], axis=2)
        pool_mask = jnp.broadcast_to(
            (jnp.arange(t)[None, None, :] < pos0[:, None, None])[:, None],
            (s, 1, k1, t),
        )
        span_mask = jnp.broadcast_to(
            jnp.tril(jnp.ones((k1, k1), bool))[None, None], (s, 1, k1, k1)
        )
        mask = jnp.concatenate([pool_mask, span_mask], axis=-1)
        if hq != hkv:
            g = hq // hkv
            att = jnp.einsum(
                "skgqd,sktd->skgqt", q.reshape(s, hkv, g, k1, dh), kf,
                preferred_element_type=jnp.float32) * scale
            att = jnp.where(mask[:, :, None], att, -jnp.inf)
            att = jax.nn.softmax(att, axis=-1)
            y = jnp.einsum("skgqt,sktd->skgqd", att.astype(vf.dtype), vf,
                           preferred_element_type=jnp.float32)
            y = y.reshape(s, hq, k1, dh)
        else:
            att = jnp.einsum("shqd,shtd->shqt", q, kf,
                             preferred_element_type=jnp.float32) * scale
            att = jnp.where(mask, att, -jnp.inf)
            att = jax.nn.softmax(att, axis=-1)
            y = jnp.einsum("shqt,shtd->shqd", att.astype(vf.dtype), vf,
                           preferred_element_type=jnp.float32)
        return y.astype(out_dtype)

    def _paged_verify_attn(self, x, bp, view, l, page):
        """Attention half of one verify step: x (S, K1, D); the pool
        view is READ-ONLY (committed panel via the block tables) — the
        span's K/V return as this layer's scan ys for the post-
        acceptance commit."""
        c = self.config
        s, k1, _ = x.shape
        scope = jax.named_scope
        with scope("tds.ln"):
            h = layernorm(x, bp["ln_1.w"], bp["ln_1.b"])
        with scope("tds.attn.qkv"):
            qkv = linear(h, self._bw(bp, "attn.qkv.w"),
                         bp.get("attn.qkv.b"))
            q, k, v = jnp.split(qkv, 3, axis=-1)

            def heads(z):
                return z.reshape(s, k1, c.n_head, c.head_dim).swapaxes(1, 2)

            qh, kh, vh = heads(q), heads(k), heads(v)
        with scope("tds.attn.kernel"):
            y = self._paged_attention(qh, view, l, page, span_kv=(kh, vh))
        with scope("tds.attn.proj"):
            y = y.swapaxes(1, 2).reshape(s, k1, c.n_embd)
            y = linear(y, self._bw(bp, "attn.proj.w"),
                       bp.get("attn.proj.b"))
            return x + y, (kh, vh)

    def _paged_verify_block(self, x, bp, view, l, page):
        with jax.named_scope("tds.block"):
            x, kv = self._paged_verify_attn(x, bp, view, l, page)
            with jax.named_scope("tds.mlp"):
                return self._mlp_decode(x, bp), kv

    def paged_verify(self, stacked, x, view, page):
        """Layer loop over a span per slot, x (S, K1, D) span
        activations: the plain decode token (K1 = 1, `paged_decode`), a
        speculative verify, a shared-prefix suffix prefill.  The view is
        never written (it rides the closure, not the carry); each
        layer's span K/V stack as scan ys — (L, S, KVH, K1, Dh) per side
        — for `paged_append` / `paged_append_span` to commit."""
        n_layer = jax.tree.leaves(stacked)[0].shape[0]

        def body(x, l):
            bp = jax.tree.map(
                lambda t: jax.lax.dynamic_index_in_dim(
                    t, l, 0, keepdims=False), stacked)
            x, kv = self._paged_verify_block(x, bp, view, l, page)
            return x, kv

        with jax.named_scope("tds.blocks"):
            x, (sks, svs) = jax.lax.scan(
                body, x, jnp.arange(n_layer),
                unroll=self.config.scan_unroll)
        return x, sks, svs

    @jax.named_scope("tds.head")
    def head_span(self, params, x):
        """Final norm + lm_head at EVERY position of x (S, K1, D) ->
        (S, K1, V) f32 — the verify step needs the target distribution
        at all K1 span positions, not just the last (the `position`
        slice `head` takes on the single-token path)."""
        x = self.final_norm(params, x)
        return linear(x, self._lm_head_w(params), None).astype(jnp.float32)

    def paged_prefill(self, params, idx, last_pos, block_ids, view,
                      block_tokens: int, stacked=None):
        """Prompt pass for ONE request into the paged pool: idx (1, P)
        bucket-padded prompt, last_pos (traced) the true last prompt
        position, block_ids (P/block_tokens,) the physical blocks this
        request owns (padding-bucket tail entries point at the scratch
        block).  Returns (last-position logits (1, V) f32, view with the
        prompt's K/V scattered).  Reuses the training forward via the
        `return_kv` prefill hook, so family overrides (Llama RoPE/GQA)
        inherit it.  Pass the precomputed `stacked` compute-dtype tree
        when params are frozen (the serving engine does) — recomputing
        it per admission re-reads the full master param tree every
        prefill."""
        x = self.embed(params, idx)
        if stacked is None:
            stacked = self.stacked_compute_params(params)
        with jax.named_scope("tds.blocks"):
            x, (ks, vs) = jax.lax.scan(self._prefill_body, x, stacked,
                                       unroll=self.config.scan_unroll)
        from ..serving.pool import paged_scatter
        with jax.named_scope("tds.kv_write"):
            view = paged_scatter(view, ks, vs, block_ids, block_tokens)
        return self.head(params, x, position=last_pos)[:, 0], view

    def embed_tokens(self, params, idx):
        """wte gather (+ optional row-norm cap) -> (B, T, D) compute dtype.
        Shared across families; raises on over-length sequences."""
        c = self.config
        t = idx.shape[1]
        if t > c.block_size:
            raise ValueError(
                f"sequence length {t} > block_size {c.block_size}"
            )  # reference asserts the same (model.py:142)
        tok = embedding(idx, params["wte"])
        if c.wte_max_norm is not None:
            # cap the GATHERED rows, not the whole (vocab, d) table — same
            # values (renorm is row-wise), but O(B*T*d) instead of
            # O(vocab*d) per forward (and per remat re-forward)
            from ..ops.embedding import renorm_weight
            tok = renorm_weight(tok, c.wte_max_norm)
        return tok.astype(c.compute_dtype)

    @staticmethod
    def _constrain_activations(x, pctx):
        if pctx is not None and pctx.is_multi_device:
            from jax.sharding import NamedSharding, PartitionSpec as P
            x = jax.lax.with_sharding_constraint(
                x, NamedSharding(
                    pctx.mesh, P(pctx.data_axis, pctx.seq_axis, None)
                ),
            )
        return x

    @jax.named_scope("tds.embed")
    def embed(self, params, idx, pctx=None):
        """Token + position embedding -> (B, T, D) in compute dtype."""
        t = idx.shape[1]
        tok = self.embed_tokens(params, idx)
        pos = params["wpe"][:t].astype(tok.dtype)
        return self._constrain_activations(tok + pos[None], pctx)

    def _quant_eligible(self, name: str, v) -> bool:
        """Which stacked leaves the fp8 gather applies to: the block matmul
        weights (ndim >= 3 rules out layernorm w/b and all biases)."""
        return (self.config.gather_quant == "fp8"
                and name.endswith(".w") and v.ndim >= 3)

    @jax.named_scope("tds.cast")
    def stacked_compute_params(self, params):
        """The per-block scan xs: "h.*" tensors cast to compute dtype ONCE
        per step — per-layer casts inside the scan would re-read the float32
        masters three times per step (fwd, remat re-fwd, bwd).  Under ZeRO-3
        this also halves the bytes each per-layer all-gather moves.

        With config.gather_quant="fp8", eligible weights become
        float8_e4m3 + a per-output-channel f32 scale (key + "#scale") —
        consumed through `_bw`, which dequantizes after the gather.  The
        scale is STOP-GRADIENTED (straight-through estimator): the exact
        vjp of the absmax/quotient round trip is quantization-sawtooth
        noise, and carrying it cost ~4.6 MB/step of scale-cotangent
        all-reduce on the TPU-partitioned HLO (round-5 measurement,
        PROFILE.md finding 5) — with STE the weight cotangent passes
        straight through the dequant multiply and the scale moves no
        backward bytes."""
        cd = self.config.compute_dtype
        out = {}
        for k, v in params.items():
            if not k.startswith("h."):
                continue
            name = k[len("h."):]
            if self._quant_eligible(name, v):
                # per-(layer, out-channel) absmax scale; e4m3 max = 448
                s = jnp.max(
                    jnp.abs(v.astype(jnp.float32)),
                    axis=tuple(range(1, v.ndim - 1)), keepdims=True,
                ) / 448.0 + 1e-12
                s = jax.lax.stop_gradient(s)
                out[name] = (v / s).astype(jnp.float8_e4m3fn)
                out[name + "#scale"] = s.astype(jnp.float32)
            else:
                out[name] = v.astype(cd)
        return out

    def _bw(self, bp, name: str, pctx=None):
        """Block weight from the stacked tree, dequantized when the fp8
        gather stacked it as (e4m3, scale).

        The sharding constraint pins the PRE-dequant f8 tensor to its
        gathered layout (tp/ep placements, ZeRO data axis replicated) so
        GSPMD's per-layer all-gather moves f8 bytes; without it the
        partitioner computes the dequant multiply shard-side and gathers
        full precision (observed in the compiled HLO).  Skipped inside the
        pipeline's manual region, where constraints cannot name manual
        axes."""
        w = bp[name]
        s = bp.get(name + "#scale")
        if s is None:
            return w
        if (pctx is not None and pctx.is_multi_device
                and not pctx.pipe_parallel
                and pctx.stacked_specs is not None
                and name in pctx.stacked_specs):
            from jax.sharding import NamedSharding
            w = jax.lax.with_sharding_constraint(
                w, NamedSharding(pctx.mesh, pctx.stacked_specs[name])
            )
        cd = self.config.compute_dtype
        return w.astype(cd) * s.astype(cd)

    def remat_policy(self):
        return {
            "nothing": jax.checkpoint_policies.nothing_saveable,
            "dots": jax.checkpoint_policies.dots_saveable,
            "dots_no_batch":
                jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
            "all": jax.checkpoint_policies.everything_saveable,
        }[self.config.remat_policy]

    def _dropout_setup(self, stacked, x, rng):
        """Embedding dropout on `x` + one PRNG key per layer into the
        stacked scan tree (consumed by `_block` via bp["dropout_rng"]).
        No-op (train==eval) when rng is None or config.dropout == 0.
        Shared by every model family's apply()."""
        c = self.config
        if rng is None or not c.dropout:
            return stacked, x
        keys = jax.random.split(rng, c.n_layer + 1)
        x = _dropout(x, keys[0], c.dropout)
        return dict(stacked, dropout_rng=keys[1:]), x

    def block_fn(self, pctx=None):
        """(x, block_params) -> x, with the configured remat policy applied.
        A "health_probe" row in bp (engine telemetry layers mode) taps the
        block output through the per-layer health probe — here rather than
        in _block so LlamaModel's _block override inherits it."""
        def block(x, bp):
            y = self._block(x, bp, pctx)
            if "health_probe" in bp:
                from ..parallel.schedule import layer_health_tap
                y = layer_health_tap(y, bp["health_probe"])
            return y

        if self.config.remat:
            block = jax.checkpoint(block, policy=self.remat_policy())
        return block

    def final_norm(self, params, x):
        """Pre-head normalization — the one hook model families override
        (LlamaModel swaps in rmsnorm); the head/loss policy below stays in
        exactly one place."""
        cd = self.config.compute_dtype
        return layernorm(
            x, params["ln_f.w"].astype(cd), params["ln_f.b"].astype(cd)
        )

    def _lm_head_w(self, params):
        """(d, vocab) projection weight — wte.T when tied (the transpose
        folds into the matmul's dimension numbers, no copy)."""
        c = self.config
        w = params["wte"].T if c.tie_weights else params["lm_head.w"]
        return w.astype(c.compute_dtype)

    @jax.named_scope("tds.head")
    def head(self, params, x, targets: Optional[jax.Array] = None,
             pctx=None, position=None):
        """Final norm + lm_head (+ loss when targets given)."""
        c = self.config
        x = self.final_norm(params, x)
        w = self._lm_head_w(params)

        if targets is not None:
            # ONE shared predicate (effective_xent_impl) decides the head
            # implementation: this gate, and whoever labels a run with it
            impl = effective_xent_impl(
                c,
                multi_device=pctx is not None and pctx.is_multi_device,
                seq_sharded=pctx is not None and pctx.seq_parallel,
                tokens=x.shape[0] * x.shape[1],
            )
            from ..ops.dispatch import note_kernel
            note_kernel("loss_head",
                        "pallas:fused_xent" if impl == "pallas"
                        else f"xla:{impl}")
            if impl == "pallas":
                # single-device only for now: the custom call would
                # force GSPMD to gather the vocab-sharded w under tp
                from ..ops.xent_pallas import pallas_fused_xent
                return pallas_fused_xent(x, w, targets)
            if impl == "chunked":
                from ..ops.softmax_xent import fused_linear_xent
                return fused_linear_xent(x, w, targets)
            logits = linear(x, w, None)
            return softmax_cross_entropy(logits, targets)
        # inference path: one position only (cheap lm_head) — `position`
        # (static or traced int) selects it, default the last
        if position is None:
            x = x[:, -1:]
        else:
            x = jax.lax.dynamic_slice_in_dim(x, position, 1, axis=1)
        logits = linear(x, w, None)
        return logits.astype(jnp.float32)

    def apply(self, params, idx, targets: Optional[jax.Array] = None,
              pctx=None, position=None, rng=None, sched=None):
        """Forward pass.  Returns mean loss if targets given, else logits —
        same contract as reference GPT2Model.forward (model.py:139-157).

        `pctx` (ParallelContext) makes the forward mesh-aware: activations
        shard (batch over "data", tokens over "seq" when sequence-parallel)
        and attention dispatches to the sharded kernels.

        `rng` (train-time only) enables dropout when config.dropout > 0:
        one key per layer rides the stacked scan tree, so the same masks
        are recomputed bit-exactly by the remat backward.

        `sched` is THE scheduler seam (parallel/schedule.py): an executor
        with `.scan(block, stacked, x, unroll=)` that replaces the plain
        layer scan — the probe row rider (ProbeScan), the bucketed
        grad-release tap (GradBucketTap), or the prefetched weight-gather
        scan (GatherPrefetchScan).  The engine builds it from the
        validated slot Schedule; None (default) keeps the exact
        single-scan program.  (The composed multi-slot lowering drives
        its own scan via schedule.composed_step and never passes
        sched= here.)"""
        x = self.embed(params, idx, pctx)
        stacked = self.stacked_compute_params(params)
        stacked, x = self._dropout_setup(stacked, x, rng)
        block = self.block_fn(pctx)

        if sched is not None:
            if pctx is not None and pctx.pipe_parallel:
                raise ValueError(
                    "sched= (the in-scan collective scheduler) does not "
                    "compose with the pipeline forward"
                )
            with jax.named_scope("tds.blocks"):
                x = sched.scan(block, stacked, x,
                               unroll=self.config.scan_unroll)
            return self.head(params, x, targets, pctx, position)

        if pctx is not None and pctx.pipe_parallel:
            # GPipe-style SPMD pipeline over the "pipe" axis: each stage owns
            # n_layer/S stacked layers, microbatches hop stage->stage via
            # ppermute (parallel/pipeline.py; absent from the reference).
            from ..parallel.pipeline import spmd_pipeline
            with jax.named_scope("tds.blocks"):
                x = spmd_pipeline(
                    block, stacked, x,
                    mesh=pctx.mesh, pipe_axis=pctx.pipe_axis,
                    data_axis=pctx.data_axis,
                    microbatches=pctx.pipe_microbatches or None,
                    seq_axis=pctx.seq_axis,
                )
        else:
            def scan_body(x, bp):
                return block(x, bp), None

            # tds.blocks: the layer loop with its own work (slicing the
            # stacked weights, stacking their gradients) around tds.block
            with jax.named_scope("tds.blocks"):
                x, _ = jax.lax.scan(scan_body, x, stacked,
                                    unroll=self.config.scan_unroll)
        return self.head(params, x, targets, pctx, position)

    def __call__(self, params, idx, targets=None, pctx=None, rng=None):
        return self.apply(params, idx, targets, pctx, rng=rng)

    # 1F1B needs the loss INSIDE the pipeline (per-microbatch head at the
    # last stage), so it cannot ride `apply` + autodiff like GPipe does;
    # engines with pipeline_schedule="1f1b" call this instead.
    supports_1f1b = True

    def _pipeline_1f1b_block(self, pctx):
        """(block_fn, aux_weight, with_aux) for the 1F1B schedule — the
        hook MoEGPT overrides to thread its load-balance aux loss."""
        return self.block_fn(pctx), 0.0, False

    def head_param_names(self):
        """Params the head (final norm + lm_head) differentiates — the
        1F1B pipeline accumulates their grads at the last stage."""
        c = self.config
        # filtered against the actual param dict at use (llama has no ln_f.b)
        return ["ln_f.w", "ln_f.b",
                "wte" if c.tie_weights else "lm_head.w"]

    def loss_and_grad_1f1b(self, params, idx, targets, pctx,
                           loss_seed=1.0, rng=None):
        """(scaled loss, grads) via the 1F1B pipeline schedule
        (parallel/pipeline.py::spmd_pipeline_1f1b) — same contract as
        `jax.value_and_grad(lambda p: loss_seed * apply(p, ...))(params)`
        but with in-flight activations bounded at O(stages) instead of
        O(microbatches).  The pipeline hands back cotangents at its three
        seams (stacked block params, head params, embedded activations);
        explicit vjps push them to the master params and the pieces sum.

        `rng` enables dropout: per-layer keys ride the pipeline outside
        the differentiated args, folded per microbatch (independent masks
        per microbatch, bit-exact backward recompute); the embedding
        dropout joins the embed vjp here."""
        # gather_quant="fp8" composes: the f8 stacked leaves' cotangents
        # accumulate in f32 across ticks and cast to e4m3 once at the
        # pipeline boundary — the same one-crossing precision profile as
        # the autodiff (GPipe/plain) fp8 path, loss-curve validated there
        if pctx is None or pctx.pipe_axis is None:
            raise ValueError("loss_and_grad_1f1b needs a pipeline pctx")
        from ..parallel.pipeline import spmd_pipeline_1f1b

        block, aux_w, with_aux = self._pipeline_1f1b_block(pctx)
        drop_keys = None
        c = self.config
        if rng is not None and c.dropout:
            keys = jax.random.split(rng, c.n_layer + 1)
            drop_keys = keys[1:]

            def embed_fn(p):
                return _dropout(self.embed(p, idx, pctx), keys[0],
                                c.dropout)
        else:
            def embed_fn(p):
                return self.embed(p, idx, pctx)
        x, embed_vjp = jax.vjp(embed_fn, params)
        stacked, stacked_vjp = jax.vjp(self.stacked_compute_params, params)
        head_names = [n for n in self.head_param_names() if n in params]
        head_params = {n: params[n] for n in head_names}

        def head_fn(hp, y, tg):
            # one-hot CE, not the gather/fused paths: this head runs inside
            # the pipeline's partial-manual region where the take_along_axis
            # gather on (possibly vocab-sharded) logits CHECK-crashes the
            # SPMD partitioner (ops/softmax_xent.py::softmax_cross_entropy_
            # onehot); per-microbatch logits keep the memory bounded anyway
            from ..ops.softmax_xent import softmax_cross_entropy_onehot
            from ..ops.linear import linear
            h = self.final_norm(hp, y)
            return softmax_cross_entropy_onehot(
                linear(h, self._lm_head_w(hp), None), tg
            )

        loss, dstacked, dhead, dx = spmd_pipeline_1f1b(
            block, head_fn, stacked, head_params,
            x, targets,
            mesh=pctx.mesh,
            pipe_axis=pctx.pipe_axis or "pipe",
            data_axis=pctx.data_axis,
            microbatches=pctx.pipe_microbatches or None,
            loss_seed=loss_seed,
            with_aux=with_aux, aux_weight=aux_w,
            rng_stacked=drop_keys,
            seq_axis=pctx.seq_axis,
        )
        g_embed = embed_vjp(dx.astype(x.dtype))[0]
        g_stack = stacked_vjp(dstacked)[0]
        grads = jax.tree.map(
            lambda a, b: a.astype(jnp.float32) + b.astype(jnp.float32),
            g_embed, g_stack,
        )
        for n, g in dhead.items():
            grads[n] = grads[n] + g.astype(jnp.float32)
        grads = jax.tree.map(lambda g, p: g.astype(p.dtype), grads, params)
        return loss, grads

    # Table-driven schedules (interleaved virtual stages / zero-bubble
    # B/W split) reuse the 1F1B seams but need an aux-free block: MoEGPT
    # opts out (its load-balance aux would need to ride every F *and* be
    # replayed in W's re-linearization).
    supports_pipe_table = True

    def loss_and_grad_pipe(self, params, idx, targets, pctx, program,
                           loss_seed=1.0, rng=None):
        """(scaled loss, grads) via a static pipeline tick table
        (parallel/pipeline.py::spmd_pipeline_table) — interleaved and
        zero-bubble schedules.  Same contract and seam composition as
        `loss_and_grad_1f1b`: the pipeline hands back cotangents at the
        stacked/head/embed seams and explicit vjps push them to the
        master params."""
        if pctx is None or pctx.pipe_axis is None:
            raise ValueError("loss_and_grad_pipe needs a pipeline pctx")
        from ..parallel.pipeline import spmd_pipeline_table

        block, aux_w, with_aux = self._pipeline_1f1b_block(pctx)
        if with_aux or aux_w:
            raise ValueError("table schedules do not thread aux losses; "
                             "use pipeline_schedule='1f1b'")
        drop_keys = None
        c = self.config
        if rng is not None and c.dropout:
            keys = jax.random.split(rng, c.n_layer + 1)
            drop_keys = keys[1:]

            def embed_fn(p):
                return _dropout(self.embed(p, idx, pctx), keys[0],
                                c.dropout)
        else:
            def embed_fn(p):
                return self.embed(p, idx, pctx)
        x, embed_vjp = jax.vjp(embed_fn, params)
        stacked, stacked_vjp = jax.vjp(self.stacked_compute_params, params)
        head_names = [n for n in self.head_param_names() if n in params]
        head_params = {n: params[n] for n in head_names}

        def head_fn(hp, y, tg):
            # one-hot CE for the same partial-manual reason as 1F1B
            from ..ops.softmax_xent import softmax_cross_entropy_onehot
            from ..ops.linear import linear
            h = self.final_norm(hp, y)
            return softmax_cross_entropy_onehot(
                linear(h, self._lm_head_w(hp), None), tg
            )

        loss, dstacked, dhead, dx = spmd_pipeline_table(
            block, head_fn, stacked, head_params,
            x, targets,
            mesh=pctx.mesh,
            program=program,
            pipe_axis=pctx.pipe_axis or "pipe",
            data_axis=pctx.data_axis,
            loss_seed=loss_seed,
            rng_stacked=drop_keys,
        )
        g_embed = embed_vjp(dx.astype(x.dtype))[0]
        g_stack = stacked_vjp(dstacked)[0]
        grads = jax.tree.map(
            lambda a, b: a.astype(jnp.float32) + b.astype(jnp.float32),
            g_embed, g_stack,
        )
        for n, g in dhead.items():
            grads[n] = grads[n] + g.astype(jnp.float32)
        grads = jax.tree.map(lambda g, p: g.astype(p.dtype), grads, params)
        return loss, grads

    def generate(self, params, idx, max_new_tokens: int, *,
                 temperature: float = 1.0, top_k: Optional[int] = None,
                 key=None, use_cache: bool = True):
        """Autoregressive sampling: (B, T0) prompt -> (B, T0+max_new_tokens).

        The reference has no sampling loop (its model only trains); this is
        the capability users expect from a GPT training framework.  TPU-first
        shape discipline: the token buffer is a FIXED-shape array updated in
        place and the decode loop is a `lax.fori_loop` inside one cached jit
        (keyed on shapes + sampling settings, so repeat calls don't
        retrace).  use_cache=True (default) decodes with a per-layer KV
        cache: prompt prefill + one (B, 1, D) pass per token, O(L*T) not
        O(L*T^2) — greedy outputs are bit-checked equal to the uncached
        full-forward path (tests/test_model.py; for MoE the equality holds
        whenever expert capacity overflows in neither path — the
        full-sequence path's static capacity can drop tokens the drop-free
        decode keeps, models/moe.py).  temperature=0 gives
        greedy decoding and needs no key; stochastic sampling requires an
        explicit PRNG key (no silent fixed seed).
        """
        c = self.config
        b, t0 = idx.shape
        if t0 + max_new_tokens > c.block_size:
            raise ValueError(
                f"prompt {t0} + new {max_new_tokens} tokens > "
                f"block_size {c.block_size}"
            )
        if key is None:
            if temperature != 0.0:
                raise ValueError(
                    "stochastic sampling (temperature != 0) requires an "
                    "explicit PRNG key; pass key=jax.random.PRNGKey(...) "
                    "or use temperature=0.0 for greedy decoding"
                )
            key = jax.random.PRNGKey(0)  # unused by the greedy path

        cache_key = (b, t0, max_new_tokens, temperature, top_k, use_cache)
        fn = self._generate_cache.get(cache_key)
        if fn is None:
            # bounded LRU: each entry pins a jitted executable on the model
            # instance; unbounded growth across distinct shape/sampling
            # combinations would leak compiled programs (ADVICE r1)
            if len(self._generate_cache) >= 32:
                self._generate_cache.pop(next(iter(self._generate_cache)))
            impl = (self._generate_impl_cached if use_cache
                    else self._generate_impl)
            fn = jax.jit(
                partial(
                    impl, t0=t0,
                    max_new_tokens=max_new_tokens,
                    temperature=temperature, top_k=top_k,
                )
            )
            self._generate_cache[cache_key] = fn
        else:
            self._generate_cache[cache_key] = self._generate_cache.pop(
                cache_key
            )  # mark most-recently-used
        return fn(params, idx, key)

    def _generate_impl(self, params, idx, key, *, t0, max_new_tokens,
                       temperature, top_k):
        c = self.config
        b = idx.shape[0]
        buf = jnp.zeros((b, c.block_size), jnp.int32)
        buf = jax.lax.dynamic_update_slice(buf, idx.astype(jnp.int32), (0, 0))

        def body(i, carry):
            buf, key = carry
            logit = self.apply(params, buf, position=i - 1)[:, 0]  # (B, V)
            key, sub = jax.random.split(key)
            nxt = self._sample(logit, sub, temperature, top_k)
            buf = jax.lax.dynamic_update_slice(buf, nxt[:, None], (0, i))
            return buf, key

        buf, _ = jax.lax.fori_loop(t0, t0 + max_new_tokens, body, (buf, key))
        return buf[:, : t0 + max_new_tokens]

    # -- what a family may state differently about the paged pool ----------
    # (kept last: the Mosaic kernels' cache keys hold their callers' line
    # numbers, so a method put above them would re-key every program)

    def paged_layout(self, max_seq: int, block_tokens: int):
        """What a slot holds in the paged pool and how it fills its
        block-table row (serving/pool.DenseLayout states what the
        engine asks of a layout): K and V of the whole context here,
        one table of ceil(max_seq / block_tokens) entries;
        `models/evabyte.EvaLayout` for a family that keeps a window and
        chunk summaries."""
        from ..serving.pool import BlockKind, DenseLayout
        c = self.config
        width = -(-max_seq // block_tokens)
        kind = BlockKind(c.n_layer, getattr(c, "kv_heads", c.n_head),
                         c.head_dim, c.head_dim, width)
        return DenseLayout(width, block_tokens, (kind,))

    def paged_page_ref(self, tables, pos, block_tokens: int):
        """The decode step's write coordinates (serving/pool.page_ref:
        position p lands in table entry p // block_tokens)."""
        from ..serving.pool import page_ref
        return page_ref(tables, pos, block_tokens)

    def sampling_logits(self, logits):
        """The columns of the head's output that score the next token:
        all of them here."""
        return logits
