# Copyright 2026 tiny-deepspeed-tpu authors
# SPDX-License-Identifier: Apache-2.0

"""Llama-family model: RMSNorm + RoPE + SwiGLU + grouped-query attention.

No reference counterpart (the reference's only model is the nanoGPT-style
GPT-2, reference example/model.py) — this is the second model family proving
the framework generalizes: it reuses the op layer (ops/linear, ops/rmsnorm,
ops/attention), the stacked-block `lax.scan`, every ZeRO stage, tensor/
sequence/pipeline parallelism, checkpointing, and `generate()` without any
engine changes.

TPU-first notes:
  * RoPE is computed in float32 and applied to q/k only; positions are
    GLOBAL indices — under seq x pipe (both axes manual in the pipeline
    region) the local shard offsets by axis_index(seq) * T_local.
  * GQA: n_kv_head <= n_head; K/V enter attention at kv_heads — the FA2
    kernel consumes them grouped (ops/flash_fa2.py indexes kv panels by
    query_head // group), so K/V HBM traffic stays at kv_heads; non-flash
    paths expand in ops/attention.py (free under GSPMD head sharding).
  * SwiGLU hidden defaults to the Llama convention round(8/3 * d) padded up
    to a multiple of 128 so the MXU tiles cleanly.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from ..ops import linear
from ..ops.rmsnorm import rmsnorm
from ..ops.attention import sharded_attention
from .gpt2 import GPTConfig, GPT2Model, _dropout


def _round_up(x: int, mult: int) -> int:
    return ((x + mult - 1) // mult) * mult


@dataclasses.dataclass(frozen=True)
class LlamaConfig(GPTConfig):
    """GPTConfig fields reused (block_size, vocab_size, n_layer, n_head,
    n_embd, attn_impl, dtypes, remat, fused_xent, dropout) + Llama knobs.

    The inherited `bias` field is IGNORED: the Llama architecture is
    bias-free by definition (every projection below passes bias=None).
    `dropout` works exactly as in GPT2Model (post-attention + post-MLP
    residual dropout + embedding dropout, keyed per step by the engine)."""

    n_kv_head: Optional[int] = None     # None -> n_head (MHA)
    rope_theta: float = 10000.0
    ffn_hidden: Optional[int] = None    # None -> round_up(8/3 * d, 128)

    @property
    def kv_heads(self) -> int:
        return self.n_kv_head or self.n_head

    @property
    def ffn(self) -> int:
        return self.ffn_hidden or _round_up(int(8 * self.n_embd / 3), 128)


LLAMA_PRESETS: Dict[str, LlamaConfig] = {
    "llama-tiny": LlamaConfig(block_size=256, vocab_size=512, n_layer=2,
                              n_head=4, n_kv_head=2, n_embd=64,
                              compute_dtype=jnp.float32),
    "llama-160m": LlamaConfig(block_size=1024, vocab_size=50304, n_layer=12,
                              n_head=12, n_kv_head=4, n_embd=768),
    "llama-1b": LlamaConfig(block_size=2048, vocab_size=50304, n_layer=22,
                            n_head=32, n_kv_head=8, n_embd=2048),
}


def rope(x, positions, theta: float):
    """Rotary position embedding on (B, H, T, Dh); positions (T,) ints."""
    dh = x.shape[-1]
    half = dh // 2
    freqs = theta ** (
        -jnp.arange(0, half, dtype=jnp.float32) / half
    )  # (half,)
    ang = positions.astype(jnp.float32)[:, None] * freqs[None, :]  # (T, half)
    cos = jnp.cos(ang)[None, None]
    sin = jnp.sin(ang)[None, None]
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:].astype(jnp.float32)
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    )
    return out.astype(x.dtype)


def rope_at(x, positions, theta: float):
    """RoPE for a one-token-per-row batch: x (B, H, 1, Dh), positions (B,)
    — each row rotated at its OWN position (the serving tier's paged
    decode, where concurrent requests sit at different lengths).  The
    one-position special case of `rope_span` — delegating keeps the
    rotation math in a single body, so a row at position p gets
    bit-identical treatment on every path."""
    return rope_span(x, positions[:, None], theta)


def rope_span(x, positions, theta: float):
    """RoPE for a draft-span batch: x (S, H, K1, Dh), positions (S, K1)
    — row s's span position j rotated at positions[s, j] (the serving
    tier's speculative verify, where each slot's span starts at its own
    committed head).  Same elementwise math as `rope`/`rope_at`, so a
    token at absolute position p gets bit-identical treatment on every
    path."""
    dh = x.shape[-1]
    half = dh // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None] * freqs  # (S, K1, half)
    cos = jnp.cos(ang)[:, None]  # (S, 1, K1, half)
    sin = jnp.sin(ang)[:, None]
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:].astype(jnp.float32)
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    )
    return out.astype(x.dtype)


class LlamaModel(GPT2Model):
    """Same functional contract as GPT2Model: init / apply / generate."""

    pipeline_capable = True
    # inherits apply() and with it the bucketed grad-release tap AND the
    # ZeRO-3 prefetched weight-gather scan — restated so a future apply()
    # override can't silently claim capabilities it dropped
    grad_bucket_capable = True
    gather_prefetch_capable = True
    layer_health_capable = True
    # paged decode: _paged_verify_attn below (RoPE at each span position)
    paged_decode_capable = True

    def __init__(self, config: LlamaConfig):
        super().__init__(config)

    # -- params ------------------------------------------------------------

    def init(self, key) -> Dict[str, jax.Array]:
        c = self.config
        d, l, v = c.n_embd, c.n_layer, c.vocab_size
        hd = c.head_dim
        kvd = c.kv_heads * hd
        f = c.ffn
        std = 0.02
        pstd = std / math.sqrt(2 * l)
        keys = iter(jax.random.split(key, 12))

        def nrm(k, shape, s):
            return (jax.random.normal(k, shape, jnp.float32) * s).astype(
                c.param_dtype
            )

        params = {
            "wte": nrm(next(keys), (v, d), std),
            "h.ln_1.w": jnp.ones((l, d), c.param_dtype),
            "h.attn.q.w": nrm(next(keys), (l, d, d), std),
            "h.attn.k.w": nrm(next(keys), (l, d, kvd), std),
            "h.attn.v.w": nrm(next(keys), (l, d, kvd), std),
            "h.attn.o.w": nrm(next(keys), (l, d, d), pstd),
            "h.ln_2.w": jnp.ones((l, d), c.param_dtype),
            "h.mlp.gate.w": nrm(next(keys), (l, d, f), std),
            "h.mlp.up.w": nrm(next(keys), (l, d, f), std),
            "h.mlp.down.w": nrm(next(keys), (l, f, d), pstd),
            "ln_f.w": jnp.ones((d,), c.param_dtype),
            "lm_head.w": nrm(next(keys), (d, v), std),
        }
        if c.tie_weights:
            del params["lm_head.w"]
        return params

    def tp_rules(self) -> Dict[str, int]:
        """Column-parallel q/k/v/gate/up, row-parallel o/down, vocab-parallel
        lm_head (needs n_head % tp == 0 and kv_heads % tp == 0)."""
        return {
            "h.attn.q.w": 2,
            "h.attn.k.w": 2,
            "h.attn.v.w": 2,
            "h.attn.o.w": 1,
            "h.mlp.gate.w": 2,
            "h.mlp.up.w": 2,
            "h.mlp.down.w": 1,
            "lm_head.w": 1,
        }

    # -- forward -----------------------------------------------------------

    def embed(self, params, idx, pctx=None):
        """Token embedding only — positions enter via RoPE in each block
        (no wpe table)."""
        return self._constrain_activations(
            self.embed_tokens(params, idx), pctx
        )

    def _positions(self, t_local, pctx):
        pos = jnp.arange(t_local, dtype=jnp.int32)
        if (pctx is not None and pctx.seq_parallel and pctx.pipe_parallel):
            # inside the pipeline's manual-{pipe, seq} region the block sees
            # a LOCAL T shard; offset to global positions
            pos = pos + jax.lax.axis_index(pctx.seq_axis) * t_local
        return pos

    def _block(self, x, bp, pctx=None, return_kv=False):
        c = self.config
        b, t, d = x.shape
        hd = c.head_dim
        nq, nkv = c.n_head, c.kv_heads

        h = rmsnorm(x, bp["ln_1.w"])
        q = linear(h, self._bw(bp, "attn.q.w", pctx), None)
        k = linear(h, self._bw(bp, "attn.k.w", pctx), None)
        v = linear(h, self._bw(bp, "attn.v.w", pctx), None)
        q = q.reshape(b, t, nq, hd).swapaxes(1, 2)
        k = k.reshape(b, t, nkv, hd).swapaxes(1, 2)
        v = v.reshape(b, t, nkv, hd).swapaxes(1, 2)

        pos = self._positions(t, pctx)
        q = rope(q, pos, c.rope_theta)
        k = rope(k, pos, c.rope_theta)
        kv = (k, v)  # cached UNREPEATED (post-rope): decode groups q heads
        # GQA: K/V go in at nkv heads — sharded_attention keeps them
        # grouped into the FA2 kernel on the flash paths (K/V HBM traffic
        # stays at kv_heads) and expands only where a path needs equal
        # head counts (ops/attention.py)
        y = sharded_attention(q, k, v, c.attn_impl, pctx)
        y = y.swapaxes(1, 2).reshape(b, t, d)
        y = linear(y, self._bw(bp, "attn.o.w", pctx), None)
        dkey = bp.get("dropout_rng")
        if dkey is not None:
            y = _dropout(y, jax.random.fold_in(dkey, 0), c.dropout)
        x = x + y

        h = rmsnorm(x, bp["ln_2.w"])
        gate = jax.nn.silu(linear(h, self._bw(bp, "mlp.gate.w", pctx), None))
        up = linear(h, self._bw(bp, "mlp.up.w", pctx), None)
        y = linear(gate * up, self._bw(bp, "mlp.down.w", pctx), None)
        if dkey is not None:
            y = _dropout(y, jax.random.fold_in(dkey, 1), c.dropout)
        x = x + y
        return (x, kv) if return_kv else x

    # -- KV-cache decode (GPT2Model machinery; Llama attention/MLP) --------

    def _attn_decode(self, x, bp, ks, vs, l, pos):
        """Stacked-cache contract (GPT2Model._attn_decode): write this
        position's K/V sliver in place at (l, pos), read layer l's
        panel, attend (grouped — the cache rests at kv_heads)."""
        c = self.config
        b = x.shape[0]
        hd = c.head_dim
        h = rmsnorm(x, bp["ln_1.w"])
        q = linear(h, self._bw(bp, "attn.q.w"), None)
        k = linear(h, self._bw(bp, "attn.k.w"), None)
        v = linear(h, self._bw(bp, "attn.v.w"), None)
        q = q.reshape(b, 1, c.n_head, hd).swapaxes(1, 2)
        k = k.reshape(b, 1, c.kv_heads, hd).swapaxes(1, 2)
        v = v.reshape(b, 1, c.kv_heads, hd).swapaxes(1, 2)
        p1 = jnp.reshape(pos, (1,))
        q = rope(q, p1, c.rope_theta)
        k = rope(k, p1, c.rope_theta)
        ks = jax.lax.dynamic_update_slice(
            ks, k.astype(ks.dtype)[None], (l, 0, 0, pos, 0)
        )
        vs = jax.lax.dynamic_update_slice(
            vs, v.astype(vs.dtype)[None], (l, 0, 0, pos, 0)
        )
        ck = jax.lax.dynamic_index_in_dim(ks, l, 0, keepdims=False)
        cv = jax.lax.dynamic_index_in_dim(vs, l, 0, keepdims=False)
        y = self._decode_attention(q, ck, cv, pos)
        y = y.swapaxes(1, 2).reshape(b, 1, c.n_embd)
        return x + linear(y, self._bw(bp, "attn.o.w"), None), ks, vs

    def _mlp_decode(self, x, bp):
        h = rmsnorm(x, bp["ln_2.w"])
        gate = jax.nn.silu(linear(h, self._bw(bp, "mlp.gate.w"), None))
        up = linear(h, self._bw(bp, "mlp.up.w"), None)
        return x + linear(gate * up, self._bw(bp, "mlp.down.w"), None)

    def _block_decode(self, x, bp, ks, vs, l, pos):
        x, ks, vs = self._attn_decode(x, bp, ks, vs, l, pos)
        return self._mlp_decode(x, bp), ks, vs

    def _paged_verify_attn(self, x, bp, view, l, page):
        """Speculative-verify attention (GPT2Model contract): separate
        q/k/v projections, RoPE at each span token's absolute position,
        grouped attention over committed panel + windowed span."""
        c = self.config
        s, k1, _ = x.shape
        hd = c.head_dim
        h = rmsnorm(x, bp["ln_1.w"])
        q = linear(h, self._bw(bp, "attn.q.w"), None)
        k = linear(h, self._bw(bp, "attn.k.w"), None)
        v = linear(h, self._bw(bp, "attn.v.w"), None)
        q = q.reshape(s, k1, c.n_head, hd).swapaxes(1, 2)
        k = k.reshape(s, k1, c.kv_heads, hd).swapaxes(1, 2)
        v = v.reshape(s, k1, c.kv_heads, hd).swapaxes(1, 2)
        positions = page.pos[:, None] + jnp.arange(k1)[None, :]
        q = rope_span(q, positions, c.rope_theta)
        k = rope_span(k, positions, c.rope_theta)
        y = self._paged_attention(q, view, l, page, span_kv=(k, v))
        y = y.swapaxes(1, 2).reshape(s, k1, c.n_embd)
        return x + linear(y, self._bw(bp, "attn.o.w"), None), (k, v)

    def _embed_decode(self, params, tok, pos):
        """No wpe table — position enters via RoPE inside each block."""
        del pos
        return self.embed_tokens(params, tok[:, None])

    def _embed_decode_span(self, params, toks, positions):
        """No wpe table — span positions enter via `rope_span` inside
        each verify block."""
        del positions
        return self.embed_tokens(params, toks)

    def final_norm(self, params, x):
        """RMSNorm pre-head (GPT2Model.head's one overridable hook — the
        lm_head/fused-xent/position-slice policy stays in gpt2.py)."""
        return rmsnorm(x, params["ln_f.w"].astype(self.config.compute_dtype))
