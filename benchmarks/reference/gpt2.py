"""GPT-2 forward and loss in plain float32 `jax.numpy`: the reference the
cells' `correct` is decided against.

No kernels, no cache, no remat, no fused head: embedding gather, pre-LN
blocks (layernorm eps 1e-5, causal softmax attention scaled by
1/sqrt(head_dim), tanh-approximated GELU), final layernorm, an untied
lm_head, mean cross-entropy.  It follows Radford et al. 2019 as the
program's preset does; the one departure is the preset's own -- the head
is not tied to wte (`tiny_deepspeed_tpu/models/gpt2.py`), so it reads
`lm_head.w` when the parameters have one.

Every matmul runs under `jax.default_matmul_precision("highest")`: on a
TPU a float32 matmul otherwise runs in bf16 passes.  Parameters arrive in
whatever type the engine rests them in and are cast to float32 one layer
at a time inside the layer scan, so the reference of a 1.5B model needs
one layer in float32, not the model.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

_BLOCK_KEYS = ("ln_1.w", "ln_1.b", "attn.qkv.w", "attn.qkv.b",
               "attn.proj.w", "attn.proj.b", "ln_2.w", "ln_2.b",
               "mlp.fc.w", "mlp.fc.b", "mlp.proj.w", "mlp.proj.b")


def _f32(x):
    return x.astype(jnp.float32)


def _layernorm(x, w, b, eps=1e-5):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * w + b


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _block(x, bp, n_head):
    t, d = x.shape
    dh = d // n_head
    h = _layernorm(x, bp["ln_1.w"], bp["ln_1.b"])
    qkv = h @ bp["attn.qkv.w"] + bp["attn.qkv.b"]
    q, k, v = (z.reshape(t, n_head, dh).transpose(1, 0, 2)
               for z in jnp.split(qkv, 3, axis=-1))
    scores = (q @ k.transpose(0, 2, 1)) / math.sqrt(dh)
    mask = jnp.tril(jnp.ones((t, t), bool))
    scores = jnp.where(mask, scores, -jnp.inf)
    y = jax.nn.softmax(scores, axis=-1) @ v
    y = y.transpose(1, 0, 2).reshape(t, d)
    x = x + y @ bp["attn.proj.w"] + bp["attn.proj.b"]
    h = _layernorm(x, bp["ln_2.w"], bp["ln_2.b"])
    h = _gelu_tanh(h @ bp["mlp.fc.w"] + bp["mlp.fc.b"])
    return x + h @ bp["mlp.proj.w"] + bp["mlp.proj.b"]


def hidden(params, idx, n_head):
    """Final-layernormed hidden states of ONE sequence: idx (t,) -> (t, d)."""
    t = idx.shape[0]
    x = _f32(params["wte"][idx]) + _f32(params["wpe"][:t])
    stacked = {k: params["h." + k] for k in _BLOCK_KEYS}

    def body(x, bp):
        return _block(x, {k: _f32(v) for k, v in bp.items()}, n_head), None

    x, _ = jax.lax.scan(body, x, stacked)
    return _layernorm(x, _f32(params["ln_f.w"]), _f32(params["ln_f.b"]))


def _head(params):
    w = params.get("lm_head.w")
    return _f32(params["wte"]).T if w is None else _f32(w)


def loss(params, idx, targets, n_head):
    """Mean next-token cross-entropy over a batch, one sequence at a time
    (idx, targets: (b, t) int32) -- the value `engine.step` reports for the
    same batch at the same parameters."""
    with jax.default_matmul_precision("highest"):
        head = _head(params)

        def one(pair):
            ix, tg = pair
            logits = hidden(params, ix, n_head) @ head
            logz = jax.scipy.special.logsumexp(logits, axis=-1)
            gold = jnp.take_along_axis(logits, tg[:, None], axis=-1)[:, 0]
            return jnp.mean(logz - gold)

        return jnp.mean(jax.lax.map(one, (idx, targets)))


def logits_at(params, idx, positions, n_head):
    """Full-forward logits of each sequence at one position: idx (b, t)
    padded on the right (causal attention never sees the padding),
    positions (b,) -> (b, vocab).  What prefill and decode through a KV
    cache must reproduce."""
    with jax.default_matmul_precision("highest"):
        head = _head(params)

        def one(pair):
            ix, pos = pair
            return hidden(params, ix, n_head)[pos] @ head

        return jax.lax.map(one, (idx, positions))
