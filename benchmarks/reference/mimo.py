"""MiMo-V2-Flash forward and loss in plain float32 `jax.numpy`: the
reference the `mimo-v2-flash` cells' `correct` is decided against.

No kernels, no cache, no ring, no sorting of tokens by expert: every
query's softmax runs over ALL T keys under a mask, and every held expert
is applied to ALL T rows and weighted by its gate, which is zero where the
router did not choose it.  It imports nothing from `tiny_deepspeed_tpu.ops`
or `.models`.

The layer, as huggingface.co/XiaomiMiMo/MiMo-V2-Flash config.json gives it
(`model_type` "mimo_v2_flash").  For layer l, attention kind a(l) from
`hybrid_layer_pattern` (0 global, 1 window), MLP kind from
`moe_layer_freq` (0 dense, 1 experts), s = 192^-1/2:

    h  = x / sqrt(mean(x^2) + 1e-5) * g1        (RMSNorm, plain weight;
                                                 assumed: no unit offset)
    q = h Wq -> 64 x 192 ; k = h Wk -> KVH_a x 192 ; v = h Wv -> KVH_a x 128
        KVH = 4 global, 8 window ; no bias
    q, k <- RoPE on the first 64 of the 192 (0.334 x 192 = 64.1, assumed
        64), theta 5e6 global, 1e4 window, halves paired, absolute position
    v <- 0.707 v      (attention_value_scale; assumed: on V, which equals
                       on the output)
    s_nm = s q_n . k_m, query head i on KV head i // (64 / KVH_a)
    visible: m <= n (global) ; n - 128 < m <= n (window: 128 counts the
        query; assumed the Hugging Face convention)
    p_nm = exp(s_nm) / (Z_i + sum_m' exp(s_nm')), Z_i = exp(b_i) in window
        layers (add_swa_attention_sink_bias; one learned b per query head,
        in the denominator only), Z_i = 0 in global layers
        (add_full_attention_sink_bias false)
    x <- x + concat_i(sum_m p_nm v_m) Wo                  (8192 -> 4096)
    h2 = RMSNorm(x; g2)
    dense (layer 0):  x <- x + (silu(h2 Wg) * h2 Wu) Wd          (16384)
    experts:  r = sigmoid(h2 Wr) in R^256, float32 ;
              T = top-8 of (r + b), b the selection bias, used for the
              choice only ;  w_e = r_e / sum_{e' in T} r_e'
              (norm_topk_prob; routed_scaling_factor null = 1; n_group =
              topk_group = 1: no group limit)
              x <- x + sum_{e in T and held here} w_e (silu(h2 Wg_e) *
              h2 Wu_e) Wd_e   (2048) ;  no shared expert
    logits = RMSNorm(x; gf) Whead, over the held slice of the vocabulary,
             untied

Left out (benchmarks/configs/mimo-v2-flash.json lists each under
`assumed`): the 3 multi-token-prediction layers, `attention_chunk_size`
(taken to be unused by the forward pass), the V2.5 vision and audio
towers.  The chip's share: `cfg.experts_first`, `cfg.experts_held` say
which experts' weights `params` hold (what the others would add is left
out here as in the program), `cfg.vocab_size` the slice of the
vocabulary.

Parameters (the program's names): stacked by kind in layer order, "g.*"
the global layers' attention, "w.*" the window layers', "dense.*" and
"moe.*" the MLPs; the held experts of all expert layers lie layer-major
in "moe.experts.*" (layer j's expert e at j * held + e).

Every matmul runs under `jax.default_matmul_precision("highest")`.
Parameters arrive in whatever type the engine rests them in and are cast
to float32 one layer (one expert) at a time; the softmax runs by blocks of
128 queries and the MLPs by blocks of 1024 rows.  `dtype` is for the
control only (the same forward with every activation, the residual stream
and the softmax in a lower precision, which the cell's tolerance has to
refuse; the router stays float32 on whatever it is handed), `fault` for
the planted faults only (FAULTS: each a wrong reading of the layer above
that the tolerance has to refuse): the reference itself is float32 and
has none.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

_QUERIES = 128      # queries a softmax block
_ROWS = 1024        # rows an MLP block

FAULTS = ("expert_dropped", "gates_unnormalised", "bias_in_gate",
          "sink_left_out", "window_127", "window_129", "theta_swapped")


def _norm(x, g, eps):
    xf = x.astype(jnp.float32)
    y = xf / jnp.sqrt(jnp.mean(jnp.square(xf), axis=-1, keepdims=True) + eps)
    return (y * g.astype(jnp.float32)).astype(x.dtype)


def _rope(x, theta, rotary):
    """x (H, T, D), row n rotated at position n on its first `rotary`
    numbers, halves paired; the rest passes."""
    t = x.shape[1]
    half = rotary // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs[None]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:rotary].astype(jnp.float32)
    turned = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                             axis=-1).astype(x.dtype)
    return jnp.concatenate([turned, x[..., rotary:]], axis=-1)


def _attention(q, k, v, window, sink):
    """q (H, T, Dk), k (KVH, T, Dk), v (KVH, T, Dv) -> (H, T, Dv).
    window 0: causal; else n - window < m <= n.  sink (H,) or None."""
    h, t, dk = q.shape
    group = h // k.shape[0]
    keys, values = jnp.repeat(k, group, axis=0), jnp.repeat(v, group, axis=0)
    qb = min(_QUERIES, t)
    cols = jnp.arange(t)

    def block(args):
        qq, n = args                               # (H, qb, Dk), (qb,)
        seen = cols[None] <= n[:, None]
        if window:
            seen &= cols[None] > n[:, None] - window
        s = jnp.einsum("hqd,hkd->hqk", qq, keys) / math.sqrt(dk)
        s = jnp.where(seen[None], s, -jnp.inf).astype(jnp.float32)
        if sink is not None:  # a key with no value
            s = jnp.concatenate([s, jnp.broadcast_to(
                sink.astype(jnp.float32)[:, None, None], (h, qb, 1))],
                axis=-1)
        p = jax.nn.softmax(s, axis=-1)[..., :t].astype(qq.dtype)
        return jnp.einsum("hqk,hkd->hqd", p, values)

    out = jax.lax.map(block, (
        q.reshape(h, t // qb, qb, dk).swapaxes(0, 1),
        jnp.arange(t).reshape(t // qb, qb)))
    return out.swapaxes(0, 1).reshape(h, t, -1)


def _experts(rows, lp, held, cfg, dtype, fault):
    """rows (n, D) normed -> the held experts' part, (n, D).  `held`:
    every expert layer's held experts and this layer's first among
    them."""
    first, count = cfg.experts_first, cfg.experts_held
    top_k = cfg.n_experts_per_tok
    r = jax.nn.sigmoid(rows.astype(jnp.float32)
                       @ lp["router.w"].astype(jnp.float32))
    bias = lp["router.bias"].astype(jnp.float32)
    _, choice = jax.lax.top_k(r + bias, top_k)
    chosen = jnp.sum(jax.nn.one_hot(choice, r.shape[-1], dtype=r.dtype),
                     axis=1)
    weigh = r + bias if fault == "bias_in_gate" else r
    gate = chosen * weigh
    if fault != "gates_unnormalised":
        gate = gate / jnp.sum(gate, axis=-1, keepdims=True)
    gate = gate[:, first:first + count]
    if fault == "expert_dropped":
        # the busiest held expert gives nothing
        gate = gate * (jnp.arange(count) != jnp.argmax(
            jnp.sum(gate > 0, axis=0)))[None]

    stacks, base = held

    def one(y, e):
        wg, wu, wd = (stacks[k][base + e].astype(dtype)
                      for k in ("gate.w", "up.w", "down.w"))
        act = jax.nn.silu(rows @ wg) * (rows @ wu)
        return y + gate[:, e, None].astype(dtype) * (act @ wd), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(rows), jnp.arange(count))
    return y


def _layer(x, ap, mp, held, kind, moe, cfg, dtype, fault):
    t, d = x.shape
    nh, dk, dv = cfg.n_head, cfg.head_dim, cfg.v_head_dim
    kvh = cfg.swa_n_kv_head if kind else cfg.n_kv_head
    thetas = (cfg.rope_theta, cfg.swa_rope_theta)
    if fault == "theta_swapped":
        thetas = thetas[::-1]
    window = cfg.window + {"window_127": -1, "window_129": 1}.get(fault, 0)
    ap = {k: v.astype(dtype) for k, v in ap.items()}
    h = _norm(x, ap["ln_1.w"], cfg.rms_norm_eps)

    def heads(name, n, width):
        return (h @ ap[name]).reshape(t, n, width).swapaxes(0, 1)

    q = _rope(heads("attn.q.w", nh, dk), thetas[kind], cfg.rotary_dim)
    k = _rope(heads("attn.k.w", kvh, dk), thetas[kind], cfg.rotary_dim)
    v = heads("attn.v.w", kvh, dv) * cfg.value_scale
    sink = ap["attn.sink"] if kind and fault != "sink_left_out" else None
    o = _attention(q, k, v, window if kind else 0, sink)
    x = x + o.swapaxes(0, 1).reshape(t, nh * dv) @ ap["attn.o.w"]

    def mlp(rows):
        r = _norm(rows, mp["ln_2.w"].astype(dtype), cfg.rms_norm_eps)
        if moe:
            return _experts(r, mp, held, cfg, dtype, fault)
        return (jax.nn.silu(r @ mp["mlp.gate.w"].astype(dtype))
                * (r @ mp["mlp.up.w"].astype(dtype))
                ) @ mp["mlp.down.w"].astype(dtype)

    rb = min(_ROWS, t)
    return x + jax.lax.map(mlp, x.reshape(t // rb, rb, d)).reshape(t, d)


def hidden(params, idx, cfg, dtype=jnp.float32, fault=""):
    """The residual stream of ONE sequence after the last layer, before
    the final norm: idx (t,) -> (t, d).  The sequence is padded on the
    right to whole blocks; padding lies behind every real position."""
    t = idx.shape[0]
    to = (-(-t // _QUERIES) * _QUERIES if t <= _ROWS
          else -(-t // _ROWS) * _ROWS)
    x = params["wte"][jnp.pad(idx, (0, to - t))].astype(dtype)
    seen = {"g": 0, "w": 0, "dense": 0, "moe": 0}

    def of(name, i):  # layer i of its kind, all but the experts' stacks
        return {k[len(name) + 1:]: v[i] for k, v in params.items()
                if k.startswith(name + ".")
                and not k.startswith("moe.experts.")}

    for kind, moe in zip(cfg.layer_kinds, cfg.moe_layers):
        a, m = ("g", "w")[kind], ("dense", "moe")[moe]
        lm = seen[m]
        held = ({k[len("moe.experts."):]: v for k, v in params.items()
                 if k.startswith("moe.experts.")}, lm * cfg.experts_held)
        x = _layer(x, of(a, seen[a]), of(m, lm), held, kind, moe, cfg,
                   dtype, fault)
        seen[a] += 1
        seen[m] += 1
    return x[:t]


def _logits(params, z, cfg, dtype):
    """Rows z (n, d) -> (n, vocab) float32."""
    z = _norm(z, params["ln_f.w"].astype(dtype), cfg.rms_norm_eps)
    return (z @ params["lm_head.w"].astype(dtype)).astype(jnp.float32)


def loss(params, idx, targets, cfg, dtype=jnp.float32, fault=""):
    """idx, targets (b, t), targets[n] = token n + 1: the mean
    cross-entropy over all positions."""
    with jax.default_matmul_precision("highest"):
        def one(pair):
            ix, tg = pair
            logp = jax.nn.log_softmax(_logits(
                params, hidden(params, ix, cfg, dtype, fault), cfg, dtype),
                axis=-1)
            return -jnp.mean(jnp.take_along_axis(logp, tg[:, None], axis=-1))

        return jnp.mean(jax.lax.map(one, (idx, targets)))


def logits_at(params, idx, positions, cfg, dtype=jnp.float32, fault=""):
    """Full-forward logits of each sequence at one position: idx (b, t)
    padded on the right, positions (b,) -> (b, vocab).  What prefill and
    decode through the table and the ring must reproduce."""
    with jax.default_matmul_precision("highest"):
        def one(pair):
            ix, pos = pair
            z = hidden(params, ix, cfg, dtype, fault)[pos]
            return _logits(params, z[None], cfg, dtype)[0]

        return jax.lax.map(one, (idx, positions))
