"""EvaByte forward and loss in plain float32 `jax.numpy`: the reference the
`evabyte-6.5b` cells' `correct` is decided against.

No kernels, no cache, no merge by log-sum-exp: every query's softmax runs
over ALL T keys and ALL T / C chunk summaries at once, with E_n (the
query's own window, causal) and R_n (the chunks of earlier windows) as
masks.  It imports nothing from `tiny_deepspeed_tpu.ops` or `.models`.

The layer, as the EvaByte release runs EVA (arXiv:2302.04542;
huggingface.co/EvaByte/EvaByte, config.json: `attention_class` "eva",
`window_size` W, `chunk_size` C, `norm_add_unit_offset`, `fp32_skip_add`,
`fp32_logits`, `num_pred_heads`), head h, s = Dh^-1/2, w(n) = n // W:

    x' = x / sqrt(mean(x^2) + eps) * (1 + g)
    q, k, v = x'Wq, x'Wk, x'Wv ;  q_n, k_n <- RoPE(theta, n)
    kbar_c = sum_{m in c} softmax_m(mu_h . k_m) k_m
    vbar_c = sum_{m in c} softmax_m(phi_h . k_m) v_m
    E_n = {m : w(n) W <= m <= n}      R_n = {c : (c + 1) C <= w(n) W}
    o_n = softmax over E_n, R_n of (s q_n.k_m | s q_n.kbar_c) . (v_m | vbar_c)
    y = x + concat_h(o) Wo ;  z = y + (silu(y'Wg) * y'Wu) Wd
    logits_n = norm(z_n) Whead, 8 heads of 320; head j scores byte n + 1 + j

Departures from the source, each because config.json does not settle it
(benchmarks/configs/evabyte-6.5b.json lists them under `assumed`):
  * the pooling logits mu . k and phi . k carry no extra scale;
  * the pooled keys are the ROTATED ones (what the cache holds);
  * RoPE rotates halves (x1, x2 = the head's first and second half), the
    Hugging Face convention, at the absolute position;
  * the head is one 4096 -> 8 * 320 matrix, untied, and the loss is the
    mean over the 8 heads of the cross-entropy against byte n + 1 + j,
    over the positions that have such a byte;
  * a chunk is summarised only once its whole WINDOW is past (the release
    attends finished windows through their summaries), so no partial
    chunk and no chunk of the query's own window is ever in R_n.

Every matmul runs under `jax.default_matmul_precision("highest")`.
Parameters arrive in whatever type the engine rests them in and are cast
to float32 one layer at a time inside the layer scan; the softmax runs by
blocks of 128 queries and the MLP by blocks of 1024 rows, so 16,385
positions at the published widths need about 3 GB beside the engine.
`dtype` is for the control only (the same forward with every activation,
the residual stream and the softmax in a lower precision, which the
cell's tolerance has to refuse): the reference itself is float32.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

_BLOCK_KEYS = ("ln_1.w", "attn.q.w", "attn.k.w", "attn.v.w", "attn.mu",
               "attn.phi", "attn.o.w", "ln_2.w", "mlp.gate.w", "mlp.up.w",
               "mlp.down.w")
_QUERIES = 128      # queries a softmax block
_ROWS = 1024        # rows an MLP block


def _norm(x, g, eps):
    xf = x.astype(jnp.float32)
    y = xf / jnp.sqrt(jnp.mean(jnp.square(xf), axis=-1, keepdims=True) + eps)
    return (y * (1.0 + g.astype(jnp.float32))).astype(x.dtype)


def _rope(x, theta):
    """x (H, T, Dh), row n rotated at position n, halves paired."""
    _, t, dh = x.shape
    half = dh // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs[None]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(
        jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def _pool(keys, values, vec, chunk):
    """(H, T, Dh) -> (H, T / chunk, Dh): sum_m softmax_m(vec . k_m) value_m
    within each chunk."""
    h, t, dh = keys.shape
    kc = keys.reshape(h, t // chunk, chunk, dh)
    vc = values.reshape(h, t // chunk, chunk, dh)
    a = jax.nn.softmax(jnp.einsum("hncd,hd->hnc", kc, vec), axis=-1)
    return jnp.einsum("hnc,hncd->hnd", a, vc)


def _attention(q, k, v, kbar, vbar, window, chunk):
    """(H, T, Dh) each; kbar, vbar (H, T / chunk, Dh) -> (H, T, Dh)."""
    h, t, dh = q.shape
    qb = min(_QUERIES, t)
    scale = 1.0 / math.sqrt(dh)
    cols = jnp.arange(t)                       # key m
    ends = (jnp.arange(t // chunk) + 1) * chunk  # one past chunk c's last
    keys = jnp.concatenate([k, kbar], axis=1)
    values = jnp.concatenate([v, vbar], axis=1)

    def block(args):
        qq, n = args                            # (H, qb, Dh), (qb,)
        start = (n // window) * window          # w(n) W
        in_e = (cols[None] >= start[:, None]) & (cols[None] <= n[:, None])
        in_r = ends[None] <= start[:, None]
        mask = jnp.concatenate([in_e, in_r], axis=1)
        s = jnp.einsum("hqd,hkd->hqk", qq, keys) * scale
        p = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,hkd->hqd", p, values)

    out = jax.lax.map(block, (
        q.reshape(h, t // qb, qb, dh).swapaxes(0, 1),
        jnp.arange(t).reshape(t // qb, qb)))
    return out.swapaxes(0, 1).reshape(h, t, dh)


def _block(x, bp, cfg):
    t, d = x.shape
    nh = cfg.n_head
    dh = d // nh
    h = _norm(x, bp["ln_1.w"], cfg.rms_norm_eps)

    def heads(z):
        return z.reshape(t, nh, dh).swapaxes(0, 1)

    q = _rope(heads(h @ bp["attn.q.w"]), cfg.rope_theta)
    k = _rope(heads(h @ bp["attn.k.w"]), cfg.rope_theta)
    v = heads(h @ bp["attn.v.w"])
    kbar = _pool(k, k, bp["attn.mu"], cfg.chunk_size)
    vbar = _pool(k, v, bp["attn.phi"], cfg.chunk_size)
    o = _attention(q, k, v, kbar, vbar, cfg.window_size, cfg.chunk_size)
    x = x + o.swapaxes(0, 1).reshape(t, d) @ bp["attn.o.w"]

    def mlp(rows):
        r = _norm(rows, bp["ln_2.w"], cfg.rms_norm_eps)
        return (jax.nn.silu(r @ bp["mlp.gate.w"]) * (r @ bp["mlp.up.w"])
                ) @ bp["mlp.down.w"]

    rb = min(_ROWS, t)
    return x + jax.lax.map(mlp, x.reshape(t // rb, rb, d)).reshape(t, d)


def hidden(params, idx, cfg, dtype=jnp.float32):
    """The residual stream of ONE sequence after the last block, before
    the final norm: idx (t,) -> (t, d).  The sequence is padded on the
    right to whole blocks; padding lies behind every real position, and a
    chunk that holds any of it lies in a window no real query is past."""
    t = idx.shape[0]
    to = (-(-t // _QUERIES) * _QUERIES if t <= _ROWS
          else -(-t // _ROWS) * _ROWS)
    x = params["wte"][jnp.pad(idx, (0, to - t))].astype(dtype)
    stacked = {k: params["h." + k] for k in _BLOCK_KEYS}

    def body(x, bp):
        return _block(x, {k: v.astype(dtype) for k, v in bp.items()},
                      cfg), None

    x, _ = jax.lax.scan(body, x, stacked)
    return x[:t]


def _logits(params, z, cfg, dtype):
    """Rows z (n, d) -> (n, heads * vocab) float32 (`fp32_logits`)."""
    z = _norm(z, params["ln_f.w"], cfg.rms_norm_eps)
    return (z @ params["lm_head.w"].astype(dtype)).astype(jnp.float32)


def loss(params, idx, targets, cfg, dtype=jnp.float32):
    """idx, targets (b, t), targets[n] = byte n + 1: the mean over the
    heads j of the mean cross-entropy of head j at position n against byte
    n + 1 + j = targets[n + j], over the positions n + j < t."""
    heads, vocab = cfg.num_pred_heads, cfg.vocab_size
    with jax.default_matmul_precision("highest"):
        def one(pair):
            ix, tg = pair
            t = ix.shape[0]
            logp = jax.nn.log_softmax(_logits(
                params, hidden(params, ix, cfg, dtype), cfg, dtype
            ).reshape(t, heads, vocab), axis=-1)
            total = 0.0
            for j in range(min(heads, t)):
                gold = jnp.take_along_axis(
                    logp[:t - j, j], tg[j:, None], axis=-1)
                total = total - jnp.mean(gold)
            return total / heads

        return jnp.mean(jax.lax.map(one, (idx, targets)))


def logits_at(params, idx, positions, cfg, dtype=jnp.float32):
    """Full-forward logits of each sequence at one position: idx (b, t)
    padded on the right, positions (b,) -> (b, heads * vocab), head j's
    scores in columns [j * vocab, (j + 1) * vocab).  What prefill and
    decode through the window ring and the summary rows must reproduce."""
    with jax.default_matmul_precision("highest"):
        def one(pair):
            ix, pos = pair
            z = hidden(params, ix, cfg, dtype)[pos]
            return _logits(params, z[None], cfg, dtype)[0]

        return jax.lax.map(one, (idx, positions))
