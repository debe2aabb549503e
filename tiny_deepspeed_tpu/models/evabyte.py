# Copyright 2026 tiny-deepspeed-tpu authors
# SPDX-License-Identifier: Apache-2.0

"""EvaByte: a byte-level decoder with EVA chunked linear attention.

EVA (arXiv:2302.04542), in the form the EvaByte release runs
(huggingface.co/EvaByte/EvaByte, `attention_class: "eva"`): a query
attends EXACTLY, by softmax, to the keys of its own window of
`window_size` positions, and to ONE summary per `chunk_size` positions of
every earlier window, all under one softmax.  A chunk's summary key and
value are softmax-poolings of the chunk's keys and values, by two learned
vectors a head.  With head h, Dh its size, s = Dh^-1/2, W the window, C
the chunk, w(n) = n // W:

    kbar_c = sum_{m in c} softmax_m(mu_h . k_m) k_m
    vbar_c = sum_{m in c} softmax_m(phi_h . k_m) v_m
    E_n = {m : w(n) W <= m <= n}        R_n = {c : (c + 1) C <= w(n) W}
    o_n = softmax over E_n and R_n of (s q_n . k_m | s q_n . kbar_c),
          applied to (v_m | vbar_c)                  # float32

A chunk enters R_n only once its whole window is past, so no partial
chunk is ever attended.  The rest is a Llama block (RoPE, SwiGLU, no
bias) with three departures the release states: RMSNorm with a unit
offset (the stored weight is g, the scale 1 + g), a residual stream kept
in float32, and `num_pred_heads` output heads (head j at position n
scores byte n + 1 + j; the loss is the mean over the heads).

What a request holds while it is served is therefore bounded: at most W
exact K/V rows (a ring: row n % W) and one summary row per closed chunk.
Both kinds of row have the pool's one shape (serving/pool.pool_shape), so
they lie side by side in ONE pool array, reached through two block
tables a slot (`EvaLayout`).  What the published config.json does not
settle is listed in benchmarks/configs/evabyte-6.5b.json under `assumed`.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, NamedTuple

import jax
import jax.numpy as jnp

from ..ops.eva_attention import (
    eva_attention, eva_bounds, eva_pad_len, eva_paged_attention,
    eva_summaries,
)
from ..ops.eva_attn_pallas import eva_steps
from ..ops.rmsnorm import rmsnorm
from .llama import LlamaConfig, LlamaModel, rope, rope_at


@dataclasses.dataclass(frozen=True)
class EvaByteConfig(LlamaConfig):
    """LlamaConfig (context `block_size`, RoPE, SwiGLU width, heads) plus
    EVA's window and chunk and the number of output heads."""

    window_size: int = 2048
    chunk_size: int = 16
    num_pred_heads: int = 8
    rms_norm_eps: float = 1e-5
    init_std: float = 0.01275


_FULL = EvaByteConfig(
    block_size=32768, vocab_size=320, n_layer=32, n_head=32, n_kv_head=32,
    n_embd=4096, ffn_hidden=11008, rope_theta=100000.0)

EVABYTE_PRESETS: Dict[str, EvaByteConfig] = {
    "evabyte-6.5b": _FULL,
    # one pipeline stage of the same model: 6 whole layers with the
    # embedding and the heads (the benchmark's cut; every width as above)
    "evabyte-6.5b-6l": dataclasses.replace(_FULL, n_layer=6),
    "evabyte-tiny": EvaByteConfig(
        block_size=512, vocab_size=320, n_layer=2, n_head=4, n_kv_head=4,
        n_embd=64, ffn_hidden=128, rope_theta=100000.0, window_size=32,
        chunk_size=4, num_pred_heads=2, compute_dtype=jnp.float32),
}


def _mm(x, w):
    """x @ w with the product left in float32: what a matmul of bf16
    operands accumulates in anyway.  Every product here feeds float32
    arithmetic (the residual add, RoPE, the gate), so rounding it to
    bf16 first, as `ops.linear` does, would round twice."""
    return jax.lax.dot_general(
        x, w, (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


class EvaLayout(NamedTuple):
    """What one slot can hold in the paged pool, in blocks of
    `block_tokens` rows: `window` blocks of exact K/V, written as a ring
    (position n rests in row n % W), then `summary` blocks, one row per
    closed chunk.  A slot's block-table row is the two tables side by
    side: entries [0, window) and [window, window + summary).  The
    members are those of every slot layout (serving/pool.DenseLayout
    says what the engine asks of each)."""

    window: int
    summary: int
    window_size: int
    chunk_size: int
    block_tokens: int
    # both kinds of row have one shape: ONE kind of block
    kinds: tuple = ()

    tables = (0, 0)
    span = "roll"
    fetched = ()
    # a slot can hold no more than its ring and its summary rows, and
    # nothing but slots holds blocks: a block beyond max_active slots'
    # worst case could never be allocated
    bounds_pool = True
    refuses = {
        "prefix_cache": "be served with prefix_cache: the radix tree "
                        "shares blocks of K/V by token prefix, and knows "
                        "no window ring or summary rows",
        "spec_draft": "be served with spec_draft: the verify program "
                      "scores a span per slot, and the window ring and "
                      "summary rows take one position",
        "quant": "be served with quant: a quantized pool keeps "
                 "per-vector scales the summary rows and the EVA decode "
                 "kernel do not read",
        **{verb + "_request": verb + " a request's blocks: export_blocks "
           "/ import_blocks move one table of K/V blocks, not a window "
           "ring and summary rows" for verb in ("export", "import")},
    }

    @property
    def width(self) -> int:
        return self.window + self.summary

    def need(self, pos: int):
        """(window blocks, summary blocks) a slot owns before it writes
        position `pos`: the ring up to its row, and the row of the chunk
        that closes next."""
        bt = self.block_tokens
        return (min(pos // bt + 1, self.window),
                min(pos // self.chunk_size // bt + 1, self.summary))

    def prefill_panel(self, bucket: int):
        """(window blocks, summary blocks) a prefill of `bucket` positions
        scatters: the rows of one window, and a summary per chunk."""
        bt = self.block_tokens
        return (min(bucket, self.window_size) // bt,
                -(-(bucket // self.chunk_size) // bt))

    def fill_row(self, row, table, summary) -> None:
        row[:len(table)] = table
        row[self.window:self.window + len(summary)] = summary

    def tick_counts(self, slots, max_active: int):
        """What the slots hold this tick: blocks by kind, and how many
        slots START A NEW WINDOW with this step -- their ring is written
        from row 0 again, with no free and no alloc.  The span carries
        besides the live slots and the rows the decode step will attend
        (live window rows and visible summaries).  And how much of their
        table rows that is in the decode kernel's unit, a chunk of a
        range (ops/eva_attn_pallas.eva_steps): `kv_steps` the chunks
        `max_active` rows hold, `kv_steps_live` those that begin below
        their range's bound, which are all the kernel copies and folds,
        a layer."""
        w = self.window_size
        bounds = [eva_bounds(s.pos, w, self.chunk_size) for s in slots]
        nb, npw, nps = eva_steps(self.window, self.summary,
                                 self.block_tokens)
        chunk = nb * self.block_tokens
        counts = dict(
            window_blocks=sum(len(s.table) for s in slots),
            summary_blocks=sum(len(s.summary) for s in slots),
            windows_rolled=sum(s.pos > 0 and s.pos % w == 0
                               for s in slots),
            kv_steps_live=sum(-(-n_win // chunk) + -(-n_sum // chunk)
                              for n_win, n_sum in bounds),
            kv_steps=max_active * (npw + nps))
        return counts, dict(
            active=len(slots),
            rows=sum(n_win + n_sum for n_win, n_sum in bounds),
            **counts)


class EvaByteModel(LlamaModel):
    """init / apply as every family; paged_prefill / paged_decode through
    the window ring and the summary rows (no other decode cache)."""

    # apply() is inherited, and with it the capabilities LlamaModel
    # states; the head's 1F1B and table seams score one vocabulary, not
    # 8 heads
    supports_1f1b = False
    supports_pipe_table = False

    def __init__(self, config: EvaByteConfig):
        c = config
        if c.window_size % c.chunk_size:
            raise ValueError(
                f"chunk_size={c.chunk_size} must divide window_size="
                f"{c.window_size}: a chunk lies in one window")
        if c.kv_heads != c.n_head:
            raise ValueError("EVA pools keys per query head: n_kv_head "
                             "must equal n_head")
        if c.dropout or c.tie_weights or c.gather_quant:
            raise ValueError("EvaByteModel has no dropout, tied head or "
                             "quantized gather")
        super().__init__(config)

    # -- params ------------------------------------------------------------

    def init(self, key) -> Dict[str, jax.Array]:
        c = self.config
        d, l, f = c.n_embd, c.n_layer, c.ffn
        h, hd = c.n_head, c.head_dim
        keys = iter(jax.random.split(key, 12))

        def nrm(k, shape, s=c.init_std):
            return (jax.random.normal(k, shape, jnp.float32) * s).astype(
                c.param_dtype)

        def pool_vec(k):  # clip(N(0, 1), +-1) * Dh^-1/2
            z = jnp.clip(jax.random.normal(k, (l, h, hd), jnp.float32), -1, 1)
            return (z / math.sqrt(hd)).astype(c.param_dtype)

        return {
            "wte": nrm(next(keys), (c.vocab_size, d)),
            # unit offset: the scale is 1 + g, so g starts at zero
            "h.ln_1.w": jnp.zeros((l, d), c.param_dtype),
            "h.attn.q.w": nrm(next(keys), (l, d, d)),
            "h.attn.k.w": nrm(next(keys), (l, d, d)),
            "h.attn.v.w": nrm(next(keys), (l, d, d)),
            "h.attn.mu": pool_vec(next(keys)),
            "h.attn.phi": pool_vec(next(keys)),
            "h.attn.o.w": nrm(next(keys), (l, d, d)),
            "h.ln_2.w": jnp.zeros((l, d), c.param_dtype),
            "h.mlp.gate.w": nrm(next(keys), (l, d, f)),
            "h.mlp.up.w": nrm(next(keys), (l, d, f)),
            "h.mlp.down.w": nrm(next(keys), (l, f, d)),
            "ln_f.w": jnp.zeros((d,), c.param_dtype),
            "lm_head.w": nrm(next(keys),
                             (d, c.num_pred_heads * c.vocab_size)),
        }

    # -- forward -----------------------------------------------------------

    def _norm(self, x, g):
        """x / sqrt(mean(x^2) + eps) * (1 + g), statistics in float32;
        the result goes into a matmul, so it is in compute dtype."""
        c = self.config
        y = rmsnorm(x, 1.0 + g.astype(jnp.float32), c.rms_norm_eps)
        return y.astype(c.compute_dtype)

    def embed(self, params, idx, pctx=None):
        """The residual stream is float32 from the embedding on."""
        x = self.embed_tokens(params, idx).astype(jnp.float32)
        return self._constrain_activations(x, pctx)

    def _embed_decode(self, params, tok, pos):
        del pos  # enters through RoPE
        return self.embed_tokens(params, tok[:, None]).astype(jnp.float32)

    def _qkv(self, h, bp, rot, positions, pctx=None):
        """(B, T, D) -> q, k, v as (B, H, T, Dh), q and k rotated by
        `rot`: `rope` at positions (T,) the rows share, `rope_at` at
        (B,), one a row (T == 1)."""
        c = self.config
        b, t, _ = h.shape
        cd = c.compute_dtype

        def heads(name):  # float32 until rotated: one rounding, not two
            z = _mm(h, self._bw(bp, name, pctx))
            return z.reshape(b, t, c.n_head, c.head_dim).swapaxes(1, 2)

        return (rot(heads("attn.q.w"), positions, c.rope_theta).astype(cd),
                rot(heads("attn.k.w"), positions, c.rope_theta).astype(cd),
                heads("attn.v.w").astype(cd))

    def _mlp(self, x, bp, pctx=None):
        h = self._norm(x, bp["ln_2.w"])
        gate = jax.nn.silu(_mm(h, self._bw(bp, "mlp.gate.w", pctx)))
        up = _mm(h, self._bw(bp, "mlp.up.w", pctx))
        act = (gate * up).astype(self.config.compute_dtype)
        return x + _mm(act, self._bw(bp, "mlp.down.w", pctx))

    def _block(self, x, bp, pctx=None, return_kv=False):
        """One block over whole sequences, x (B, T, D) float32.  With
        `return_kv` (the prefill) also k, v and the chunk summaries, and
        the window part may run in the FA2 forward kernel (no gradient is
        taken there)."""
        c = self.config
        b, t, d = x.shape
        scope = jax.named_scope
        with scope("tds.block"):
            with scope("tds.ln"):
                h = self._norm(x, bp["ln_1.w"])
            with scope("tds.attn.qkv"):
                tp = eva_pad_len(t, c.window_size, c.chunk_size)
                q, k, v = (
                    jnp.pad(z, ((0, 0), (0, 0), (0, tp - t), (0, 0)))
                    for z in self._qkv(h, bp, rope,
                                       self._positions(t, pctx), pctx))
            with scope("tds.attn.summary"):
                kbar, vbar = eva_summaries(k, v, bp["attn.mu"],
                                           bp["attn.phi"], c.chunk_size)
            with scope("tds.attn.kernel"):
                y = eva_attention(q, k, v, kbar, vbar, c.window_size,
                                  c.chunk_size, kernel_ok=return_kv)
            with scope("tds.attn.proj"):
                y = y[:, :, :t].swapaxes(1, 2).reshape(b, t, d)
                x = x + _mm(y, self._bw(bp, "attn.o.w", pctx))
            with scope("tds.mlp"):
                x = self._mlp(x, bp, pctx)
        return (x, (k, v, kbar, vbar)) if return_kv else x

    def final_norm(self, params, x):
        return self._norm(x, params["ln_f.w"])

    @jax.named_scope("tds.head")
    def head(self, params, x, targets=None, pctx=None, position=None):
        """Final norm and the 8 heads at once, logits in float32.  With
        targets (byte n + 1 at position n, as every family's batch has
        them): the mean over heads j of the cross-entropy of head j
        against byte n + 1 + j, over the positions that have one.
        Without: (B, 1, heads * vocab) at `position` (default the last);
        head j's scores are columns [j * vocab, (j + 1) * vocab)."""
        c = self.config
        w = self._lm_head_w(params)

        def logits_of(z):
            return _mm(self.final_norm(params, z), w)

        if targets is None:
            if position is None:
                x = x[:, -1:]
            else:
                x = jax.lax.dynamic_slice_in_dim(x, position, 1, axis=1)
            return logits_of(x)
        b, t, _ = x.shape
        heads, v = c.num_pred_heads, c.vocab_size
        logp = jax.nn.log_softmax(
            logits_of(x).reshape(b, t, heads, v), axis=-1)
        total = 0.0
        for j in range(min(heads, t)):
            gold = jnp.take_along_axis(
                logp[:, :t - j, j], targets[:, j:, None], axis=-1)
            total = total - jnp.mean(gold)
        return total / heads

    def sampling_logits(self, logits):
        """Decoding reads head 0: the next byte."""
        return logits[..., :self.config.vocab_size]

    # -- the decode caches this family does not have -----------------------

    def generate(self, *a, **kw):
        raise NotImplementedError(
            "EvaByteModel.generate: the contiguous decode cache keeps one "
            "K/V of the whole context; EVA keeps a window ring and chunk "
            "summaries, which only the paged pool holds: serve it through "
            "serving.ServingEngine")

    def paged_verify(self, stacked, x, view, page):
        raise NotImplementedError(
            "EvaByteModel.paged_verify: a span of more than one position "
            "per slot (speculation, suffix prefill) is not wired through "
            "the window ring and the summary rows")

    # -- the paged pool ----------------------------------------------------

    def paged_layout(self, max_seq: int, block_tokens: int) -> EvaLayout:
        c = self.config
        w, ch, bt = c.window_size, c.chunk_size, block_tokens
        if w % bt:
            raise ValueError(
                f"block_tokens={bt} must divide window_size={w}: the "
                "window's blocks are reused as a ring")
        from ..serving.pool import BlockKind
        nw, ns = w // bt, -(-(-(-max_seq // w) * (w // ch)) // bt)
        kind = BlockKind(c.n_layer, c.kv_heads, c.head_dim, c.head_dim,
                         nw + ns)
        return EvaLayout(window=nw, summary=ns, window_size=w,
                         chunk_size=ch, block_tokens=bt, kinds=(kind,))

    def paged_page_ref(self, tables, pos, block_tokens: int):
        """The decode step's write coordinates: position n rests in ring
        row n % W of the slot's window table."""
        from ..serving.pool import PageRef
        row = pos % self.config.window_size
        blk = jnp.take_along_axis(
            tables, (row // block_tokens)[:, None], axis=1)[:, 0]
        return PageRef(tables, blk, off=row % block_tokens, pos=pos)

    def paged_prefill(self, params, idx, last_pos, block_ids, view,
                      block_tokens: int, stacked=None):
        """One request's prompt, idx (1, P) padded to its bucket, into
        the pool: the K/V rows of the window that position last_pos + 1
        lies in (the one the first decode step attends) into the window
        blocks, and a summary per chunk into the summary blocks.
        `block_ids` is the two panels side by side
        (`EvaLayout.prefill_panel`); entries the slot does not own point
        at scratch.  Rows past the prompt hold padding: the decode step
        masks the window by its length and overwrites a summary row when
        its chunk really closes, before any query can see it."""
        from ..serving.pool import paged_scatter
        c = self.config
        bt = block_tokens
        p = idx.shape[1]
        wp = min(c.window_size, p)
        if p % wp or wp % bt or p % c.chunk_size:
            raise ValueError(
                f"prefill bucket {p} is no whole number of windows "
                f"({c.window_size}), blocks ({bt}) and chunks")
        nwb, nsb = self.paged_layout(c.block_size, bt).prefill_panel(p)
        start = jnp.minimum((last_pos + 1) // wp, p // wp - 1) * wp
        x = self.embed(params, idx)
        if stacked is None:
            stacked = self.stacked_compute_params(params)

        def body(x, bp):
            x, (k, v, kbar, vbar) = self._block(x, bp, None, return_kv=True)
            kw = jax.lax.dynamic_slice_in_dim(k, start, wp, axis=2)
            vw = jax.lax.dynamic_slice_in_dim(v, start, wp, axis=2)
            return x, (kw, vw, kbar, vbar)

        with jax.named_scope("tds.blocks"):
            x, (kw, vw, kbar, vbar) = jax.lax.scan(
                body, x, stacked, unroll=c.scan_unroll)
        with jax.named_scope("tds.attn.window"):
            view = paged_scatter(view, kw, vw, block_ids[:nwb], bt)
        with jax.named_scope("tds.attn.summary"):
            pad = ((0, 0),) * 3 + ((0, nsb * bt - kbar.shape[3]), (0, 0))
            view = paged_scatter(view, jnp.pad(kbar, pad), jnp.pad(vbar, pad),
                                 block_ids[nwb:], bt)
        return self.head(params, x, position=last_pos)[:, 0], view

    def paged_decode(self, stacked, x, view, page):
        """One byte per slot, x (S, 1, D) float32 at positions page.pos.
        Every layer attends the slot's visible summaries, the live rows
        of its window and the byte itself under one softmax; then ONE
        write of the byte's K/V rows of all layers into ring row n % W
        (at n % W == 0 the window starts over: row 0 is written, the
        rows behind it are masked until they are written again), and,
        where n closes a chunk, one write of its summary row."""
        from ..serving.pool import paged_append
        c = self.config
        n_layer = jax.tree.leaves(stacked)[0].shape[0]
        s = x.shape[0]
        scope = jax.named_scope
        # the table's width says how many positions the engine serves
        bt = view.k.shape[1]
        nw = c.window_size // bt
        lay = EvaLayout(nw, page.tables.shape[1] - nw, c.window_size,
                        c.chunk_size, bt)

        def body(x, l):
            bp = jax.tree.map(
                lambda t: jax.lax.dynamic_index_in_dim(
                    t, l, 0, keepdims=False), stacked)
            with scope("tds.block"):
                with scope("tds.ln"):
                    h = self._norm(x, bp["ln_1.w"])
                with scope("tds.attn.qkv"):
                    q, k, v = self._qkv(h, bp, rope_at, page.pos)
                with scope("tds.attn.kernel"):
                    y = eva_paged_attention(q, view, page, l, (k, v), lay)
                with scope("tds.attn.proj"):
                    y = y.swapaxes(1, 2).reshape(s, 1, c.n_embd)
                    x = x + _mm(y, self._bw(bp, "attn.o.w"))
                with scope("tds.mlp"):
                    x = self._mlp(x, bp)
            return x, (k[:, :, 0], v[:, :, 0])

        with scope("tds.blocks"):
            x, (ks, vs) = jax.lax.scan(body, x, jnp.arange(n_layer),
                                       unroll=c.scan_unroll)
        with scope("tds.attn.window"):
            view = paged_append(view, ks, vs, page)
        with scope("tds.attn.summary"):
            view = self._paged_close_chunk(stacked, view, page, lay)
        return x, view

    def _paged_close_chunk(self, stacked, view, page, lay: EvaLayout):
        """Where position n is the last of its chunk, pool the chunk's C
        keys and values (its rows of the window ring, the one just
        written among them) into summary row n // C.  Every slot computes
        one; a slot whose chunk stays open writes it to scratch."""
        from ..serving.pool import SCRATCH_BLOCK, _rest, _set_rows
        c = self.config
        ch, bt = c.chunk_size, lay.block_tokens
        s = page.pos.shape[0]
        n_layer = jax.tree.leaves(stacked)[0].shape[0]
        rows = ((page.pos // ch) * ch)[:, None] + jnp.arange(ch)[None]
        rows = rows % c.window_size                      # (S, C) ring rows
        blk = jnp.take_along_axis(page.tables, rows // bt, axis=1)

        def chunk(pool):  # -> (S, L, H, C, Dh)
            got = pool[blk, rows % bt]                   # (S, C, L*H*Dh)
            return got.reshape(s, ch, n_layer, c.n_head,
                               c.head_dim).transpose(0, 2, 3, 1, 4)

        kbar, vbar = eva_summaries(
            chunk(view.k), chunk(view.v), stacked["attn.mu"][None],
            stacked["attn.phi"][None], ch)               # (S, L, H, 1, Dh)
        closes = (page.pos + 1) % ch == 0
        crow = page.pos // ch
        cblk = jnp.take_along_axis(
            page.tables, (lay.window + crow // bt)[:, None], axis=1)[:, 0]
        dest = (jnp.where(closes, cblk, SCRATCH_BLOCK),
                jnp.where(closes, crow % bt, 0))
        out = _rest(view, kbar[:, :, :, 0], vbar[:, :, :, 0], (s,))
        return _set_rows(view, dest, *out)
