# Copyright 2026 tiny-deepspeed-tpu authors
# SPDX-License-Identifier: Apache-2.0

"""MiMo-V2-Flash: layers of two kinds, a leading dense layer, and
dropless experts of which this chip holds a share.

The published model (huggingface.co/XiaomiMiMo/MiMo-V2-Flash,
config.json, `model_type` "mimo_v2_flash"): 48 layers at hidden 4096, 64
query heads with q/k of 192 and v of 128.  `hybrid_layer_pattern` gives
each layer its attention: 1 a sliding window of 128 positions over 8 KV
heads, with a learned sink a query head in the softmax's denominator and
RoPE theta 1e4; 0 global causal attention over 4 KV heads, no sink, theta
5e6; rotary on the first 64 of the 192.  `moe_layer_freq` gives each its
MLP: 0 a dense SwiGLU of 16384 (layer 0 alone), 1 a mixture of 256
experts of width 2048, top-8 by sigmoid score plus a selection bias
(`noaux_tc`), gates normalised, no shared expert.  For layer l of
attention kind a and MLP kind m, s = 192^-1/2:

    h  = x / sqrt(mean(x^2) + eps) * g1
    q = h Wq -> 64 x 192 ; k = h Wk -> KVH_a x 192 ; v = h Wv -> KVH_a x 128
    q, k <- RoPE on the first 64 of the 192 (theta_a, halves paired)
    v <- 0.707 v                                   (attention_value_scale)
    s_nm = s q_n . k_m, query head i on KV head i // (64 / KVH_a)
    visible: m <= n (global) ; n - 128 < m <= n (window)
    p_nm = exp(s_nm) / (Z_i + sum_m' exp(s_nm')), Z_i = exp(b_i) in window
           layers (the sink), 0 in global ones
    x <- x + concat_i(sum_m p_nm v_m) Wo
    h2 = RMSNorm(x; g2)
    dense:    x <- x + (silu(h2 Wg) * h2 Wu) Wd
    experts:  r = sigmoid(h2 Wr) ; T = top-8 of (r + bias) ;
              w_e = r_e / sum_{e' in T} r_e'
              x <- x + sum_{e in T, held here} w_e (silu(h2 Wg_e) * h2 Wu_e) Wd_e

The residual stream, the norms' statistics, the scores, the softmax and
everything of the router are float32; matmul operands are in compute
dtype with float32 accumulation.  What config.json does not settle is
listed in benchmarks/configs/mimo-v2-flash.json under `assumed`; the
three multi-token-prediction layers are left out.

**The share.**  A deployment splits the experts over chips.  The expert
layer here (`moe_layer`) is TOLD which experts it holds, `held = (first,
count)`: it routes over all of the router's outputs, keeps the (token,
choice) pairs whose expert it holds, sorts them by expert, runs grouped
matrix products over its own experts and adds their part of the result;
what the absent experts would add is left out, and no token is dropped
(no capacity: a step of N tokens has at most top-k x N pairs, which is
the static shape).  The embedding and the head hold a slice of the
vocabulary (`vocab_size`).

**The caches.**  A global layer keeps K/V of every position; a window
layer keeps 128 rows as a ring (position n in row n % 128) for as long as
the slot lives.  They rest in two kinds of pool block of different width
(`MiMoLayout.kinds`), K wider than V in both.

**Layers in order, compile time not by depth.**  Parameters are stacked
by kind (attention: "g.*" global, "w.*" window; MLP: "dense.*", "moe.*"),
each kind in layer order.  `layer_plan` cuts the published order into runs
of like layers and folds a group of runs that repeats (5 window + 1
global, seven times over at 48 layers) into one scan over the repetitions:
the 48-layer stack traces five block bodies, the 7-layer cut four.
"""

from __future__ import annotations

import dataclasses
import itertools
import math
from typing import Any, Dict, NamedTuple

import jax
import jax.numpy as jnp

from ..ops.paged_attn_pallas import paged_attention, use_paged_kernel
from ..ops.rmsnorm import rmsnorm
from .llama import rope, rope_at

# hybrid_layer_pattern and moe_layer_freq as published
_PATTERN = (0, 1, 1, 1, 1, 0) + (1, 1, 1, 1, 1, 0) * 7
_MOE = (0,) + (1,) * 47


@dataclasses.dataclass(frozen=True)
class MiMoConfig:
    """Every size the model is built from.  `n_routed_experts` is the
    width of the router; `experts_first` and `experts_held` say which of
    its experts live here.  `layer_kinds` (0 global, 1 window) and
    `moe_layers` (0 dense, 1 experts) have one entry a layer."""

    block_size: int = 262144
    vocab_size: int = 152576
    n_layer: int = 48
    n_head: int = 64
    n_kv_head: int = 4
    swa_n_kv_head: int = 8
    n_embd: int = 4096
    head_dim: int = 192
    v_head_dim: int = 128
    rotary_dim: int = 64
    window: int = 128
    ffn_hidden: int = 16384
    moe_hidden: int = 2048
    n_routed_experts: int = 256
    n_experts_per_tok: int = 8
    experts_first: int = 0
    experts_held: int = 256
    layer_kinds: tuple = _PATTERN
    moe_layers: tuple = _MOE
    rope_theta: float = 5000000.0
    swa_rope_theta: float = 10000.0
    value_scale: float = 0.707
    rms_norm_eps: float = 1e-5
    init_std: float = 0.02
    param_dtype: Any = jnp.float32
    compute_dtype: Any = jnp.bfloat16
    cache_dtype: Any = None
    scan_unroll: Any = 1

    def kv_heads_of(self, kind: int) -> int:
        return self.swa_n_kv_head if kind else self.n_kv_head


_FULL = MiMoConfig()

MIMO_PRESETS: Dict[str, MiMoConfig] = {
    # for the record: 309 B parameters, never instantiated on one chip
    "mimo-v2-flash": _FULL,
    # one chip of sixteen that share each layer, one pipeline stage: the
    # leading dense layer and one whole period of 5 window : 1 global
    # (layers 0-6), experts 0-15 of 256, an eighth of the vocabulary;
    # every width as above (the benchmark's cut)
    "mimo-v2-flash-7l": dataclasses.replace(
        _FULL, n_layer=7, layer_kinds=_PATTERN[:7], moe_layers=_MOE[:7],
        experts_held=16, vocab_size=19072, block_size=16384),
    # the same pattern at CPU size: K wider than V, 1 and 2 KV heads
    "mimo-tiny": MiMoConfig(
        block_size=256, vocab_size=320, n_layer=7, n_head=4, n_kv_head=1,
        swa_n_kv_head=2, n_embd=64, head_dim=24, v_head_dim=16,
        rotary_dim=8, window=16, ffn_hidden=128, moe_hidden=32,
        n_routed_experts=16, n_experts_per_tok=4, experts_held=16,
        layer_kinds=_PATTERN[:7], moe_layers=_MOE[:7],
        compute_dtype=jnp.float32),
}


def _mm(x, w):
    """x @ w with the product left in float32: what a matmul of bf16
    operands accumulates in anyway (models/evabyte._mm says why)."""
    return jax.lax.dot_general(
        x, w, (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


def layer_plan(kinds, moe):
    """The published order as [(repetitions, [(attention kind, MLP kind,
    layers), ..]), ..]: maximal runs of like layers, and a pair of runs
    that comes several times in a row folded into one entry.  A folded
    pair has two attention kinds (like runs that touch are one run), so
    within a kind the layers of all repetitions stay in order when one
    repetition's are laid after another's."""
    runs = [(a, m, len(list(g)))
            for (a, m), g in itertools.groupby(zip(kinds, moe))]
    plan, i = [], 0
    while i < len(runs):
        pair, reps = runs[i:i + 2], 1
        while len(pair) == 2 and pair[0][0] != pair[1][0] and \
                runs[i + 2 * reps:i + 2 * reps + 2] == pair:
            reps += 1
        group = pair if reps > 1 else runs[i:i + 1]
        plan.append((reps, group))
        i += len(group) * reps
    return plan


# ---------------------------------------------------------------------------
# the expert layer
# ---------------------------------------------------------------------------

# tokens a pass of the expert layer takes: a prefill's (token, choice)
# pairs are gathered, multiplied and scattered back that many tokens at a
# time, so the temporaries of 8192 tokens x 8 choices never all exist
_MOE_TOKENS = 2048
# pairs up to which the combine is a product with the gates, not a gather
_COMBINE_BY_PRODUCT = 4096


def moe_route(h, router_w, router_b, top_k: int):
    """h (N, D) -> (choice (N, k) int32, gate (N, k) float32): the top-k
    of sigmoid scores plus the selection bias, which chooses and does not
    weigh; gates are the chosen scores over their sum.  All float32."""
    r = jax.nn.sigmoid(_mm(h, router_w))
    _, choice = jax.lax.top_k(r + router_b.astype(jnp.float32), top_k)
    gate = jnp.take_along_axis(r, choice, axis=1)
    return choice, gate / jnp.sum(gate, axis=-1, keepdims=True)


def moe_layer(h, router_w, router_b, wg, wu, wd, layer, *, top_k: int,
              held, valid=None):
    """The held experts' part of a mixture-of-experts layer, dropless.

    h (N, D) in compute dtype (the normed stream); router_w (D, E) and
    router_b (E,) over ALL E experts; wg, wu (L * C, D, F) and wd
    (L * C, F, D) the C = held[1] experts from held[0] on of every expert
    layer, layer-major; `layer` which of the L (traced or not): the
    layer's experts are C groups of L * C, the others get no row, so no
    weight is sliced out of the stack.  valid (N,) bool: rows that are
    tokens (a decode step's live slots, a prefill's prompt), the others
    routed nowhere.  -> ((N, D) float32, (2,) int32: the (token, choice)
    pairs computed here and the held experts that got a token)."""
    n, d = h.shape
    first, count = held
    scope = jax.named_scope
    with scope("tds.moe.router"):
        choice, gate = moe_route(h, router_w, router_b, top_k)
    with scope("tds.moe.dispatch"):
        local = choice - first
        mine = (local >= 0) & (local < count)
        if valid is not None:
            mine = mine & valid[:, None]
        # pairs of experts held elsewhere sort behind every group
        key = jnp.where(mine, local, count).reshape(-1)
        order = jnp.argsort(key)
        token = order // top_k
        sizes = jnp.bincount(key, length=count).astype(jnp.int32)
        at = jnp.asarray(layer, jnp.int32) * count

    def products(tokens, sizes):
        """The three grouped products for the rows of `tokens`, sorted
        by expert, `sizes` of them for each held expert."""
        groups = jax.lax.dynamic_update_slice(
            jnp.zeros((wg.shape[0],), jnp.int32), sizes, (at,))

        def grouped(x, w):
            return jax.lax.ragged_dot(x, w, groups,
                                      preferred_element_type=jnp.float32)

        rows = jnp.take(h, tokens, axis=0)
        act = (jax.nn.silu(grouped(rows, wg))
               * grouped(rows, wu)).astype(h.dtype)
        return grouped(act, wd)

    with scope("tds.moe.experts"):
        # one pass over all pairs: rows behind the last group cost the
        # grouped product next to nothing (passes over the held pairs
        # alone measured no faster, 27.8 against 28.0 ms a layer at 8192
        # tokens: what a prefill's expert layer costs is its sort, its
        # gathers and its passes over (pairs, D), PERF.md section 6)
        out = products(token, sizes)
    with scope("tds.moe.combine"):
        # rows behind the last group hold whatever they held: a pair held
        # elsewhere has gate 0 and takes none of them
        w = jnp.where(mine, gate, 0.0).reshape(-1)[order]
        out = jnp.where(w[:, None] > 0, out, 0.0)
        if n * top_k <= _COMBINE_BY_PRODUCT:
            # a decode step's few pairs: each token's gated sum as ONE
            # exact product with its gates laid out by pair (a gather of
            # 512 rows costs 0.14 ms a layer on a v5e, this 0.01)
            gates = jnp.where(token[None] == jnp.arange(n)[:, None],
                              w[None], 0.0)
            y = jnp.dot(gates, out, precision=jax.lax.Precision.HIGHEST)
        else:
            # each pair's row back to its (token, choice) place by the
            # sort's inverse, then the gated sum over a token's choices
            back = jnp.zeros_like(order).at[order].set(
                jnp.arange(order.shape[0], dtype=order.dtype))
            picked = jnp.take(out * w[:, None], back, axis=0)
            y = jnp.sum(picked.reshape(n, top_k, d), axis=1)
    return y, jnp.stack([jnp.sum(mine), jnp.sum(sizes > 0)]).astype(
        jnp.int32)


# ---------------------------------------------------------------------------
# attention over whole sequences (prefill, and the full forward)
# ---------------------------------------------------------------------------

# queries a block and keys a chunk of the global layers' softmax: a block
# of queries folds the chunks of keys up to its own into a running
# softmax, so neither T^2 scores nor the chunks past the diagonal exist.
# (All T keys a block in ONE softmax took 1.55 s a layer at 8192 on a v5e
# where 2048 took 2.3 ms: PERF.md section 6, PR 34.)
_QUERIES = 512
_KEYS = 1024
_MASKED = -1e30


def _grouped(q, kvh: int):
    b, h, t, d = q.shape
    return q.reshape(b, kvh, h // kvh, t, d)


def global_attention(q, k, v):
    """Causal attention, q (B, H, T, Dk), k (B, KVH, T, Dk), v (B, KVH,
    T, Dv) -> (B, H, T, Dv): blocks of queries, each against the chunks
    of keys up to its diagonal, scores and softmax float32.  Forward
    only: the chunk loop's length is the block's place."""
    b, h, t, dk = q.shape
    kvh, dv = k.shape[1], v.shape[-1]
    g = h // kvh
    kc = min(_KEYS, -(-t // 16) * 16)
    qb = min(_QUERIES, kc)
    pad = -t % kc
    widen = ((0, 0), (0, 0), (0, pad), (0, 0))
    k, v = jnp.pad(k, widen), jnp.pad(v, widen)
    qg = jnp.pad(_grouped(q, kvh), ((0, 0),) + widen)
    nq = (t + pad) // qb
    scale = 1.0 / math.sqrt(dk)

    def block(args):
        qq, start = args  # (B, KVH, G, qb, Dk), the block's first query
        rows = start + jnp.arange(qb)

        def chunk(j, carry):
            m, l, acc = carry
            kj = jax.lax.dynamic_slice_in_dim(k, j * kc, kc, axis=2)
            vj = jax.lax.dynamic_slice_in_dim(v, j * kc, kc, axis=2)
            s = jnp.einsum("bkgqd,bktd->bkgqt", qq, kj,
                           preferred_element_type=jnp.float32) * scale
            seen = (j * kc + jnp.arange(kc))[None] <= rows[:, None]
            s = jnp.where(seen, s, _MASKED)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new[..., None])
            return (m_new, l * alpha + jnp.sum(p, axis=-1),
                    acc * alpha[..., None] + jnp.einsum(
                        "bkgqt,bktd->bkgqd", p.astype(v.dtype), vj,
                        preferred_element_type=jnp.float32))

        lead = (b, kvh, g, qb)
        _, l, acc = jax.lax.fori_loop(
            0, (start + qb - 1) // kc + 1, chunk,
            (jnp.full(lead, _MASKED, jnp.float32),
             jnp.zeros(lead, jnp.float32),
             jnp.zeros(lead + (dv,), jnp.float32)))
        return acc / l[..., None]

    out = jax.lax.map(block, (
        jnp.moveaxis(qg.reshape(b, kvh, g, nq, qb, dk), 3, 0),
        jnp.arange(nq) * qb))
    out = jnp.moveaxis(out, 0, 3).reshape(b, h, t + pad, dv)
    return out[:, :, :t].astype(q.dtype)


def window_attention(q, k, v, sink, window: int):
    """Sliding-window attention with a sink: query n sees keys n - window
    < m <= n, and exp(sink[head]) joins its softmax's denominator with no
    value.  Banded: each block of `window` queries against its own block
    and the one before, never T^2.  Shapes as `global_attention`."""
    b, h, t, dk = q.shape
    kvh, dv, w = k.shape[1], v.shape[-1], window
    g = h // kvh
    pad = -t % w
    nb = (t + pad) // w

    def blocks(z):  # (B, KVH, T, D) -> (B, KVH, nb, w, D)
        z = jnp.pad(z, ((0, 0), (0, 0), (0, pad), (0, 0)))
        return z.reshape(b, kvh, nb, w, z.shape[-1])

    def with_previous(z):  # -> (B, KVH, nb, 2 w, D), block 0's a blank
        zb = blocks(z)
        before = jnp.pad(zb[:, :, :-1], ((0, 0), (0, 0), (1, 0), (0, 0),
                                         (0, 0)))
        return jnp.concatenate([before, zb], axis=3)

    qb = jnp.pad(_grouped(q, kvh), ((0, 0),) * 3 + ((0, pad), (0, 0))
                 ).reshape(b, kvh, g, nb, w, dk)
    k2, v2 = with_previous(k), with_previous(v)
    s = jnp.einsum("bkgnqd,bkncd->bkgnqc", qb, k2,
                   preferred_element_type=jnp.float32) / math.sqrt(dk)
    i = jnp.arange(w)[:, None]
    c = jnp.arange(2 * w)[None]
    seen = (c > i) & (c <= w + i)                    # (w, 2 w)
    first = (c >= w)[None] | (jnp.arange(nb) > 0)[:, None, None]
    s = jnp.where(seen[None] & first, s, _MASKED)    # (.., nb, w, 2 w)
    z = sink.astype(jnp.float32).reshape(kvh, g)[None, :, :, None, None,
                                                  None]
    m = jnp.maximum(jnp.max(s, axis=-1, keepdims=True), z)
    p = jnp.exp(s - m)
    p = p / (jnp.sum(p, axis=-1, keepdims=True) + jnp.exp(z - m))
    out = jnp.einsum("bkgnqc,bkncd->bkgnqd", p.astype(v.dtype), v2,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, h, t + pad, dv)[:, :, :t].astype(q.dtype)


# ---------------------------------------------------------------------------
# the slot layout
# ---------------------------------------------------------------------------


class MiMoLayout(NamedTuple):
    """What one slot holds in the paged pool: `table` blocks of the
    global layers' K/V, one per `block_tokens` positions as
    `serving/pool.DenseLayout`, then `ring` blocks of the window layers'
    K/V, a ring of `window` rows (position n in row n % window): taken
    whole at admission, never grown, freed with the slot.  The two lists
    draw from two kinds of block of different width (`kinds`: a global
    row is 2 layers x 4 heads at the published sizes, a ring row 5 x 8),
    so no layer keeps what it never reads.  A slot's block-table row is
    the two lists side by side: entries [0, table) and [table, table +
    ring).  The members are those of every slot layout."""

    table: int
    ring: int
    window: int
    block_tokens: int
    kinds: tuple = ()

    tables = (0, 1)
    span = "route"
    # what the decode program counts of its own step, behind its tokens
    fetched = ("pairs", "experts_touched")
    # a slot holds no more than its table and its ring, and nothing but
    # slots holds blocks
    bounds_pool = True
    refuses = {
        "prefix_cache": "be served with prefix_cache: the radix tree "
                        "shares blocks of K/V by token prefix, and a "
                        "window ring is overwritten in place, so no "
                        "prefix of it outlives its slot",
        "spec_draft": "be served with spec_draft: the verify program "
                      "scores a span per slot, and a ring row holds one "
                      "position that a rejected draft would have "
                      "overwritten",
        "quant": "be served with quant: a quantized pool keeps "
                 "per-vector scales, which the two kinds of block and "
                 "the ring's decode kernel do not carry",
        **{verb + "_request": verb + " a request's blocks: export_blocks "
           "/ import_blocks move one table of blocks of one kind, not a "
           "table and a ring of two" for verb in ("export", "import")},
    }

    @property
    def width(self) -> int:
        return self.table + self.ring

    def need(self, pos: int):
        """(global blocks, ring blocks) a slot owns before it writes
        position `pos`: the table up to the position's block, and the
        whole ring from the first."""
        return min(pos // self.block_tokens + 1, self.table), self.ring

    def prefill_panel(self, bucket: int):
        bt = self.block_tokens
        return bucket // bt, min(bucket, self.window) // bt

    def fill_row(self, row, table, ring) -> None:
        row[:len(table)] = table
        row[self.table:self.table + len(ring)] = ring

    def tick_counts(self, slots, max_active: int):
        """What the slots hold this tick, blocks by kind, and on the
        span the live slots and the rows the decode step will attend:
        every position before its own in a global layer, the window's
        other positions in a window layer."""
        counts = dict(global_blocks=sum(len(s.table) for s in slots),
                      window_blocks=sum(len(s.summary) for s in slots))
        return counts, dict(
            active=len(slots),
            rows_global=sum(s.pos for s in slots),
            rows_window=sum(min(s.pos, self.window - 1) for s in slots),
            **counts)


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------

_ATTN = ("g", "w")          # parameter prefix by attention kind
_MLP = ("dense", "moe")     # and by MLP kind


class MiMoModel:
    """init / apply as every family; paged_prefill / paged_decode through
    a table of global blocks and a ring of window blocks."""

    paged_decode_capable = True

    def __init__(self, config: MiMoConfig):
        c = config
        if len(c.layer_kinds) != c.n_layer or len(c.moe_layers) != c.n_layer:
            raise ValueError(
                f"layer_kinds and moe_layers need one entry for each of "
                f"the {c.n_layer} layers, got {len(c.layer_kinds)} and "
                f"{len(c.moe_layers)}")
        if not (0 <= c.experts_first
                and c.experts_first + c.experts_held <= c.n_routed_experts):
            raise ValueError(
                f"held experts [{c.experts_first}, {c.experts_first} + "
                f"{c.experts_held}) are not among the router's "
                f"{c.n_routed_experts}")
        if c.rotary_dim % 2 or c.rotary_dim > c.head_dim:
            raise ValueError("rotary_dim must be even and at most head_dim")
        self.config = c
        self.plan = layer_plan(c.layer_kinds, c.moe_layers)

    # -- params ------------------------------------------------------------

    def init(self, key) -> Dict[str, jax.Array]:
        """Random weights: N(0, init_std) matrices, norms one, sinks
        N(ln window, 1) (about as heavy as the window's keys together at
        score 0, so a dropped sink shows), selection biases N(0, 0.01):
        the top 8 of 256 sigmoid scores lie within 0.07 of each other, so
        a bias of 0.1 would choose by itself, the same few experts for
        every token (36 of 96 held experts touched by 64 tokens where
        even routing touches 83), which the bias of a trained router is
        there to prevent."""
        c = self.config
        d, h, dk, dv = c.n_embd, c.n_head, c.head_dim, c.v_head_dim
        keys = iter(jax.random.split(key, 24))

        def nrm(shape, s=c.init_std, mean=0.0):
            z = jax.random.normal(next(keys), shape, jnp.float32) * s + mean
            return z.astype(c.param_dtype)

        out = {"wte": nrm((c.vocab_size, d)),
               "ln_f.w": jnp.ones((d,), c.param_dtype),
               "lm_head.w": nrm((d, c.vocab_size))}
        for kind, a in enumerate(_ATTN):
            n = sum(k == kind for k in c.layer_kinds)
            if not n:
                continue
            kvh = c.kv_heads_of(kind)
            out.update({
                a + ".ln_1.w": jnp.ones((n, d), c.param_dtype),
                a + ".attn.q.w": nrm((n, d, h * dk)),
                a + ".attn.k.w": nrm((n, d, kvh * dk)),
                a + ".attn.v.w": nrm((n, d, kvh * dv)),
                a + ".attn.o.w": nrm((n, h * dv, d))})
            if kind:
                out["w.attn.sink"] = nrm((n, h), 1.0, math.log(c.window))
        n = sum(m == 0 for m in c.moe_layers)
        if n:
            f = c.ffn_hidden
            out.update({
                "dense.ln_2.w": jnp.ones((n, d), c.param_dtype),
                "dense.mlp.gate.w": nrm((n, d, f)),
                "dense.mlp.up.w": nrm((n, d, f)),
                "dense.mlp.down.w": nrm((n, f, d))})
        n = sum(m == 1 for m in c.moe_layers)
        if n:
            f, e, held = c.moe_hidden, c.n_routed_experts, c.experts_held
            out.update({
                "moe.ln_2.w": jnp.ones((n, d), c.param_dtype),
                "moe.router.w": nrm((n, d, e)),
                "moe.router.bias": nrm((n, e), 0.01),
                # the held experts of every expert layer, layer-major
                "moe.experts.gate.w": nrm((n * held, d, f)),
                "moe.experts.up.w": nrm((n * held, d, f)),
                "moe.experts.down.w": nrm((n * held, f, d))})
        return out

    def stacked_compute_params(self, params):
        """The layers' tensors by kind, in compute dtype (one that rests
        in it already is handed on as it is)."""
        cd = self.config.compute_dtype
        return {k: v.astype(cd) for k, v in params.items()
                if k.split(".")[0] in _ATTN + _MLP}

    # -- pieces of a layer ---------------------------------------------------

    def _norm(self, x, g):
        c = self.config
        return rmsnorm(x, g.astype(jnp.float32),
                       c.rms_norm_eps).astype(c.compute_dtype)

    def _qkv(self, h, ap, kind: int, rot, positions):
        """(B, T, D) -> q (B, H, T, Dk), k (B, KVH, T, Dk), v (B, KVH, T,
        Dv); q and k rotated on their first `rotary_dim` numbers by `rot`
        (`rope` at positions (T,), `rope_at` at (B,) with T == 1), v
        scaled; float32 until then, one rounding."""
        c = self.config
        b, t, _ = h.shape
        cd, rd = c.compute_dtype, c.rotary_dim
        theta = c.swa_rope_theta if kind else c.rope_theta

        def heads(name, n, width):
            # the barrier keeps the compiler from carrying the rotary /
            # plain split below back through the product into the weight,
            # which it then slices and lays out anew every step (100 MB a
            # layer for q at the published widths)
            z = jax.lax.optimization_barrier(_mm(h, ap[name]))
            return z.reshape(b, t, n, width).swapaxes(1, 2)

        def rotated(z):
            return jnp.concatenate(
                [rot(z[..., :rd], positions, theta), z[..., rd:]],
                axis=-1).astype(cd)

        kvh = c.kv_heads_of(kind)
        return (rotated(heads("attn.q.w", c.n_head, c.head_dim)),
                rotated(heads("attn.k.w", kvh, c.head_dim)),
                (heads("attn.v.w", kvh, c.v_head_dim)
                 * c.value_scale).astype(cd))

    def _mlp(self, x, mp, experts, m: int, lm, valid):
        """The layer's second half on the float32 stream x (.., D); mp
        the layer's own tensors, `experts` every expert layer's held
        experts, of which `lm` says the layer.  -> (x, the expert
        layer's two counts)."""
        c = self.config
        h = self._norm(x, mp["ln_2.w"])
        if m == 0:
            with jax.named_scope("tds.mlp"):
                act = (jax.nn.silu(_mm(h, mp["mlp.gate.w"]))
                       * _mm(h, mp["mlp.up.w"])).astype(c.compute_dtype)
                return (x + _mm(act, mp["mlp.down.w"]),
                        jnp.zeros((2,), jnp.int32))
        rows = h.reshape(-1, h.shape[-1])
        kw = dict(top_k=c.n_experts_per_tok,
                  held=(c.experts_first, c.experts_held))
        weights = (mp["router.w"], mp["router.bias"],
                   experts["gate.w"], experts["up.w"], experts["down.w"],
                   lm)
        with jax.named_scope("tds.moe"):
            n = rows.shape[0]
            if n <= _MOE_TOKENS or n % _MOE_TOKENS:
                y, counts = moe_layer(rows, *weights, valid=valid, **kw)
            else:
                # a long prefill, a pass of _MOE_TOKENS tokens at a time
                ok = (jnp.ones((n,), bool) if valid is None else valid)
                y, counts = jax.lax.map(
                    lambda a: moe_layer(a[0], *weights, valid=a[1], **kw),
                    (rows.reshape(-1, _MOE_TOKENS, rows.shape[-1]),
                     ok.reshape(-1, _MOE_TOKENS)))
                y, counts = y.reshape(n, -1), jnp.sum(counts, axis=0)
            return x + y.reshape(x.shape), counts

    def _block(self, x, ap, mp, experts, a: int, m: int, lm, attend,
               valid):
        """One layer on x (B, T, D) float32.  attend(a, h, ap) -> (the
        heads' results (B, T, H * Dv) in compute dtype, what the caller
        keeps of the layer's K/V)."""
        scope = jax.named_scope
        with scope("tds.block"):
            with scope("tds.ln"):
                h = self._norm(x, ap["ln_1.w"])
            y, kept = attend(a, h, ap)
            with scope("tds.attn.proj"):
                x = x + _mm(y, ap["attn.o.w"])
            x, counts = self._mlp(x, mp, experts, m, lm, valid)
        return x, kept, counts

    def _layers(self, stacked, x, attend, valid=None):
        """Every layer in published order (`self.plan`).  attend(a, h, ap,
        la) is handed the layer's place `la` among those of its attention
        kind.  -> (x, {attention kind: what attend kept, stacked over the
        kind's layers in order}, the expert layers' counts summed)."""
        c = self.config
        by_kind = {name: {k[len(name) + 1:]: v for k, v in stacked.items()
                          if k.startswith(name + ".")
                          and not k.startswith("moe.experts.")}
                   for name in _ATTN + _MLP}
        # the experts' stacks are indexed by group, never sliced
        experts = {k[len("moe.experts."):]: v for k, v in stacked.items()
                   if k.startswith("moe.experts.")}

        def at(tree, i):
            return jax.tree.map(lambda t: jax.lax.dynamic_index_in_dim(
                t, i, 0, keepdims=False), tree)

        def run(x, counts, a, m, n, la0, lm0):
            """n like layers from places la0, lm0 of their kinds on."""
            def body(carry, i):
                x, counts = carry
                la, lm = la0 + i, lm0 + i
                x, kept, cnt = self._block(
                    x, at(by_kind[_ATTN[a]], la), at(by_kind[_MLP[m]], lm),
                    experts, a, m, lm,
                    lambda a, h, ap: attend(a, h, ap, la), valid)
                return (x, counts + cnt), kept

            if n == 1:
                (x, counts), kept = body((x, counts), 0)
                return x, counts, jax.tree.map(lambda t: t[None], kept)
            (x, counts), kept = jax.lax.scan(
                body, (x, counts), jnp.arange(n), unroll=c.scan_unroll)
            return x, counts, kept

        seen = {name: 0 for name in _ATTN + _MLP}
        kept = {0: [], 1: []}
        counts = jnp.zeros((2,), jnp.int32)
        with jax.named_scope("tds.blocks"):
            for reps, group in self.plan:
                # a run's first layer in repetition r, among its kinds:
                # start + r * (the group's layers of that kind)
                starts, stride = [], {name: 0 for name in seen}
                for a, m, n in group:
                    starts.append((seen[_ATTN[a]] + stride[_ATTN[a]],
                                   seen[_MLP[m]] + stride[_MLP[m]]))
                    stride[_ATTN[a]] += n
                    stride[_MLP[m]] += n
                for name, n in stride.items():
                    seen[name] += n * reps

                def once(carry, r, group=group, starts=starts,
                         stride=stride):
                    x, counts = carry
                    out = []
                    for (a, m, n), (la0, lm0) in zip(group, starts):
                        x, counts, k = run(
                            x, counts, a, m, n,
                            la0 + r * stride[_ATTN[a]],
                            lm0 + r * stride[_MLP[m]])
                        out.append(k)
                    return (x, counts), out

                if reps == 1:
                    (x, counts), out = once((x, counts), 0)
                else:
                    (x, counts), out = jax.lax.scan(
                        once, (x, counts), jnp.arange(reps))
                    # (reps, n, ..) -> (reps * n, ..): a kind has one run
                    # in a folded group, so its layers stay in order
                    out = [jax.tree.map(lambda t: t.reshape(
                        (-1,) + t.shape[2:]), k) for k in out]
                for (a, _, _), k in zip(group, out):
                    kept[a].append(k)
        return x, {a: jax.tree.map(lambda *t: jnp.concatenate(t), *ks)
                   for a, ks in kept.items() if ks}, counts

    # -- forward -------------------------------------------------------------

    def embed(self, params, idx):
        """The residual stream is float32 from the embedding on."""
        if idx.shape[1] > self.config.block_size:
            raise ValueError(f"sequence length {idx.shape[1]} > block_size "
                             f"{self.config.block_size}")
        with jax.named_scope("tds.embed"):
            return jnp.take(params["wte"], idx, axis=0).astype(jnp.float32)

    def _embed_decode(self, params, tok, pos):
        del pos  # enters through RoPE
        return self.embed(params, tok[:, None])

    def _attend_sequence(self, keep):
        """attend() over whole sequences at positions 0..T-1; `keep(a, k,
        v)` picks what the caller keeps of a layer's K/V."""
        c = self.config
        scope = jax.named_scope

        def attend(a, h, ap, la):
            b, t, _ = h.shape
            with scope("tds.attn.qkv"):
                q, k, v = self._qkv(h, ap, a, rope, jnp.arange(t))
            with scope("tds.attn.kernel"):
                y = (window_attention(q, k, v, ap["attn.sink"], c.window)
                     if a else global_attention(q, k, v))
            return y.swapaxes(1, 2).reshape(b, t, -1), keep(a, k, v)

        return attend

    def hidden(self, params, idx, stacked=None, keep=None, valid=None):
        """idx (B, T) -> the stream after the last layer (B, T, D)
        float32, what `keep` kept by attention kind, the routed counts."""
        if stacked is None:
            stacked = self.stacked_compute_params(params)
        return self._layers(
            stacked, self.embed(params, idx),
            self._attend_sequence(keep or (lambda a, k, v: ())), valid)

    @jax.named_scope("tds.head")
    def head(self, params, x, targets=None, position=None):
        """Final norm and the head over the held slice of the vocabulary,
        logits float32.  With targets: the mean cross-entropy.  Without:
        (B, 1, V) at `position` (default the last)."""
        def logits_of(z):
            return _mm(self._norm(z, params["ln_f.w"]),
                       params["lm_head.w"].astype(self.config.compute_dtype))

        if targets is None:
            if position is None:
                x = x[:, -1:]
            else:
                x = jax.lax.dynamic_slice_in_dim(x, position, 1, axis=1)
            return logits_of(x)
        logp = jax.nn.log_softmax(logits_of(x), axis=-1)
        return -jnp.mean(jnp.take_along_axis(logp, targets[..., None],
                                             axis=-1))

    def apply(self, params, idx, targets=None, position=None):
        """Full forward: the loss with targets, else the logits at
        `position` (default the last), as every family's `apply`."""
        x, _, _ = self.hidden(params, idx)
        return self.head(params, x, targets, position=position)

    def logits(self, params, idx):
        """(B, T) -> (B, T, V): every position's logits."""
        x, _, _ = self.hidden(params, idx)
        return _mm(self._norm(x, params["ln_f.w"]),
                   params["lm_head.w"].astype(self.config.compute_dtype))

    def sampling_logits(self, logits):
        return logits

    # -- the paged pool ------------------------------------------------------

    def paged_layout(self, max_seq: int, block_tokens: int) -> MiMoLayout:
        from ..serving.pool import BlockKind
        c, bt = self.config, block_tokens
        if c.window % bt:
            raise ValueError(
                f"block_tokens={bt} must divide window={c.window}: the "
                "window's blocks are a ring")
        table, ring = -(-max_seq // bt), c.window // bt
        kinds = tuple(
            BlockKind(sum(k == kind for k in c.layer_kinds),
                      c.kv_heads_of(kind), c.head_dim, c.v_head_dim, blocks)
            for kind, blocks in ((0, table), (1, ring)))
        return MiMoLayout(table, ring, c.window, bt, kinds)

    def paged_page_ref(self, tables, pos, block_tokens: int):
        """The decode step's write coordinates in the global table
        (position n in entry n // block_tokens); `paged_decode` derives
        the ring's from them."""
        from ..serving.pool import page_ref
        ring = self.config.window // block_tokens
        page = page_ref(tables[:, :tables.shape[1] - ring], pos,
                        block_tokens)
        return page._replace(tables=tables)

    @staticmethod
    def _ring_ids(ids, view):
        """Block ids of the second kind as that kind's arrays index them:
        the pool numbers its kinds one after another (serving/pool.
        PagedKVPool), and 0 stays scratch."""
        return jnp.maximum(ids - (view[0].k.shape[0] - 1), 0)

    def paged_prefill(self, params, idx, last_pos, block_ids, view,
                      block_tokens: int, stacked=None):
        """One request's prompt, idx (1, P) padded to its bucket, into
        the pool: the global layers' K/V of every position into the
        table's blocks, and of each window layer the last min(P, window)
        positions up to `last_pos` into the ring, position m in row m %
        window.  `block_ids` is the two panels side by side
        (`MiMoLayout.prefill_panel`).  Rows past the prompt hold padding,
        which every read masks by the slot's length."""
        from ..serving.pool import paged_scatter
        c, bt = self.config, block_tokens
        p = idx.shape[1]
        wp = min(c.window, p)
        if p % bt or wp % bt:
            raise ValueError(f"prefill bucket {p} is no whole number of "
                             f"blocks ({bt})")
        ng = p // bt
        # ring row r holds the last position <= last_pos that is r mod wp
        r = jnp.arange(wp)
        newest = jnp.maximum(last_pos - (last_pos - r) % wp, 0)

        def keep(a, k, v):
            if a == 0:
                return k, v
            return jnp.take(k, newest, axis=2), jnp.take(v, newest, axis=2)

        x, kept, _ = self.hidden(params, idx, stacked, keep,
                                 valid=jnp.arange(p) <= last_pos)
        vg, vw = view
        with jax.named_scope("tds.kv_write"):
            vg = paged_scatter(vg, *kept[0], block_ids[:ng], bt)
        with jax.named_scope("tds.attn.window"):
            vw = paged_scatter(vw, *kept[1],
                               self._ring_ids(block_ids[ng:], view), bt)
        return self.head(params, x, position=last_pos)[:, 0], (vg, vw)

    def paged_decode(self, stacked, x, view, page):
        """One token per slot, x (S, 1, D) float32 at positions page.pos.
        A global layer attends its slot's table up to the position, a
        window layer the ring's live rows but the one this position will
        overwrite, with the sink; both with the token itself.  Then ONE
        write of the token's K/V of all global layers into the table and
        one of all window layers into ring row n % window.  Slots that
        hold no request (position 0) are routed to no expert.  -> (x,
        view, the expert layers' two counts)."""
        from ..serving.pool import PageRef, paged_append
        c = self.config
        s = x.shape[0]
        vg, vw = view
        bt = vg.k.shape[1]
        nt = page.tables.shape[1] - c.window // bt
        ring = self._ring_ids(page.tables[:, nt:], view)
        row = page.pos % c.window
        pages = (page._replace(tables=page.tables[:, :nt]),
                 PageRef(ring, jnp.take_along_axis(
                     ring, (row // bt)[:, None], axis=1)[:, 0],
                     off=row % bt, pos=page.pos))
        scope = jax.named_scope

        def attend(a, h, ap, la):
            with scope("tds.attn.qkv"):
                q, k, v = self._qkv(h, ap, a, rope_at, page.pos)
            with scope("tds.attn.kernel"):
                y = _paged_attend(
                    q, view[a], pages[a], la, (k, v),
                    kv_heads=c.kv_heads_of(a), ring=c.window if a else 0,
                    sink=ap["attn.sink"] if a else None)
            return (y.swapaxes(1, 2).reshape(s, 1, -1),
                    (k[:, :, 0], v[:, :, 0]))

        x, kept, counts = self._layers(stacked, x, attend,
                                       valid=page.pos > 0)
        with scope("tds.kv_write"):
            vg = paged_append(vg, *kept[0], pages[0])
        with scope("tds.attn.window"):
            vw = paged_append(vw, *kept[1], pages[1])
        return x, (vg, vw), counts

    def paged_verify(self, stacked, x, view, page):
        raise NotImplementedError(
            "MiMoModel.paged_verify: a span of more than one position per "
            "slot (speculation, suffix prefill) is not wired through the "
            "window ring")

    def generate(self, *a, **kw):
        raise NotImplementedError(
            "MiMoModel.generate: the contiguous decode cache keeps one "
            "K/V shape for all layers; this family keeps two kinds, which "
            "only the paged pool holds: serve it through "
            "serving.ServingEngine")


def _paged_attend(q, view, page, l, self_kv, *, kv_heads: int, ring: int,
                  sink):
    """A decode step's attention through the block table: the Pallas
    kernel (ops/paged_attn_pallas.py) where it runs, else the same sum in
    XLA over a gathered panel.  Shapes as `paged_attention`, K1 == 1."""
    if use_paged_kernel():
        return paged_attention(q, view, page, l, self_kv,
                               kv_heads=kv_heads, ring=ring, sink=sink)
    from ..serving.pool import _get_columns
    k, v = self_kv
    s, hq, _, dk = q.shape
    dv = v.shape[-1]
    g = hq // kv_heads

    def panel(pool, width):  # -> (S, KVH, rows, width)
        got = _get_columns(pool, page.tables, l * (kv_heads * width),
                           kv_heads * width)
        return got.reshape(s, -1, kv_heads, width).swapaxes(1, 2)

    kp = jnp.concatenate([panel(view.k, dk), k.astype(view.k.dtype)], axis=2)
    vp = jnp.concatenate([panel(view.v, dv), v.astype(view.v.dtype)], axis=2)
    rows = kp.shape[2] - 1
    at = jnp.arange(rows + 1)[None]
    pos = page.pos[:, None]
    if ring:
        live = (at < jnp.minimum(pos, ring)) & (at != pos % ring)
    else:
        live = at < pos
    live = live | (at == rows)  # the token itself
    qg = q.reshape(s, kv_heads, g, dk)
    sc = jnp.einsum("skgd,sktd->skgt", qg, kp,
                    preferred_element_type=jnp.float32) / math.sqrt(dk)
    sc = jnp.where(live[:, None, None], sc, _MASKED)
    m = jnp.max(sc, axis=-1, keepdims=True)
    p = jnp.exp(sc - m)
    denom = jnp.sum(p, axis=-1, keepdims=True)
    if sink is not None:
        denom = denom + jnp.exp(sink.astype(jnp.float32).reshape(
            1, kv_heads, g, 1) - m)
    out = jnp.einsum("skgt,sktd->skgd", (p / denom).astype(vp.dtype), vp,
                     preferred_element_type=jnp.float32)
    return out.reshape(s, hq, 1, dv).astype(q.dtype)
