# Copyright 2026 tiny-deepspeed-tpu authors
# SPDX-License-Identifier: Apache-2.0

"""Pallas paged-attention decode kernel: fused block-table gather + attention.

The XLA paged decode path (serving/pool.paged_panel + the models'
`_span_attention`) MATERIALIZES each slot's K/V panel every token: the
block-table gather writes an (S, KVH, W*bt, Dh) pair to HBM, attention
reads it back, and on a quantized pool a third dequantized copy joins
them — PROFILE.md "Decode under load" measures exactly this gather as
the decode step's dominant non-matmul cost.  This kernel reads the pool
blocks DIRECTLY: the block table rides the grid's scalar prefetch, each
grid step DMAs one physical (block, layer) into VMEM, dequantizes
int8/fp8 resting blocks in-register against their per-vector scales,
and folds the block into a flash-style online softmax — the panel never
exists in HBM.

The pool rests as (blocks, bt, L * KVH * Dh) (serving/pool.py) and the
kernel takes it as it rests: a (block, layer) is the (bt, KVH * Dh)
window at column l * KVH * Dh, for GPT-2's 12 heads of 64 in bf16 six
whole (16, 128) tiles in a row, 24 KiB.  No head is sliced out of it and
nothing is relaid: the queries arrive BLOCK-DIAGONAL over the merged
dimension (query row (h, g, t) carries its Dh numbers in head h's
columns and zeros elsewhere), so one (R, C) x (C, bt) product gives the
scores of every head, one (R, bt) x (bt, C) product their weighted V
rows, and the caller reads each row's own head out of the C columns.
The MXU multiplies KVH times more zeros than numbers; at decode's one
row a head that is nothing, and a long span is cut into row tiles of at
most 256 rows so the accumulator stays small.

One entry point, `paged_attention(q, view, page, l, (sk, sv))`: q holds
a SPAN of K1 positions per slot — one on the plain decode step, k+1 on
a speculative verify, a bucket on a suffix prefill — the pool
contributes the COMMITTED prefix (positions < page.pos) and the span's
own K/V, not yet in the pool, enter as one extra grid step under the
windowed causal mask.  The caller commits the span afterwards
(serving/pool.paged_append, paged_append_span).

Grid: (S, row tiles, ceil(W / nb) + 1) — slots and row tiles parallel,
table entries sequential, nb of them a step (a step costs about a
third of a microsecond whatever it brings, so it brings 256 tokens),
with VMEM softmax stats (m, l, acc) carried across the steps and reset
at j=0
(the bundled TPU flash kernels' accumulation discipline).  Unused table
entries point at the scratch block; their positions fall outside the
mask, so the extra DMAs are dead weight but never dead wrong.

Numerics: scores, softmax stats and accumulation are float32 (like the
XLA reference); the output casts back to the query's dtype.  The online
softmax re-associates the sum, so results match the reference to float
tolerance, not bit-for-bit — the serving pins assert greedy TOKEN
identity through a real engine trace (tests/test_paged_kernel.py), the
same contract the quantized-pool and spec paths already carry.

Dispatch: `use_paged_kernel()` — module mode ("auto" | "on" | "off",
`ServeConfig.paged_kernel` wires it per engine) composed with the
standard trace-time `kernel_target()` gate.  "auto" runs the kernel on
TPU targets only; tests force "on" with INTERPRET=True on the CPU mesh
like every other kernel here.
"""

from __future__ import annotations

import functools
import math
import threading
from contextlib import contextmanager

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

INTERPRET = False  # tests flip this on CPU (no Mosaic backend there)

PAGED_KERNEL_MODES = ("auto", "on", "off")
_MODE = "auto"
# serializes forced-mode windows: _MODE is a module global, and a
# FleetRouter(parallel=True) ticking two engines whose configs force
# DIFFERENT modes would otherwise race their lazy jit traces (engine
# "off" tracing while a sibling's wrapper holds "on").  Forced modes
# are A/B and test vehicles, so serializing their calls is the right
# trade; "auto" engines never enter the lock.  Reentrant: a forced
# window may nest (engine program + spec verify in one tick path).
_MODE_LOCK = threading.RLock()

# scores at masked positions: finite (not -inf) so a fully-masked block
# cannot poison the online-softmax stats with NaN; exp(-1e30 - m)
# underflows to exactly 0 against any live row max
_MASKED = -1e30


def set_paged_kernel(mode: str) -> None:
    """Pin the paged-attention dispatch for subsequent traces: "on"
    (always the Pallas kernel), "off" (always the XLA reference path),
    or "auto" (kernel on TPU kernel targets only)."""
    global _MODE
    if mode not in PAGED_KERNEL_MODES:
        raise ValueError(
            f"paged_kernel must be one of {PAGED_KERNEL_MODES}, got {mode!r}"
        )
    _MODE = mode


def paged_kernel_mode() -> str:
    return _MODE


@contextmanager
def paged_kernel_forced(mode: str):
    """Scoped set_paged_kernel — the serving engine brackets its program
    CALLS with this so per-engine `ServeConfig.paged_kernel` choices
    never leak into sibling engines' traces.  Holds _MODE_LOCK for the
    window: concurrent forced windows (parallel fleet ticks) serialize
    instead of clobbering each other's trace-time gate."""
    with _MODE_LOCK:
        prev = _MODE
        set_paged_kernel(mode)
        try:
            yield
        finally:
            set_paged_kernel(prev)


def use_paged_kernel() -> bool:
    """Trace-time gate consulted by the models' paged attention sites."""
    if _MODE == "on":
        return True
    if _MODE == "off":
        return False
    from .dispatch import in_gspmd_auto_region, kernel_target
    # Mosaic custom calls cannot be auto-partitioned by GSPMD (see
    # ops/dispatch.py) — the serving engines run single-device today,
    # but the gate stays honest if one ever traces inside that region
    return kernel_target() == "tpu" and not in_gspmd_auto_region()


def effective_paged_kernel() -> str:
    """What the gate would dispatch RIGHT NOW: "pallas" | "xla" — the
    bench records stamp this so a measurement can never claim a kernel
    arm that fell back."""
    return "pallas" if use_paged_kernel() else "xla"


# ---------------------------------------------------------------------------
# kernel
# ---------------------------------------------------------------------------


def _paged_attn_kernel(
    # scalar prefetch
    tables_ref, pos_ref, l_ref,
    # inputs (the scales and their selector on a quantized pool only)
    *refs,
    bt: int, nb: int, tk: int, quant: bool, scale: float,
):
    """One (slot, row tile, table entries) grid step: fold `nb` pool
    blocks — or, on the final step, the span's own K/V — into the
    online softmax of the tile's R query rows.  Everything is
    two-dimensional with the pool's merged minor dimension C = KVH * Dh
    in the lanes: the queries come block-diagonal (row (h, g, t) holds its Dh numbers
    in head h's columns, zeros elsewhere), so ONE q @ k^T over C gives
    every head's scores and no head is ever sliced out of a block; the
    accumulator keeps all C columns per row and the caller reads the
    row's own head back out.  Scratch (acc, m, ll) persists across the
    sequential j dimension and resets at j == 0."""
    q_ref = refs[0]
    k_refs, v_refs = refs[1:1 + nb], refs[1 + nb:1 + 2 * nb]
    i = 1 + 2 * nb
    if quant:
        ks_refs, vs_refs = refs[i:i + nb], refs[i + nb:i + 2 * nb]
        sel_ref = refs[i + 2 * nb]
        i += 2 * nb + 1
    sk_ref, sv_ref, o_ref, acc, m, ll = refs[i:i + 6]

    s = pl.program_id(0)
    t = pl.program_id(1)
    j = pl.program_id(2)
    nj = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        acc[...] = jnp.zeros(acc.shape, jnp.float32)
        m[...] = jnp.full(m.shape, _MASKED, jnp.float32)
        ll[...] = jnp.zeros(ll.shape, jnp.float32)

    q = q_ref[0, 0].astype(jnp.float32) * scale  # (R, C)
    limit = pos_ref[s]

    def dot_nt(a, b, **kw):  # a @ b^T over the lanes of both
        return jax.lax.dot_general(
            a, b, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32, **kw)

    def fold(scores, vblk, vscale=None):
        """Online-softmax update: scores (R, T'), vblk (T', C), both
        f32; vscale (R, T') dequantizes V's rows on the way in."""
        m_cur = jnp.max(scores, axis=-1, keepdims=True)
        m_new = jnp.maximum(m[...], m_cur)
        alpha = jnp.exp(m[...] - m_new)
        p = jnp.exp(scores - m_new)
        ll[...] = ll[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        if vscale is not None:
            p = p * vscale
        acc[...] = acc[...] * alpha + jnp.dot(
            p, vblk, preferred_element_type=jnp.float32)
        m[...] = m_new

    def rows(block_refs):  # the step's nb blocks, one under the other
        return jnp.concatenate([r[0] for r in block_refs], axis=0)

    @pl.when(j < nj - 1)
    def _pool_blocks():
        scores = dot_nt(q, rows(k_refs).astype(jnp.float32))  # (R, nb * bt)
        vscale = None
        if quant:
            # a row's head picks its scale column: the one-hot `sel`
            # (R, L * KVH) is made by the caller for this layer, and
            # the exact (fp32) product with it is a gather
            hi = dict(precision=jax.lax.Precision.HIGHEST)
            scores = scores * dot_nt(sel_ref[...], rows(ks_refs), **hi)
            vscale = dot_nt(sel_ref[...], rows(vs_refs), **hi)
        # a table entry past the last (w no multiple of nb) repeats the
        # last block at positions no slot reaches: masked like the rest
        tpos = j * (nb * bt) + jax.lax.broadcasted_iota(
            jnp.int32, scores.shape, 1)
        fold(jnp.where(tpos < limit, scores, _MASKED),
             rows(v_refs).astype(jnp.float32), vscale)

    @pl.when(j == nj - 1)
    def _span_and_emit():
        scores = dot_nt(q, sk_ref[0].astype(jnp.float32))  # (R, K1)
        qoff = t * tk + jax.lax.broadcasted_iota(
            jnp.int32, scores.shape, 0) % tk
        koff = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
        fold(jnp.where(koff <= qoff, scores, _MASKED),
             sv_ref[0].astype(jnp.float32))
        o_ref[0, 0] = (acc[...] / ll[...]).astype(o_ref.dtype)


# query rows a grid step folds at most: the accumulator is (rows, C) f32
# in VMEM, and every row pays for all C columns on the MXU
_MAX_ROWS = 256
# pool tokens a grid step folds.  A step costs 0.36 us whatever it brings
# and the arithmetic of a decode tick 4.9 ms (v5e, gpt2-124m, 64 slots x
# 64 table entries x 12 layers, PERF.md section 6, PR 28): one 16-token
# block a step is 22.4 ms of kernel a tick, 256 tokens a step 6.0
_STEP_TOKENS = 256


def paged_attention(q, view, page, l, span_kv, *, kv_heads: int):
    """Fused block-table-gather attention over the paged pool.

    q: (S, Hq, K1, Dh) span queries (K1 == 1 on the plain decode step);
    view: serving.pool.KVPoolView in its resting shape (blocks, bt,
    L * KVH * Dh) (int8/fp8 pools dequantize in-kernel against
    view.k_scale/v_scale); page: serving.pool.PageRef; l: the layer
    index (traced — it rides the layer scan's carry); span_kv =
    (sk, sv), each (S, KVH, K1, Dh): the span's own K/V; kv_heads: the
    model's static KV head count, which the merged minor dimension no
    longer shows.  A query sees pool positions < page.pos plus the span
    itself under the windowed causal mask (the exact mask of models'
    `_span_attention`).  Returns (S, Hq, K1, Dh) in q's dtype.

    The pool arrays are handed to the kernel as they rest; what is
    reshaped to meet them is small: the queries (block-diagonal over
    the heads, see the kernel), the span's K/V and the result."""
    s, hq, k1, dh = q.shape
    kvh = kv_heads
    g = hq // kvh
    c = kvh * dh
    bt = view.k.shape[1]
    w = page.tables.shape[1]
    quant = view.k_scale is not None
    nb = max(1, min(w, _STEP_TOKENS // bt))  # table entries a step
    npool = -(-w // nb)  # steps over the table; the span's is one more
    # span offsets per row tile: all of them while the rows fit, else
    # halved (suffix-prefill buckets are powers of two)
    tk = k1
    while kvh * g * tk > _MAX_ROWS and tk % 2 == 0:
        tk //= 2
    nt = k1 // tk
    rows = kvh * g * tk
    rpad = -rows % 8  # whole sublane tiles; zero rows fold harmlessly

    eye = jnp.eye(kvh, dtype=q.dtype)
    qt = q.reshape(s, kvh, g, nt, tk, dh).transpose(0, 3, 1, 2, 4, 5)
    qbd = (qt[..., None, :] * eye[:, None, None, :, None]).reshape(
        s, nt, rows, c)
    qbd = jnp.pad(qbd, ((0, 0), (0, 0), (0, rpad), (0, 0)))
    tables = page.tables.astype(jnp.int32)
    pos = page.pos.astype(jnp.int32)
    larr = jnp.reshape(jnp.asarray(l, jnp.int32), (1,))

    def pool_specs(width, layer):
        """The step's nb table entries of one pool array, each a
        (block, layer): bt rows of the layer's `width` columns (all
        columns where `layer` is False).  An entry past the table (w
        no multiple of nb) is clamped to the last: its block is fetched
        and masked.  The span step names the blocks of the step before
        it, so nothing is fetched for it."""
        def spec(i):
            def index(si, ti, j, tr, pr, lr):
                entry = jnp.minimum(
                    jnp.minimum(j, npool - 1) * nb + i, w - 1)
                return (tr[si, entry], 0, lr[0] if layer else 0)
            return pl.BlockSpec((1, bt, width), index)
        return [spec(i) for i in range(nb)]

    row_spec = pl.BlockSpec((1, 1, rows + rpad, c),
                            lambda si, ti, j, tr, pr, lr: (si, ti, 0, 0))
    in_specs = [row_spec] + 2 * pool_specs(c, True)
    args = [qbd] + nb * [view.k] + nb * [view.v]
    if quant:
        # the scales' minor dimension L * KVH is no whole number of
        # lane tiles a layer: a step takes its blocks' scales of every
        # layer, and `sel` picks this layer's head for each row
        nlk = view.k_scale.shape[2]
        head = jnp.arange(rows + rpad) // (g * tk)
        sel = jax.nn.one_hot(l * kvh + head, nlk, dtype=jnp.float32)
        in_specs += 2 * pool_specs(nlk, False) + [
            pl.BlockSpec(sel.shape, lambda si, ti, j, tr, pr, lr: (0, 0))]
        args += nb * [view.k_scale] + nb * [view.v_scale] + [sel]
    span_spec = pl.BlockSpec((1, k1, c), lambda si, ti, j, tr, pr, lr:
                             (si, 0, 0))
    in_specs += [span_spec, span_spec]
    args += [a.swapaxes(1, 2).reshape(s, k1, c) for a in span_kv]

    kernel = functools.partial(
        _paged_attn_kernel,
        bt=bt, nb=nb, tk=tk, quant=quant, scale=1.0 / math.sqrt(dh),
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(s, nt, npool + 1),
        in_specs=in_specs,
        out_specs=row_spec,
        scratch_shapes=[
            pltpu.VMEM((rows + rpad, c), jnp.float32),
            pltpu.VMEM((rows + rpad, 1), jnp.float32),
            pltpu.VMEM((rows + rpad, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((s, nt, rows + rpad, c), q.dtype),
        interpret=INTERPRET,
        # slots and row tiles are independent (scratch resets at
        # j == 0), so they may split across Mosaic cores; j must stay
        # ordered
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        name="tds_paged_attn",
    )(tables, pos, larr, *args)
    # a row's own head out of its C columns (a gather: the other heads'
    # columns are never computed with)
    out = out[:, :, :rows].reshape(s, nt, kvh, g * tk, kvh, dh)
    hsel = jnp.arange(kvh)
    out = out[:, :, hsel, :, hsel]  # (KVH, S, NT, G * tk, Dh)
    out = out.reshape(kvh, s, nt, g, tk, dh).transpose(1, 0, 3, 2, 4, 5)
    return out.reshape(s, hq, k1, dh)
