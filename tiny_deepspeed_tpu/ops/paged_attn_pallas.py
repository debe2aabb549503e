# Copyright 2026 tiny-deepspeed-tpu authors
# SPDX-License-Identifier: Apache-2.0

"""Pallas paged-attention decode kernel: fused block-table gather + attention.

The XLA paged decode path (serving/pool.paged_panel + the models'
`_span_attention`) MATERIALIZES each slot's K/V panel every token: the
block-table gather writes an (S, KVH, W*bt, Dh) pair to HBM, attention
reads it back, and on a quantized pool a third dequantized copy joins
them — PROFILE.md "Decode under load" measures exactly this gather as
the decode step's dominant non-matmul cost.  This kernel reads the pool
blocks DIRECTLY: the block table rides the scalar prefetch, the kernel
copies each live (block, layer) from HBM into VMEM itself, dequantizes
int8/fp8 resting blocks in-register against their per-vector scales,
and folds the blocks into a flash-style online softmax — the panel never
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
own K/V, not yet in the pool, are folded last under the windowed causal
mask.  The caller commits the span afterwards (serving/pool.paged_append,
paged_append_span).

Grid: (S, row tiles), both parallel.  A grid step owns one slot: it
walks the slot's table in chunks of nb entries (`pool_steps`), copies a
chunk's blocks into one half of a two-deep VMEM buffer while it folds
the other half, keeps the softmax stats (m, l, acc) in VMEM, and ends
with the span's own K/V.

Only what is live is worked on.  The pool arrays stay in HBM
(`memory_space=HBM`: no BlockSpec, no pipeline of Pallas's), and the
walk ends at the slot's length `page.pos`: a block whose first token
lies at or past it is neither looked up in the table nor copied nor
folded, and an empty slot (pos 0, a table of scratch) goes straight to
its span.  (A quantized pool's scales, a sixteenth of its bytes or
less, are the exception: their minor dimension L * KVH is no whole
number of lane tiles, which a copy out of HBM must be, so the slots'
scales of the layer are gathered before the kernel, `_scale_panel`.)
A slot costs its grid step (0.6 us) and 1.4-1.5 us for each
chunk it fills.  Measured on a v5e at gpt2-124m's serving sizes, 64
slots x 64 table entries x 12 layers a decode tick (PERF.md section 6,
PR 31): 0.45 ms with every slot empty, 0.65 with 5 slots live at 80-896
tokens, 1.96 with 51 live, 4.74 with all 64 full; the kernel this one
replaced gave every slot a grid step for each chunk of its table,
live or not, with a BlockSpec for each of the chunk's K and V blocks,
and took 7.15 ms whatever the slots held.  (The same grid with a
dead step's arithmetic skipped and its BlockSpecs naming the blocks of
the step before, so that nothing was fetched for it, took 11.9 ms, 8.8
with the index maps' two integer divisions made shifts: what a grid
step costs is its BlockSpecs, 36 here, each 50-80 ns a step whether or
not it fetches.)  `ops/eva_attn_pallas.py` reads the same pool the
same way since PR 33: two ranges of a slot's table row in one loop.

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
    # inputs (the scales and their selector on a quantized pool only),
    # output, scratch
    *refs,
    bt: int, nb: int, w: int, tk: int, quant: bool, scale: float,
    ring: int = 0, sink: bool = False,
):
    """One (slot, row tile) grid step: fold the slot's live pool blocks,
    `nb` of them a chunk, and then the span's own K/V, into the online
    softmax of the tile's R query rows.  The pool arrays stay in HBM;
    the kernel copies the blocks it folds into a two-deep VMEM buffer
    itself, the next chunk's while it folds this one's.  Everything is
    two-dimensional with the pool's merged minor dimension C = KVH * Dh
    in the lanes: the queries come block-diagonal (row (h, g, t) holds
    its Dh numbers in head h's columns, zeros elsewhere), so ONE
    q @ k^T over C gives every head's scores and no head is ever sliced
    out of a block; the accumulator keeps all C columns per row and the
    caller reads the row's own head back out.  K and V may differ in
    width (C is K's, the accumulator has V's columns).

    `ring` > 0: the table is a ring of `ring` rows, position n resting
    in row n % ring, and a query sees the `ring` - 1 positions before
    its own: rows below min(pos, ring) are live but for row pos % ring,
    which holds the position that just left the window (the caller
    overwrites it with this step's).  The order of rows in a ring does
    not matter to a softmax over keys rotated before they were written.
    `sink`: one more input, a number a query row, which joins the
    softmax's denominator as exp(number) and no value."""
    q_ref, pools = refs[0], refs[1:3]
    i = 3
    if quant:
        ks_ref, vs_ref, sel_ref = refs[3:6]
        i = 6
    if sink:
        sink_ref = refs[i]
        i += 1
    sk_ref, sv_ref, o_ref = refs[i:i + 3]
    bufs = refs[i + 3:i + 5]
    sem, acc, m, ll = refs[i + 5:]

    s = pl.program_id(0)
    t = pl.program_id(1)
    c_lanes = bufs[0].shape[-1]
    col = pl.ds(pl.multiple_of(l_ref[0] * c_lanes, c_lanes), c_lanes)
    cols = [col, col]
    if bufs[1].shape[-1] != c_lanes:  # V narrower or wider than K
        v_lanes = bufs[1].shape[-1]
        cols[1] = pl.ds(pl.multiple_of(l_ref[0] * v_lanes, v_lanes),
                        v_lanes)
    step = nb * bt
    limit = pos_ref[s]
    if ring:
        left = limit % ring  # the row of the position that left
        limit = jnp.minimum(limit, ring)
    held = jnp.minimum(limit, w * bt)  # what the table's w entries hold

    acc[...] = jnp.zeros(acc.shape, jnp.float32)
    m[...] = jnp.full(m.shape, _MASKED, jnp.float32)
    ll[...] = jnp.zeros(ll.shape, jnp.float32)
    q = q_ref[0, 0].astype(jnp.float32) * scale  # (R, C)

    def dot_nt(a, b):  # a @ b^T over the lanes of both
        return jax.lax.dot_general(
            a, b, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)

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

    def copies(c, half, act):
        """Start, or wait for, the copies of chunk c's live blocks into
        buffer `half`: table entry c * nb + i lands in plane i.  An
        entry whose first token the slot has not reached is not read
        from the table and not copied: its plane keeps what it held."""
        for i in range(nb):
            entry = c * nb + i

            @pl.when(entry * bt < held)
            def _(i=i, entry=entry):
                blk = tables_ref[s, entry]
                for n, (pool, buf) in enumerate(zip(pools, bufs)):
                    act(pltpu.make_async_copy(
                        pool.at[blk, :, cols[n]], buf.at[half, i],
                        sem.at[half, n]))

    def rows(buf, half):  # the chunk's nb planes, one under the other
        return jnp.concatenate(
            [buf[half, i].astype(jnp.float32) for i in range(nb)], axis=0)

    @pl.when(held > 0)
    def _first():
        copies(0, 0, lambda cp: cp.start())

    def chunk(c):
        half = c % 2

        @pl.when((c + 1) * step < held)
        def _next():
            copies(c + 1, 1 - half, lambda cp: cp.start())

        copies(c, half, lambda cp: cp.wait())
        scores = dot_nt(q, rows(bufs[0], half))  # (R, nb * bt)
        tpos = c * step + jax.lax.broadcasted_iota(
            jnp.int32, scores.shape, 1)
        live = tpos < limit
        if ring:
            live = live & (tpos != left)
        vscale = None
        if quant:
            # a row's head picks its scale row: the one-hot `sel`
            # (R, KVH), and the exact (fp32) product with it is a gather
            at = pl.ds(pl.multiple_of(c * step, step), step)
            hi = dict(precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)
            scores = scores * jnp.dot(sel_ref[...], ks_ref[0, :, at], **hi)
            vscale = jnp.where(
                live, jnp.dot(sel_ref[...], vs_ref[0, :, at], **hi), 0.0)
        # a plane that was not copied, and the last block's rows past
        # the slot's length, hold whatever they held: K's scores are
        # masked, and V's rows zeroed, since p = 0 times a stray NaN is
        # no 0
        vblk = rows(bufs[1], half)
        vrow = c * step + jax.lax.broadcasted_iota(jnp.int32, vblk.shape, 0)
        vlive = vrow < limit
        if ring:
            vlive = vlive & (vrow != left)
        fold(jnp.where(live, scores, _MASKED),
             jnp.where(vlive, vblk, 0.0), vscale)
        return c + 1

    jax.lax.while_loop(lambda c: c * step < held, chunk, jnp.int32(0))

    scores = dot_nt(q, sk_ref[0].astype(jnp.float32))  # (R, K1)
    qoff = t * tk + jax.lax.broadcasted_iota(
        jnp.int32, scores.shape, 0) % tk
    koff = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
    fold(jnp.where(koff <= qoff, scores, _MASKED),
         sv_ref[0].astype(jnp.float32))
    if sink:
        o_ref[0, 0] = (acc[...] / (ll[...] + jnp.exp(
            sink_ref[...] - m[...]))).astype(o_ref.dtype)
    else:
        o_ref[0, 0] = (acc[...] / ll[...]).astype(o_ref.dtype)


# query rows a grid step folds at most: the accumulator is (rows, C) f32
# in VMEM, and every row pays for all C columns on the MXU
_MAX_ROWS = 256
# pool tokens a chunk holds: the VMEM buffer is two of them for K and two
# for V, and a chunk is what one pass of the loop copies and folds.  On a
# v5e at the serve cell's sizes, ms a decode tick of kernel at 128 / 256 /
# 512: 0.70 / 0.65 / 0.66 with 5 of 64 slots live, 2.17 / 1.96 / 2.05
# with 51 live, 5.98 / 4.74 / 4.68 with all 64 full (PERF.md section 6,
# PR 31)
_STEP_TOKENS = 256


def pool_steps(w: int, bt: int) -> tuple[int, int]:
    """(nb, npool): the table entries a chunk holds, and the chunks
    that cover a table row of `w` entries of `bt` tokens each."""
    nb = max(1, min(w, _STEP_TOKENS // bt))
    return nb, -(-w // nb)


def _scale_panel(scales, tables, l, kvh: int, entries: int):
    """(S, KVH, entries * bt): layer l's scales of the blocks each
    slot's table names (the last entry again up to `entries`), a head a
    row and the tokens in the lanes."""
    layer = jax.lax.dynamic_slice_in_dim(scales, l * kvh, kvh, axis=2)
    tables = jnp.pad(tables, ((0, 0), (0, entries - tables.shape[1])),
                     mode="edge")
    panel = layer[tables]  # (S, entries, bt, KVH)
    return panel.reshape(tables.shape[0], -1, kvh).swapaxes(1, 2)


def paged_attention(q, view, page, l, span_kv, *, kv_heads: int,
                    ring: int = 0, sink=None):
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

    K and V may differ in width: Dh is then K's and the queries', the
    result has V's (span_kv's sv and view.v say which).  `ring` > 0
    reads the table as a ring of that many rows with one position a
    slot in the span (models/mimo.py's window layers): a query sees the
    ring's live rows but the one its own position will overwrite.
    `sink` (Hq,) float32 adds exp(sink[h]) to head h's denominator.

    The pool arrays are handed to the kernel as they rest; what is
    reshaped to meet them is small: the queries (block-diagonal over
    the heads, see the kernel), the span's K/V and the result."""
    s, hq, k1, dh = q.shape
    kvh = kv_heads
    g = hq // kvh
    c = kvh * dh
    dv = span_kv[1].shape[-1]
    cv = kvh * dv
    if ring and k1 != 1:
        raise ValueError("a ring holds one new position a slot")
    bt = view.k.shape[1]
    w = page.tables.shape[1]
    quant = view.k_scale is not None
    nb, npool = pool_steps(w, bt)  # table entries a chunk, chunks a row
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

    # (slot, row tile) -> that tile's rows; the span's K/V a slot
    row_spec = pl.BlockSpec((1, 1, rows + rpad, c),
                            lambda si, ti, tr, pr, lr: (si, ti, 0, 0))
    span_spec = pl.BlockSpec((1, k1, c), lambda si, ti, tr, pr, lr:
                             (si, 0, 0))
    out_spec, vspan_spec = row_spec, span_spec
    if cv != c:
        out_spec = pl.BlockSpec((1, 1, rows + rpad, cv),
                                lambda si, ti, tr, pr, lr: (si, ti, 0, 0))
        vspan_spec = pl.BlockSpec((1, k1, cv), lambda si, ti, tr, pr, lr:
                                  (si, 0, 0))
    in_hbm = pl.BlockSpec(memory_space=pltpu.HBM)
    in_specs = [row_spec, in_hbm, in_hbm]
    args = [qbd, view.k, view.v]
    # the two-deep buffer: nb planes of one block's (bt, C) a half
    scratch = [pltpu.VMEM((2, nb, bt, c), view.k.dtype),
               pltpu.VMEM((2, nb, bt, cv), view.v.dtype)]
    if quant:
        # a scale a head vector is a sixteenth or less of the pool: the
        # slots' scales of this layer are gathered whole, tokens in the
        # lanes, and `sel` picks its head's row for each query row
        sel = jax.nn.one_hot(jnp.arange(rows + rpad) // (g * tk), kvh,
                             dtype=jnp.float32)
        scale_spec = pl.BlockSpec((1, kvh, npool * nb * bt),
                                  lambda si, ti, tr, pr, lr: (si, 0, 0))
        in_specs += [scale_spec, scale_spec, pl.BlockSpec(
            sel.shape, lambda si, ti, tr, pr, lr: (0, 0))]
        args += [_scale_panel(a, tables, l, kvh, npool * nb)
                 for a in (view.k_scale, view.v_scale)] + [sel]
    if sink is not None:
        # query row (h, g, t) is head h * G + g: its sink, a column
        per_row = jnp.repeat(sink.astype(jnp.float32), tk)
        in_specs.append(pl.BlockSpec(
            (rows + rpad, 1), lambda si, ti, tr, pr, lr: (0, 0)))
        args.append(jnp.pad(per_row, (0, rpad))[:, None])
    in_specs += [span_spec, vspan_spec]
    args += [a.swapaxes(1, 2).reshape(s, k1, -1) for a in span_kv]

    kernel = functools.partial(
        _paged_attn_kernel,
        bt=bt, nb=nb, w=w, tk=tk, quant=quant, scale=1.0 / math.sqrt(dh),
        ring=ring, sink=sink is not None,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(s, nt),
        in_specs=in_specs,
        out_specs=out_spec,
        scratch_shapes=scratch + [
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.VMEM((rows + rpad, cv), jnp.float32),
            pltpu.VMEM((rows + rpad, 1), jnp.float32),
            pltpu.VMEM((rows + rpad, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((s, nt, rows + rpad, cv), q.dtype),
        interpret=INTERPRET,
        # a grid step starts and waits for its own copies and resets
        # its own softmax stats, so slots and row tiles may split
        # across Mosaic cores
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
        ),
        name="tds_paged_attn",
    )(tables, pos, larr, *args)
    # a row's own head out of its C columns (a gather: the other heads'
    # columns are never computed with)
    out = out[:, :, :rows].reshape(s, nt, kvh, g * tk, kvh, dv)
    hsel = jnp.arange(kvh)
    out = out[:, :, hsel, :, hsel]  # (KVH, S, NT, G * tk, Dh)
    out = out.reshape(kvh, s, nt, g, tk, dv).transpose(1, 0, 3, 2, 4, 5)
    return out.reshape(s, hq, k1, dv)
