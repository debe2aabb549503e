# Copyright 2026 tiny-deepspeed-tpu authors
# SPDX-License-Identifier: Apache-2.0

"""Paged KV cache: one preallocated HBM pool, per-request block tables.

The contiguous decode cache (`GPT2Model._prefill`) allocates
(L, B, Hkv, T_max, Dh) per generate() call — every request pays for its
MAXIMUM length up front, and concurrent requests of different lengths
cannot share the allocation.  Serving traffic needs the opposite: the
pool here is ONE (num_blocks, block_tokens, L * KVH * Dh) K/V pair sized
for the whole engine, carved into fixed `block_tokens`-token blocks.  A
request owns just the blocks its current length needs (a host-side block
table of physical block ids); a finished request's blocks return to the
free list and the next admission reuses them.  On TPU this is the
decode-throughput design point (the Gemma serving comparison, PAPERS.md
arXiv:2605.25645): HBM stays densely packed with live cache, so batch
occupancy — not per-request padding — bounds tokens/s.

The resting shape is the one both compiled programs index as it stands
(`pool_shape`): a token's K (or V) vectors of every layer and head lie
side by side in the minor dimension, layer-major then head then Dh, so
layer l is the column block [l * KVH * Dh, (l + 1) * KVH * Dh).  With
KVH * Dh a multiple of 128 and `block_tokens` a multiple of the dtype's
sublane tile (16 rows of bf16) the last two dimensions are whole TPU
tiles: the device's default layout is row-major with no padding, a
(block, layer) is one contiguous run of tiles for the attention
kernel's DMA, and neither program has to convert the pool on entry or
exit (a trailing (KVH, Dh) = (12, 64) would pad to (16, 128) tiles, and
the layout the runtime picks to avoid that is one no program uses).
Where KVH * Dh is not a multiple of 128 (the tiny CPU test models) or
the dtype's tile is taller than a block (int8, fp8: 32 rows), the
device pads the pool and everything stays correct.  Nothing here
reshapes or transposes a pool array: writes are scatters of slivers
reshaped to meet it, reads are gathers of column windows.

Physical block 0 is SCRATCH: never allocated, it absorbs the writes of
invalid slots and bucket-padding positions so the compiled step stays
shape-stable without branching.  Scratch contents are garbage by design;
every read path masks by true position before the softmax.

Quantized cache blocks (`quant="int8" | "fp8"`) rest the pool at 1
byte/element, reusing the blockwise-absmax codec from `parallel/comm.py`
(the grad_comm PR's machinery) with the codec block = one (Dh,) head
vector and the f32 scale stored per (block, token, layer, head) as
(num_blocks, block_tokens, L * KVH), the same rule one Dh shorter — the
place a per-vector scale gets to live that the contiguous in-scan cache
never had.  Dequantization happens at attention time on the gathered
panel; `_span_attention` then accumulates in f32 as always.

Everything jit-traceable is a pure function over `KVPoolView` (a pytree
riding the decode scan's carry); `PagedKVPool` is the host-side owner:
device arrays + free list + exact accounting.
"""

from __future__ import annotations

import bisect
from typing import Dict, List, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp

from ..ops.paged_attn_pallas import pool_steps

# the never-allocated block absorbing invalid-slot / padding writes
SCRATCH_BLOCK = 0

KV_QUANT_MODES = (None, "int8", "fp8")
_QDTYPE = {"int8": jnp.int8, "fp8": jnp.float8_e4m3fn}


class KVPoolView(NamedTuple):
    """The pool's device arrays, as traced through the compiled steps.

    k/v: (num_blocks, block_tokens, L * KVH * Dh) in the resting dtype
    (resolved_cache_dtype, or int8/e4m3 when quantized); k_scale/v_scale:
    (num_blocks, block_tokens, L * KVH) f32 per-head-vector absmax scales,
    None on the unquantized path (None prunes to an empty pytree subtree,
    so the compiled step never sees the operands)."""

    k: jax.Array
    v: jax.Array
    k_scale: Optional[jax.Array]
    v_scale: Optional[jax.Array]


class PageRef(NamedTuple):
    """Per-slot cache coordinates for one decode step (loop-invariant
    across layers): tables (S, max_blocks) physical block ids (unused
    entries -> SCRATCH_BLOCK), blk/off (S,) this token's write block and
    in-block offset, pos (S,) each slot's current length (the attention
    mask bound)."""

    tables: jax.Array
    blk: jax.Array
    off: jax.Array
    pos: jax.Array


def page_ref(tables, pos, block_tokens: int) -> PageRef:
    """Derive the write coordinates once per token, outside the layer
    scan: position p lands in logical block p // block_tokens at offset
    p % block_tokens."""
    j = pos // block_tokens
    blk = jnp.take_along_axis(tables, j[:, None], axis=1)[:, 0]
    return PageRef(tables, blk, off=pos % block_tokens, pos=pos)


def quant_mode(view: KVPoolView) -> Optional[str]:
    """The pool's quantization mode, read off its STATIC dtypes — no
    extra non-array argument has to thread through jit."""
    if view.k_scale is None:
        return None
    return "int8" if view.k.dtype == jnp.int8 else "fp8"


def pool_shape(num_blocks: int, block_tokens: int, n_layer: int,
               kv_heads: int, head_dim: int) -> tuple:
    """THE resting shape of a K (or V) pool array — the one rule, from
    the sizes alone.  The scales of a quantized pool follow it with
    head_dim = 1."""
    return (num_blocks, block_tokens, n_layer * kv_heads * head_dim)


class BlockKind(NamedTuple):
    """One kind of block: `block_tokens` rows, each the K vectors (width
    `k_dim`) and the V vectors (width `v_dim`) of `kv_heads` heads of
    `layers` layers, side by side as `pool_shape` says, a side an array.
    `blocks` is how many: in a slot layout the most ONE slot can hold,
    handed to `PagedKVPool` the usable blocks of the pool."""

    layers: int
    kv_heads: int
    k_dim: int
    v_dim: int
    blocks: int


class DenseLayout(NamedTuple):
    """What one slot holds in the pool, in blocks of `block_tokens`
    rows, and how that reaches its block-table row: K and V of the whole
    context, one block per `block_tokens` positions, one table.

    A slot layout is the one thing `serving/engine.py` asks about a
    model's cache, and every servable model states one
    (`GPT2Model.paged_layout`; `models/evabyte.EvaLayout` and
    `models/mimo.MiMoLayout` are the others).  Its members, all of them
    host arithmetic:

    width             entries of a slot's block-table row
    kinds             the pool's kinds of block (`BlockKind`): which
                      layers rest in each, their KV heads, K and V
                      widths, and the most blocks one slot can hold.
                      The engine builds the pool from them; one kind
                      here, the model's L x KVH x Dh on both sides
    tables            the kind a slot's table and its second block list
                      draw from, by their place in `kinds`
    need(pos)         (table blocks, summary blocks) a slot owns before
                      it writes position `pos`: never fewer for a later
                      position, never more than the row holds
    prefill_panel(b)  (table, summary) entries of the block-id panel a
                      prefill of bucket `b` scatters through
    fill_row(..)      a slot's two block lists into its table row
    span, tick_counts(..)
                      what a decode tick counts of its slots: `counts`
                      go into the tick's record, `ids` onto the span
                      `tds.tick.<span>` (utils/profiling.TABLE)
    fetched           names of the counts the model's decode program
                      hands back behind its tokens (`paged_decode`'s
                      third result); with any, the span is opened after
                      the fetch and carries them too.  None here
    bounds_pool       whether max_active x a kind's `blocks` is all the
                      pool can ever hold of it.  Not here: a prefix tree
                      keeps blocks that no slot owns
    refuses           {engine feature: why}, each refused by the engine
                      as "<Model> cannot <why>"; nothing here"""

    width: int
    block_tokens: int
    kinds: tuple = ()

    tables = (0, 0)
    span = "decode.operands"
    fetched = ()
    bounds_pool = False
    refuses = {}

    def need(self, pos: int):
        return pos // self.block_tokens + 1, 0

    def prefill_panel(self, bucket: int):
        return bucket // self.block_tokens, 0

    def fill_row(self, row, table, summary) -> None:
        row[:len(table)] = table

    def tick_counts(self, slots, max_active: int):
        """How much of their table rows the slots' lengths fill, in the
        paged kernel's unit, a chunk of a row
        (ops/paged_attn_pallas.pool_steps): `kv_steps` the chunks
        `max_active` rows hold, `kv_steps_live` those that begin below
        their slot's length, which are all the kernel copies and folds,
        a layer."""
        nb, npool = pool_steps(self.width, self.block_tokens)
        chunk = nb * self.block_tokens
        counts = dict(
            kv_steps_live=sum(min(-(-s.pos // chunk), npool)
                              for s in slots),
            kv_steps=max_active * npool)
        return counts, counts


def _quant_vectors(x, mode: str):
    """(..., Dh) f32-able -> (q same shape, scales (...,)) via the
    grad-comm blockwise-absmax codec with codec block = the Dh head
    vector (parallel/comm.quantize_blockwise, round-to-nearest — KV
    vectors are read many times, so unbiasedness-via-dither buys nothing
    and costs a PRNG operand)."""
    from ..parallel.comm import quantize_blockwise
    dh = x.shape[-1]
    q, s = quantize_blockwise(
        x.astype(jnp.float32).reshape(-1), mode, block=dh
    )
    return q.reshape(x.shape), s.reshape(x.shape[:-1])


def _rest(view: KVPoolView, k, v, lead: tuple):
    """K/V (..., KVH, Dh) in compute dtype -> the four arrays as the
    pool rests them, the trailing dimensions of each merged into the
    pool's minor one and the leading ones reshaped to `lead`:
    (k, v, k_scale, v_scale), the scales None on an unquantized pool."""
    mode = quant_mode(view)
    if mode is None:
        return (k.astype(view.k.dtype).reshape(*lead, -1),
                v.astype(view.v.dtype).reshape(*lead, -1), None, None)
    qk, sk = _quant_vectors(k, mode)
    qv, sv = _quant_vectors(v, mode)
    return (qk.reshape(*lead, -1), qv.reshape(*lead, -1),
            sk.reshape(*lead, -1), sv.reshape(*lead, -1))


def _set_rows(view: KVPoolView, idx: tuple, rk, rv, sk, sv) -> KVPoolView:
    """view[idx] = rows, every layer's columns at once: `idx` indexes
    the leading dimension(s), the rows span the whole minor one."""
    new = view._replace(k=view.k.at[idx].set(rk), v=view.v.at[idx].set(rv))
    if sk is None:
        return new
    return new._replace(k_scale=view.k_scale.at[idx].set(sk),
                        v_scale=view.v_scale.at[idx].set(sv))


def _get_columns(pool, tables, col, width: int):
    """pool[tables[s, j], :, col : col + width] -> (S, W, bt, width):
    one gather of block-high column windows, nothing pool-sized made."""
    idx = jnp.stack(
        [tables, jnp.broadcast_to(col, tables.shape)], axis=-1
    ).astype(jnp.int32)
    dn = jax.lax.GatherDimensionNumbers(
        offset_dims=(2, 3), collapsed_slice_dims=(0,),
        start_index_map=(0, 2))
    return jax.lax.gather(pool, idx, dn,
                          slice_sizes=(1, pool.shape[1], width))


def paged_append(view: KVPoolView, ks, vs, page: PageRef) -> KVPoolView:
    """Write one token's K/V per slot, every layer at once — ks/vs
    (L, S, KVH, Dh), the decode step's scan ys — as ONE row per slot
    at (page.blk, page.off).  Invalid slots' coordinates point at the
    scratch block, so the scatter is branch-free."""
    rows = _rest(view, ks.swapaxes(0, 1), vs.swapaxes(0, 1),
                 (ks.shape[1],))
    return _set_rows(view, (page.blk, page.off), *rows)


def paged_panel(view: KVPoolView, l, page: PageRef, kv_heads: int,
                head_dim: int, out_dtype):
    """Gather layer l's K/V panels through the block tables:
    (S, KVH, max_blocks * block_tokens, Dh) per side, ready for
    `_span_attention`.  `kv_heads` / `head_dim` are the caller's
    static sizes: the merged minor dimension no longer shows them.
    Unquantized panels stay in the pool's resting dtype (the attention
    consumes it directly); quantized panels dequantize to `out_dtype`
    here — the 1-byte blocks are what crossed HBM, the dequantized
    panel is attention-local."""
    mode = quant_mode(view)
    kvh, dh = kv_heads, head_dim

    def panel(pool, scale):
        g = _get_columns(pool, page.tables, l * (kvh * dh), kvh * dh)
        s, bmax, bt, _ = g.shape
        g = g.reshape(s, bmax * bt, kvh, dh).swapaxes(1, 2)
        if mode is None:
            return g
        sg = _get_columns(scale, page.tables, l * kvh, kvh)
        sg = sg.reshape(s, bmax * bt, kvh).swapaxes(1, 2)
        return (g.astype(jnp.float32) * sg[..., None]).astype(out_dtype)

    return panel(view.k, view.k_scale), panel(view.v, view.v_scale)


def paged_append_span(view: KVPoolView, ks, vs, tables, pos0, count,
                      block_tokens: int) -> KVPoolView:
    """Commit a verified SPAN of tokens' K/V per slot — the speculative
    decoding multi-token append.  ks/vs: (L, S, KVH, K1, Dh) span K/V
    stacks (the verify scan's ys: span offset j is the token at absolute
    position pos0[s]+j); tables: (S, W) block tables; pos0: (S,) span
    base positions; count: (S,) int32 in [0, K1] — how many leading span
    offsets COMMIT.  Offsets >= count (rejected drafts, inactive slots,
    positions past the request's K/V horizon) route to the scratch block
    and never enter the pool, so acceptance truncates the write itself:
    no rejected-draft K/V to clean up, boundary-exact per slot (the
    block index comes through the slot's own table, same as the single-
    token `paged_append`).  One scatter per side covers all L layers."""
    L, S, KVH, K1, Dh = ks.shape
    j = jnp.arange(K1)[None, :]
    wpos = pos0[:, None] + j  # (S, K1) absolute write positions
    valid = j < count[:, None]
    W = tables.shape[1]
    # clamp the table lookup BEFORE masking: an invalid offset's write
    # position may index past the table, and OOB gather clamping would
    # otherwise read a real block id that the where() must override
    bidx = jnp.minimum(wpos // block_tokens, W - 1)
    blk = jnp.take_along_axis(tables, bidx, axis=1)
    blk = jnp.where(valid, blk, SCRATCH_BLOCK)
    off = jnp.where(valid, wpos % block_tokens, 0)

    def prep(a):  # (L, S, KVH, K1, Dh) -> (S, K1, L, KVH, Dh) token rows
        return a.transpose(1, 3, 0, 2, 4)

    rows = _rest(view, prep(ks), prep(vs), (S * K1,))
    return _set_rows(view, (blk.reshape(-1), off.reshape(-1)), *rows)


class BlockPayload(NamedTuple):
    """The CONTENTS of a set of pool blocks in transit between two
    engines' pools — the disaggregated prefill->decode migration unit
    (fleet/disagg.py).  Arrays keep the pool's RESTING dtype: a
    quantized pool hands off 1-byte blocks plus their f32 scales, so
    migrated bytes get the same 4x compression as pool bytes — and the
    pool's resting SHAPE, so neither side converts anything.  k/v:
    (n_blocks, block_tokens, L * KVH * Dh); scales (n_blocks,
    block_tokens, L * KVH) or None on the unquantized path."""

    k: jax.Array
    v: jax.Array
    k_scale: Optional[jax.Array]
    v_scale: Optional[jax.Array]


def export_blocks(view: KVPoolView, ids: List[int]) -> BlockPayload:
    """Gather physical blocks `ids` out of the pool, contents only —
    the source side of a paged-KV migration.  The gather materializes
    fresh arrays, so the caller may free (and the pool reuse) the
    source blocks immediately after."""
    idx = jnp.asarray(list(ids), jnp.int32)

    def sel(a):
        return None if a is None else a[idx]

    return BlockPayload(sel(view.k), sel(view.v),
                        sel(view.k_scale), sel(view.v_scale))


def import_blocks(view: KVPoolView, ids: List[int],
                  payload: BlockPayload) -> KVPoolView:
    """Scatter a migrated payload into freshly allocated blocks `ids`
    of THIS pool — the destination side of a paged-KV migration.  The
    two pools must agree on resting dtype, quantization mode, and block
    geometry; a mismatch is refused up front naming both sides (the
    alternative is garbage K/V read through the decode panel)."""
    if payload.k.dtype != view.k.dtype:
        raise ValueError(
            f"paged-KV migration dtype mismatch: payload rests at "
            f"{jnp.dtype(payload.k.dtype)} but this pool at "
            f"{jnp.dtype(view.k.dtype)} — source and destination pools "
            "must share the same `quant` / cache dtype"
        )
    if (payload.k_scale is None) != (view.k_scale is None):
        raise ValueError(
            "paged-KV migration quantization mismatch: payload is "
            f"{'un' if payload.k_scale is None else ''}scaled but this "
            f"pool is {'un' if view.k_scale is None else ''}scaled"
        )
    if tuple(payload.k.shape[1:]) != tuple(view.k.shape[1:]):
        raise ValueError(
            f"paged-KV migration geometry mismatch: payload blocks are "
            f"{tuple(payload.k.shape[1:])} (block_tokens, L * KVH * Dh) "
            f"but this pool's are {tuple(view.k.shape[1:])}"
        )
    if len(ids) != payload.k.shape[0]:
        raise ValueError(
            f"{len(ids)} destination blocks for a "
            f"{payload.k.shape[0]}-block payload"
        )
    return _set_rows(view, (jnp.asarray(list(ids), jnp.int32),), *payload)


def payload_bytes(payload: BlockPayload) -> int:
    """The migration's wire footprint: what actually moves between the
    pools (resting-dtype blocks + scales — NOT the dequantized f32
    size), summed from the arrays' own dtypes/shapes so the priced
    number is measured, not modeled."""
    return int(sum(
        a.size * jnp.dtype(a.dtype).itemsize
        for a in payload if a is not None
    ))


def paged_scatter(view: KVPoolView, ks, vs, block_ids,
                  block_tokens: int) -> KVPoolView:
    """Scatter a prefill's full-prompt K/V — ks/vs (L, 1, KVH, P, Dh)
    from the `return_kv` forward hook — into the pool blocks `block_ids`
    ((P / block_tokens,) physical ids; bucket-padding tail entries point
    at scratch).  P is the bucket length, always a block multiple.  The
    slab is what is transposed to meet the pool, never the pool."""
    p = ks.shape[3]

    def prep(a):  # b == 1: prefill is per-request
        return a[:, 0].transpose(2, 0, 1, 3)  # (P, L, KVH, Dh)

    rows = _rest(view, prep(ks), prep(vs),
                 (p // block_tokens, block_tokens))
    return _set_rows(view, (block_ids,), *rows)


class PagedKVPool:
    """Host-side pool owner: the device arrays plus exact block
    accounting.  A kind's `blocks` is its USABLE count — one extra
    scratch block is allocated on top and never handed out.

    Blocks are REFCOUNTED (the prefix-cache extension of the original
    LIFO free list): `alloc` hands a block out at refcount 1, `share`
    bumps it for every additional holder (a second request's block
    table aliasing a shared prefix, or the radix tree keeping a
    finished request's prompt blocks warm), and `free_blocks` is a
    DECREMENT — the block returns to the free list only when its last
    holder lets go.  With no sharing in play every refcount is 1 and
    the semantics (and the LIFO realloc determinism the tests pin) are
    byte-identical to the pre-refcount pool.  The exact-accounting
    invariant becomes: free + distinct-allocated == usable, and every
    allocated block's refcount equals its holder count (table
    occurrences + one for a prefix-tree node) — what
    tests/test_serving_prefix.py asserts per tick."""

    def __init__(self, *, kinds: Sequence[BlockKind], block_tokens: int,
                 dtype, quant: Optional[str] = None):
        if quant not in KV_QUANT_MODES:
            raise ValueError(
                f"KV-cache quant must be one of {KV_QUANT_MODES}, "
                f"got {quant!r}"
            )
        if block_tokens < 1 or any(k.blocks < 1 for k in kinds):
            raise ValueError("blocks and block_tokens must be >= 1")
        self.kinds = tuple(kinds)
        self.block_tokens = int(block_tokens)
        self.quant = quant
        # ONE id space: kind j's blocks are ids bases[j] + 1 ..
        # bases[j] + blocks, and rest in its arrays at id - bases[j];
        # each kind's row 0 is its scratch, and id 0 names them all
        self.bases = [0]
        for k in self.kinds[:-1]:
            self.bases.append(self.bases[-1] + int(k.blocks))
        self.num_usable = self.bases[-1] + int(self.kinds[-1].blocks)
        rest = _QDTYPE.get(quant, dtype)

        def arrays(kind: BlockKind) -> KVPoolView:
            total = int(kind.blocks) + 1  # + scratch

            def side(width, dt):
                # distinct arrays per side: the view is DONATED through
                # the compiled steps, and two fields aliasing one zeros
                # buffer would be a double donation
                return jnp.zeros(pool_shape(total, block_tokens,
                                            kind.layers, kind.kv_heads,
                                            width), dt)

            return KVPoolView(
                k=side(kind.k_dim, rest), v=side(kind.v_dim, rest),
                k_scale=side(1, jnp.float32) if quant else None,
                v_scale=side(1, jnp.float32) if quant else None)

        self.views = tuple(arrays(k) for k in self.kinds)
        # pop() hands out ascending ids from a kind's first; frees push
        # back LIFO -- both deterministic, which the
        # realloc-determinism test pins
        self._free: List[List[int]] = [
            list(range(base + int(k.blocks), base, -1))
            for base, k in zip(self.bases, self.kinds)]
        # block id -> holder count, for every allocated block (ids in
        # the free list never appear here)
        self._ref: Dict[int, int] = {}

    # -- accounting ---------------------------------------------------------

    @property
    def blocks_free(self) -> int:
        return sum(len(f) for f in self._free)

    @property
    def blocks_in_use(self) -> int:
        """DISTINCT allocated blocks — a block aliased by three holders
        still occupies one physical block."""
        return self.num_usable - self.blocks_free

    def kind_of(self, b: int) -> int:
        """Which kind block id `b` is of."""
        return bisect.bisect_left(self.bases, int(b)) - 1

    def free_of(self, kind: int) -> int:
        """Free blocks of one kind."""
        return len(self._free[kind])

    @classmethod
    def dense(cls, *, n_layer: int, kv_heads: int, head_dim: int,
              num_blocks: int, **kw) -> "PagedKVPool":
        """A pool of one kind, K and V of one width."""
        return cls(kinds=(BlockKind(n_layer, kv_heads, head_dim, head_dim,
                                    num_blocks),), **kw)

    @property
    def view(self):
        """What the model's programs take and hand back: the one kind's
        four arrays, or a tuple of such views where the layout states
        several kinds.  Everything else here reads `views`."""
        return self.views[0] if len(self.views) == 1 else self.views

    @view.setter
    def view(self, new) -> None:
        self.views = (new,) if len(self.views) == 1 else tuple(new)

    @property
    def dtype(self):
        """The resting dtype, of every kind and side."""
        return self.views[0].k.dtype

    def refcount(self, b: int) -> int:
        """Holder count of block `b` (0 = free)."""
        return self._ref.get(int(b), 0)

    def ref_counts(self) -> Dict[int, int]:
        """{block id: holder count} snapshot over every allocated block
        — what the per-tick exact-accounting pin compares against the
        holders it can enumerate (active tables + prefix-tree nodes)."""
        return dict(self._ref)

    def alloc(self, n: int, kind: int = 0) -> Optional[List[int]]:
        """n physical block ids of `kind` at refcount 1, or None WITHOUT
        allocating when fewer than n are free (admission is
        all-or-nothing)."""
        free = self._free[kind]
        if n > len(free):
            return None
        ids = [free.pop() for _ in range(n)]
        for b in ids:
            self._ref[b] = 1
        return ids

    def share(self, ids: List[int]) -> None:
        """Add one holder to each allocated block in `ids` — the
        aliasing primitive: a new request's block table (or the prefix
        tree) referencing blocks some other holder already owns.
        Sharing a free block is refused: its contents are up for
        reuse, so an alias would read garbage."""
        for b in ids:
            if self._ref.get(b, 0) < 1:
                raise ValueError(
                    f"cannot share block {b}: not allocated (a free "
                    "block's contents are reusable garbage)"
                )
        for b in ids:
            self._ref[b] += 1

    def free_blocks(self, ids: List[int]) -> None:
        """Drop one holder per id; a block whose LAST holder lets go
        returns to the free list (LIFO, in `ids` order — with all
        refcounts at 1 this is exactly the pre-refcount extend)."""
        from collections import Counter
        drops = Counter(int(b) for b in ids)
        for b, n in drops.items():
            if not 1 <= b <= self.num_usable:
                raise ValueError(f"freeing invalid block id {b}")
            if self._ref.get(b, 0) < n:
                raise ValueError(
                    f"double free of block {b}: {n} release(s) against "
                    f"refcount {self._ref.get(b, 0)}"
                )
        for b in ids:
            b = int(b)
            self._ref[b] -= 1
            if self._ref[b] == 0:
                del self._ref[b]
                self._free[self.kind_of(b)].append(b)

    def kv_bytes(self) -> dict:
        """The pool's resting HBM footprint, FROM the device arrays'
        dtypes/shapes (what the quantization acceptance asserts against,
        not a model): K+V block bytes, scale bytes, and the per-element
        width."""
        def nbytes(*arrays):
            return sum(a.size * jnp.dtype(a.dtype).itemsize
                       for a in arrays if a is not None)

        blocks = sum(nbytes(v.k, v.v) for v in self.views)
        scales = sum(nbytes(v.k_scale, v.v_scale) for v in self.views)
        return {
            "kv_block_bytes": int(blocks),
            "scale_bytes": int(scales),
            "total_bytes": int(blocks + scales),
            "dtype": str(jnp.dtype(self.dtype)),
            "itemsize": int(jnp.dtype(self.dtype).itemsize),
        }
