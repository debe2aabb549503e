# Copyright 2026 tiny-deepspeed-tpu authors
# SPDX-License-Identifier: Apache-2.0

"""Pallas paged-attention decode kernel: fused block-table gather + attention.

The XLA paged decode path (serving/pool.paged_panel + the models'
`_decode_attention`) MATERIALIZES each slot's K/V panel every token: the
block-table gather writes an (S, KVH, W*bt, Dh) pair to HBM, attention
reads it back, and on a quantized pool a third dequantized copy joins
them — PROFILE.md "Decode under load" measures exactly this gather as
the decode step's dominant non-matmul cost.  This kernel reads the pool
blocks DIRECTLY: the block table rides the grid's scalar prefetch, each
grid step DMAs one physical (bt, KVH, Dh) block into VMEM, dequantizes
int8/fp8 resting blocks in-register against their per-vector scales,
and folds the block into a flash-style online softmax — the panel never
exists in HBM.

Two entry points share one kernel body:

  * `paged_attention(q, view, page, l)` — the decode step: q holds ONE
    query position per slot, the mask is positions <= page.pos (the
    slot's own token was just appended through `paged_append`, so it is
    read back through the pool exactly like the XLA path — on a
    quantized pool both paths see the same quantized sliver).
  * `paged_attention(q, view, page, l, span_kv=(sk, sv))` — the
    speculative-verify / suffix-prefill span variant: q holds K1
    positions per slot, the pool contributes the COMMITTED prefix
    (positions < page.pos) and the span's own K/V enter as one extra
    grid step under the windowed causal mask — the k+1-position verify
    program stops re-reading the panel per offset.

Grid: (S, W [+1]) — slots parallel, table entries sequential with VMEM
softmax stats (m, l, acc) carried across the W steps and reset at j=0
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
    # inputs (quant/span operands present per the static flags)
    *refs,
    bt: int, w: int, k1: int, span: bool, quant: bool, inclusive: bool,
    scale: float,
):
    """One (slot, table-entry) grid step: fold one pool block — or, on
    the final span step, the span's own K/V — into the slot's online
    softmax.  Scratch (acc, m, ll) persists across the sequential j
    dimension and resets at j == 0."""
    i = 0
    q_ref = refs[i]; i += 1
    k_ref = refs[i]; i += 1
    v_ref = refs[i]; i += 1
    if quant:
        ks_ref = refs[i]; i += 1
        vs_ref = refs[i]; i += 1
    if span:
        sk_ref = refs[i]; i += 1
        sv_ref = refs[i]; i += 1
    o_ref, acc, m, ll = refs[i:i + 4]

    s = pl.program_id(0)
    j = pl.program_id(1)
    nj = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        acc[...] = jnp.zeros(acc.shape, jnp.float32)
        m[...] = jnp.full(m.shape, _MASKED, jnp.float32)
        ll[...] = jnp.zeros(ll.shape, jnp.float32)

    q = q_ref[0].astype(jnp.float32) * scale  # (KVH, G*K1, Dh)
    limit = pos_ref[s]

    def fold(scores, vblk):
        """Online-softmax update: scores (KVH, G*K1, T'), vblk
        (KVH, T', Dh), both f32."""
        m_cur = jnp.max(scores, axis=-1)
        m_new = jnp.maximum(m[...], m_cur)
        alpha = jnp.exp(m[...] - m_new)
        p = jnp.exp(scores - m_new[..., None])
        ll[...] = ll[...] * alpha + jnp.sum(p, axis=-1)
        pv = jax.lax.dot_general(
            p, vblk, dimension_numbers=(((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )
        acc[...] = acc[...] * alpha[..., None] + pv
        m[...] = m_new

    @pl.when(j < w)
    def _pool_block():
        kb = k_ref[0, :, 0].astype(jnp.float32)  # (bt, KVH, Dh)
        vb = v_ref[0, :, 0].astype(jnp.float32)
        if quant:
            kb = kb * ks_ref[0, :, 0][..., None]
            vb = vb * vs_ref[0, :, 0][..., None]
        kb = kb.swapaxes(0, 1)  # (KVH, bt, Dh)
        vb = vb.swapaxes(0, 1)
        scores = jax.lax.dot_general(
            q, kb, dimension_numbers=(((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )  # (KVH, G*K1, bt)
        tpos = j * bt + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 2)
        ok = (tpos <= limit) if inclusive else (tpos < limit)
        fold(jnp.where(ok, scores, _MASKED), vb)

    if span:
        @pl.when(j == w)
        def _span_block():
            kb = sk_ref[0].astype(jnp.float32)  # (KVH, K1, Dh)
            vb = sv_ref[0].astype(jnp.float32)
            scores = jax.lax.dot_general(
                q, kb, dimension_numbers=(((2,), (2,)), ((0,), (0,))),
                preferred_element_type=jnp.float32,
            )  # (KVH, G*K1, K1)
            qoff = jax.lax.broadcasted_iota(
                jnp.int32, scores.shape, 1) % k1
            koff = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 2)
            fold(jnp.where(koff <= qoff, scores, _MASKED), vb)

    @pl.when(j == nj - 1)
    def _emit():
        o_ref[0] = (acc[...] / ll[...][..., None]).astype(o_ref.dtype)


def paged_attention(q, view, page, l, *, span_kv=None):
    """Fused block-table-gather attention over the paged pool.

    q: (S, Hq, K1, Dh) span queries (K1 == 1 on the plain decode step);
    view: serving.pool.KVPoolView (resting-dtype blocks; int8/fp8 pools
    dequantize in-kernel against view.k_scale/v_scale); page:
    serving.pool.PageRef; l: the layer index (traced — it rides the
    layer scan's carry).  span_kv = (sk, sv), each (S, KVH, K1, Dh),
    switches to the span-verify variant: pool positions < page.pos plus
    the span itself under the windowed causal mask (the exact mask of
    models' `_span_attention`); None is the decode variant (positions
    <= page.pos).  Returns (S, Hq, K1, Dh) in q's dtype."""
    s, hq, k1, dh = q.shape
    nb, bt, nl, kvh, _ = view.k.shape
    g = hq // kvh
    w = page.tables.shape[1]
    quant = view.k_scale is not None
    span = span_kv is not None
    nj = w + (1 if span else 0)

    qg = q.reshape(s, kvh, g, k1, dh).reshape(s, kvh, g * k1, dh)
    tables = page.tables.astype(jnp.int32)
    pos = page.pos.astype(jnp.int32)
    larr = jnp.reshape(jnp.asarray(l, jnp.int32), (1,))

    def blk_idx(si, j, tr, pr, lr):
        # unused at the span step (j == w) but must stay in range; the
        # clamped entry's block is fetched and ignored
        return tr[si, jnp.minimum(j, w - 1)]

    in_specs = [
        pl.BlockSpec((1, kvh, g * k1, dh), lambda si, j, tr, pr, lr:
                     (si, 0, 0, 0)),
        pl.BlockSpec((1, bt, 1, kvh, dh), lambda si, j, tr, pr, lr:
                     (blk_idx(si, j, tr, pr, lr), 0, lr[0], 0, 0)),
        pl.BlockSpec((1, bt, 1, kvh, dh), lambda si, j, tr, pr, lr:
                     (blk_idx(si, j, tr, pr, lr), 0, lr[0], 0, 0)),
    ]
    args = [qg, view.k, view.v]
    if quant:
        in_specs += [
            pl.BlockSpec((1, bt, 1, kvh), lambda si, j, tr, pr, lr:
                         (blk_idx(si, j, tr, pr, lr), 0, lr[0], 0)),
            pl.BlockSpec((1, bt, 1, kvh), lambda si, j, tr, pr, lr:
                         (blk_idx(si, j, tr, pr, lr), 0, lr[0], 0)),
        ]
        args += [view.k_scale, view.v_scale]
    if span:
        sk, sv = span_kv
        in_specs += [
            pl.BlockSpec((1, kvh, k1, dh), lambda si, j, tr, pr, lr:
                         (si, 0, 0, 0)),
            pl.BlockSpec((1, kvh, k1, dh), lambda si, j, tr, pr, lr:
                         (si, 0, 0, 0)),
        ]
        args += [sk, sv]

    kernel = functools.partial(
        _paged_attn_kernel,
        bt=bt, w=w, k1=k1, span=span, quant=quant,
        inclusive=not span, scale=1.0 / math.sqrt(dh),
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(s, nj),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, kvh, g * k1, dh),
                               lambda si, j, tr, pr, lr: (si, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((kvh, g * k1, dh), jnp.float32),
            pltpu.VMEM((kvh, g * k1), jnp.float32),
            pltpu.VMEM((kvh, g * k1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((s, kvh, g * k1, dh), q.dtype),
        interpret=INTERPRET,
        # slots are independent (scratch resets at j == 0), so the s
        # dimension may split across Mosaic cores; j must stay ordered
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        name="tds_paged_attn",
    )(tables, pos, larr, *args)
    return out.reshape(s, kvh, g, k1, dh).reshape(s, hq, k1, dh)
