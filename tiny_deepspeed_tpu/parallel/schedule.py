# Copyright 2026 tiny-deepspeed-tpu authors
# SPDX-License-Identifier: Apache-2.0

"""One composable in-scan collective scheduler.

Until this module the repo carried FOUR separate custom_vjp "tap"
mechanisms riding the block scan — the bucketed grad-release tap (PR 3),
the prefetched weight-gather scan (PR 4), the per-layer health probe
(PR 5), and the monolithic quantized grad schedule (PR 2) — and they
pairwise refused.  Here each engine mode declares its per-layer work as
composable SLOTS:

  GatherSlot — ZeRO-3 weight gathers: prefetch depth K, optional 2-hop
               groups, optional hpZ secondary partition (gathers stay
               intra-slice; ZeRO++ arXiv:2306.10209).
  GradSlot   — gradient releases: bucket count, collective codec
               (fp32/int8/fp8 + error-feedback residual slices), 2-hop
               groups.
  ProbeSlot  — per-layer health (the layer_health_tap).

`build_schedule` validates the composition ONCE (the single loud refusal
path, `ScheduleConflictError`, names the conflicting slot) and picks a
lowering:

  "probe"      — the probe row rides the plain GSPMD scan (legacy
                 program, HLO byte-identical).
  "bucket"     — the GradBucketTap nested scan (legacy, byte-identical).
  "quant_mono" — the monolithic quantized schedule (legacy,
                 byte-identical).
  "prefetch"   — the GatherPrefetchScan custom_vjp (legacy,
                 byte-identical).
  "composed"   — ANY multi-slot combination: ONE custom_vjp
                 (`composed_step`) emits the merged schedule into the
                 forward and remat-backward scan bodies inside a
                 shard_map manual region over the data axis — explicit
                 per-layer weight gathers (prefetched, optionally
                 intra-slice under hpZ), per-bucket grad collectives
                 released inside the backward scan, and the health
                 probe riding every layer.  This is the real DeepSpeed
                 hot path in one program: ZeRO-3 + gather prefetch +
                 bucketed quantized grads + per-layer health
                 simultaneously.

The model seam is ONE hook: `model.apply(..., sched=...)` receives an
executor with `.scan(block, stacked, x, unroll=)` — the grad_tap= /
health_probe= / pctx.gather_prefetch special cases are gone.

hpZ (secondary weight partitioning): with `hpz=True` the engine holds a
full compute-dtype (bf16/fp8) replica of the block weights WITHIN each
DCN granule (slice): one top-level inter-slice all-gather per step
rebuilds the secondary partition from the global fp32 ZeRO-3 shards, and
every in-scan forward/backward gather then runs over the intra-slice
group only — `dcn_wire_bytes` for in-scan gathers drops to ~zero
(measured by utils/hlo_comm.wire_link_split, the PR-14 ledger).  The
fp32 optimizer shards stay global ZeRO-3.  The secondary partition is
stashed as a backward residual — the deliberate HBM cost of hpZ (one
compute-dtype model replica per slice, PROFILE.md).
"""

from __future__ import annotations

import dataclasses
import warnings
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from .comm import (
    DEFAULT_BLOCK, GRAD_COMM_MODES, _dequant_rows, _hier_groups,
    as_wire, bucket_layout, from_wire, padded_size, quantize_blockwise,
    quantized_grad_sync,
)


class ScheduleConflictError(ValueError):
    """THE refusal path for slot combinations the scheduler cannot emit.

    Every message names the conflicting SLOT (gather/grad/probe), not a
    legacy knob — callers composing programmatically see which slot to
    drop."""


# ---------------------------------------------------------------------------
# slot declarations
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class GatherSlot:
    """Per-layer weight gathers (ZeRO-3).  `prefetch` = gathered layers
    held live (1 = on-demand, 2 = double buffer ...); `groups` = 2-hop
    hierarchical gather inner size (legacy prefetch lowering only);
    `hpz` = gathers run intra-slice from the secondary partition."""
    prefetch: int = 1
    groups: Optional[int] = None
    hpz: bool = False
    # secondary-rebuild codec (qwZ, ZeRO++): "fp32" gathers the stacked
    # compute dtypes; int8/fp8 moves blockwise-quantized payload + scales
    # over the inter-slice hop and dequantizes once per granule
    hpz_mode: str = "fp32"

    def describe(self) -> str:
        s = f"gather_prefetch={self.prefetch}"
        if self.groups:
            s += f"(2-hop inner={self.groups})"
        if self.hpz:
            s += "+hpz"
            if self.hpz_mode != "fp32":
                s += f"[{self.hpz_mode}]"
        return s


@dataclasses.dataclass(frozen=True)
class GradSlot:
    """Gradient releases: `buckets` layer buckets (+ non-block tail),
    collective codec `mode` with `block`-sized absmax scales and optional
    error-feedback residual slices; `groups` = 2-hop hierarchical
    schedule inner size (monolithic AND composed lowerings — every
    quantized release inside composed_step passes it down to
    quantized_grad_sync's inner/outer split)."""
    buckets: int = 1
    mode: str = "fp32"
    block: int = DEFAULT_BLOCK
    groups: Optional[int] = None
    error_feedback: bool = True
    # composed ZeRO-3 tail codec: "fp32" keeps the differentiable
    # gather's full-precision transpose reduce-scatter; int8/fp8 routes
    # the tail cotangents through the blockwise quantized sync with its
    # own error-feedback residual slice (stages 0-2 already quantize the
    # tail via `mode` — this knob exists only where the tail would
    # otherwise be the last fp32 collective)
    tail_mode: str = "fp32"

    def describe(self) -> str:
        s = f"grad_buckets={self.buckets},grad_comm={self.mode}"
        if self.groups:
            s += f"(2-hop inner={self.groups})"
        if self.mode != "fp32" and not self.error_feedback:
            s += "(no-ef)"
        if self.tail_mode != "fp32":
            s += f",tail_comm={self.tail_mode}"
        return s


@dataclasses.dataclass(frozen=True)
class ProbeSlot:
    """Per-layer health probe (engine telemetry layers mode)."""
    kind: str = "layer_health"

    def describe(self) -> str:
        return "health"


@dataclasses.dataclass(frozen=True)
class PipeSlot:
    """Table-driven pipeline schedule (parallel/pipe_schedule.py):
    `kind` = "interleaved" (virtual stages, combined backward) or "zbub"
    (zero-bubble B/W split); `virtual` chunks per physical stage.  The
    validated Schedule carries the compiled tick program alongside —
    the engine's step interprets it via pipeline.spmd_pipeline_table."""
    kind: str = "interleaved"
    virtual: int = 1
    stages: int = 0
    microbatches: int = 0

    def describe(self) -> str:
        return (f"pipe={self.kind}:{self.virtual}"
                f"[m={self.microbatches}]")


# ---------------------------------------------------------------------------
# --sched spec parsing (examples/common.py, ONE translation site)
# ---------------------------------------------------------------------------

def parse_sched_spec(spec: str) -> Dict[str, Any]:
    """Parse a `--sched` composition string into engine kwargs.

    e.g. "gather_prefetch=2,grad_buckets=4,grad_comm=int8,health,hpz"
    -> {"gather_prefetch": 2, "grad_buckets": 4, "grad_comm": "int8",
        "telemetry_layers": True, "hpz": True}.

    `grad_buckets`, `gather_groups` and `grad_comm` also accept the
    literal "auto" — resolved by `auto_comm_plan` against the mesh's
    DCN granule map at engine build.  `grad_comm_tail` / `hpz_comm`
    extend the codec vocabulary to the composed ZeRO-3 tail release and
    the hpZ secondary rebuild.

    `pipe=KIND[:V]` selects the pipeline schedule slot: `pipe=gpipe`,
    `pipe=1f1b`, `pipe=interleaved:2` (V virtual chunks per stage,
    default 2), `pipe=zbub[:V]` (zero-bubble B/W split, default V=1) —
    translated to `pipeline_schedule` / `pipeline_virtual` engine kwargs.

    `telemetry_layers` is not an engine kwarg — the caller upgrades its
    Telemetry to layers=True (examples/common.py does)."""
    out: Dict[str, Any] = {}
    int_keys = ("gather_prefetch", "gather_groups", "grad_buckets",
                "grad_comm_groups", "grad_comm_block")
    auto_ok = ("gather_groups", "grad_buckets", "grad_comm")
    mode_keys = ("grad_comm", "grad_comm_tail", "hpz_comm")
    pipe_kinds = ("gpipe", "1f1b", "interleaved", "zbub")
    for part in (p.strip() for p in spec.split(",") if p.strip()):
        if part == "health":
            out["telemetry_layers"] = True
            continue
        if part == "hpz":
            out["hpz"] = True
            continue
        if "=" not in part:
            raise ValueError(
                f"--sched element {part!r} is not 'key=value', 'health' "
                f"or 'hpz'"
            )
        key, val = (s.strip() for s in part.split("=", 1))
        if key == "pipe":
            kind, _, vtxt = val.partition(":")
            if kind not in pipe_kinds:
                raise ValueError(
                    f"--sched pipe must be one of {pipe_kinds} "
                    f"(optionally KIND:V), got {val!r}"
                )
            out["pipeline_schedule"] = kind
            if vtxt:
                out["pipeline_virtual"] = int(vtxt)
            elif kind == "interleaved":
                out["pipeline_virtual"] = 2
            continue
        if val == "auto" and key in auto_ok:
            out[key] = "auto"
        elif key in int_keys:
            out[key] = int(val)
        elif key in mode_keys:
            if val not in GRAD_COMM_MODES:
                raise ValueError(
                    f"--sched {key} must be one of {GRAD_COMM_MODES}, "
                    f"got {val!r}"
                )
            out[key] = val
        else:
            raise ValueError(f"unknown --sched key {key!r}")
    return out


# ---------------------------------------------------------------------------
# DCN-aware "auto" comm sizing + the tune_e2e plan bridge
# ---------------------------------------------------------------------------

def auto_comm_plan(*, n_shard: int, n_layer: int, shapes=None,
                   granule_of=None, block: int = DEFAULT_BLOCK,
                   max_buckets: int = 8,
                   overhead_tol: float = 0.10) -> Dict[str, Any]:
    """Resolve the "auto" comm knobs from the link hierarchy + modeled
    bytes — the DCN-aware sizing policy (ZeRO++ arXiv:2306.10209,
    EQuARX arXiv:2506.17615: quantized/bucketed collectives pay exactly
    when sized against the real link topology).

    Policy (each rule checkable against the measured
    `wire_bytes_by_link` split, tests/test_schedule.py):

      * grad_comm — "int8" whenever there IS a gradient collective
        (n_shard > 1): halves-to-quarters the wire on every link and the
        error-fed stochastic rounding keeps parity; "fp32" on a single
        rank (the collective does not exist).
      * grad_buckets — the LARGEST divisor of n_layer (capped at
        `max_buckets`, and at max(2, max_buckets // n_granules) on a
        hybrid mesh: every bucket sync crosses DCN, and DCN latency is
        per-collective) whose per-bucket padding keeps the modeled quant
        wire within `overhead_tol` of the monolithic sync.  More buckets
        = more backward overlap window; the tolerance is what stops tiny
        buckets from paying padding + scale overhead for it.
      * gather_inner — the intra-granule rank count (`ici`) on a hybrid
        mesh, so a 2-hop gather's fat first hop stays on ICI; None on a
        flat mesh (a 2-hop over uniform links moves the same bytes
        twice).  `build_schedule` applies it ONLY when the composition
        lowers to the single-slot prefetch program — the composed
        machine refuses 2-hop groups, so "auto" resolves to flat there
        instead of tripping the ScheduleConflictError.

    Pure function of static geometry (unit-testable without a mesh);
    returns the resolved knobs plus the modeled bytes behind them."""
    from .mesh import granule_geometry
    from .comm import modeled_wire_bytes

    n_gran, ici = granule_geometry(granule_of, n_shard)
    plan: Dict[str, Any] = {
        "n_granules": n_gran,
        "grad_comm": "int8" if n_shard > 1 else "fp32",
        "grad_buckets": 1,
        "gather_inner": (ici if n_gran > 1 and 2 <= ici < n_shard
                         and n_shard % ici == 0 else None),
    }
    if n_shard <= 1 or n_layer <= 1 or not shapes:
        return plan
    cap = max_buckets if n_gran <= 1 else max(2, max_buckets // n_gran)
    divisors = [k for k in range(1, min(n_layer, cap) + 1)
                if n_layer % k == 0]
    block_elems = sum(
        int(np.prod(s.shape)) for nm, s in shapes.items()
        if nm.startswith("h.")
    )
    if not block_elems:
        return plan
    mode = plan["grad_comm"]
    base = modeled_wire_bytes(block_elems, n_shard, mode, block=block)
    budget = (1.0 + overhead_tol) * base["quant_wire_bytes"]
    best_k, best_wire = 1, base["quant_wire_bytes"]
    for k in divisors:
        per = modeled_wire_bytes(
            block_elems // k, n_shard, mode, block=block
        )
        wire_k = k * per["quant_wire_bytes"]
        if wire_k <= budget:
            best_k, best_wire = k, wire_k
    plan["grad_buckets"] = best_k
    plan["modeled"] = {
        "grad_wire_bytes": float(best_wire),
        "grad_wire_bytes_monolithic": float(base["quant_wire_bytes"]),
        "fp32_allreduce_wire_bytes": base["fp32_allreduce_wire_bytes"],
        # flat DP: every grad collective spans all granules, so its
        # whole wire bills to DCN under the ledger's conservative
        # crossing rule (utils/hlo_comm.wire_link_split)
        "dcn_frac_est": 1.0 if n_gran > 1 else 0.0,
    }
    return plan


# the comm knobs a tune_e2e / auto plan may carry, in engine-kwarg
# spelling — ONE list shared by the bench comm phase, the AOT plan
# round-trip, and the tests
COMM_PLAN_KEYS = ("grad_comm", "grad_buckets", "grad_comm_tail",
                  "gather_groups", "gather_prefetch", "hpz", "hpz_comm")


def comm_plan_engine_kwargs(plan: Dict[str, Any]) -> Dict[str, Any]:
    """Filter a persisted tune_e2e plan down to the engine kwargs it
    carries (the AOT-cache round-trip seam: bench stores the winning
    plan via RuntimeAutoTuner.store_plan; a later run feeds it straight
    back into Zero3(**comm_plan_engine_kwargs(plan)))."""
    return {k: plan[k] for k in COMM_PLAN_KEYS
            if k in plan and plan[k] is not None}


# ---------------------------------------------------------------------------
# per-layer health probe (ProbeSlot; engine telemetry layers mode, ISSUE 5)
# ---------------------------------------------------------------------------

def _act_stats(x) -> jax.Array:
    """(2,) f32: [sum of squares, non-finite element count] of one layer's
    output activation.  Sums run over the LOGICAL array, so under sharded
    activations XLA inserts the cross-shard psum and every rank reports
    the same global numbers (the health_vector convention).  Inside a
    shard_map manual region the sums are LOCAL — the composed lowering
    psums the collected stats once at the end."""
    xf = x.astype(jnp.float32)
    return jnp.stack([
        jnp.sum(jnp.square(xf)),
        jnp.sum((~jnp.isfinite(xf)).astype(jnp.float32)),
    ])


@jax.custom_vjp
def layer_health_tap(x, probe):
    """Identity on `x`; the (4,) f32 `probe`'s COTANGENT smuggles this
    layer's health stats out of the step — [act sq-sum, act non-finite
    count, d(act) sq-sum, d(act) non-finite count].

    The GradBucketTap trick pointed at observability instead of
    collectives: the engine differentiates the loss w.r.t. a zeros
    (n_layer, 4) probe that rides the stacked scan tree (one (4,) row per
    layer, like the per-layer dropout keys), each layer's block output
    passes through this tap, and the "gradient" of the probe comes back
    as the per-layer activation/activation-gradient stats — computed
    INSIDE the compiled step, per layer, with no scan restructuring and
    no extra host transfers.  The first-NaN layer is read off the stats
    in one step instead of by bisection.  Forward stats are recomputed
    bit-exactly by the remat backward (they live inside the block's
    jax.checkpoint), so the fwd residual costs 2 floats per layer."""
    return x


def _lht_fwd(x, probe):
    return x, _act_stats(x)


def _lht_bwd(stats, g):
    return g, jnp.concatenate([stats, _act_stats(g)])


layer_health_tap.defvjp(_lht_fwd, _lht_bwd)

# probe row width: [act_sq, act_nonfinite, dact_sq, dact_nonfinite]
LAYER_PROBE_WIDTH = 4


class ProbeScan:
    """Probe-only lowering: the (n_layer, 4) probe rides the stacked scan
    tree (the model's block_fn taps every layer's output when the
    "health_probe" row is present) and the scan itself stays the plain
    GSPMD lax.scan — byte-identical to the pre-scheduler program."""

    def __init__(self, probe):
        self.probe = probe

    def scan(self, block, stacked, x, unroll=1):
        stacked = dict(stacked, health_probe=self.probe)

        def scan_body(x, bp):
            return block(x, bp), None

        x, _ = jax.lax.scan(scan_body, x, stacked, unroll=unroll)
        return x


# ---------------------------------------------------------------------------
# bucketed backward-overlapped release (GradSlot legacy lowering, ISSUE 3)
# ---------------------------------------------------------------------------

def _make_tap(reduce_fn):
    """Identity-forward custom_vjp whose BACKWARD runs `reduce_fn` on the
    cotangent: `reduce_fn(grad_chunk_tree, extras) -> (reduced_chunk_tree,
    extras_cotangent)`.  The reduced tree must match the chunk's leaf
    dtypes exactly (custom_vjp checks the bwd output against the primal
    avals); the extras cotangent is the smuggling channel — e.g. the new
    error-feedback residual rides out of the backward as the "gradient"
    of the residual slice that rode in."""
    @jax.custom_vjp
    def tap(chunk, extras):
        return chunk

    def fwd(chunk, extras):
        return chunk, extras

    def bwd(extras, g):
        return reduce_fn(g, extras)

    tap.defvjp(fwd, bwd)
    return tap


class GradBucketTap:
    """Per-bucket gradient release inside the model's layer scan.

    Built by the engine INSIDE its shard_map manual region over the data
    axis and handed to `model.apply(..., sched=self)`.  The model's
    layer loop calls `scan(block, stacked, x, unroll=...)`: the stacked
    (L, ...) leaves reshape to (K, L/K, ...), an outer lax.scan runs over
    the K buckets with the layer scan inside, and each bucket's param
    slice passes through an identity `custom_vjp` whose backward runs
    this bucket's gradient collective.  That places the reduce for bucket
    k INSIDE the backward scan body — issued while buckets k-1..0 still
    have backward compute in flight for XLA's latency-hiding scheduler /
    collective pipeliner to overlap — the reference's per-parameter
    backward-hook all-reduce (reference ddp/module.py:36-78) and its
    unshipped "communication bucketing" TODO (reference README.md:66-71),
    expressed in XLA terms.

    `extras` is a dict of per-bucket float32 side inputs, every leaf with
    leading dim K, sliced by the outer scan and fed through the tap:

      "res"  — (K, bucket_pad) error-feedback residual slices; the tap's
               cotangent for it IS the new residual (smuggled out of the
               backward through the vjp).
      "acc"  — accumulated-gradient prefix chunks (grad accumulation:
               the first A-1 microbatches sum locally, the final
               microbatch's taps add the prefix before the one collective
               per bucket).
      "rng"  — stochastic-rounding key rows BITCAST to f32 (an integer
               tap input would need a float0 cotangent; a 2-word bitcast
               keeps the tap all-float).

    Integer leaves of the stacked tree itself (the per-layer dropout
    keys) stay OUTSIDE the tap for the same float0 reason."""

    def __init__(self, n_buckets: int, reduce_fn, extras=None):
        self.n_buckets = int(n_buckets)
        self._tap = _make_tap(reduce_fn)
        self.extras = extras or {}

    def scan(self, block, stacked, x, unroll=1):
        """Drop-in replacement for the model's plain layer scan: same
        (x, stacked) -> x contract, buckets of layers instead of single
        layers as the outer iteration."""
        k = self.n_buckets

        def resh(a):
            return a.reshape((k, a.shape[0] // k) + a.shape[1:])

        stacked_b = jax.tree.map(resh, stacked)

        def bucket_body(carry, xs):
            bp, ex = xs
            tappable = {
                n: v for n, v in bp.items()
                if jnp.issubdtype(v.dtype, jnp.floating)
            }
            tapped = self._tap(tappable, ex)
            bp = dict(bp, **tapped)

            def layer(c, lp):
                return block(c, lp), None

            c, _ = jax.lax.scan(layer, carry, bp, unroll=unroll)
            return c, None

        x, _ = jax.lax.scan(bucket_body, x, (stacked_b, self.extras))
        return x


# ---------------------------------------------------------------------------
# ZeRO-3 layer-ahead weight-gather prefetch (GatherSlot legacy lowering,
# ISSUE 4)
# ---------------------------------------------------------------------------

class GatherPrefetchScan:
    """Layer-ahead weight-gather prefetch for the ZeRO-3 block scan.

    Under plain ZeRO-3 the per-layer all-gather is GSPMD-implicit: the
    scan slices layer k's sharded weights and the partitioner gathers
    them AT THE TOP of body k — serialized in front of layer k's compute
    (DeepSpeed ships stage-3 parameter prefetch for exactly this cost;
    ZeRO++ qwZ quantizes the same gathers).  This scan makes the gather
    explicit and moves it one-plus layers AHEAD: body k issues layer
    k+(K-1)'s gather (a sharding constraint to the gathered layout — or
    the 2-hop shard_map schedule under `groups`) while layer k computes
    from the double buffer carried through the scan, so the latency-
    hiding scheduler can overlap gather wire with block compute.  At most
    K layers' gathered weights are live (K=2 = classic double buffer).

    The SAME structure runs on the backward: the whole prefetched stack
    is an identity-story `custom_vjp` (the GradBucketTap machinery, the
    symmetric twin on the forward/weight side) whose bwd is a reverse
    scan over layers — recompute layer k's block from the stashed input
    activation (remat, policy "nothing": only the L per-layer activations
    are saved, same as the plain remat stash) while prefetching layer
    k-(K-1), and constraining each layer's dW to the sharded slice spec
    so the grad reduce-scatter stays in-loop too.  Integer leaves of the
    stacked tree (the per-layer dropout keys) cross the custom_vjp
    boundary bitcast to f32 (the PR-3 tap rule: no float0 cotangents),
    and ride the scan un-prefetched — they are replicated scalars, there
    is no wire to hide.

    `groups=m` (engine `gather_groups`) runs the hierarchical 2-hop
    gather, mirroring `grad_comm_groups`: hop 1 all-gathers each leaf's
    shards WITHIN m consecutive ranks at the resting precision (f8 when
    the leaf is `gather_quant`-quantized), dequantizes the group chunk
    once, hop 2 all-gathers the compute-dtype chunks ACROSS groups —
    "fp8 intra-group, bf16 inter-group" on a bf16-compute model.  Leaves
    the ZeRO layout left replicated (norm weights on small models,
    biases, scales) skip the shard_map: they have no shards to gather.

    Cost model: each pass (fwd, and the bwd re-forward) issues K-1 extra
    clamped end-of-scan gathers — (L+K-1)/L of the on-demand gather wire
    (priced in utils/profiling.comm_report); `utils/hlo_comm.
    overlap_report` measures the placement (`gather_overlap_frac`)."""

    def __init__(self, depth: int, mesh, gather_specs, shard_specs, *,
                 groups: Optional[int] = None, data_axis: str = "data",
                 compute_dtype=jnp.bfloat16):
        if depth < 2:
            raise ValueError(
                f"GatherPrefetchScan needs depth >= 2 (depth-1 layers of "
                f"lookahead), got {depth}"
            )
        self.depth = int(depth)
        self.mesh = mesh
        self.gather_specs = dict(gather_specs or {})
        self.shard_specs = dict(shard_specs or {})
        self.groups = int(groups) if groups else None
        self.data_axis = data_axis
        self.cd = compute_dtype

    # -- one layer's gather --------------------------------------------------

    def _shard_dim(self, name: str) -> Optional[int]:
        """Index of the ZeRO data-sharded dim in the SLICED leaf, or None
        when the layout left it replicated (nothing to gather)."""
        spec = self.shard_specs.get(name)
        if spec is None:
            return None
        for i, ax in enumerate(spec):
            if ax == self.data_axis or (
                isinstance(ax, tuple) and self.data_axis in ax
            ):
                return i
        return None

    def _dequant_names(self, sliced) -> Tuple[str, ...]:
        """Leaves the 2-hop gather dequantizes between hops: quantized
        (a '#scale' partner exists) AND data-sharded (they go through the
        shard_map; replicated leaves never enter it)."""
        if not self.groups:
            return ()
        return tuple(sorted(
            n for n in sliced
            if n + "#scale" in sliced and self._shard_dim(n) is not None
        ))

    def _gather(self, sliced):
        """One layer's float leaves, sharded slice -> gathered block-param
        tree.  Flat path: a sharding constraint per leaf to its gathered
        spec (f8 + scale kept; the block's `_bw` dequantizes after the
        gather, exactly the on-demand fp8 contract).  2-hop path: explicit
        shard_map all-gathers; quantized leaves come back DEQUANTIZED in
        compute dtype with their scales dropped (hop 2 moved the
        dequantized chunks)."""
        if not self.groups:
            out = {}
            for name, v in sliced.items():
                spec = self.gather_specs.get(name)
                if spec is not None:
                    v = jax.lax.with_sharding_constraint(
                        v, NamedSharding(self.mesh, spec))
                out[name] = v
            return out

        n = self.mesh.shape[self.data_axis]
        inner = self.groups
        intra, inter = _hier_groups(n, inner)
        cd = self.cd
        dq = set(self._dequant_names(sliced))
        sharded, dims, scales, out = {}, {}, {}, {}
        for name, v in sliced.items():
            if name.endswith("#scale") and name[: -len("#scale")] in dq:
                continue  # consumed by its weight's inter-hop dequant
            d = self._shard_dim(name)
            if d is None:
                out[name] = v  # replicated at rest: no shards to gather
                continue
            sharded[name] = v
            dims[name] = d
            if name in dq:
                scales[name] = sliced[name + "#scale"]
        if not sharded:
            return out

        @jax.named_scope("tds.gather")
        def local(vals, scs):
            res = {}
            for name, v in vals.items():
                dim = dims[name]
                g1 = jax.lax.all_gather(
                    v, self.data_axis, axis=dim, tiled=True,
                    axis_index_groups=intra)
                s = scs.get(name)
                if s is not None:
                    # dequantize ONCE per group chunk; hop 2 moves the
                    # compute-dtype values (fp8 intra, bf16 inter)
                    g1 = g1.astype(cd) * s.astype(cd)
                res[name] = jax.lax.all_gather(
                    g1, self.data_axis, axis=dim, tiled=True,
                    axis_index_groups=inter)
            return res

        vspecs = {
            name: P(*(self.data_axis if i == dims[name] else None
                      for i in range(v.ndim)))
            for name, v in sharded.items()
        }
        sspecs = {name: P() for name in scales}
        ospecs = {name: P() for name in sharded}
        gathered = jax.shard_map(
            local, mesh=self.mesh, in_specs=(vspecs, sspecs),
            out_specs=ospecs, check_vma=False,
        )(sharded, scales)
        out.update(gathered)
        return out

    def _pullback(self, dwg, sfk):
        """Map the block-vjp cotangent (gathered structure) back onto the
        sliced stacked-tree structure.  Flat path: identity.  2-hop path:
        the dequant multiply lived inside the gather, so dequantized
        leaves' compute-dtype cotangents pull back through it here
        (d_f8 = dw * scale, cast; scale cotangent zero — it is
        stop-gradiented upstream by stacked_compute_params)."""
        dq = self._dequant_names(sfk)
        if not dq:
            return dict(dwg)
        out = dict(dwg)
        for name in dq:
            s = sfk[name + "#scale"]
            out[name] = (
                dwg[name].astype(jnp.float32) * s.astype(jnp.float32)
            ).astype(sfk[name].dtype)
            out[name + "#scale"] = jnp.zeros_like(s)
        return out

    def _constrain_shard(self, name: str, g):
        """Pin one layer's dW cotangent to the sharded slice layout so the
        grad reduce-scatter is emitted INSIDE the backward scan body (the
        on-demand path's property, kept)."""
        spec = self.shard_specs.get(name)
        if spec is None:
            return g
        return jax.lax.with_sharding_constraint(
            g, NamedSharding(self.mesh, spec))

    # -- the scan ------------------------------------------------------------

    def scan(self, block, stacked, x, unroll=1):
        """Drop-in replacement for the model's plain layer scan: same
        (x, stacked) -> x contract, with layer k+(K-1)'s gather issued in
        body k on the forward AND the reverse (remat backward) scan."""
        fkeys = sorted(
            n for n, v in stacked.items()
            if not jnp.issubdtype(v.dtype, jnp.integer)
        )
        ikeys = sorted(n for n in stacked if n not in set(fkeys))
        idtypes = {n: stacked[n].dtype for n in ikeys}
        L = int(jax.tree.leaves(stacked)[0].shape[0])
        look = self.depth - 1
        if look >= L:
            raise ValueError(
                f"gather_prefetch={self.depth} holds more layers than the "
                f"model has (n_layer={L})"
            )

        def slice_f(sf, i):
            return {
                n: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False)
                for n, a in sf.items()
            }

        def int_slices(si_b, i):
            return {
                n: jax.lax.bitcast_convert_type(
                    jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False),
                    idtypes[n])
                for n, a in si_b.items()
            }

        def init_buf(sf, idxs):
            slots = [self._gather(slice_f(sf, i)) for i in idxs]
            return jax.tree.map(lambda *xs: jnp.stack(xs), *slots)

        def shift_in(buf, new):
            return jax.tree.map(
                lambda b, nw: jnp.concatenate([b[1:], nw[None]]), buf, new)

        def fwd_scan(sf, si_b, x0, stash):
            buf = init_buf(sf, list(range(look)))

            def body(carry, k):
                x, buf = carry
                # issue layer k+look's gather FIRST; nothing in this body
                # consumes it, so its wire can hide behind block(k)
                nxt = self._gather(
                    slice_f(sf, jnp.minimum(k + look, L - 1)))
                w = jax.tree.map(lambda b: b[0], buf)
                y = block(x, dict(w, **int_slices(si_b, k)))
                return (y, shift_in(buf, nxt)), (x if stash else None)

            (y, _), xs = jax.lax.scan(
                body, (x0, buf), jnp.arange(L), unroll=unroll)
            return y, xs

        @jax.custom_vjp
        def run(sf, si_b, x0):
            y, _ = fwd_scan(sf, si_b, x0, stash=False)
            return y

        def run_fwd(sf, si_b, x0):
            y, xs = fwd_scan(sf, si_b, x0, stash=True)
            # residuals: the SHARDED stacked tree (no copy) + the L
            # per-layer input activations — the plain remat stash
            return y, (sf, si_b, xs)

        def run_bwd(res, dy):
            sf, si_b, xs = res
            buf = init_buf(sf, [L - 1 - i for i in range(look)])

            def body(carry, inp):
                dx, buf = carry
                x_k, k = inp
                nxt = self._gather(
                    slice_f(sf, jnp.maximum(k - look, 0)))
                w = jax.tree.map(lambda b: b[0], buf)
                ints = int_slices(si_b, k)

                def f(x_, wf):
                    return block(x_, dict(wf, **ints))

                # remat: recompute layer k's block from the stashed input
                _, vjp = jax.vjp(f, x_k, w)
                dx_new, dwg = vjp(dx)
                dw = self._pullback(dwg, slice_f(sf, k))
                dw = {n: self._constrain_shard(n, g)
                      for n, g in dw.items()}
                return (dx_new, shift_in(buf, nxt)), dw

            (dx, _), dws = jax.lax.scan(
                body, (dy, buf), (xs, jnp.arange(L)), reverse=True,
                unroll=unroll)
            return dws, jax.tree.map(jnp.zeros_like, si_b), dx

        run.defvjp(run_fwd, run_bwd)
        return run(
            {n: stacked[n] for n in fkeys},
            {n: jax.lax.bitcast_convert_type(stacked[n], jnp.float32)
             for n in ikeys},
            x,
        )


# ---------------------------------------------------------------------------
# hpZ group geometry
# ---------------------------------------------------------------------------

def hpz_groups(granule_of: Dict[int, int], n: int):
    """(intra, inter, ici, n_gran) axis_index_groups for hpZ over a data
    axis of `n` ranks whose DCN granule is `granule_of[rank]`
    (parallel/mesh.granule_map on the mesh-flat order, or the CPU
    emulation override).

    Requires equal-sized CONTIGUOUS granules (rank r in granule r//ici) —
    the layout `make_mesh` builds (DCN carries the leading data axis).
    intra = the ranks of one slice (the in-scan gather group, ICI only);
    inter = same intra-position ranks across slices (the ONE top-level
    secondary-partition rebuild, the only DCN hop)."""
    grans = [granule_of.get(r) for r in range(n)]
    if any(g is None for g in grans):
        raise ScheduleConflictError(
            f"gather slot (hpz): granule map covers {sorted(granule_of)} "
            f"but the data axis has ranks 0..{n - 1}"
        )
    n_gran = len(set(grans))
    if n_gran < 2:
        raise ScheduleConflictError(
            "gather slot (hpz): the mesh has a single DCN granule — "
            "every gather is already intra-slice; hpz would only add "
            "a redundant secondary partition"
        )
    if n % n_gran:
        raise ScheduleConflictError(
            f"gather slot (hpz): {n_gran} granules must evenly divide "
            f"the data axis ({n} ranks)"
        )
    ici = n // n_gran
    if grans != [r // ici for r in range(n)]:
        raise ScheduleConflictError(
            f"gather slot (hpz): granules must be contiguous equal "
            f"blocks of the data axis (expected rank r in granule "
            f"r//{ici}, got {grans})"
        )
    intra = [[g * ici + l for l in range(ici)] for g in range(n_gran)]
    inter = [[g * ici + l for g in range(n_gran)] for l in range(ici)]
    return intra, inter, ici, n_gran


# ---------------------------------------------------------------------------
# the compiled Schedule + builder
# ---------------------------------------------------------------------------

_LOWERINGS = ("plain", "probe", "bucket", "quant_mono", "prefetch",
              "composed")


@dataclasses.dataclass
class Schedule:
    """A validated slot composition + its chosen lowering.  Built once at
    engine construction by `build_schedule`; the engine routes its step
    through the matching executor (`bucketed_step`, `monolithic_quant_
    step`, `composed_step`) or passes the executor object straight into
    `model.apply(sched=...)` (probe / prefetch lowerings)."""
    gather: Optional[GatherSlot] = None
    grad: Optional[GradSlot] = None
    probe: Optional[ProbeSlot] = None
    pipe: Optional[PipeSlot] = None
    # the compiled tick table (pipe_schedule.PipeProgram) when a pipe
    # slot is declared — validated once here, interpreted per step by
    # pipeline.spmd_pipeline_table; its bubble_frac is the telemetry
    # gauge's source of truth
    pipe_program: Optional[object] = None
    lowering: str = "plain"
    # grad-slot geometry (parallel/comm.bucket_layout) when a grad slot
    # is declared; None otherwise
    layout: Optional[dict] = None
    # error-feedback residual row length (0 = no residual): composed
    # ZeRO-3 with a fp32 tail drops the tail slice (the tail
    # reduce-scatters at full precision through the differentiable
    # gather's transpose); a quantized tail (GradSlot.tail_mode) keeps
    # its own slice, laid out after the bucket slices like stages 0-2
    residual_len: int = 0
    # hpZ geometry: (intra, inter, ici, n_gran) or None
    hpz_geom: Optional[tuple] = None
    # the resolved auto_comm_plan when any knob arrived as "auto"
    # (observability: bench/telemetry report what the policy picked)
    auto_plan: Optional[dict] = None

    @property
    def slots(self):
        return [s for s in (self.gather, self.grad, self.probe,
                            self.pipe)
                if s is not None]

    def describe(self) -> str:
        """Composition string — stable across knob spellings; used by
        engine.describe() and the bench `_config_fingerprint` sched arm."""
        if not self.slots:
            return "plain"
        return "+".join(s.describe() for s in self.slots) + \
            f"@{self.lowering}"


def build_schedule(
    *, model, stage: int, n_shard: int, busy_axes, accum_steps: int,
    scan_unroll, grad_comm: str = "fp32",
    grad_comm_block: int = DEFAULT_BLOCK,
    grad_comm_groups: Optional[int] = None,
    grad_comm_error_feedback: bool = True, grad_buckets: int = 1,
    grad_comm_tail: str = "fp32",
    gather_prefetch: int = 0, gather_groups: Optional[int] = None,
    hpz: bool = False, hpz_comm: str = "fp32",
    granule_of: Optional[Dict[int, int]] = None,
    telemetry_layers: bool = False, pipeline: bool = False,
    pipe_schedule: Optional[str] = None, pipe_stages: int = 0,
    pipe_virtual: int = 1, pipe_microbatches: int = 0,
) -> Schedule:
    """Translate engine knobs into slot declarations, validate the
    composition ONCE, and pick the lowering.

    `grad_comm`, `grad_buckets` and `gather_groups` may arrive as the
    literal "auto": resolved here by `auto_comm_plan` against the DCN
    granule map before slots are declared (the resolved plan rides the
    Schedule as `auto_plan`).

    Legacy single-slot requests lower to their pre-scheduler programs
    (HLO byte-identical, pinned by tests/test_schedule.py); any genuine
    composition lowers to the merged `composed_step` machine.  Genuinely
    inexpressible combinations raise `ScheduleConflictError` naming the
    conflicting SLOT."""
    n_layer = int(
        getattr(getattr(model, "config", None), "n_layer", 0) or 0
    )
    gq = bool(getattr(getattr(model, "config", None), "gather_quant",
                      None))

    # ---- resolve "auto" knobs against the link hierarchy -------------------
    auto_plan = None
    if "auto" in (grad_comm, grad_buckets, gather_groups):
        try:
            shapes = model.param_shapes()
        except Exception:
            shapes = None
        auto_plan = auto_comm_plan(
            n_shard=n_shard, n_layer=n_layer, shapes=shapes,
            granule_of=granule_of, block=int(grad_comm_block),
        )
        if grad_comm == "auto":
            grad_comm = auto_plan["grad_comm"]
        if grad_buckets == "auto":
            # bucketing exists to pipeline the QUANTIZED syncs; a plain
            # fp32 all-reduce program has no bucket machinery to size
            grad_buckets = (auto_plan["grad_buckets"]
                            if grad_comm != "fp32" else 1)
        if gather_groups == "auto":
            # the 2-hop gather only exists in the single-slot prefetch
            # lowering; under any composition "auto" means flat, not a
            # ScheduleConflictError
            legacy_prefetch = (
                gather_prefetch > 1 and not hpz
                and not telemetry_layers
                and grad_comm == "fp32"
                and (grad_buckets in (0, 1))
            )
            gather_groups = (auto_plan["gather_inner"]
                             if legacy_prefetch else None)

    # ---- tail / hpz codec preconditions (loud, before slots settle) --------
    if grad_comm_tail not in GRAD_COMM_MODES:
        raise ValueError(
            f"grad_comm_tail must be one of {GRAD_COMM_MODES}, "
            f"got {grad_comm_tail!r}"
        )
    if hpz_comm not in GRAD_COMM_MODES:
        raise ValueError(
            f"hpz_comm must be one of {GRAD_COMM_MODES}, "
            f"got {hpz_comm!r}"
        )
    if hpz_comm != "fp32" and not hpz:
        raise ValueError(
            "hpz_comm quantizes the hpZ secondary rebuild; it needs "
            "hpz=True"
        )
    if grad_comm_tail != "fp32":
        if stage < 3:
            raise ValueError(
                "grad_comm_tail is a ZeRO-3 knob: at stages 0-2 the "
                "non-block tail already syncs through the grad_comm "
                "codec — drop grad_comm_tail or set grad_comm="
            )
        if grad_comm == "fp32":
            raise ValueError(
                "grad_comm_tail composes with a quantized grad slot "
                "(the tail shares the codec machinery and the residual "
                "row); set grad_comm='int8'/'fp8' first"
            )

    # ---- declare slots from the knobs --------------------------------------
    gather = None
    if hpz or gather_prefetch > 1:
        gather = GatherSlot(
            prefetch=max(int(gather_prefetch) or 0, 1),
            groups=gather_groups, hpz=bool(hpz),
            hpz_mode=str(hpz_comm),
        )
    grad = None
    if grad_buckets > 1 or grad_comm != "fp32":
        grad = GradSlot(
            buckets=max(int(grad_buckets), 1), mode=grad_comm,
            block=int(grad_comm_block), groups=grad_comm_groups,
            error_feedback=bool(grad_comm_error_feedback),
            tail_mode=str(grad_comm_tail),
        )
    probe = ProbeSlot() if telemetry_layers else None
    # ZeRO-3 with a grad slot needs the explicit in-region gathers even
    # when no prefetch was asked for: declare the on-demand gather slot
    # (prefetch=1) implicitly — the lift of the old "stages 0-2" refusal
    if stage >= 3 and grad is not None and gather is None:
        gather = GatherSlot(prefetch=1)

    # ---- pipe slot: table-driven schedules validate + compile here ---------
    if pipe_schedule in ("interleaved", "zbub"):
        pipe = PipeSlot(
            kind=pipe_schedule, virtual=max(int(pipe_virtual), 1),
            stages=int(pipe_stages),
            microbatches=int(pipe_microbatches) or int(pipe_stages),
        )
        # the table executor runs the whole loss inside its own
        # partial-manual scan: the in-scan gather/grad/probe machinery
        # of the composed step does not exist there (yet) — refuse each
        # pair by name rather than silently dropping a slot
        for other in (s for s in (gather, grad, probe) if s is not None):
            raise ScheduleConflictError(
                f"pipe slot ({pipe.describe()}) conflicts with the "
                f"{other.describe()} slot: the table-driven pipeline "
                f"computes its gradients explicitly inside the tick "
                f"scan, which does not thread the in-scan "
                f"release/gather/probe machinery — drop one of the "
                f"two slots"
            )
        if not getattr(model, "supports_pipe_table", False):
            raise ScheduleConflictError(
                f"pipe slot ({pipe.describe()}): "
                f"{type(model).__name__} does not support table-driven "
                f"pipeline schedules (supports_pipe_table=False — e.g. "
                f"the MoE aux loss would need to ride every F tick and "
                f"replay in W's re-linearization); use "
                f"pipeline_schedule='1f1b'"
            )
        busy = [ax for ax in busy_axes
                if ax is not None and ax != "pipe"]
        if busy:
            raise ScheduleConflictError(
                f"pipe slot ({pipe.describe()}): the table executor is "
                f"manual over the pipe axis only (data stays GSPMD) — "
                f"it does not compose with active axes {busy}; use "
                f"pipeline_schedule='1f1b' for seq parallelism"
            )
        if n_layer and n_layer % (pipe.stages * pipe.virtual):
            raise ScheduleConflictError(
                f"pipe slot ({pipe.describe()}): n_layer={n_layer} not "
                f"divisible by stages*virtual="
                f"{pipe.stages}*{pipe.virtual}"
            )
        from .pipe_schedule import build_pipe_program
        try:
            prog = build_pipe_program(
                pipe.stages, pipe.virtual, pipe.microbatches,
                split_w=(pipe.kind == "zbub"),
                n_layer=n_layer or None,
            )
        except ValueError as e:
            raise ScheduleConflictError(
                f"pipe slot ({pipe.describe()}): {e}"
            ) from e
        return Schedule(pipe=pipe, pipe_program=prog, lowering="pipe")

    if gather is None and grad is None and probe is None:
        return Schedule(lowering="plain")

    # ---- single-feature inert fallbacks (1-device data axis) ---------------
    if n_shard <= 1:
        if grad is not None:
            warnings.warn(
                f"grad slot ({grad.describe()}) is inert on a 1-device "
                "data axis (there is no gradient collective); running "
                "the exact unscheduled path", stacklevel=3,
            )
            grad = None
        if gather is not None:
            warnings.warn(
                f"gather slot ({gather.describe()}) is inert on a "
                "1-device data axis (there is no weight gather); running "
                "the on-demand path", stacklevel=3,
            )
            gather = None
        if probe is None:
            return Schedule(lowering="plain")

    slots = [s for s in (gather, grad, probe) if s is not None]
    # a bucketed grad slot over fp8-quantized stacked leaves must run
    # the composed machine even solo: the legacy tap would put e4m3
    # cotangents on the bucket collectives (the refusal this PR lifts),
    # while the composed backward accumulates dW in f32 before release
    multi = (len(slots) > 1
             or (gather is not None
                 and (gather.hpz or gather.prefetch == 1))
             or (grad is not None and grad.buckets > 1 and gq))

    # ---- composition validation (the ONE refusal path) ---------------------
    if multi:
        if accum_steps > 1:
            raise ScheduleConflictError(
                f"the composed schedule "
                f"({'+'.join(s.describe() for s in slots)}) does not "
                f"support accum_steps={accum_steps} yet — prefix "
                f"microbatches would bypass the probe/gather slots; "
                f"drop a slot or set accum_steps=1"
            )
        if gather is not None and gather.groups:
            raise ScheduleConflictError(
                f"gather slot: the 2-hop gather (gather_groups="
                f"{gather.groups}) is only emitted by the single-slot "
                f"prefetch lowering; it conflicts with "
                f"{'+'.join(s.describe() for s in slots if s is not gather)}"
            )
        if grad is not None and n_layer and n_layer % grad.buckets:
            raise ValueError(
                f"grad_buckets={grad.buckets} must divide "
                f"n_layer={n_layer} (equal layers per bucket is what "
                "keeps the buckets size-balanced and the scan body "
                "uniform)"
            )
        # MoE-style models sit out: their scan carries an aux-loss
        # accumulator the merged scan bodies do not thread
        for s, flag in ((gather, "gather_prefetch_capable"),
                        (grad, "grad_bucket_capable"),
                        (probe, "layer_health_capable")):
            if s is not None and not getattr(model, flag, False):
                raise ScheduleConflictError(
                    f"{type(model).__name__} cannot run the "
                    f"{s.describe()} slot through the composed scan "
                    f"({flag}=False — e.g. the MoE scan carries an "
                    f"aux-loss accumulator the merged scan body does "
                    f"not thread)"
                )


    # ---- slot-level validation ---------------------------------------------
    busy = [ax for ax in busy_axes if ax is not None]
    if probe is not None:
        if pipeline:
            raise ValueError(
                "telemetry layers mode rides the layer scan; it does "
                "not compose with the pipeline forward "
                "(pipeline_parallel / pipeline_schedule='1f1b')"
            )
        if not getattr(model, "layer_health_capable", False):
            raise ValueError(
                f"{type(model).__name__} does not thread the per-layer "
                "health probe through its layer scan "
                "(layer_health_capable=False)"
            )
        if not n_layer:
            raise ValueError(
                "telemetry layers mode needs a layered model "
                "(config.n_layer)"
            )
    if grad is not None:
        if grad.mode not in GRAD_COMM_MODES:
            raise ValueError(
                f"grad_comm must be one of {GRAD_COMM_MODES}, "
                f"got {grad.mode!r}"
            )
        if busy:
            raise ValueError(
                f"the grad slot needs a pure data-parallel mesh (the "
                f"explicit schedule replays the model inside a shard_map "
                f"over the data axis); active axes: {busy}"
            )
        if grad.buckets > 1 and not getattr(
                model, "grad_bucket_capable", False):
            raise ValueError(
                f"{type(model).__name__} does not thread the bucketed "
                "grad-release tap through its layer scan "
                "(grad_bucket_capable=False)"
            )
        if grad.groups is not None and (
            grad.groups < 2 or grad.groups >= n_shard
            or n_shard % grad.groups
        ):
            raise ValueError(
                f"grad_comm_groups={grad.groups} must be a proper "
                f"divisor of the data-axis size {n_shard} (>= 2)"
            )
    if gather is not None:
        if stage < 3:
            raise ValueError(
                "the gather slot (gather_prefetch / hpz) requires ZeRO-3 "
                "(stages 0-2 keep params replicated/gathered once — "
                "there is no per-layer weight gather to schedule)"
            )
        if not getattr(model, "gather_prefetch_capable", False):
            raise ValueError(
                f"{type(model).__name__} does not thread the scheduled "
                "weight-gather scan through its layer loop "
                "(gather_prefetch_capable=False)"
            )
        if busy:
            raise ValueError(
                f"the gather slot needs a pure data-parallel mesh; "
                f"active axes: {busy}"
            )
        if scan_unroll is True or scan_unroll not in (1, False):
            raise ValueError(
                "the gather slot rides the layer scan; it cannot "
                "combine with scan_unroll != 1"
            )
        if n_layer and gather.prefetch > n_layer:
            raise ValueError(
                f"gather_prefetch={gather.prefetch} holds more layers "
                f"than the model has (n_layer={n_layer})"
            )
        if gather.groups is not None and (
            gather.groups < 2 or gather.groups >= n_shard
            or n_shard % gather.groups
        ):
            raise ValueError(
                f"gather_groups={gather.groups} must be a proper "
                f"divisor of the data-axis size {n_shard} (>= 2)"
            )

    # ---- hpZ geometry -------------------------------------------------------
    geom = None
    if gather is not None and gather.hpz:
        if granule_of is None:
            raise ScheduleConflictError(
                "gather slot (hpz): no DCN granule map — the mesh spans "
                "a single slice/process (parallel/mesh.granule_map "
                "returned None) and no granule_of= override was given"
            )
        geom = hpz_groups(granule_of, n_shard)

    # ---- pick the lowering --------------------------------------------------
    layout = None
    residual_len = 0
    if grad is not None:
        shapes = model.param_shapes()
        stack_dims = [s.shape[0] for nm, s in shapes.items()
                      if nm.startswith("h.")]
        if grad.buckets > 1 and not stack_dims:
            raise ValueError(
                "grad_buckets needs a stacked-block model (no 'h.*' "
                "leaves to bucket by layer)"
            )
        if grad.buckets > 1 or multi:
            layout = bucket_layout(
                shapes, stack_dims[0], grad.buckets, n_shard, grad.block
            )
        if grad.mode != "fp32" and grad.error_feedback:
            if layout is not None:
                residual_len = grad.buckets * layout["bucket_pad"]
                if stage < 3 or grad.tail_mode != "fp32":
                    residual_len += layout["tail_pad"]
                # composed ZeRO-3 with a fp32 tail: the non-block tail
                # reduce-scatters at full precision through the
                # differentiable gather's transpose — no tail residual
                # slice.  grad_comm_tail routes it through the quantized
                # sync instead, with its own error-feedback slice laid
                # out after the bucket slices (the stages-0-2 layout).
            else:
                total = sum(int(np.prod(s.shape))
                            for s in shapes.values())
                residual_len = padded_size(total, n_shard, grad.block)

    if multi:
        lowering = "composed"
    elif probe is not None:
        lowering = "probe"
    elif grad is not None:
        lowering = "bucket" if grad.buckets > 1 else "quant_mono"
    elif gather is not None:
        lowering = "prefetch"
    else:
        lowering = "plain"
    return Schedule(gather=gather, grad=grad, probe=probe,
                    lowering=lowering, layout=layout,
                    residual_len=residual_len, hpz_geom=geom,
                    auto_plan=auto_plan)


# ---------------------------------------------------------------------------
# step executors — legacy single-slot lowerings (moved from engine.py,
# traced programs unchanged: the pre-scheduler HLO pins hold)
# ---------------------------------------------------------------------------

def monolithic_quant_step(eng, state, idx, targets, rng, scale):
    """The grad_comm != "fp32" gradient phase (quant_mono lowering):
    local grads + explicit quantized collectives inside a shard_map over
    the data axis (parallel/comm.py module docstring for the schedule).

    The model replays with pctx=None — each device sees its batch
    shard and the full (replicated) params, exactly the SingleDevice
    forward — so no sharding constraint inside the manual region
    (the MoE pure-DP dispatch contract).  Microbatches accumulate
    LOCALLY and sync once: quantizing every microbatch would compound
    rounding error accum_steps-fold and multiply the collectives.

    Returns (loss scaled+replicated, grads reduced/UNSCALED in param
    dtypes, new (n, pad) residual or None)."""
    from . import comm as qcomm

    n = eng.n_shard
    mode = eng.grad_comm
    block = eng.grad_comm_block
    inner = eng.grad_comm_groups
    accum = eng.accum_steps
    params = state.params
    residual = state.grad_residual
    model = eng.model
    # stochastic-rounding stream (int8): fresh per step via the
    # optimizer counter, decorrelated per device inside the region
    qkey = None
    if mode == "int8":
        qkey = jax.random.fold_in(
            jax.random.PRNGKey(0x6C51), state.opt_state["step"]
        )
    has_res, has_rng = residual is not None, rng is not None
    has_qk, has_sc = qkey is not None, scale is not None

    def local(p, ix, tg, *rest):
        rest = list(rest)
        res = rest.pop(0) if has_res else None
        r = rest.pop(0) if has_rng else None
        qk = rest.pop(0) if has_qk else None
        sc = rest.pop(0) if has_sc else None
        di = jax.lax.axis_index("data")
        if r is not None:
            # per-device fold: masks stay independent across batch
            # shards (the GSPMD path draws one global mask stream)
            r = jax.random.fold_in(r, di)
        if qk is not None:
            qk = jax.random.fold_in(qk, di)

        def lloss(p_, ix_, tg_, r_):
            kw = {"rng": r_} if r_ is not None else {}
            loss = model.apply(p_, ix_, tg_, pctx=None, **kw)
            return loss * sc if sc is not None else loss

        if accum == 1:
            loss_l, g = jax.value_and_grad(lloss)(p, ix, tg, r)
        else:
            def body(carry, mb):
                al, ag = carry
                ix_, tg_, mb_i = mb
                mb_r = (jax.random.fold_in(r, mb_i)
                        if r is not None else None)
                l, g_ = jax.value_and_grad(lloss)(p, ix_, tg_, mb_r)
                ag = jax.tree.map(
                    lambda a, b: a + b.astype(jnp.float32), ag, g_
                )
                return (al + l, ag), None

            zg = jax.tree.map(
                lambda q: jnp.zeros(q.shape, jnp.float32), p
            )
            (loss_l, g), _ = jax.lax.scan(
                body, (jnp.zeros((), jnp.float32), zg),
                (ix, tg, jnp.arange(accum)),
            )
            loss_l = loss_l / accum
            g = jax.tree.map(
                lambda a, q: (a / accum).astype(q.dtype), g, p
            )
        if sc is not None:
            # unscale BEFORE the quantized sync: the residual must
            # carry true gradient units or a dynamic-scale change
            # between steps corrupts the compensation
            g = jax.tree.map(
                lambda x: (x.astype(jnp.float32)
                           * (1.0 / sc)).astype(x.dtype), g
            )
        res_row = res[0] if res is not None else None
        g_red, res_new = qcomm.quantized_grad_sync(
            g, res_row, "data", n, mode, block=block, rng=qk,
            inner=inner,
        )
        outs = [jax.lax.pmean(loss_l, "data"), g_red]
        if res is not None:
            outs.append(res_new[None])
        return tuple(outs)

    pspec = jax.tree.map(lambda _: P(), params)
    bspec = P(None, "data") if accum > 1 else P("data")
    in_specs = [pspec, bspec, bspec]
    args = [params, idx, targets]
    for cond, spec, val in (
        (has_res, P("data"), residual), (has_rng, P(), rng),
        (has_qk, P(), qkey), (has_sc, P(), scale),
    ):
        if cond:
            in_specs.append(spec)
            args.append(val)
    out_specs = [P(), jax.tree.map(lambda _: P(), params)]
    if has_res:
        out_specs.append(P("data"))
    out = jax.shard_map(
        local, mesh=eng.mesh, in_specs=tuple(in_specs),
        out_specs=tuple(out_specs), check_vma=False,
    )(*args)
    if has_res:
        return out
    return out[0], out[1], None


def bucketed_step(eng, state, idx, targets, rng, scale):
    """The grad_buckets > 1 gradient phase (bucket lowering): per-bucket
    release inside the backward scan (GradBucketTap).

    Like monolithic_quant_step, everything runs inside a shard_map
    over the data axis with the model replayed pctx=None (replicated
    params, local batch shard).  The K layer buckets reduce INSIDE
    the backward scan body — the tap's custom_vjp emits each bucket's
    collective as soon as that bucket's grads are final, while
    earlier buckets' backward compute is still in flight for the
    scheduler to hide the wire behind.  The non-block tail
    (wte/wpe/ln_f/lm_head) reduces once after value_and_grad: its
    grads finalize only when the whole backward is over (wte last of
    all), so there is no window to chase.

    grad_comm="fp32" buckets pmean in compute dtype (what the GSPMD
    all-reduce moves — comm_report round-4 finding); int8/fp8 buckets
    run the quantized schedule with per-bucket error-feedback
    residual slices laid out [b0 | ... | bK-1 | tail] in
    TrainState.grad_residual (the new residual is smuggled out of the
    backward as the tap's cotangent for the slice that rode in).
    Microbatches accumulate LOCALLY and the buckets fire only on the
    final microbatch — the accumulated prefix rides into the taps as
    the "acc" extra, so the one collective per bucket reduces the
    full mean gradient.

    Returns (loss scaled+replicated, grads reduced/UNSCALED in param
    dtypes, new (n, pad) residual or None)."""
    from . import comm as qcomm

    n = eng.n_shard
    mode = eng.grad_comm
    blk = eng.grad_comm_block
    inner = eng.grad_comm_groups
    accum = eng.accum_steps
    kb = eng.grad_buckets
    lay = eng._bucket_layout
    bpad = lay["bucket_pad"]
    lb = lay["layers_per_bucket"]
    tail_names = lay["tail_names"]
    params = state.params
    residual = state.grad_residual
    model = eng.model
    cd = getattr(
        getattr(model, "config", None), "compute_dtype", jnp.float32
    )
    qkey = None
    if mode == "int8":
        qkey = jax.random.fold_in(
            jax.random.PRNGKey(0x6C51), state.opt_state["step"]
        )
    has_res, has_rng = residual is not None, rng is not None
    has_qk, has_sc = qkey is not None, scale is not None

    def local(p, ix, tg, *rest):
        rest = list(rest)
        res = rest.pop(0) if has_res else None
        r = rest.pop(0) if has_rng else None
        qk = rest.pop(0) if has_qk else None
        sc = rest.pop(0) if has_sc else None
        di = jax.lax.axis_index("data")
        if r is not None:
            r = jax.random.fold_in(r, di)
        if qk is not None:
            qk = jax.random.fold_in(qk, di)
        res_row = res[0] if res is not None else None
        bres = res_row[: kb * bpad] if res_row is not None else None
        tres = res_row[kb * bpad:] if res_row is not None else None
        bkeys = tkey = None
        if qk is not None:
            keys = jax.random.split(qk, kb + 1)
            # per-bucket stochastic-rounding keys ride through the tap
            # bitcast to f32 (integer tap inputs would need float0
            # cotangents); the tail keeps its key directly
            bkeys = jax.lax.bitcast_convert_type(
                keys[:kb], jnp.float32
            )
            tkey = keys[kb]

        def bucket_reduce(g, ex):
            """Tap backward: ONE bucket's collective, emitted inside
            the backward scan body."""
            ex_cot = {}
            gf = jax.tree.map(lambda a: a.astype(jnp.float32), g)
            if "acc" in ex:
                # final microbatch: fold in the locally-accumulated
                # prefix so the single sync reduces the full mean grad
                gf = jax.tree.map(
                    lambda a, b: (a + b) / accum, gf, ex["acc"]
                )
                ex_cot["acc"] = jax.tree.map(
                    jnp.zeros_like, ex["acc"]
                )
            if "scale" in ex:
                # unscale BEFORE the sync: the residual must carry
                # true gradient units (the monolithic_quant_step
                # rule).  The scale rides the extras rather than the
                # closure — a custom_vjp bwd rule must not capture
                # tracers
                gf = jax.tree.map(
                    lambda a: a * (1.0 / ex["scale"]), gf
                )
                ex_cot["scale"] = jnp.zeros_like(ex["scale"])
            key = None
            if "rng" in ex:
                key = jax.lax.bitcast_convert_type(
                    ex["rng"], jnp.uint32
                )
                ex_cot["rng"] = jnp.zeros_like(ex["rng"])
            if mode == "fp32":
                # compute-dtype pmean: the same bytes the GSPMD
                # all-reduce moves (it commutes the reduction with
                # the grad's f32 cast — comm_report round-4)
                red = jax.tree.map(
                    lambda a, o: jax.lax.pmean(
                        a.astype(o.dtype), "data"
                    ), gf, g,
                )
            else:
                red, new_r = qcomm.quantized_grad_sync(
                    gf, ex.get("res"), "data", n, mode, block=blk,
                    rng=key, inner=inner,
                )
                if "res" in ex:
                    ex_cot["res"] = new_r
            red = jax.tree.map(
                lambda a, o: a.astype(o.dtype), red, g
            )
            return red, ex_cot

        def tapped_loss(p_, bres_, ix_, tg_, r_, acc=None):
            extras = {}
            if bres_ is not None:
                extras["res"] = bres_.reshape(kb, bpad)
            if acc is not None:
                extras["acc"] = acc
            if bkeys is not None:
                extras["rng"] = bkeys
            if sc is not None:
                extras["scale"] = jnp.full((kb,), sc, jnp.float32)
            tap = GradBucketTap(kb, bucket_reduce, extras)
            kw = {"rng": r_} if r_ is not None else {}
            loss = model.apply(
                p_, ix_, tg_, pctx=None, sched=tap, **kw
            )
            return loss * sc if sc is not None else loss

        def run_final(ix_, tg_, r_, acc=None):
            if bres is not None:
                loss_l, (gp, new_b) = jax.value_and_grad(
                    tapped_loss, argnums=(0, 1)
                )(p, bres, ix_, tg_, r_, acc)
            else:
                loss_l, gp = jax.value_and_grad(tapped_loss)(
                    p, None, ix_, tg_, r_, acc
                )
                new_b = None
            return loss_l, gp, new_b

        if accum == 1:
            loss_l, gp, new_bres = run_final(ix, tg, r)
        else:
            def body(carry, mb):
                al, ag = carry
                ix_, tg_, mb_i = mb
                mb_r = (jax.random.fold_in(r, mb_i)
                        if r is not None else None)

                def plain(p_, ix2, tg2, r2):
                    kw = {"rng": r2} if r2 is not None else {}
                    loss = model.apply(p_, ix2, tg2, pctx=None, **kw)
                    return loss * sc if sc is not None else loss

                l, g_ = jax.value_and_grad(plain)(p, ix_, tg_, mb_r)
                ag = jax.tree.map(
                    lambda a, b: a + b.astype(jnp.float32), ag, g_
                )
                return (al + l, ag), None

            zg = jax.tree.map(
                lambda q: jnp.zeros(q.shape, jnp.float32), p
            )
            (al, ag), _ = jax.lax.scan(
                body, (jnp.zeros((), jnp.float32), zg),
                (ix[:-1], tg[:-1], jnp.arange(accum - 1)),
            )
            # accumulated h.* prefix, chunked (K, L/K, ...) under the
            # STACKED-tree keys the taps see
            acc_blocks = {
                nm[len("h."):]: ag[nm].reshape(
                    (kb, lb) + ag[nm].shape[1:]
                )
                for nm in ag if nm.startswith("h.")
            }
            mb_r = (jax.random.fold_in(r, accum - 1)
                    if r is not None else None)
            loss_f, gp, new_bres = run_final(
                ix[-1], tg[-1], mb_r, acc=acc_blocks
            )
            loss_l = (al + loss_f) / accum
            gp = dict(gp)
            for nm in tail_names:
                # the taps folded the prefix in for h.*; the tail
                # leaves get it here, before their own sync below
                gp[nm] = (
                    (ag[nm] + gp[nm].astype(jnp.float32)) / accum
                ).astype(gp[nm].dtype)

        # tail bucket: one sync after the backward completes
        tail = {
            nm: gp[nm].astype(jnp.float32) for nm in tail_names
        }
        if sc is not None:
            tail = jax.tree.map(lambda a: a * (1.0 / sc), tail)
        if mode == "fp32":
            tail_red = jax.tree.map(
                lambda a: jax.lax.pmean(a.astype(cd), "data"), tail
            )
            new_tres = None
        else:
            tail_red, new_tres = qcomm.quantized_grad_sync(
                tail, tres, "data", n, mode, block=blk, rng=tkey,
                inner=inner,
            )
        gp = dict(gp)
        for nm in tail_names:
            gp[nm] = tail_red[nm]
        grads = jax.tree.map(
            lambda a, q: a.astype(q.dtype), gp, params
        )
        outs = [jax.lax.pmean(loss_l, "data"), grads]
        if has_res:
            outs.append(jnp.concatenate([new_bres, new_tres])[None])
        return tuple(outs)

    pspec = jax.tree.map(lambda _: P(), params)
    bspec = P(None, "data") if accum > 1 else P("data")
    in_specs = [pspec, bspec, bspec]
    args = [params, idx, targets]
    for cond, spec, val in (
        (has_res, P("data"), residual), (has_rng, P(), rng),
        (has_qk, P(), qkey), (has_sc, P(), scale),
    ):
        if cond:
            in_specs.append(spec)
            args.append(val)
    out_specs = [P(), jax.tree.map(lambda _: P(), params)]
    if has_res:
        out_specs.append(P("data"))
    out = jax.shard_map(
        local, mesh=eng.mesh, in_specs=tuple(in_specs),
        out_specs=tuple(out_specs), check_vma=False,
    )(*args)
    if has_res:
        return out
    return out[0], out[1], None


# ---------------------------------------------------------------------------
# the composed lowering: ONE custom_vjp, every slot in one scan program
# ---------------------------------------------------------------------------

def composed_step(eng, state, idx, targets, rng, scale):
    """Merged-schedule gradient phase: every declared slot emitted into
    ONE forward + remat-backward scan pair inside a shard_map manual
    region over the data axis.

    Structure (all explicit — no GSPMD-implicit collectives inside):

      top level   stacked compute tree derived from the f32 masters via
                  jax.vjp(model.stacked_compute_params, params) — cast /
                  fp8-quantize once per step, pullback applied to the
                  released grads at the end (the 1F1B seam pattern).
      region      ZeRO-3: stacked + tail leaves enter SHARDED (each rank
                  its slice); stages 0-2: replicated.  The non-block
                  tail gathers through a DIFFERENTIABLE lax.all_gather,
                  so its grads come back pre-reduce-scattered via the
                  transpose (ZeRO-3) or release explicitly (stages 0-2).
      fwd scan    nested buckets x layers; body k issues layer
                  k+(prefetch-1)'s explicit all-gather (intra-slice
                  under hpZ, from the secondary partition built by ONE
                  top-of-region inter-slice gather), computes the block
                  (health-tapped when the probe slot is on), stashes the
                  layer input (plain remat stash).
      bwd scan    reverse nested scans: recompute each block from the
                  stash, prefetch reverse gathers, accumulate per-layer
                  dW in f32, and at each bucket boundary release the
                  bucket's collective (fp32 pmean or the int8/fp8
                  error-fed quantized schedule) INSIDE the outer scan
                  body — loop-resident grad wire next to loop-resident
                  gather wire, the full-compose acceptance.  Probe
                  cotangents collect as scan ys.  Under ZeRO-3 the
                  released full grads slice back to this rank's
                  canonical shard so the optimizer stays global ZeRO-3.

    Returns (loss, grads [param dtypes; sharded under ZeRO-3],
    new residual or None, probe stats (L, 4) or None)."""
    sched = eng._schedule
    model = eng.model
    mesh = eng.mesh
    n = eng.n_shard
    ax = "data"
    gather = sched.gather
    grad = sched.grad
    probe_on = sched.probe is not None
    stage3 = eng.stage >= 3
    cfgm = getattr(model, "config", None)
    L = int(getattr(cfgm, "n_layer"))
    dropout_p = float(getattr(cfgm, "dropout", 0.0) or 0.0)
    kb = grad.buckets if grad is not None else 1
    lb = L // kb
    mode = grad.mode if grad is not None else "fp32"
    blk = grad.block if grad is not None else DEFAULT_BLOCK
    # 2-hop hierarchical release: every quantized sync below (bucket,
    # quantized tail, stage<3 tail) passes the SAME inner split down to
    # quantized_grad_sync — the composed counterpart of the monolithic
    # lowering's grad_comm_groups schedule
    inner = grad.groups if grad is not None else None
    lay = sched.layout
    bpad = lay["bucket_pad"] if lay is not None else 0
    tail_names = sorted(nm for nm in state.params
                        if not nm.startswith("h."))
    look = (gather.prefetch - 1) if gather is not None else 0
    hpz = bool(gather is not None and gather.hpz)
    if hpz:
        intra, inter, ici, n_gran = sched.hpz_geom
    else:
        intra = inter = None
        ici = n_gran = 1
    # quantized tail release (ZeRO-3 only — build_schedule validated);
    # fp32 keeps the differentiable gather's transpose byte-identical
    tmode = grad.tail_mode if grad is not None else "fp32"
    tail_q = stage3 and tmode != "fp32"
    # hpZ rebuild codec (qwZ): fp32 = compute-dtype passthrough
    hq = gather.hpz_mode if hpz else "fp32"

    params = state.params
    residual = state.grad_residual
    # masters -> compute-dtype stacked tree at TOP level (cast /
    # fp8-quantize once per step, logical GSPMD semantics — global absmax
    # scales even when the shard axis crosses the reduced dims); the
    # pullback maps released stacked cotangents onto the f32 masters
    stacked_full, stacked_vjp = jax.vjp(
        model.stacked_compute_params, params
    )
    fkeys = sorted(stacked_full)  # all float (ints join inside: dropout)
    rel_keys = [nm for nm in fkeys if not nm.endswith("#scale")]
    sdtypes = {nm: stacked_full[nm].dtype for nm in fkeys}

    # per-leaf data-shard dim in the STACKED (L, ...) array (None =
    # replicated at rest, nothing to gather / slice)
    def _spec_dim(spec):
        if spec is None:
            return None
        for i, a in enumerate(spec):
            if a == ax or (isinstance(a, tuple) and ax in a):
                return i
        return None

    sdim = {}
    st_spec = {}
    for nm in fkeys:
        spec = eng._shard_spec.get("h." + nm) if stage3 else None
        sdim[nm] = _spec_dim(spec)
        st_spec[nm] = (spec if spec is not None and sdim[nm] is not None
                       else P())
    tdim = {}
    t_spec = {}
    for nm in tail_names:
        spec = eng._param_spec_rest.get(nm)
        tdim[nm] = _spec_dim(spec) if stage3 else None
        t_spec[nm] = spec if stage3 and spec is not None else P()
    tailp = {nm: params[nm] for nm in tail_names}

    qkey = None
    if mode == "int8" or (tail_q and tmode == "int8"):
        qkey = jax.random.fold_in(
            jax.random.PRNGKey(0x6C51), state.opt_state["step"]
        )
    has_res = residual is not None
    has_rng = rng is not None
    has_qk = qkey is not None
    has_sc = scale is not None
    block_fn = model.block_fn(None)
    # honor the model's scan_unroll on the inner layer scans (the legacy
    # bucket lowering does via GradBucketTap.scan; a gather slot already
    # forces scan_unroll == 1 at build_schedule), clamped to the
    # per-bucket scan length
    _u = getattr(cfgm, "scan_unroll", 1)
    unroll = lb if _u is True else max(1, min(int(_u or 1), lb))

    def local(sf, tp, ix, tg, *rest):
        rest = list(rest)
        res = rest.pop(0) if has_res else None
        r = rest.pop(0) if has_rng else None
        qk = rest.pop(0) if has_qk else None
        sc = rest.pop(0) if has_sc else None
        di = jax.lax.axis_index(ax)
        if r is not None:
            # per-device fold: masks stay independent across batch
            # shards (the explicit-schedule convention)
            r = jax.random.fold_in(r, di)
        if qk is not None:
            qk = jax.random.fold_in(qk, di)
        res_row = res[0] if res is not None else None
        bres = res_row[: kb * bpad] if res_row is not None else None
        tres = res_row[kb * bpad:] if (res_row is not None
                                       and (not stage3 or tail_q)) \
            else None
        bkeys = tkey = None
        if qk is not None:
            keys_q = jax.random.split(qk, kb + 1)
            bkeys = jax.lax.bitcast_convert_type(
                keys_q[:kb], jnp.float32
            )
            tkey = keys_q[kb]
        dkeys = None
        emb_key = None
        if r is not None and dropout_p:
            dk = jax.random.split(r, L + 1)
            emb_key = dk[0]
            dkeys = jax.lax.bitcast_convert_type(dk[1:], jnp.float32)
        si = {"dropout_rng": dkeys} if dkeys is not None else {}
        sidt = {"dropout_rng": jnp.uint32}

        # ---- the ONE custom_vjp: merged fwd/bwd scan schedule ----------
        def slice_k(tree, k):
            return {
                nm: jax.lax.dynamic_index_in_dim(a, k, 0, keepdims=False)
                for nm, a in tree.items()
            }

        def int_slices(si_, k):
            return {
                nm: jax.lax.bitcast_convert_type(
                    jax.lax.dynamic_index_in_dim(
                        a, k, 0, keepdims=False), sidt[nm])
                for nm, a in si_.items()
            }

        def unperm(x, d):
            """Undo the (intra-position, granule) interleave of the hpZ
            two-stage gather: one local transpose restores canonical
            rank-ascending shard order."""
            s = x.shape
            x = x.reshape(
                s[:d] + (ici, n_gran, s[d] // (ici * n_gran)) + s[d + 1:]
            )
            x = jnp.swapaxes(x, d, d + 1)
            return x.reshape(s)

        @jax.named_scope("tds.gather")
        def build_sec(sf_):
            """hpZ secondary partition: ONE inter-slice all-gather per
            leaf turns each rank's global 1/n shard into its slice's
            1/ici shard — the only DCN hop; every in-scan gather below
            then stays intra-slice.

            hpz_comm != "fp32" (qwZ, ZeRO++ arXiv:2306.10209): instead
            of compute-dtype leaves, ONE concatenated blockwise-
            quantized payload + its f32 scales cross the inter-slice
            hop (two gathers over the same groups), dequantized once
            per granule and split back per leaf — ~4x fewer rebuild
            DCN bytes under fp8.  Runs inside the custom_vjp forward
            only, so the weight rounding is straight-through for
            gradients (d_sf releases explicitly in the backward)."""
            out = {}
            if hq != "fp32":
                names = [nm for nm in sorted(sf_)
                         if sdim[nm] is not None]
                sizes = [int(np.prod(sf_[nm].shape)) for nm in names]
                for nm in sf_:
                    if sdim[nm] is None:
                        out[nm] = sf_[nm]
                if names:
                    flat = jnp.concatenate([
                        sf_[nm].astype(jnp.float32).reshape(-1)
                        for nm in names
                    ])
                    pad = -flat.shape[0] % DEFAULT_BLOCK
                    if pad:
                        flat = jnp.concatenate(
                            [flat, jnp.zeros((pad,), jnp.float32)])
                    # round-to-nearest (rng=None) even for int8: a
                    # deterministic weight replica per step — dither
                    # buys nothing without an error-feedback loop
                    q, s = quantize_blockwise(flat, hq, DEFAULT_BLOCK)
                    qg = jax.lax.all_gather(
                        as_wire(q), ax, axis_index_groups=inter)
                    sg = jax.lax.all_gather(
                        s.reshape(1, -1), ax,
                        axis_index_groups=inter, tiled=True)
                    vals = _dequant_rows(
                        from_wire(qg, hq),
                        sg.reshape(n_gran, -1))  # (n_gran, P) f32
                    off = 0
                    for nm, sz in zip(names, sizes):
                        v = sf_[nm]
                        d = sdim[nm]
                        seg = vals[:, off:off + sz].reshape(
                            (n_gran,) + v.shape)
                        out[nm] = jnp.concatenate(
                            [seg[i] for i in range(n_gran)], axis=d
                        ).astype(v.dtype)
                        off += sz
                return out
            for nm, v in sf_.items():
                d = sdim[nm]
                if d is None:
                    out[nm] = v
                    continue
                out[nm] = jax.lax.all_gather(
                    v, ax, axis=d, tiled=True,
                    axis_index_groups=inter)
            return out

        @jax.named_scope("tds.gather")
        def gather_k(src, k):
            """Layer k's full weights from the gather source (the
            sharded stacked tree, or the hpZ secondary partition)."""
            w = slice_k(src, k)
            if gather is None:
                return w
            out = {}
            for nm, v in w.items():
                d = sdim[nm]
                if d is None:
                    out[nm] = v
                    continue
                # the layer axis is sliced off: the shard dim shifts -1
                g = jax.lax.all_gather(
                    v, ax, axis=d - 1, tiled=True,
                    axis_index_groups=intra)
                out[nm] = unperm(g, d - 1) if hpz else g
            return out

        def shard_slice(nm, g, lead=1):
            """This rank's canonical 1/n shard of a released full
            gradient — keeps the optimizer layout global ZeRO-3
            whatever the gather slot did (hpZ included).  `lead` is the
            number of leading stack dims on `g` standing in for the
            sliced-off layer axis (1 for (lb, ...) bucket stacks, 0 for
            a single layer's dW)."""
            d = sdim[nm]
            if d is None:
                return g
            d = d - 1 + lead
            size = g.shape[d] // n
            return jax.lax.dynamic_slice_in_dim(g, di * size, size, d)

        def init_buf(src, idxs):
            slots = [gather_k(src, i) for i in idxs]
            return jax.tree.map(lambda *xs: jnp.stack(xs), *slots)

        def shift_in(buf, new):
            return jax.tree.map(
                lambda b, nw: jnp.concatenate([b[1:], nw[None]]),
                buf, new)

        def fwd_pass(sf_, si_, probe_, x0, stash):
            src = build_sec(sf_) if hpz else sf_
            buf = init_buf(src, list(range(look))) if look else ()

            def body_inner(carry, k):
                x, buf = carry
                if look:
                    # issue layer k+look's gather FIRST; nothing in
                    # this body consumes it, so its wire hides behind
                    # block(k)
                    nxt = gather_k(src, jnp.minimum(k + look, L - 1))
                    w = jax.tree.map(lambda b: b[0], buf)
                    buf = shift_in(buf, nxt)
                else:
                    w = gather_k(src, k)
                bp = dict(w, **int_slices(si_, k))
                if probe_ is not None:
                    bp["health_probe"] = jax.lax.dynamic_index_in_dim(
                        probe_, k, 0, keepdims=False)
                y = block_fn(x, bp)
                return (y, buf), (x if stash else None)

            def body_outer(carry, ks):
                return jax.lax.scan(body_inner, carry, ks,
                                    unroll=unroll)

            (y, _), xs = jax.lax.scan(
                body_outer, (x0, buf),
                jnp.arange(L).reshape(kb, lb))
            return y, xs, src

        def make_run():
            @jax.custom_vjp
            def run(sf_, si_, ops_, x0):
                y, _, _ = fwd_pass(sf_, si_, ops_.get("probe"), x0,
                                   stash=False)
                return y

            def run_fwd(sf_, si_, ops_, x0):
                y, xs, src = fwd_pass(sf_, si_, ops_.get("probe"), x0,
                                      stash=True)
                # residuals: sharded stacked tree + the (kb, lb) layer-
                # input stash (plain remat) + the gather source — sf
                # itself when not hpZ (free), the secondary partition
                # under hpZ (the deliberate per-slice replica HBM cost)
                return y, (sf_, si_, ops_, xs, src)

            def run_bwd(resid, dy):
                sf_, si_, ops_, xs, src = resid
                probe_ = ops_.get("probe")
                buf = (init_buf(src, [L - 1 - i for i in range(look)])
                       if look else ())

                def body_inner(carry, inp):
                    dx, buf = carry
                    x_k, k = inp
                    if look:
                        nxt = gather_k(src, jnp.maximum(k - look, 0))
                        w = jax.tree.map(lambda b: b[0], buf)
                        buf = shift_in(buf, nxt)
                    else:
                        w = gather_k(src, k)
                    ints = int_slices(si_, k)
                    wf = dict(w)
                    if probe_ is not None:
                        wf["health_probe"] = \
                            jax.lax.dynamic_index_in_dim(
                                probe_, k, 0, keepdims=False)

                    def f(x_, wd):
                        return block_fn(x_, dict(wd, **ints))

                    # remat: recompute layer k from the stashed input
                    _, vjp = jax.vjp(f, x_k, wf)
                    dx_new, dwf = vjp(dx)
                    dprobe_k = (dwf.pop("health_probe")
                                if probe_ is not None else None)
                    if grad is not None:
                        # accumulate in f32; the bucket boundary below
                        # runs the ONE collective per bucket
                        dws = {nm: dwf[nm].astype(jnp.float32)
                               for nm in rel_keys}
                    else:
                        # no grad slot: per-layer fp32 release keeps
                        # the grad wire in-loop like the GSPMD path
                        dws = {}
                        for nm in rel_keys:
                            g32 = dwf[nm].astype(jnp.float32)
                            if "scale" in ops_:
                                g32 = g32 * (1.0 / ops_["scale"])
                            red = jax.lax.pmean(
                                g32.astype(dwf[nm].dtype), ax)
                            dws[nm] = shard_slice(
                                nm, red, lead=0).astype(sdtypes[nm])
                    ys = (dws, dprobe_k) if probe_ is not None \
                        else (dws,)
                    return (dx_new, buf), ys

                def body_outer(carry, inp):
                    xs_b, ks_b, res_b, key_b = inp
                    carry, ys = jax.lax.scan(
                        body_inner, carry, (xs_b, ks_b), reverse=True,
                        unroll=unroll)
                    dws_b = ys[0]
                    dprobe_b = ys[1] if probe_ is not None else None
                    new_res_b = jnp.zeros((0,), jnp.float32)
                    if grad is not None:
                        # bucket release: one collective, emitted inside
                        # this outer scan body — the backward for buckets
                        # k-1..0 is still ahead, so the scheduler can
                        # hide the wire (the grad slot's point)
                        gf = {nm: dws_b[nm] for nm in rel_keys}
                        if "scale" in ops_:
                            gf = jax.tree.map(
                                lambda a: a * (1.0 / ops_["scale"]), gf
                            )
                        key = None
                        if key_b is not None:
                            key = jax.lax.bitcast_convert_type(
                                key_b, jnp.uint32)
                        if mode == "fp32":
                            red = {
                                nm: jax.lax.pmean(
                                    gf[nm].astype(sdtypes[nm]), ax)
                                for nm in rel_keys
                            }
                        else:
                            red, new_res_b = quantized_grad_sync(
                                gf, res_b if "res" in ops_ else None,
                                ax, n, mode, block=blk, rng=key,
                                inner=inner,
                            )
                            if new_res_b is None:
                                new_res_b = jnp.zeros((0,), jnp.float32)
                        dws_b = {
                            nm: shard_slice(
                                nm, red[nm].astype(jnp.float32)
                            ).astype(sdtypes[nm])
                            for nm in rel_keys
                        }
                    outs = (dws_b, dprobe_b, new_res_b)
                    return carry, outs

                ks = jnp.arange(L).reshape(kb, lb)
                res_rows = (ops_["res"] if "res" in ops_
                            else jnp.zeros((kb, 0), jnp.float32))
                key_rows = (ops_["rng"] if "rng" in ops_
                            else None)
                inp = (xs, ks, res_rows,
                       key_rows if key_rows is not None
                       else jnp.zeros((kb, 0), jnp.float32))
                if key_rows is None:
                    # thread a dummy so the scan xs structure is static;
                    # body ignores it when the codec needs no key
                    def body_outer_nokey(carry, inp_):
                        xs_b, ks_b, res_b, _ = inp_
                        return body_outer(carry, (xs_b, ks_b, res_b,
                                                  None))
                    (dx, _), outs = jax.lax.scan(
                        body_outer_nokey, (dy, buf), inp, reverse=True)
                else:
                    (dx, _), outs = jax.lax.scan(
                        body_outer, (dy, buf), inp, reverse=True)
                dws_all, dprobe_all, new_res_all = outs
                d_sf = {}
                for nm in fkeys:
                    if nm in dws_all:
                        a = dws_all[nm]
                        d_sf[nm] = a.reshape((L,) + a.shape[2:])
                    else:
                        # '#scale' leaves: stop-gradiented upstream by
                        # stacked_compute_params — zero, not released
                        d_sf[nm] = jnp.zeros_like(sf_[nm])
                d_ops = {}
                if "probe" in ops_:
                    d_ops["probe"] = dprobe_all.reshape(L, -1)
                if "res" in ops_:
                    d_ops["res"] = new_res_all
                if "rng" in ops_:
                    d_ops["rng"] = jnp.zeros_like(ops_["rng"])
                if "scale" in ops_:
                    d_ops["scale"] = jnp.zeros_like(ops_["scale"])
                d_si = jax.tree.map(jnp.zeros_like, si_)
                return d_sf, d_si, d_ops, dx.astype(x0_dtype)

            run.defvjp(run_fwd, run_bwd)
            return run

        x0_dtype = getattr(cfgm, "compute_dtype", jnp.float32)
        run = make_run()

        ops = {}
        if probe_on:
            ops["probe"] = jnp.zeros((L, LAYER_PROBE_WIDTH),
                                     jnp.float32)
        if bres is not None:
            ops["res"] = bres.reshape(kb, bpad)
        if bkeys is not None:
            ops["rng"] = bkeys
        if sc is not None:
            ops["scale"] = jnp.full((), sc, jnp.float32)

        @jax.named_scope("tds.gather")
        def tail_full(tp_):
            if not stage3:
                return tp_
            out = {}
            for nm, v in tp_.items():
                d = tdim[nm]
                # DIFFERENTIABLE gather: the transpose (psum_scatter)
                # reduce-scatters the tail grads back to the shards
                out[nm] = (jax.lax.all_gather(v, ax, axis=d, tiled=True)
                           if d is not None else v)
            return out

        def make_qtail():
            """Quantized ZeRO-3 tail release (grad_comm_tail): the same
            forward gather as tail_full, but the transpose's implicit
            fp32 reduce-scatter is replaced by ONE explicit error-fed
            quantized sync of the full tail cotangents — the composed
            program's last fp32 grad collective, now on the codec.  The
            residual / rng / scale ride the `tex_` extras (a custom_vjp
            bwd rule must not capture tracers); the new residual exits
            as the residual's cotangent, the composed machine's
            standard trick (ops_["res"])."""
            @jax.custom_vjp
            def qtail(tp_, tex_):
                return tail_full(tp_)

            def qtail_fwd(tp_, tex_):
                return tail_full(tp_), (tex_,)

            def qtail_bwd(resid, dy):
                (tex_,) = resid
                inv_ = (1.0 / tex_["scale"]) if "scale" in tex_ else 1.0
                g32 = {nm: dy[nm].astype(jnp.float32) * inv_
                       for nm in tail_names}
                key = None
                if "rng" in tex_:
                    key = jax.lax.bitcast_convert_type(
                        tex_["rng"], jnp.uint32)
                red, new_tr = quantized_grad_sync(
                    g32, tex_.get("res"), ax, n, tmode, block=blk,
                    rng=key, inner=inner,
                )
                # mean full grads -> each rank's canonical 1/n shard
                # for the leaves the ZeRO layout shards; replicated
                # leaves (tdim None) keep the full mean — exactly the
                # fp32 release's psum/(inv/n) semantics
                di_ = jax.lax.axis_index(ax)
                d_tp = {}
                for nm, a in dy.items():
                    d = tdim[nm]
                    gr = red[nm]
                    if d is not None:
                        size = gr.shape[d] // n
                        gr = jax.lax.dynamic_slice_in_dim(
                            gr, di_ * size, size, d)
                    d_tp[nm] = gr.astype(a.dtype)
                d_tex = {}
                if "res" in tex_:
                    d_tex["res"] = new_tr
                if "rng" in tex_:
                    d_tex["rng"] = jnp.zeros_like(tex_["rng"])
                if "scale" in tex_:
                    d_tex["scale"] = jnp.zeros_like(tex_["scale"])
                return d_tp, d_tex

            qtail.defvjp(qtail_fwd, qtail_bwd)
            return qtail

        if tail_q:
            tex = {}
            if tres is not None:
                tex["res"] = tres
            if tkey is not None:
                tex["rng"] = jax.lax.bitcast_convert_type(
                    tkey, jnp.float32)
            if sc is not None:
                tex["scale"] = jnp.full((), sc, jnp.float32)
            qtail = make_qtail()

            def tapped_loss_qt(tp_, tex_, sf_, ops_, ix_, tg_):
                tf = qtail(tp_, tex_)
                x = model.embed(tf, ix_, None)
                if emb_key is not None:
                    from ..models.gpt2 import _dropout
                    x = _dropout(x, emb_key, dropout_p)
                y = run(sf_, si, ops_, x)
                loss = model.head(tf, y, tg_, None)
                return loss * sc if sc is not None else loss

            loss_l, (g_tail, d_tex, d_sf, g_ops) = jax.value_and_grad(
                tapped_loss_qt, argnums=(0, 1, 2, 3)
            )(tp, tex, sf, ops, ix, tg)
            # g_tail is final (mean, unscaled, sliced); the new tail
            # residual exits as the extras' cotangent
            new_tres = d_tex.get("res")
        else:
            def tapped_loss(tp_, sf_, ops_, ix_, tg_):
                tf = tail_full(tp_)
                x = model.embed(tf, ix_, None)
                if emb_key is not None:
                    from ..models.gpt2 import _dropout
                    x = _dropout(x, emb_key, dropout_p)
                y = run(sf_, si, ops_, x)
                loss = model.head(tf, y, tg_, None)
                return loss * sc if sc is not None else loss

            loss_l, (g_tail, d_sf, g_ops) = jax.value_and_grad(
                tapped_loss, argnums=(0, 1, 2)
            )(tp, sf, ops, ix, tg)

        # ---- tail release ------------------------------------------------
        if tail_q:
            pass  # released inside qtail's backward (above)
        elif stage3:
            # sharded leaves' grads arrived pre-reduce-scattered (the
            # all_gather transpose psums each shard); leaves the ZeRO
            # layout left REPLICATED at rest (tdim None — small norms /
            # biases whose dims the axis does not divide) never crossed
            # a gather, so their cotangent is still this rank's LOCAL
            # gradient and needs the explicit psum.  Both then: sum ->
            # mean, unscale.
            inv = (1.0 / sc) if sc is not None else 1.0
            out = {}
            for nm, a in g_tail.items():
                g32 = a.astype(jnp.float32)
                if tdim[nm] is None:
                    with jax.named_scope("tds.grad_sync"):
                        g32 = jax.lax.psum(g32, ax)
                out[nm] = (g32 * (inv / n)).astype(a.dtype)
            g_tail = out
            new_tres = None
        else:
            tail = {nm: g_tail[nm].astype(jnp.float32)
                    for nm in tail_names}
            if sc is not None:
                tail = jax.tree.map(lambda a: a * (1.0 / sc), tail)
            cd = getattr(cfgm, "compute_dtype", jnp.float32)
            if mode == "fp32":
                tail_red = jax.tree.map(
                    lambda a: jax.lax.pmean(a.astype(cd), ax), tail
                )
                new_tres = None
            else:
                tail_red, new_tres = quantized_grad_sync(
                    tail, tres, ax, n, mode, block=blk, rng=tkey,
                    inner=inner,
                )
            g_tail = {nm: tail_red[nm].astype(g_tail[nm].dtype)
                      for nm in tail_names}

        outs = [jax.lax.pmean(loss_l, ax), g_tail, d_sf]
        if probe_on:
            # local (batch-shard) sums -> the global numbers every rank
            # reports (the health_vector convention).  The backward ran
            # on the LOCAL batch-shard mean loss (n x the global-mean
            # cotangent per shard), so the dact sq-sum column carries
            # n^2 vs the plain probe lowering's global-loss convention —
            # normalized here so composed and single-slot engines report
            # the same LAYER_FIELDS numbers (non-finite counts and the
            # forward act columns are scale-free)
            stats = jax.lax.psum(g_ops["probe"], ax)
            stats = stats.at[:, 2].multiply(1.0 / (n * n))
            outs.append(stats)
        if has_res:
            new_row = g_ops["res"].reshape(-1)
            if new_tres is not None:
                new_row = jnp.concatenate([new_row, new_tres])
            outs.append(new_row[None])
        return tuple(outs)

    # ---- shard_map plumbing -------------------------------------------------
    st_in = {nm: st_spec[nm] for nm in fkeys}
    t_in = {nm: t_spec[nm] for nm in tail_names}
    bspec = P("data")
    in_specs = [st_in, t_in, bspec, bspec]
    args = [stacked_full, tailp, idx, targets]
    for cond, spec, val in (
        (has_res, P("data"), residual), (has_rng, P(), rng),
        (has_qk, P(), qkey), (has_sc, P(), scale),
    ):
        if cond:
            in_specs.append(spec)
            args.append(val)
    out_specs = [P(), t_in, st_in]
    if probe_on:
        out_specs.append(P())
    if has_res:
        out_specs.append(P("data"))
    out = jax.shard_map(
        local, mesh=mesh, in_specs=tuple(in_specs),
        out_specs=tuple(out_specs), check_vma=False,
    )(*args)
    out = list(out)
    loss = out.pop(0)
    g_tail = out.pop(0)
    d_stacked = out.pop(0)
    layer_probe = out.pop(0) if probe_on else None
    new_residual = out.pop(0) if has_res else state.grad_residual

    # pull the released stacked cotangents back onto the f32 masters
    # (cast / fp8-quantize transpose; '#scale' zeros through the
    # stop_gradient) and merge the tail grads
    grads = stacked_vjp(d_stacked)[0]
    grads = dict(grads)
    for nm in tail_names:
        grads[nm] = g_tail[nm].astype(params[nm].dtype)
    grads = jax.tree.map(
        lambda g, q: g.astype(q.dtype), grads, params
    )
    return loss, grads, new_residual, layer_probe
