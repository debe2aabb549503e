# Copyright 2026 tiny-deepspeed-tpu authors
# SPDX-License-Identifier: Apache-2.0

"""ZeRO engines: DDP / ZeRO-1 / ZeRO-2 / ZeRO-3 as sharding strategies.

This file replaces the reference's entire zero/{ddp,zero1,zero2,zero3}
package family (wrapper.py + module.py + optim.py + utils.py per mode,
reference core/zero/) — ~1,100 LoC of per-mode re-derived modules injecting
NCCL calls into backward callbacks — with ONE engine parameterized by a
sharding strategy.  The mapping:

  reference mechanism                        TPU-native expression here
  -----------------------------------------  --------------------------------
  DDP: per-param async all-reduce in bwd      batch sharded over mesh "data";
  callback + wait (ddp/module.py:36-78)       params replicated -> XLA emits
                                              the grad all-reduce and overlaps
                                              it with the dx matmuls (latency-
                                              hiding scheduler).
  ZeRO-1: grad reduce-to-owner + owner        optimizer state laid out sharded
  steps + param broadcast                     (NamedSharding); update compute
  (zero1/module.py:17-24, optim.py:25-34)     partitions to the shard, new
                                              params constrained replicated ->
                                              all-gather.
  ZeRO-2: + non-owner grads dropped           grads constrained to the sharded
  (zero2/module.py:26-36 — a 1-elem           spec right after value_and_grad
  placeholder hack, "impossible in            -> XLA turns the all-reduce into
  pytorch, maybe solved by plugin C++")       reduce-scatter; full grads never
                                              materialize.  The hack vanishes.
  ZeRO-3: params broadcast-on-demand per      params *live* sharded; the scan
  layer, broken in the reference              over stacked blocks slices one
  (zero3/module.py:17-46, SURVEY §2.18:       layer then XLA all-gathers just
  NameError, rank-0 falsy, frees discarded)   that layer's shards inside the
                                              loop (fwd and, via remat, bwd) —
                                              the design the reference
                                              attempted, but correct.
  per-param `bwd_sync` grad-accum gating      explicit microbatch axis +
  (ddp/wrapper.py:25-33)                      lax.scan accumulation; collective
                                              cost paid once per step.
  cache rank map placement                    partition_tensors table exposed
  (zero/utils/partition.py)                   as `engine.rank_map` (ownership
                                              report / API parity); physical
                                              layout is even axis-sharding
                                              (SPMD) — see partition.py note.

Quirk decisions (SURVEY §8): reference DDP *sums* grads across ranks and never
divides (quirk #1); here the loss is the mean over the GLOBAL batch, so grads
are the true global gradient — DDP-vs-single-device parity becomes exact
instead of lr-rescaled.  Recorded in tests/test_engine.py
(test_stage_trains_and_matches_single_device).

Dynamic grad-sync (the reference's per-iteration `require_backward_grad_sync`
toggle, ddp/wrapper.py:25-33): engines of the same stage with different
`accum_steps` produce and accept the SAME TrainState (identical shardings),
so per-iteration sync policy = choosing which already-jitted engine to step
with this iteration; no re-jit, no state conversion
(tests/test_engine.py::test_engines_share_state_dynamic_accum).  A
data-dependent toggle *inside* one compiled step is deliberately not offered:
under XLA it would force both program paths into every step."""

from __future__ import annotations

import dataclasses
import warnings
from functools import partial
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..utils.profiling import span
from .mesh import make_mesh, ParallelContext
from .partition import partition_tensors

try:
    from flax import struct as _struct

    @_struct.dataclass
    class TrainState:
        params: Dict[str, Any]
        opt_state: Dict[str, Any]
        # dynamic loss-scale state ({"scale": f32, "good": i32}) when the
        # engine runs with loss_scale="dynamic"; None (no pytree leaves)
        # otherwise, so existing states/checkpoints keep their structure
        scaler: Any = None
        # dropout mask stream base key (derived from the init seed) when the
        # model has dropout > 0; None otherwise.  Carried in the STATE — not
        # as a jit closure constant — so checkpoint-restore resumes the
        # original run's mask stream without re-init (round-3 advice: a
        # restored state stepping on a fresh engine replayed the
        # constructor's hard-coded base)
        dropout_base: Any = None
        # quantized-grad-comm error feedback (parallel/comm.py): the flat
        # per-device quantization error carried to next step, global shape
        # (n_dev, padded_elems) sharded over "data"; None (no leaves)
        # unless grad_comm is int8/fp8 with error feedback on
        grad_residual: Any = None
except Exception:  # pragma: no cover - flax always present in this image
    TrainState = None


# ---------------------------------------------------------------------------
# sharding rules
# ---------------------------------------------------------------------------

def _leaf_spec(name: str, shape, n_dev: int, axis: str = "data",
               reserved: Optional[Dict[int, str]] = None,
               prefer_dim: Optional[int] = None) -> P:
    """Even axis-sharding rule for one tensor.

    `reserved` pre-places mesh axes on specific dims (tensor/expert
    parallelism); the ZeRO data-axis shard then goes on the largest
    *remaining* axis divisible by the mesh size.  Tensors from the stacked
    block ("h.*") never shard the leading (n_layer,) axis — the scan slices
    it, and keeping it unsharded is what makes XLA's all-gather happen
    per-layer *inside* the loop (the ZeRO-3 gather-on-demand).  Indivisible /
    small tensors replicate.

    `prefer_dim` overrides the largest-axis walk when that dim is free and
    divisible.  Used by the fp8 gather (engine passes the IN dim for
    quant-eligible leaves): an OUT-dim shard is exactly aligned with the
    per-out-channel dequant scale, so the SPMD partitioner dequantizes
    shard-side for free and all-gathers bf16 — the f8 wire saving only
    exists when the shard axis and the scale axis differ (round-5
    TPU-HLO measurement, PROFILE.md finding 5).
    """
    if not shape:
        return P()
    spec = [None] * len(shape)
    for dim, ax in (reserved or {}).items():
        spec[dim] = ax
    if n_dev > 1:
        best = None
        if (prefer_dim is not None and spec[prefer_dim] is None
                and shape[prefer_dim] % n_dev == 0
                and shape[prefer_dim] >= n_dev):
            best = prefer_dim
        else:
            start = 1 if name.startswith("h.") and len(shape) > 1 else 0
            for ax in range(start, len(shape)):
                if spec[ax] is None and shape[ax] % n_dev == 0 \
                        and shape[ax] >= n_dev:
                    if best is None or shape[ax] > shape[best]:
                        best = ax
        if best is not None:
            spec[best] = axis
    while spec and spec[-1] is None:  # P(None, ...) normalizes to P()
        spec.pop()
    return P(*spec)


def _param_spec_tree(
    shapes: Dict[str, Any], n_dev: int,
    reserved: Optional[Dict[str, Dict[int, str]]] = None,
    prefer_dims: Optional[Dict[str, int]] = None,
) -> Dict[str, P]:
    reserved = reserved or {}
    prefer_dims = prefer_dims or {}
    return {
        n: _leaf_spec(n, s.shape, n_dev, reserved=reserved.get(n),
                      prefer_dim=prefer_dims.get(n))
        for n, s in shapes.items()
    }


def _opt_spec_tree(opt_shapes, param_specs: Dict[str, P], sharded: bool,
                   base_specs: Optional[Dict[str, P]] = None):
    """Sharding tree matching the optimizer-state structure.

    Per-param slots (m/v/velocity/vmax, shaped like the param) inherit the
    param's full ZeRO spec when `sharded`, else the base (tensor-parallel
    placement only) spec; the global step counter replicates.
    """
    table = param_specs if sharded else (base_specs or {})

    def spec_for(path, leaf):
        names = [p.key for p in path if isinstance(p, jax.tree_util.DictKey)]
        # path looks like ('state', '<param name>', 'm')
        for key in names:
            if key in table and len(table[key]) <= len(leaf.shape):
                return table[key]
        return P()

    return jax.tree_util.tree_map_with_path(spec_for, opt_shapes)


def _to_shardings(tree_of_specs, mesh: Mesh):
    return jax.tree.map(
        lambda spec: NamedSharding(mesh, spec),
        tree_of_specs,
        is_leaf=lambda x: isinstance(x, P),
    )


# ---------------------------------------------------------------------------
# engine
# ---------------------------------------------------------------------------

class ZeroEngine:
    """Training engine; subclasses pin the ZeRO stage.

    API parity with the reference wrappers + sharded optimizers
    (e.g. `Zero2(model, partition_table)` + `Zero2AdamW(...)`,
    reference zero2/wrapper.py:16-48, zero2/optim.py): here the pair is
    fused — `Zero2(model, optimizer, mesh).init(key)` then
    `state, loss = engine.step(state, batch)`.
    """

    stage: int = 0
    data_parallel: bool = True

    def __init__(
        self,
        model,
        optimizer,
        mesh: Optional[Mesh] = None,
        accum_steps: int = 1,
        evenness_priority: float = 0.0,
        donate: bool = True,
        seq_parallel: int = 1,
        seq_impl: str = "ring",
        tensor_parallel: int = 1,
        expert_parallel: int = 1,
        pipeline_parallel: int = 1,
        pipeline_microbatches: Optional[int] = None,
        pipeline_schedule: str = "gpipe",
        pipeline_virtual: int = 1,
        grad_clip: Optional[float] = None,
        loss_scale=None,
        loss_scale_growth_interval: int = 2000,
        offload_opt_state: bool = False,
        offload_prefetch: int = 2,
        telemetry=None,
        grad_comm: str = "fp32",
        grad_comm_block: int = 256,
        grad_comm_groups: Optional[int] = None,
        grad_comm_error_feedback: bool = True,
        grad_buckets: int = 1,
        grad_comm_tail: str = "fp32",
        gather_prefetch: int = 0,
        gather_groups: Optional[int] = None,
        hpz: bool = False,
        hpz_comm: str = "fp32",
        hpz_granule_of: Optional[Dict[int, int]] = None,
    ):
        """seq_parallel > 1 carves a "seq" mesh axis out of the devices:
        tokens shard over it and attention runs as a ppermute ring
        (context parallelism) or, with seq_impl="ulysses", as the
        DeepSpeed-Ulysses all-to-all head/sequence reshard (two
        collectives + the plain local kernel; needs n_head/tp divisible
        by the seq size).  tensor_parallel > 1 carves a "model" axis:
        Megatron-style intra-layer sharding per the model's `tp_rules()`.
        expert_parallel > 1 carves an "expert" axis: MoE expert sharding per
        `ep_rules()`.  pipeline_parallel > 1 carves a "pipe" axis: the
        stacked transformer blocks partition into S contiguous stages and
        microbatches flow through a GPipe ppermute pipeline
        (parallel/pipeline.py; `pipeline_microbatches` defaults to S).
        All compose with every ZeRO stage (the data axis keeps the ZeRO
        semantics); all are absent from the reference (SURVEY §2.20).

        pipeline_schedule: "gpipe" (default — forward-all-then-backward-all
        via autodiff, O(M) in-flight activations) or "1f1b" (combined
        fwd/bwd tick schedule, O(S) in-flight — raise microbatches to
        amortize the bubble without the activation bill; MoE aux loss,
        dropout, fp8 weight gather, and ring/Ulysses sequence
        parallelism all compose — see pipeline.py::spmd_pipeline_1f1b).
        "interleaved[:V]" and "zbub[:V]" run the table-driven executor
        instead (pipeline.py::spmd_pipeline_table): each stage holds V
        virtual model chunks (pipeline_virtual, or the ':V' suffix) and
        a static (tick, stage) -> {F/B/W, chunk, microbatch} program
        compiled by parallel/pipe_schedule.py drives a lax.switch per
        tick; "zbub" further splits backward into dgrad (critical path)
        and wgrad (bubble filler).  Both cut the pipeline bubble below
        1F1B's (S-1)/(M+S-1) — measured by the `bubble_frac` gauge —
        but compose with fewer knobs: the composed scheduler's pipe
        slot names each unsupported pairing (ScheduleConflictError).

        grad_clip: clip gradients to this global L2 norm (computed across
        every leaf; under ZeRO-2/3 the per-leaf square-sums run on the
        sharded grads and XLA inserts the psum).  loss_scale: None (off),
        a float (static scaling), or "dynamic" — scale the loss before
        backward, unscale grads after; dynamic keeps {scale, good-step
        count} in TrainState.scaler, halves the scale and SKIPS the
        optimizer step on non-finite grads, and doubles it after
        `loss_scale_growth_interval` consecutive finite steps.  This is
        fp16 AMP (the reference's unchecked TODO, reference README.md:68):
        bf16 — the TPU default policy — never needs it, fp16
        (compute_dtype=float16) does.

        telemetry: opt-in in-step observability (a
        `tiny_deepspeed_tpu.telemetry.Telemetry` instance, or any object
        with `on_step_output(aux)`).  When set, the compiled step also
        computes the packed on-device health vector (loss, grad/update/
        param global norms, non-finite grad count — telemetry/health.py)
        and `step()` pushes it into the telemetry object WITHOUT syncing;
        the vector rides the step output, so reading it costs the same
        single device->host transfer as reading the loss.  With
        telemetry=None (the default) the step program is byte-identical
        to an un-knobbed engine (tests/test_telemetry.py pins the HLO).
        A Telemetry constructed with layers=True additionally turns on
        per-layer health: the block scan taps every layer's output
        (parallel/schedule.layer_health_tap — the scheduler's probe
        slot) and the step also returns an (n_layer, 6) matrix of
        per-layer activation/activation-gradient/gradient norms and
        non-finite counts (telemetry/health.LAYER_FIELDS) — the
        first-NaN layer is localized in one step.  Composes with
        grad_buckets / quantized grad_comm / gather_prefetch / hpz via
        the composed scheduler lowering (pipeline forwards still
        refuse), model permitting (layer_health_capable: GPT-2/Llama;
        MoE is not).  With layers
        off the program is byte-identical to plain telemetry
        (tests/test_trace_flight.py pins the HLO).

        grad_comm: gradient-collective precision — "fp32" (default: the
        exact GSPMD path, compiled step byte-identical to an un-knobbed
        engine, pinned by tests/test_grad_comm.py), "int8" (blockwise
        absmax scales + stochastic rounding) or "fp8" (e4m3).  Quantized
        modes compute LOCAL grads inside a shard_map over the data axis
        and run the explicit schedule in parallel/comm.py: error-feedback
        residual (carried in TrainState.grad_residual, re-injected next
        step so quantization error cancels instead of accumulating),
        blockwise quantize, all-to-all reduce-scatter, quantized
        all-gather — ~4x less gradient wire than fp32 (ZeRO++ qgZ /
        EQuARX).  `grad_comm_block` sets the scale-block size;
        `grad_comm_groups` enables the hierarchical 2-hop schedule (that
        many consecutive ranks per low-precision intra-group hop, bf16
        across groups — for 2D meshes/tori where the inner group maps to
        the fast links); `grad_comm_error_feedback=False` drops the
        residual (saves its memory, costs convergence margin).  Needs a
        pure data-parallel mesh (no tp/sp/ep/pp — the explicit schedule
        replays the model inside a shard_map over the data axis, the
        same manual-region contract as the MoE pure-DP dispatch).
        Stages 0-2 run the legacy monolithic/bucketed lowerings
        unchanged; ZeRO-3 now composes too — the scheduler declares an
        implicit on-demand gather slot and runs the merged program
        (parallel/schedule.composed_step).  Composes with accumulation
        on the legacy lowerings (microbatches accumulate locally, ONE
        quantized sync per step; the composed lowering refuses accum
        loudly), grad clipping, loss scaling, and telemetry INCLUDING
        layers mode.  Under
        stage >= 2 the dequantized full gradient does materialize
        per-device before the sharding constraint re-slices it — the
        wire-vs-memory trade qgZ makes; keep fp32 when grad memory, not
        interconnect, is the binding constraint.  Inert (warning) on a
        1-device data axis.

        grad_buckets: bucketed backward-overlapped gradient release
        (parallel/schedule.GradBucketTap).  With K > 1 the gradient is split
        into K size-balanced buckets of consecutive layers (the stacked
        "h.*" leaves; K must divide n_layer) plus a tail bucket for the
        non-block leaves, and each layer bucket's collective — fp32
        pmean or the grad_comm int8/fp8 quantized schedule with
        per-bucket error-feedback residual slices — is emitted INSIDE
        the backward scan body via an identity custom_vjp on the bucket's
        param slice, as soon as that bucket's grads are final.  XLA's
        latency-hiding scheduler can then overlap bucket k's wire time
        with buckets k-1..0's backward compute — the reference's
        per-parameter backward-hook all-reduce (ddp/module.py:36-78) and
        its unshipped "communication bucketing" TODO (README.md:66-71).
        The monolithic schedule serializes ALL gradient wire behind the
        full backward; `utils/hlo_comm.overlap_report` measures the
        difference off the compiled HLO (the `grad_comm_overlap_frac`
        telemetry gauge).  grad_buckets=1 (default) keeps the exact
        monolithic program (byte-identical, pinned by
        tests/test_grad_buckets.py).  Same mesh contract as quantized
        grad_comm (pure data-parallel, model replayed with pctx=None
        inside a shard_map over the data axis) — plus the model must be
        grad_bucket_capable (GPT-2/Llama; MoE's scan carries an aux
        accumulator and is not).  ZeRO-3 and gather_quant compose via
        the scheduler's composed lowering (dW accumulates in f32 before
        each release, so no e4m3 cotangent reaches the wire).  Composes
        with grad_comm modes, accumulation (legacy lowering only, with
        buckets firing on the final microbatch), grad clip, loss
        scaling, and telemetry including layers.  Inert
        (warning) on a 1-device data axis.

        gather_prefetch: ZeRO-3 layer-ahead weight-gather prefetch
        (parallel/schedule.GatherPrefetchScan) — the forward/weight-side
        twin of grad_buckets.  With K >= 2 the block scan issues layer
        k+(K-1)'s parameter all-gather explicitly while layer k
        computes, holding at most K layers' gathered weights (K=2 =
        double buffer), on the forward AND the remat re-forward/backward
        (a custom_vjp reverse scan that also prefetches, and constrains
        each layer's dW to the sharded layout so the grad
        reduce-scatter stays in-loop) — DeepSpeed's stage-3 parameter
        prefetch, XLA-native (Xu et al. arXiv 2004.13336 is the
        weight-update-sharding precedent for making collective placement
        explicit rather than partitioner-implicit).  Composes with
        gather_quant="fp8" (the prefetched gathers move f8 bytes) and
        with accum / grad clip / loss scaling / dropout / telemetry.
        `gather_groups=m` adds the hierarchical 2-hop gather: resting
        precision (f8 when quantized) within m consecutive ranks,
        compute dtype across groups — mirroring grad_comm_groups; needs
        a pure data-parallel mesh (the gather runs a shard_map over the
        data axis).  ZeRO-3 only (stages 0-2 have no per-layer weight
        gather), scanned stack only (scan_unroll=1), no pipeline axis,
        and the model must be gather_prefetch_capable (GPT-2/Llama;
        MoE's scan carries an aux accumulator).  K in (0, 1) is OFF:
        the compiled step is byte-identical to an un-knobbed engine
        (pinned by tests/test_zero3_gather_prefetch.py).  Inert (warning) on
        a 1-device data axis.  Cost: K-1 extra clamped end-of-scan
        gathers per pass — (L+K-1)/L of the on-demand gather wire,
        priced in comm_report; placement measured by
        utils/hlo_comm.overlap_report (gather_overlap_frac).

        hpz: ZeRO++-style secondary weight partitioning
        (arXiv:2306.10209; parallel/schedule.py composed lowering).
        Each rank holds, next to its global fp32 ZeRO-3 shard, its
        SLICE's share of a full compute-dtype (bf16/fp8) block-weight
        replica — rebuilt once per step by a single top-level
        inter-slice all-gather — so every in-scan forward/backward
        weight gather runs over the intra-slice group only and moves
        ZERO DCN bytes (pinned via utils/hlo_comm.
        gather_link_split_in_loops on the emulated 2-slice mesh; the
        hpz_dcn_wire_bytes gauge).  The optimizer shards stay global
        ZeRO-3; the replica is stashed as a backward residual (HBM
        cost: compute-dtype block bytes / intra-slice ranks, per rank
        — PROFILE.md).  Requires ZeRO-3 + a pure-DP mesh with >= 2
        equal contiguous DCN granules (slices/processes;
        `hpz_granule_of` overrides the parallel/mesh.granule_map
        derivation for CPU-emulated tests).  Composes with
        gather_prefetch, grad_buckets/grad_comm, and telemetry layers.

        offload_opt_state: ZeRO-Offload-style placement — optimizer
        moments REST in host memory (NamedSharding memory_kind
        "pinned_host") instead of HBM, freeing ~8 bytes/param of chip
        memory between steps (f32 moments); the update STREAMS them
        through HBM one parameter leaf at a time (_offload_update:
        explicit transfer in -> update_one -> transfer out, barrier-
        chained so XLA cannot bulk-hoist the transfers — round-4 AOT
        topology measurement on gpt2-1.5b: compiled peak 12.8 GB streamed
        vs 17.0 GB bulk vs 15.2 GB unoffloaded; resting device state
        9.2 -> 3.1 GB).  Streaming granularity is one stacked leaf — the
        h.* tensors carry all L layers, so the largest in-flight chunk is
        one weight's (L, ...) moments.  The scalar step counter stays in
        device memory (its side-effecting placement annotation trips the
        SPMD partitioner).  TPU-runtime feature: XLA CPU does not
        implement the placement custom-call, so execution is covered by
        TPU-gated tests (tests/test_offload.py) and compilation by the
        TPU-topology AOT tests (tests/test_aot_topology.py)."""
        self.model = model
        self.optimizer = optimizer
        pp = int(pipeline_parallel)
        _unroll = getattr(getattr(model, "config", None), "scan_unroll", 1)
        if self.stage == 3 and (_unroll is True or _unroll not in (1, False)):
            # the documented footgun (GPTConfig.scan_unroll): ZeRO-3's
            # per-layer gather memory bound RELIES on the scan — an
            # unrolled stack lets XLA hoist the gathers and regrow
            # full-model HBM
            warnings.warn(
                "scan_unroll != 1 under ZeRO-3 defeats the per-layer "
                "all-gather memory bound (XLA may hoist every layer's "
                "gather); use the scanned stack (scan_unroll=1) for "
                "ZeRO-3 runs", stacklevel=2)
        if mesh is None:
            if not self.data_parallel:
                mesh = make_mesh(devices=[jax.devices()[0]])
            else:
                n = len(jax.devices())
                sp, tp = int(seq_parallel), int(tensor_parallel)
                ep = int(expert_parallel)
                if n % (sp * tp * ep * pp):
                    raise ValueError(
                        f"seq_parallel={sp} * tensor_parallel={tp} * "
                        f"expert_parallel={ep} * pipeline_parallel={pp} "
                        f"must divide device count {n}"
                    )
                shape, names = [n // (sp * tp * ep * pp)], ["data"]
                if sp > 1:
                    shape.append(sp); names.append("seq")
                if tp > 1:
                    shape.append(tp); names.append("model")
                if ep > 1:
                    shape.append(ep); names.append("expert")
                if pp > 1:
                    shape.append(pp); names.append("pipe")
                mesh = make_mesh(tuple(shape), tuple(names))
        self.mesh = mesh

        def _axis(name):
            return (
                name if name in mesh.axis_names
                and mesh.shape.get(name, 1) > 1 else None
            )

        self.seq_axis = _axis("seq")
        self.model_axis = _axis("model")
        self.expert_axis = _axis("expert")
        self.pipe_axis = _axis("pipe")
        # seq x pipe composes since pipeline v2: the pipeline's shard_map
        # goes manual over {pipe, seq} and ring attention runs inside it
        # (parallel/pipeline.py seq_axis, ops/attention.py dispatch)
        if self.pipe_axis is not None and not getattr(
            model, "pipeline_capable", False
        ):
            raise ValueError(
                f"{type(model).__name__} does not implement the pipeline "
                "forward (pipeline_capable=False); pipeline_parallel would "
                "silently run un-pipelined with the layer axis sharded"
            )
        # "interleaved:2" / "zbub:2" carry the virtual-stage count V in
        # the spec itself (the parse_sched_spec `pipe=KIND:V` form); an
        # explicit pipeline_virtual kwarg covers the programmatic path
        _psched = pipeline_schedule
        if ":" in _psched:
            _psched, _, _pv = _psched.partition(":")
            try:
                pipeline_virtual = int(_pv)
            except ValueError:
                raise ValueError(
                    f"pipeline_schedule {pipeline_schedule!r}: the ':V' "
                    f"suffix must be an integer virtual-stage count"
                ) from None
        if _psched not in ("gpipe", "1f1b", "interleaved", "zbub"):
            raise ValueError(
                f"pipeline_schedule must be 'gpipe', '1f1b', "
                f"'interleaved[:V]' or 'zbub[:V]', got "
                f"{pipeline_schedule!r}")
        self._use_1f1b = _psched == "1f1b"
        # table-driven schedules (interleaved / zero-bubble) compile a
        # static tick program via the composed scheduler's pipe slot
        self._use_pipe_table = _psched in ("interleaved", "zbub")
        self._pipe_kind = _psched
        self._pipe_virtual = max(int(pipeline_virtual), 1)
        if self._use_1f1b or self._use_pipe_table:
            # reject rather than silently run un-pipelined autodiff — a
            # user benchmarking "1f1b" must get the 1f1b code path
            if self.pipe_axis is None:
                raise ValueError(
                    f"pipeline_schedule={_psched!r} requires "
                    "pipeline_parallel > 1 (no 'pipe' mesh axis is "
                    "active)"
                )
        if self._use_1f1b and not getattr(model, "supports_1f1b", False):
            raise ValueError(
                f"{type(model).__name__} does not support the 1F1B "
                "schedule (no loss_and_grad_1f1b); use 'gpipe'"
            )
        if seq_impl not in ("ring", "ulysses"):
            raise ValueError(f"seq_impl must be 'ring' or 'ulysses', "
                             f"got {seq_impl!r}")
        if seq_impl == "ulysses" and self.seq_axis is not None:
            nh = getattr(getattr(model, "config", None), "n_head", None)
            tp_size = (mesh.shape[self.model_axis]
                       if self.model_axis is not None else 1)
            sp_size = mesh.shape[self.seq_axis]
            if nh is not None and (nh // tp_size) % sp_size:
                raise ValueError(
                    f"seq_impl='ulysses' needs local heads "
                    f"(n_head {nh} / tp {tp_size}) divisible by the seq "
                    f"axis size {sp_size} — use seq_impl='ring' instead"
                )
        self.pctx = ParallelContext(
            mesh=mesh, data_axis="data", seq_axis=self.seq_axis,
            model_axis=self.model_axis, expert_axis=self.expert_axis,
            pipe_axis=self.pipe_axis,
            pipe_microbatches=int(pipeline_microbatches or 0),
            seq_impl=seq_impl,
        )
        self.accum_steps = int(accum_steps)
        # dropout: the model's apply takes rng= when its config declares a
        # nonzero rate; the step derives a fresh key from the optimizer step
        # counter so every iteration (and every microbatch) draws new masks
        # without any state threading or re-jit
        self._dropout_active = bool(
            getattr(getattr(model, "config", None), "dropout", 0.0)
        )
        self.grad_clip = float(grad_clip) if grad_clip else None
        if loss_scale is not None and loss_scale != "dynamic" \
                and not isinstance(loss_scale, (int, float)):
            raise ValueError(
                f"loss_scale must be None, a number, or 'dynamic'; "
                f"got {loss_scale!r}"
            )
        self.loss_scale = loss_scale
        self.loss_scale_growth_interval = int(loss_scale_growth_interval)
        self.n_dev = mesh.devices.size
        # ZeRO sharding happens over the data axis only
        self.n_shard = mesh.shape["data"]

        # ---- the in-scan collective scheduler (parallel/schedule.py) ----
        # Every tap-style knob (grad_comm / grad_buckets / gather_prefetch
        # / hpz / telemetry layers) becomes a SLOT declaration; ONE
        # build_schedule call validates the composition and picks the
        # lowering -- legacy single-slot programs stay byte-identical, any
        # real composition runs the merged composed_step machine.
        from . import schedule as _sched
        from .comm import GRAD_COMM_MODES
        # "auto" = DCN-aware sizing: build_schedule derives the codec /
        # bucket count / inner-group factor from the mesh's granule map
        # (parallel/schedule.auto_comm_plan); the resolved values are
        # read back onto the engine attrs after the build below
        _auto = any(v == "auto"
                    for v in (grad_comm, grad_buckets, gather_groups))
        if grad_comm not in GRAD_COMM_MODES and grad_comm != "auto":
            raise ValueError(
                f"grad_comm must be one of {GRAD_COMM_MODES} or 'auto', "
                f"got {grad_comm!r}"
            )
        self.grad_comm = grad_comm
        self.grad_comm_block = int(grad_comm_block)
        self.grad_comm_groups = (
            int(grad_comm_groups) if grad_comm_groups else None
        )
        if grad_comm == "fp32" and self.grad_comm_groups:
            # loud rejection, not a silent fp32 run mislabeled as the
            # 2-hop schedule (the pipeline_schedule='1f1b' convention)
            raise ValueError(
                "grad_comm_groups requires grad_comm='int8' or 'fp8' "
                "(grad_comm='fp32' runs no quantized schedule)"
            )
        self.grad_comm_error_feedback = bool(grad_comm_error_feedback)
        self.grad_buckets = grad_buckets if grad_buckets == "auto" \
            else (int(grad_buckets) if grad_buckets else 1)
        if self.grad_buckets != "auto" and self.grad_buckets < 1:
            raise ValueError(
                f"grad_buckets must be >= 1, got {grad_buckets}"
            )
        if grad_comm_tail not in GRAD_COMM_MODES:
            raise ValueError(
                f"grad_comm_tail must be one of {GRAD_COMM_MODES}, "
                f"got {grad_comm_tail!r}"
            )
        self.grad_comm_tail = grad_comm_tail
        self.gather_prefetch = int(gather_prefetch) if gather_prefetch \
            else 0
        if self.gather_prefetch < 0:
            raise ValueError(
                f"gather_prefetch must be >= 0 (0/1 = the on-demand "
                f"gather; K >= 2 holds K layers), got {gather_prefetch}"
            )
        self.gather_groups = gather_groups if gather_groups == "auto" \
            else (int(gather_groups) if gather_groups else None)
        if self.gather_groups and self.gather_groups != "auto" \
                and self.gather_prefetch <= 1:
            # loud rejection, not a silently-flat gather mislabeled
            # as the 2-hop schedule (the grad_comm_groups convention)
            raise ValueError(
                "gather_groups requires gather_prefetch >= 2 (the "
                "2-hop gather lives in the explicit prefetched "
                "schedule)"
            )
        self.hpz = bool(hpz)
        if hpz_comm not in GRAD_COMM_MODES:
            raise ValueError(
                f"hpz_comm must be one of {GRAD_COMM_MODES}, "
                f"got {hpz_comm!r}"
            )
        self.hpz_comm = hpz_comm
        granule_of = hpz_granule_of
        if (self.hpz or _auto) and granule_of is None:
            from .mesh import granule_map
            granule_of = granule_map(mesh.devices.flatten())

        # telemetry attrs settle BEFORE the schedule build (the probe
        # slot comes from Telemetry(layers=True))
        self.telemetry = telemetry
        self._telemetry_on = telemetry is not None
        if self._telemetry_on and hasattr(telemetry, "attach"):
            telemetry.attach(self)
        self._layers_on = bool(
            self._telemetry_on and getattr(telemetry, "layers", False)
        )
        self._layer_count = int(
            getattr(getattr(model, "config", None), "n_layer", 0) or 0
        )

        busy_axes = (self.seq_axis, self.model_axis, self.expert_axis,
                     self.pipe_axis)
        self._schedule = _sched.build_schedule(
            model=model, stage=self.stage, n_shard=self.n_shard,
            busy_axes=busy_axes, accum_steps=self.accum_steps,
            scan_unroll=_unroll, grad_comm=grad_comm,
            grad_comm_block=self.grad_comm_block,
            grad_comm_groups=self.grad_comm_groups,
            grad_comm_error_feedback=self.grad_comm_error_feedback,
            grad_buckets=self.grad_buckets,
            grad_comm_tail=self.grad_comm_tail,
            gather_prefetch=self.gather_prefetch,
            gather_groups=self.gather_groups,
            hpz=self.hpz, hpz_comm=self.hpz_comm,
            granule_of=granule_of,
            telemetry_layers=self._layers_on,
            pipeline=self.pipe_axis is not None or self._use_1f1b,
            pipe_schedule=(self._pipe_kind if self._use_pipe_table
                           else None),
            pipe_stages=(mesh.shape[self.pipe_axis]
                         if self.pipe_axis is not None else 0),
            pipe_virtual=self._pipe_virtual,
            pipe_microbatches=self.pctx.pipe_microbatches,
        )
        self._lowering = self._schedule.lowering
        sg, sr = self._schedule.gather, self._schedule.grad
        if _auto:
            # read the DCN-aware plan's resolved values back so
            # describe()/telemetry/checkpoints see concrete knobs, never
            # the "auto" sentinel
            self.grad_comm = sr.mode if sr is not None else "fp32"
            self.grad_buckets = sr.buckets if sr is not None else 1
            self.gather_groups = sg.groups if sg is not None else None
        self._grad_comm_active = sr is not None and sr.mode != "fp32"
        self._bucketed_active = sr is not None and sr.buckets > 1
        self._gather_prefetch_active = sg is not None and sg.prefetch > 1

        shapes = model.param_shapes()
        # API-parity ownership table (the reference's cache rank map).
        self.rank_map = partition_tensors(
            shapes, self.n_shard, evenness_priority
        )
        if evenness_priority:
            # the knob is real for the TABLE but deliberately inert for the
            # layout: engines always shard evenly along tensor axes (SPMD)
            # rather than placing whole tensors per owner like the
            # reference; say so instead of silently ignoring the intent
            warnings.warn(
                "evenness_priority shapes only engine.rank_map (the "
                "reference-parity ownership report); the physical layout "
                "is always even axis-sharding.  For the reference's "
                "whole-tensor placement semantics use partition_tensors + "
                "materialize_owned directly (parallel/partition.py).",
                stacklevel=2,
            )

        # tensor/expert-parallel placements come from the model and are part
        # of EVERY spec (resting, shard, grad, optimizer) — ZeRO's data-axis
        # shard composes on a remaining dim.
        if self.model_axis is not None:
            # attention shards over heads: validate at init, not deep inside
            # a shard_map trace at step time (e.g. gpt2-1.5b has n_head=25)
            nh = getattr(getattr(model, "config", None), "n_head", None)
            tp_size = mesh.shape[self.model_axis]
            if nh is not None and nh % tp_size:
                raise ValueError(
                    f"n_head={nh} not divisible by tensor-parallel axis "
                    f"size {tp_size}"
                )

        reserved: Dict[str, Dict[int, str]] = {}
        for ax_attr, rules_fn in (
            (self.model_axis, "tp_rules"), (self.expert_axis, "ep_rules")
        ):
            if ax_attr is None:
                continue
            size = mesh.shape[ax_attr]
            for name, dim in getattr(model, rules_fn, dict)().items():
                if name not in shapes:
                    continue
                if shapes[name].shape[dim] % size:
                    raise ValueError(
                        f"{name} dim {dim} ({shapes[name].shape[dim]}) not "
                        f"divisible by {ax_attr} axis size {size}"
                    )
                reserved.setdefault(name, {})[dim] = ax_attr

        if self.pipe_axis is not None:
            # each pipeline stage owns a contiguous slab of the stacked
            # (n_layer, ...) block tensors: leading axis sharded over "pipe"
            pp_size = mesh.shape[self.pipe_axis]
            for name, s in shapes.items():
                if not name.startswith("h."):
                    continue
                if s.shape[0] % pp_size:
                    raise ValueError(
                        f"n_layer={s.shape[0]} not divisible by "
                        f"pipeline_parallel={pp_size}"
                    )
                reserved.setdefault(name, {})[0] = self.pipe_axis

        # fp8 gather: pin quant-eligible leaves' ZeRO shard to the IN dim
        # (dim 1 of the stacked (L, in, out)) so the shard axis differs
        # from the per-out-channel scale axis and the per-layer gathers
        # move f8 bytes (see _leaf_spec prefer_dim).  Under TP, o/down
        # reserve dim 1 for the model axis — those fall back to the walk.
        prefer_dims = {}
        if getattr(getattr(model, "config", None), "gather_quant", None) \
                and hasattr(model, "_quant_eligible"):
            prefer_dims = {
                n: 1 for n, s in shapes.items()
                if n.startswith("h.")
                and model._quant_eligible(n[len("h."):], s)
            }
        specs = _param_spec_tree(shapes, self.n_shard, reserved,
                                 prefer_dims=prefer_dims)
        self._shard_spec = specs  # even-shard spec per param
        self._shard_shardings = _to_shardings(specs, mesh)
        # base spec: tensor/expert placements only (no ZeRO data shard)
        base = _param_spec_tree(shapes, 1, reserved)
        # in-scan specs for the stacked block leaves (leading layer axis
        # sliced off): what each per-layer weight's gathered layout is —
        # consumed by the model's fp8-gather path (mesh.ParallelContext.
        # stacked_specs docstring)
        stacked_specs = {}
        for name, s in shapes.items():
            if not name.startswith("h."):
                continue
            entries = list(base[name]) + [None] * (
                len(s.shape) - len(base[name])
            )
            stacked_specs[name[len("h."):]] = P(*entries[1:])
        self.pctx = dataclasses.replace(
            self.pctx, stacked_specs=stacked_specs
        )
        self._prefetch_exec = None
        if self._schedule.gather is not None:
            # the scheduled gather needs BOTH per-layer layouts: gathered
            # (stacked_specs above — the gather target) and resting-
            # sharded (the gather source + the per-layer dW cotangent
            # constraint that keeps the reduce-scatter in-loop)
            stacked_shard = {}
            for name, s in shapes.items():
                if not name.startswith("h."):
                    continue
                entries = list(specs[name]) + [None] * (
                    len(s.shape) - len(specs[name])
                )
                stacked_shard[name[len("h."):]] = P(*entries[1:])
            self.pctx = dataclasses.replace(
                self.pctx,
                gather_prefetch=self.gather_prefetch,
                gather_groups=self.gather_groups,
                stacked_shard_specs=stacked_shard,
            )
            if self._lowering == "prefetch":
                # legacy single-slot lowering: the GatherPrefetchScan
                # executor passes through model.apply(sched=...) — same
                # ctor args as the pre-scheduler pctx branch, so the
                # traced program (and its HLO) is unchanged
                self._prefetch_exec = _sched.GatherPrefetchScan(
                    self.gather_prefetch, mesh, stacked_specs,
                    stacked_shard, groups=self.gather_groups,
                    data_axis="data",
                    compute_dtype=model.config.compute_dtype,
                )
        # where params LIVE between steps
        self._param_spec_rest = specs if self.stage >= 3 else base
        self._param_shardings = _to_shardings(self._param_spec_rest, mesh)

        opt_shapes = jax.eval_shape(optimizer.init, shapes)
        opt_specs = _opt_spec_tree(
            opt_shapes, specs, sharded=self.stage >= 1, base_specs=base
        )
        self._opt_shardings = _to_shardings(opt_specs, mesh)
        self.offload_opt_state = bool(offload_opt_state)
        # validated, not silently clamped (the old max(2, ...) floor ate
        # user intent): 1 is honored as "no double buffer" — each leaf's
        # inbound transfer chains on the PREVIOUS leaf's outbound, fully
        # serial streaming at minimum in-flight moment memory
        self.offload_prefetch = int(offload_prefetch)
        if self.offload_prefetch < 1:
            raise ValueError(
                f"offload_prefetch must be >= 1 (1 = serial streaming, "
                f"no double buffer; default 2), got {offload_prefetch}"
            )
        if self.offload_opt_state:
            from ..optim.base import Optimizer as _OptBase
            if type(optimizer).update is not _OptBase.update:
                # the streamed update path calls update_one per leaf; an
                # optimizer overriding update() (cross-parameter logic)
                # would be silently bypassed — refuse instead
                raise ValueError(
                    f"offload_opt_state streams moments via the per-leaf "
                    f"update_one contract, but {type(optimizer).__name__} "
                    f"overrides update(); offload is unsupported for it"
                )
            if jax.default_backend() != "tpu":
                warnings.warn(
                    "offload_opt_state needs the TPU runtime — XLA CPU "
                    "has no placement custom-call; expect "
                    "'annotate_device_placement' errors at init/step",
                    stacklevel=2,
                )
            # per-param moments to host memory; "step" (and any other
            # top-level scalar) stays device-resident.  The step streams
            # them through HBM for the update (_step_impl transfers in;
            # out_shardings put the new moments back) — TPU XLA refuses
            # mixed-memory-space arithmetic, so the transfer must be
            # explicit (caught by the round-4 AOT topology compile).
            self._opt_dev_shardings = self._opt_shardings["state"]
            self._opt_shardings = dict(
                self._opt_shardings,
                state=jax.tree.map(
                    lambda s: s.with_memory_kind("pinned_host"),
                    self._opt_shardings["state"],
                ),
            )
        self._scaler_shardings = (
            {"scale": NamedSharding(mesh, P()),
             "good": NamedSharding(mesh, P())}
            if self.loss_scale == "dynamic" else None
        )
        # error-feedback residual: per-device flat error, global shape
        # (n_shard, padded_elems) sharded over the data axis — each rank's
        # row is ITS quantization error (parallel/comm.py docstring)
        # bucketed-release geometry: layer-bucket / tail-pad sizes and the
        # residual layout (raises here, at init, when grad_buckets does
        # not divide n_layer)
        # bucket / residual geometry comes from the compiled Schedule:
        # legacy bucket lowering keeps the [b0 | ... | bK-1 | tail] row,
        # monolithic quant the whole-tree pad, composed ZeRO-3 drops the
        # tail slice (the tail reduce-scatters at full precision)
        self._bucket_layout = self._schedule.layout
        self._residual_shardings = None
        self._residual_shape = None
        if self._schedule.residual_len:
            self._residual_shape = (
                self.n_shard, self._schedule.residual_len
            )
            self._residual_shardings = NamedSharding(mesh, P("data"))
        self._dropout_shardings = (
            NamedSharding(mesh, P()) if self._dropout_active else None
        )

        if self.data_parallel:
            batch_spec = P("data", self.seq_axis)  # (B, T): tokens shard too
        else:
            batch_spec = P()
        self._eval_batch_sharding = NamedSharding(mesh, batch_spec)
        if self.accum_steps > 1:
            batch_spec = P(None, *batch_spec)
        self._batch_sharding = NamedSharding(mesh, batch_spec)

        self._build_step()

        def tds_eval(params, ix, tg):
            from ..ops.dispatch import gspmd_auto_region
            kw = {}
            if self._lowering == "prefetch":
                # keep the legacy eval program: the forward-only pass
                # also runs the prefetched gather scan
                kw["sched"] = self._prefetch_exec
            with gspmd_auto_region(self.n_dev > 1):
                return self.model.apply(params, ix, tg, pctx=self.pctx,
                                        **kw)

        # forward-only loss (validation): no dropout (no rng), no grads, no
        # state change; always takes a plain (B, T) batch (no accum axis)
        self._eval = jax.jit(
            tds_eval,
            in_shardings=(
                self._param_shardings,
                self._eval_batch_sharding, self._eval_batch_sharding,
            ),
            out_shardings=NamedSharding(mesh, P()),
        )

    def _build_step(self) -> None:
        from ..autotuner import get_default_tuner
        tuner = get_default_tuner()
        # the winner-table version this program was traced against; retune
        # rebuilds only when timing has produced new winners since
        self._tuner_version = getattr(tuner, "version", 0)
        # the name a device trace's `XLA Modules` line reads:
        # jit_tds_train_step (utils/profiling.TABLE)
        def tds_train_step(state, batch):
            return self._step_impl(state, batch)

        self._step = jax.jit(
            tds_train_step,
            in_shardings=(
                TrainState(
                    params=self._param_shardings,
                    opt_state=self._opt_shardings,
                    scaler=self._scaler_shardings,
                    dropout_base=self._dropout_shardings,
                    grad_residual=self._residual_shardings,
                ),
                (self._batch_sharding, self._batch_sharding),
            ),
            out_shardings=(
                TrainState(
                    params=self._param_shardings,
                    opt_state=self._opt_shardings,
                    scaler=self._scaler_shardings,
                    dropout_base=self._dropout_shardings,
                    grad_residual=self._residual_shardings,
                ),
                NamedSharding(self.mesh, P()),
            ) + (
                # telemetry: the packed (5,) health vector rides along,
                # replicated like the loss — plus the (n_layer, 6)
                # layer-health matrix in layers mode
                (NamedSharding(self.mesh, P()),) if self._telemetry_on
                else ()
            ) + (
                (NamedSharding(self.mesh, P()),) if self._layers_on
                else ()
            ),
            donate_argnums=(0,),
        )

    def retune(self) -> int:
        """Autotune lifecycle step: ops consulted the default RuntimeAutoTuner
        during the first trace, which RECORDS candidate requests (timing
        cannot run inside a trace — autotuner/runtime_tuner.py).  This times
        them on the device now and rebuilds the jitted step so the winners
        are baked in.  Returns the number of sites tuned; no-op (0) without
        an installed tuner or pending requests.

        Usage:  engine.step(state, batch)   # first step: trace + record
                engine.retune()             # time candidates, re-jit
                engine.step(state, batch)   # tuned program from here on
        """
        from ..autotuner import get_default_tuner
        tuner = get_default_tuner()
        if tuner is None:
            return 0
        n = tuner.resolve_pending()
        # rebuild iff timing produced winners SINCE this program was traced —
        # covers another engine resolving our pending keys (version moved,
        # n == 0 here), and correctly skips the rebuild when every site was
        # satisfied from the ahead-of-time cache during the trace (version
        # unchanged: a re-trace would compile the identical program)
        if tuner.version != self._tuner_version:
            self._build_step()
        return n

    # -- state creation ----------------------------------------------------

    def init(self, key) -> "TrainState":
        """Create params + optimizer state directly in their resting
        shardings (no full-replica materialization step — fixes the
        reference's full `.to(rank)` before wrapping, zero1/train.py:34)."""
        params = jax.jit(
            self.model.init, out_shardings=self._param_shardings
        )(key)
        opt_state = jax.jit(
            self.optimizer.init, out_shardings=self._opt_shardings
        )(params)
        scaler = None
        if self.loss_scale == "dynamic":
            scaler = jax.device_put(
                {"scale": jnp.float32(2.0 ** 15),
                 "good": jnp.zeros((), jnp.int32)},
                self._scaler_shardings,
            )
        # dropout base derived from the user's key (NOT the same stream as
        # param init) so seeded runs draw distinct mask sequences; lives in
        # the state (not a closure constant), so re-init with a new seed and
        # checkpoint restore both get the right stream with no re-jit
        dropout_base = None
        if self._dropout_active:
            dropout_base = jax.device_put(
                jax.random.fold_in(key, 0xD0), self._dropout_shardings
            )
        grad_residual = None
        if self._residual_shardings is not None:
            # zeros created directly in the (data,)-sharded layout
            grad_residual = jax.jit(
                partial(jnp.zeros, self._residual_shape, jnp.float32),
                out_shardings=self._residual_shardings,
            )()
        return TrainState(params=params, opt_state=opt_state, scaler=scaler,
                          dropout_base=dropout_base,
                          grad_residual=grad_residual)

    # -- the train step ----------------------------------------------------

    @staticmethod
    def _constrain(tree, shardings):
        return jax.tree.map(
            jax.lax.with_sharding_constraint, tree, shardings
        )

    def _offload_update(self, params, grads, opt_state, finite=None):
        """Optimizer update for `offload_opt_state`: moments REST in
        pinned_host and are STREAMED through HBM leaf by leaf — transfer
        in, update_one, transfer back — windowed: leaf i's inbound
        transfer is made data-dependent (optimization_barrier) on leaf
        i-`offload_prefetch`'s outbound copy, so at most `offload_prefetch`
        leaves' moments are in HBM while transfer and update compute
        overlap.  Without any chaining XLA hoists every transfer to the
        front and the full moments sit in HBM as one temp allocation,
        erasing the feature's point (measured on the round-4 AOT topology
        compile: 1.5B peak 17.0 GB unchained vs 12.8 GB double-buffered
        vs 15.2 GB unoffloaded).  `offload_prefetch` (round 5) makes the
        window explicit; the default stays 2 because the round-5 AOT
        schedule study came back NEGATIVE on widening at leaf
        granularity: w=4 compiles to 17.25 GB peak on the 1.5B bench
        config (four of the multi-GB stacked leaves in flight — over the
        16 GB chip) while the scheduler still refuses to hoist the
        dependency-free leading inbound copies under the fwd/bwd (first
        inbound copy-start sits at ~86% of the schedule for w=2/4/6
        alike), so the extra window buys HBM pressure, not overlap.  The
        knob remains for the chip A/B at sizes with headroom
        (774M, w=2 vs w=4); within the update
        phase the w=2 chain already lets inbound(i) overlap both
        update(i-1) and outbound(i-1) (86/110 copy pairs overlap >=1
        fusion in the compiled schedule).
        `finite` (dynamic loss scaling) applies the keep-old MOMENTS
        selection ON DEVICE before the copy-out — host-space arithmetic is
        rejected by the TPU compiler; the params selection stays with the
        caller's _sel like the non-offload path.  Mirrors
        Optimizer.update's step/state contract via the public update_one
        hook; optimizers overriding update() are rejected at engine
        construction."""
        step_new = opt_state["step"] + 1
        new_params, new_state = {}, {}
        w = self.offload_prefetch  # in-flight window (leaves of moments)
        tokens = [()] * w
        for n, p in params.items():
            host_leaf = opt_state["state"][n]
            host_leaf, _ = jax.lax.optimization_barrier(
                (host_leaf, tokens[-w])
            )
            dev_leaf = jax.tree.map(
                jax.device_put, host_leaf, self._opt_dev_shardings[n]
            )
            np_, ns = self.optimizer.update_one(
                n, p, grads[n], dev_leaf, step_new
            )
            if finite is not None:
                ns = jax.tree.map(
                    lambda a, b: jnp.where(finite, a, b.astype(a.dtype)),
                    ns, dev_leaf,
                )
            ns_host = jax.tree.map(
                jax.device_put, ns, self._opt_shardings["state"][n]
            )
            new_params[n], new_state[n] = np_, ns_host
            tokens.append(tuple(jax.tree.leaves(ns_host)))
        step_out = (
            jnp.where(finite, step_new, opt_state["step"])
            if finite is not None else step_new
        )
        return new_params, {"step": step_out, "state": new_state}

    def _step_impl(self, state: "TrainState", batch):
        # trace-time marker: on a multi-device mesh this program is GSPMD
        # auto-partitioned, so naked Mosaic custom calls cannot lower —
        # the layernorm gate reads this and keeps the XLA path
        # (ops/dispatch.py; attention wraps its own shard_map instead)
        from ..ops.dispatch import gspmd_auto_region
        with gspmd_auto_region(self.n_dev > 1):
            return self._step_body(state, batch)

    def _step_body(self, state: "TrainState", batch):
        idx, targets = batch
        params = state.params
        dynamic = self.loss_scale == "dynamic"
        if dynamic:
            scale = state.scaler["scale"]
        elif self.loss_scale:
            scale = jnp.float32(self.loss_scale)
        else:
            scale = None

        rng = (
            jax.random.fold_in(state.dropout_base, state.opt_state["step"])
            if self._dropout_active else None
        )

        # per-layer health probe (telemetry layers mode): a zeros (L, 4)
        # array differentiated alongside the params — its "gradient" is
        # the per-layer activation/activation-gradient stats smuggled out
        # of the scan by parallel/schedule.layer_health_tap
        probe0 = None
        if self._layers_on:
            from .schedule import LAYER_PROBE_WIDTH
            probe0 = jnp.zeros(
                (self._layer_count, LAYER_PROBE_WIDTH), jnp.float32
            )

        def loss_fn(p, ix, tg, rng=None, probe=None):
            from .schedule import ProbeScan
            kw = {"rng": rng} if rng is not None else {}
            if probe is not None:
                # probe lowering: the executor adds the (L, 4) probe row
                # to the stacked scan tree — the plain-scan program is
                # byte-identical to the pre-scheduler health_probe= path
                kw["sched"] = ProbeScan(probe)
            elif self._lowering == "prefetch":
                kw["sched"] = self._prefetch_exec
            l = self.model.apply(p, ix, tg, pctx=self.pctx, **kw)
            # loss scaling happens INSIDE the differentiated fn so the
            # whole backward runs on scaled values (fp16 AMP)
            return l * scale if scale is not None else l

        def loss_and_grads(p, ix, tg, rng=None):
            """(loss, grads, probe cotangent or None)."""
            if self._use_pipe_table:
                # grads computed INSIDE the tick table (per-op vjp) —
                # the interleaved/zero-bubble program is a static
                # (tick, stage) schedule compiled by build_schedule
                # (parallel/pipe_schedule.py), not autodiff output
                l, g = self.model.loss_and_grad_pipe(
                    p, ix, tg, pctx=self.pctx,
                    program=self._schedule.pipe_program,
                    loss_seed=scale if scale is not None else 1.0,
                    rng=rng,
                )
                return l, g, None
            if self._use_1f1b:
                # grads computed INSIDE the pipeline (per-tick vjp) — the
                # 1F1B schedule can't be expressed through autodiff
                l, g = self.model.loss_and_grad_1f1b(
                    p, ix, tg, pctx=self.pctx,
                    loss_seed=scale if scale is not None else 1.0,
                    rng=rng,
                )
                return l, g, None
            if self._layers_on:
                l, (g, ps) = jax.value_and_grad(
                    loss_fn, argnums=(0, 4)
                )(p, ix, tg, rng, probe0)
                return l, g, ps
            l, g = jax.value_and_grad(loss_fn)(p, ix, tg, rng)
            return l, g, None

        new_residual = state.grad_residual
        layer_probe = None
        if self._lowering == "composed":
            # the merged scheduler machine (parallel/schedule.py): every
            # declared slot — explicit prefetched/hpZ gathers, bucketed
            # quantized releases, the health probe — in ONE custom_vjp
            # scan pair inside a shard_map over the data axis.  Grads
            # come back reduced and UNSCALED like the legacy explicit
            # paths below.
            from .schedule import composed_step
            loss, grads, new_residual, layer_probe = composed_step(
                self, state, idx, targets, rng, scale
            )
        elif self._lowering == "bucket":
            # bucketed backward-overlapped release (grad_buckets > 1):
            # per-bucket collectives emitted inside the backward scan
            # body, fp32 or quantized.  Grads come back reduced and
            # UNSCALED, like the quantized path below.
            from .schedule import bucketed_step
            loss, grads, new_residual = bucketed_step(
                self, state, idx, targets, rng, scale
            )
        elif self._lowering == "quant_mono":
            # quantized gradient collectives (parallel/comm.py): local
            # grads inside a shard_map over the data axis, explicit
            # error-feedback int8/fp8 reduce-scatter + all-gather.  Grads
            # come back UNSCALED (the residual must live in true gradient
            # units); the loss is still scaled like the GSPMD path.
            from .schedule import monolithic_quant_step
            loss, grads, new_residual = monolithic_quant_step(
                self, state, idx, targets, rng, scale
            )
        elif self.accum_steps == 1:
            loss, grads, layer_probe = loss_and_grads(
                params, idx, targets, rng
            )
        else:
            # Microbatch accumulation: batch is (accum, B, T) — the
            # reference's `require_backward_grad_sync` gating
            # (ddp/wrapper.py:25-33) as explicit loop semantics.  Stage
            # <= 1 (replicated grads): summed locally, ONE all-reduce at
            # the end.  Stage >= 2 trades that for memory: the constraint
            # below keeps the f32 accumulator SHARDED, so every microbatch
            # reduce-scatters into the shard — accum_steps x the wire
            # bytes (TPU-measured, PROFILE.md) but never a full-size
            # accumulator per device, which is the point in the big-model
            # tight-HBM case accumulation exists for.
            def body(carry, mb):
                acc_loss, acc_grads, acc_probe = carry
                ix, tg, mb_i = mb
                mb_rng = (jax.random.fold_in(rng, mb_i)
                          if rng is not None else None)
                l, g, ps = loss_and_grads(params, ix, tg, mb_rng)
                acc_grads = jax.tree.map(
                    lambda a, b: a + b.astype(jnp.float32), acc_grads, g
                )
                if ps is not None:
                    # probe stats are raw sq-sums + counts, so summing
                    # across microbatches keeps global-batch semantics
                    # (norms taken once, in layer_health_matrix)
                    acc_probe = acc_probe + ps
                if self.stage >= 2:
                    # keep the f32 accumulator SHARDED across microbatches:
                    # each microbatch's grad reduce-scatters into the shard
                    # instead of carrying a full per-device replica through
                    # the scan — exactly the big-model tight-HBM case where
                    # accumulation matters (round-1 verdict weak #3).
                    acc_grads = self._constrain(
                        acc_grads, self._shard_shardings
                    )
                return (acc_loss + l, acc_grads, acc_probe), None

            zero_grads = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params
            )
            if self.stage >= 2:
                zero_grads = self._constrain(
                    zero_grads, self._shard_shardings
                )
            (loss, grads, layer_probe), _ = jax.lax.scan(
                body, (jnp.zeros((), jnp.float32), zero_grads, probe0),
                (idx, targets, jnp.arange(self.accum_steps)),
            )
            loss = loss / self.accum_steps
            grads = jax.tree.map(
                lambda g, p: (g / self.accum_steps).astype(p.dtype),
                grads, params,
            )

        def _rescale(tree, factor):
            return jax.tree.map(
                lambda g: (g.astype(jnp.float32) * factor).astype(g.dtype),
                tree,
            )

        if scale is not None:
            loss = loss / scale
            if self._lowering in ("plain", "probe", "prefetch", "pipe"):
                # the explicit-schedule lowerings (composed / bucket /
                # quant_mono) already unscaled before their collectives
                with jax.named_scope("tds.optim"):
                    grads = _rescale(grads, 1.0 / scale)
            if layer_probe is not None:
                # the backward ran on the scaled loss: the dact sq-sum
                # column (2) carries scale^2; the non-finite counts stay
                # as observed (AMP overflow IS the scaled-backward truth)
                layer_probe = layer_probe.at[:, 2].multiply(
                    1.0 / (scale * scale)
                )
        if dynamic:
            # finiteness judged on the UNSCALED grads, before clipping can
            # turn an inf norm into nans
            finite = jnp.bool_(True)
            for g in jax.tree.leaves(grads):
                finite = jnp.logical_and(finite, jnp.all(jnp.isfinite(g)))
        if self.grad_clip is not None:
            with jax.named_scope("tds.optim"):
                gsq = sum(
                    jnp.sum(jnp.square(g.astype(jnp.float32)))
                    for g in jax.tree.leaves(grads)
                )
                grads = _rescale(grads, jnp.minimum(
                    1.0, self.grad_clip / (jnp.sqrt(gsq) + 1e-6)
                ))

        if self.stage >= 2:
            # ZeRO-2/3: gradient sharding — the all-reduce XLA would emit for
            # replicated-param grads becomes a reduce-scatter.
            with jax.named_scope("tds.grad_sync"):
                grads = self._constrain(grads, self._shard_shardings)

        with jax.named_scope("tds.optim"):
            if self.offload_opt_state:
                new_params, new_opt = self._offload_update(
                    params, grads, state.opt_state,
                    finite if dynamic else None,
                )
            else:
                new_params, new_opt = self.optimizer.update(
                    params, grads, state.opt_state
                )
        new_scaler = state.scaler
        if dynamic:
            # overflow -> discard the whole update (params, moments, AND the
            # step counter: a skipped step must not advance bias correction),
            # halve the scale; grow it after `growth_interval` clean steps
            def _sel(new, old):
                return jax.tree.map(
                    lambda n, o: jnp.where(finite, n, o.astype(n.dtype)),
                    new, old,
                )
            new_params = _sel(new_params, params)
            if not self.offload_opt_state:
                # offloaded moments already selected on device inside
                # _offload_update (host-space where() won't compile on TPU)
                new_opt = _sel(new_opt, state.opt_state)
            if self._grad_comm_active and new_residual is not None:
                # the skipped step's sync consumed the carried residual
                # into a DISCARDED update; rolling it back with the rest
                # of the state keeps the deferred gradient signal from
                # being lost on every scale-halving step
                new_residual = _sel(new_residual, state.grad_residual)
            good = state.scaler["good"] + 1
            grow = good >= self.loss_scale_growth_interval
            new_scaler = {
                "scale": jnp.where(
                    finite,
                    jnp.where(grow, scale * 2.0, scale),
                    jnp.maximum(scale * 0.5, 1.0),
                ),
                "good": jnp.where(
                    jnp.logical_and(finite, jnp.logical_not(grow)), good, 0
                ).astype(jnp.int32),
            }
        # ZeRO-1/2: updated params all-gather back to replicated; ZeRO-3:
        # they stay sharded.  (The reference broadcasts per-param from the
        # owner in a python loop with no bucketing, zero1/optim.py:25-34.)
        with jax.named_scope("tds.gather"):
            new_params = self._constrain(new_params, self._param_shardings)
        new_state = TrainState(params=new_params, opt_state=new_opt,
                               scaler=new_scaler,
                               dropout_base=state.dropout_base,
                               grad_residual=new_residual)
        if self._telemetry_on:
            # on-device health metrics, packed into one (5,) vector: the
            # norms run over the logical (sharded) grads/params, so XLA
            # inserts the cross-shard psum and the numbers are global
            from ..telemetry.health import health_vector
            aux = health_vector(loss, grads, params, new_params)
            if self._layers_on:
                # (n_layer, 6) layer-health matrix: the probe cotangent
                # (act/dact stats from inside the scan) + per-layer grad
                # stats read off the stacked "h.*" gradient leaves
                from ..telemetry.health import layer_health_matrix
                mat = layer_health_matrix(layer_probe, grads)
                return new_state, loss, aux, mat
            return new_state, loss, aux
        return new_state, loss

    def step(self, state, batch):
        """One optimizer step.  batch = (idx, targets), each (B, T) int32 —
        or (accum, B, T) when accum_steps > 1.  Returns (state, loss)
        either way; with the telemetry knob the step's packed health
        vector (and, in layers mode, the per-layer health matrix) is
        pushed into the telemetry object un-synced."""
        # tds.step: what enqueueing a step costs the host (argument
        # sharding, dispatch, the telemetry hand-off); the device runs on
        with span("tds.step"):
            if self._telemetry_on:
                if self._layers_on:
                    state, loss, aux, mat = self._step(state, batch)
                    self.telemetry.on_step_output(aux, layers=mat)
                else:
                    state, loss, aux = self._step(state, batch)
                    self.telemetry.on_step_output(aux)
                return state, loss
            return self._step(state, batch)

    def eval_loss(self, state, batch):
        """Mean loss on one (B, T) batch — forward only: deterministic (no
        dropout), no gradients, no state change.  The validation half of
        the train/eval contract (the reference has no eval path at all)."""
        idx, targets = batch
        return self._eval(state.params, idx, targets)

    def state_target(self) -> "TrainState":
        """The restore target for this engine's TrainState: a pytree of
        ShapeDtypeStruct(+NamedSharding) describing where every leaf
        should land — params replicated or ZeRO-3-sharded, optimizer
        state ZeRO-sharded, scaler/dropout/residual as configured.
        Consumed by utils.checkpoint.load_checkpoint and the elastic
        resume path (resilience/elastic.py), which swaps individual
        sub-targets when the checkpoint was written on a different
        topology."""
        shapes = jax.eval_shape(
            lambda: self.init(jax.random.PRNGKey(0))
        )
        shardings = TrainState(
            params=self._param_shardings,
            opt_state=self._opt_shardings,
            scaler=self._scaler_shardings,
            dropout_base=self._dropout_shardings,
            grad_residual=getattr(self, "_residual_shardings", None),
        )
        return jax.tree.map(
            lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                               sharding=sh),
            shapes,
            shardings,
        )

    def elastic_descriptor(self) -> Dict[str, Any]:
        """JSON-safe identity of this engine's topology-dependent layout,
        persisted in the checkpoint meta sidecar so a resume onto a
        DIFFERENT mesh can decide what must be re-derived and what must
        be refused (resilience/elastic.py::check_reshapeable).  Every
        field is derivable state, not configuration — params/optimizer
        global shapes are topology-independent (Orbax reshards them on
        read); the residual shape and the non-data axes are not."""
        from .mesh import mesh_descriptor
        return {
            "engine": type(self).__name__,
            "stage": int(self.stage),
            "mesh": mesh_descriptor(self.mesh),
            "n_shard": int(self.n_shard),
            "accum_steps": int(self.accum_steps),
            "residual_shape": (
                list(self._residual_shape)
                if getattr(self, "_residual_shape", None) is not None
                else None
            ),
        }

    def gather_params(self, state):
        """Fully-replicated copy of the params — the bridge from a sharded
        TrainState to single-program consumers like `model.generate()`
        (under ZeRO-3 the resting params are axis-sharded; the decode jit
        is not mesh-aware).  One all-gather per leaf; prefer calling once
        per sampling session, not per token."""
        rep = NamedSharding(self.mesh, P())
        return jax.tree.map(lambda x: jax.device_put(x, rep), state.params)

    # -- reporting ---------------------------------------------------------

    def describe(self) -> str:
        name = type(self).__name__
        extras = ""
        if self.grad_clip is not None:
            extras += f", grad_clip={self.grad_clip}"
        if self.loss_scale is not None:
            extras += f", loss_scale={self.loss_scale}"
        if self.offload_opt_state:
            extras += ", opt state offloaded=pinned_host"
        if self._telemetry_on:
            extras += (", telemetry=layers" if self._layers_on
                       else ", telemetry=on")
        if self._grad_comm_active:
            extras += f", grad_comm={self.grad_comm}"
            if self.grad_comm_groups:
                extras += f"(2-hop inner={self.grad_comm_groups})"
            if not self.grad_comm_error_feedback:
                extras += "(no-ef)"
            if getattr(self, "grad_comm_tail", "fp32") != "fp32":
                extras += f", grad_comm_tail={self.grad_comm_tail}"
        if self._bucketed_active:
            extras += f", grad_buckets={self.grad_buckets}"
        if self._gather_prefetch_active:
            extras += f", gather_prefetch={self.gather_prefetch}"
            if self.gather_groups:
                extras += f"(2-hop inner={self.gather_groups})"
        if getattr(self, "hpz", False):
            extras += ", hpz=on"
            if getattr(self, "hpz_comm", "fp32") != "fp32":
                extras += f"[{self.hpz_comm}]"
        if getattr(self, "_lowering", "plain") not in ("plain",):
            extras += f", sched={self._schedule.describe()}"
        return (
            f"{name}(stage={self.stage}, devices={self.n_dev}, "
            f"accum={self.accum_steps}, params sharded="
            f"{self.stage >= 3}, grads sharded={self.stage >= 2}, "
            f"opt state sharded={self.stage >= 1}{extras})"
        )


class SingleDevice(ZeroEngine):
    """Stage-0, one device (reference example/single_device/train.py)."""
    stage = 0
    data_parallel = False


class DDP(ZeroEngine):
    """Replicated params, sharded batch, all-reduced grads
    (reference ddp/wrapper.py:15-33)."""
    stage = 0


class Zero1(ZeroEngine):
    """+ optimizer state sharded (reference zero1/)."""
    stage = 1


class Zero2(ZeroEngine):
    """+ gradients sharded via reduce-scatter (reference zero2/)."""
    stage = 2


class Zero3(ZeroEngine):
    """+ parameters sharded at rest, gathered per-layer on demand
    (reference zero3/ — completed here; the reference's is broken,
    SURVEY §2.18)."""
    stage = 3
