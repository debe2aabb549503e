# Copyright 2026 tiny-deepspeed-tpu authors
# SPDX-License-Identifier: Apache-2.0

"""Tracing, timing, and communication-cost reporting.

The reference's entire observability surface is the autotuner's wall-clock
timer (reference runtime_tuner.py:34-39), rank-0 loss prints, and
comm-complexity *comments* ("2g" ddp/module.py:17, "g" zero1/optim.py:20).
Here those become real subsystems:

  * `trace(logdir)`     — context manager around jax.profiler (XPlane/
    TensorBoard format) for device timelines.
  * `span(name, **ids)` — the program's own host spans (`tds.*`), on the
    device trace's clock; `TABLE` names every span, scope, program,
    kernel and counter with its layer and the metric that reads it.
  * `StepTimer`         — per-step wall timing closed by a device sync (a
    1-element device->host transfer is the barrier).
  * `comm_report(engine)` — the reference's "g"/"2g" comments as computed
    per-step collective byte counts for the engine's actual stage/mesh.
  * `MetricsLogger`     — rank-0 structured JSONL metrics (loss, step time,
    tokens/s), replacing bare prints (reference ddp/train.py:34-35).
"""

from __future__ import annotations

import contextlib
import json
import os
import time
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np


@contextlib.contextmanager
def trace(logdir: str):
    """Capture a jax.profiler trace (view in TensorBoard / xprof)."""
    jax.profiler.start_trace(logdir)
    try:
        yield logdir
    finally:
        jax.profiler.stop_trace()


# Every name the program writes into a profiler trace, or counts for one:
# name -> (kind, layer as PERF.md section 3 has it, the metric that reads
# it).  A `span` is a host TraceAnnotation (`span()` below), a `scope` a
# jax.named_scope inside a compiled program, a `program` the name of a
# jitted function (the trace's `XLA Modules` line reads `jit_<name>`), a
# `kernel` the name= of a pallas_call, a `counter` a number the host
# takes: a clock reading at start-up (`utils/startup.marks`), or a count a
# tick makes of its slots, kept in the tick's record and written as ids of
# the span that covers the counting (`tds.tick.decode.operands`, or the
# span the model's slot layout names: `tds.tick.roll`, `tds.tick.route`),
# or a count the decode program makes of its own step and hands back
# behind its tokens (`pairs`, `experts_touched`: the layout's `fetched`).
# tests/test_spans.py holds the code to this table in both directions.
_TICK, _STEP = "serving scheduler", "engine step"
TABLE = {
    "tds.submit": ("span", _TICK, "tick_host_ms"),
    "tds.tick": ("span", _TICK, "tick_host_ms"),
    "tds.tick.sched": ("span", _TICK, "tick_host_ms"),
    "tds.tick.admit": ("span", _TICK, "tick_host_ms"),
    "tds.tick.prefill.dispatch": ("span", _TICK, "tick_host_ms"),
    "tds.tick.prefill.fetch": ("span", _TICK, "tick_host_ms"),
    "tds.tick.draft": ("span", _TICK, "tick_host_ms"),
    "tds.tick.decode.operands": ("span", _TICK, "tick_host_ms"),
    "tds.tick.roll": ("span", _TICK, "cache_blocks_per_slot"),
    "tds.tick.route": ("span", _TICK, "cache_mib_per_slot"),
    "tds.tick.decode.dispatch": ("span", _TICK, "tick_host_ms"),
    "tds.tick.decode.fetch": ("span", _TICK, "tick_host_ms"),
    "tds.tick.commit": ("span", _TICK, "tick_host_ms"),
    "tds.tick.observe": ("span", _TICK, "tick_host_ms"),
    "tds.step": ("span", _STEP, "idle_share.train"),
    "tds.load": ("span", "input", "input_wait_ms"),
    "tds.h2d": ("span", "input", "input_wait_ms"),
    "tds.sync": ("span", _STEP, "idle_share.train"),
    "tds.embed": ("scope", "kernels (train)", "fwd_ms"),
    "tds.blocks": ("scope", "kernels (train)", "fwd_ms"),
    "tds.block": ("scope", "kernels (train)", "fwd_ms"),
    "tds.ln": ("scope", "kernels (train)", "fwd_ms"),
    "tds.attn.qkv": ("scope", "kernels (train)", "fwd_ms"),
    "tds.attn.kernel": ("scope", "kernels (train)", "attn_fwd_ms"),
    "tds.attn.summary": ("scope", "kernels (serve)", "eva_summary_ms"),
    "tds.attn.window": ("scope", "kernels (serve)", "decode_ms"),
    "tds.attn.proj": ("scope", "kernels (train)", "fwd_ms"),
    "tds.mlp": ("scope", "kernels (train)", "fwd_ms"),
    "tds.moe": ("scope", "kernels (serve)", "moe_ms"),
    "tds.moe.router": ("scope", "kernels (serve)", "moe_ms"),
    "tds.moe.dispatch": ("scope", "kernels (serve)", "moe_ms"),
    "tds.moe.experts": ("scope", "kernels (serve)", "moe_roofline"),
    "tds.moe.combine": ("scope", "kernels (serve)", "moe_ms"),
    "tds.head": ("scope", "kernels (train)", "head_ms"),
    "tds.cast": ("scope", _STEP, "fwd_ms"),
    "tds.optim": ("scope", _STEP, "optim_ms"),
    "tds.gather": ("scope", "collectives", "coll_gather_ms"),
    "tds.grad_sync": ("scope", "collectives", "coll_grad_ms"),
    "tds.decode": ("scope", "kernels (serve)", "decode_ms"),
    "tds.prefill": ("scope", "kernels (serve)", "prefill_ms"),
    "tds.kv_write": ("scope", "kernels (serve)", "copies_ms.decode"),
    "tds.sample": ("scope", "kernels (serve)", "decode_ms"),
    "tds_train_step": ("program", _STEP, "fwd_ms"),
    "tds_eval": ("program", _STEP, None),
    "tds_decode": ("program", "kernels (serve)", "decode_ms"),
    "tds_prefill": ("program", "kernels (serve)", "prefill_ms"),
    "tds_prefill_suffix": ("program", "kernels (serve)", None),
    "tds_prefill_spec": ("program", "kernels (serve)", None),
    "tds_verify": ("program", "kernels (serve)", None),
    "tds_fa2_fwd": ("kernel", "kernels (train)", "attn_fwd_ms"),
    "tds_fa2_dkv": ("kernel", "kernels (train)", "attn_bwd_ms"),
    "tds_fa2_dq": ("kernel", "kernels (train)", "attn_bwd_ms"),
    "tds_fa2_fwd_packed": ("kernel", "kernels (train)", "attn_fwd_ms"),
    "tds_fa2_dkv_packed": ("kernel", "kernels (train)", "attn_bwd_ms"),
    "tds_fa2_dq_packed": ("kernel", "kernels (train)", "attn_bwd_ms"),
    "tds_ln_fwd": ("kernel", "kernels (train)", "fwd_ms"),
    "tds_ln_dx": ("kernel", "kernels (train)", "bwd_ms"),
    "tds_ln_dwdb": ("kernel", "kernels (train)", "bwd_ms"),
    "tds_paged_attn": ("kernel", "kernels (serve)", "decode_ms"),
    "tds_eva_paged_attn": ("kernel", "kernels (serve)", "eva_attn_ms"),
    "tds_quant": ("kernel", "collectives", None),
    "tds_xent_fwd": ("kernel", "kernels (train)", "head_ms"),
    "tds_xent_dx": ("kernel", "kernels (train)", "head_ms"),
    "tds_xent_dw": ("kernel", "kernels (train)", "head_ms"),
    "import_begin": ("counter", "entry / start-up", "import_s"),
    "import_done": ("counter", "entry / start-up", "import_s"),
    "select_platform": ("counter", "entry / start-up", "backend_init_s"),
    "backend_up": ("counter", "entry / start-up", "backend_init_s"),
    "kv_steps_live": ("counter", "kernels (serve)", None),
    "kv_steps": ("counter", "kernels (serve)", None),
    "pairs": ("counter", "kernels (serve)", "moe_tokens_per_expert"),
    "experts_touched": ("counter", "kernels (serve)", "moe_roofline"),
    # on `tds.tick.decode.dispatch`: 1 where it dispatches the next tick's
    # step; on `tds.tick.decode.fetch`: 1 where the step it fetches went
    # out a tick early
    "ahead": ("counter", _TICK, None),
}


def span(name: str, **ids):
    """A host span on the profiler's clock, the same clock as the device's
    operations: `with span("tds.tick.admit", request=7): ...`.  With no
    profiler session active this is a dead TraceAnnotation (well under a
    microsecond, nothing kept); there is nothing to switch on."""
    return jax.profiler.TraceAnnotation(name, **ids)


def device_sync(x) -> float:
    """Barrier: materialize one element on the host; returns it as float."""
    leaf = jax.tree.leaves(x)[0]
    return float(np.asarray(leaf.ravel()[0:1])[0])


def _quantile(xs, q: float) -> float:
    """Linear-interpolated quantile of a list (no numpy dependency on the
    hot host path)."""
    if not xs:
        return 0.0
    ys = sorted(xs)
    if len(ys) == 1:
        return ys[0]
    pos = q * (len(ys) - 1)
    lo = int(pos)
    hi = min(lo + 1, len(ys) - 1)
    return ys[lo] + (ys[hi] - ys[lo]) * (pos - lo)


class StepTimer:
    """Rolling per-step timing: `with timer.step(): ... engine.step(...)`.

    Upgraded for the telemetry subsystem (tiny_deepspeed_tpu/telemetry/):

      * `mark(name)` inside the step splits the wall time into named
        segments (`data_s` loader wait, `h2d_s` host->device staging, ...);
        the tail after the last mark — the dispatched device work plus the
        sync — lands in `compute_s`.  Per-step dicts in `self.segments`.
      * `watch(target)` registers a compile-count source (an engine, a
        jitted fn, or a zero-arg int callable); each step records how many
        NEW lowerings the watched jit cache grew by (`self.compiled_steps`),
        so first-compile and shape-driven recompiles are attributed to the
        step that paid for them.
      * `p50_s` / `p95_s` / `p99_s` / `max_s` tail properties next to
        `mean_s`.
      * a step whose body RAISES clears the observed output instead of
        leaking it into the next step's sync, and records no sample.
      * `fetch_full=True` makes the closing sync materialize the whole
        observed leaf (<= 1024 elements) on the host in `last_host` —
        one transfer that both closes the clock and delivers the packed
        telemetry health vector; `last_value` is always element 0.
    """

    def __init__(self, sync_every: int = 1, fetch_full: bool = False):
        self.sync_every = sync_every
        self.fetch_full = fetch_full
        self.times = []
        self.segments = []       # per step: {"data_s": .., "compute_s": ..}
        self.compiled_steps = []  # per step: lowerings paid by this step
        self.last_value = None   # float(element 0) of the observed output
        self.last_host = None    # host copy of the observed leaf (fetch_full)
        self._last_out = None
        self._watched = []
        self._segs = {}
        self._seg_t0 = 0.0

    # -- compile watching ---------------------------------------------------

    def watch(self, target) -> None:
        """Count lowerings of `target`: a ZeroEngine (tracks its `_step`
        across retune rebuilds), a jitted function, or a callable -> int."""
        if hasattr(target, "_cache_size"):
            fn = target._cache_size
        elif hasattr(target, "step"):
            # engine-like: read its CURRENT jitted step each time, so
            # attach-at-construction (before the first _build_step) and
            # retune() rebuilds both stay counted
            def fn(eng=target):
                step = getattr(eng, "_step", None)
                return step._cache_size() if step is not None else 0
        elif callable(target):
            fn = target
        else:
            raise TypeError(f"cannot watch {type(target).__name__}")
        self._watched.append(fn)

    def _watched_lowerings(self) -> int:
        total = 0
        for fn in self._watched:
            try:
                total += int(fn())
            except Exception:
                pass
        return total

    # -- the step context ---------------------------------------------------

    @contextlib.contextmanager
    def step(self):
        t0 = time.perf_counter()
        self._seg_t0 = t0
        self._segs = {}
        c0 = self._watched_lowerings()
        try:
            yield self
        except BaseException:
            # a failed step must not leak its stale output into the next
            # step's sync barrier
            self._last_out = None
            raise
        if self._last_out is not None:
            leaf = jax.tree.leaves(self._last_out)[0]
            with span("tds.sync"):
                if self.fetch_full and leaf.size <= 1024:
                    host = np.asarray(leaf).ravel()
                else:
                    host = np.asarray(leaf.ravel()[0:1])
            self.last_host = host
            self.last_value = float(host[0])
            self._last_out = None
        now = time.perf_counter()
        if self._segs:
            self._segs["compute_s"] = now - self._seg_t0
            self.segments.append(self._segs)
        self.times.append(now - t0)
        self.compiled_steps.append(self._watched_lowerings() - c0)
        self._segs = {}

    def mark(self, name: str) -> None:
        """Close the current wall segment as `<name>_s`; the remainder of
        the step (device dispatch + sync) becomes `compute_s`."""
        now = time.perf_counter()
        self._segs[f"{name}_s"] = now - self._seg_t0
        self._seg_t0 = now

    def observe(self, out):
        """Register a step output to sync on before stopping the clock."""
        self._last_out = out
        return out

    # -- summaries ----------------------------------------------------------

    def _sample(self):
        # drop the first step (compile) once there is more than one sample
        return self.times[1:] if len(self.times) > 1 else self.times

    @property
    def mean_s(self) -> float:
        xs = self._sample()
        return sum(xs) / max(1, len(xs))

    @property
    def p50_s(self) -> float:
        return _quantile(self._sample(), 0.50)

    @property
    def p95_s(self) -> float:
        return _quantile(self._sample(), 0.95)

    @property
    def p99_s(self) -> float:
        return _quantile(self._sample(), 0.99)

    @property
    def max_s(self) -> float:
        """Worst warm step — with p99, the tail the straggler/anomaly
        analysis cares about (the p50/p95 pair hides a single stall)."""
        xs = self._sample()
        return max(xs) if xs else 0.0

    @property
    def compile_count(self) -> int:
        """Total lowerings of the watched jits across recorded steps —
        1 is the first compile; anything above is a recompile."""
        return sum(self.compiled_steps)


def _bytes(tree) -> int:
    return sum(
        x.size * x.dtype.itemsize for x in jax.tree.leaves(tree)
    )


def comm_report(engine) -> Dict[str, float]:
    """Estimated per-step collective traffic for the engine's stage/mesh.

    Uses ring-algorithm costs over the data axis (n devices, payload g bytes
    of gradients/params): all-reduce 2g(n-1)/n, reduce-scatter g(n-1)/n,
    all-gather g(n-1)/n — the quantitative version of the reference's comment
    ledger (ddp/module.py:17 "2g"; zero1/module.py:17, optim.py:13,20 "g").

    Round 3: validated against the compiled step's ledger
    (utils/hlo_comm.py, tests/test_profiling.py, PROFILE.md).  Findings
    baked in:
      * DDP / ZeRO-1 rows match the compiled HLO to <0.01%.
      * ZeRO-3 per-layer gathers move the BLOCK params twice (fwd + remat
        bwd) and the non-block params (wte/wpe/ln_f/lm_head) once, all in
        COMPUTE dtype — the previous hard-coded 0.5 "bf16 factor" was
        wrong for f32-compute models.
      * grad_reduce_scatter is the ring-model INTENT of the sharded-grad
        constraint; XLA's CPU partitioner instead realizes it as a full
        all-reduce + slice (2x the wire bytes).  The report exposes this
        as `grad_reduce_scatter_is_upper_bounded_by_allreduce`.
    """
    n = engine.n_shard
    shapes = engine.model.param_shapes()
    g = _bytes(shapes)  # grads are param-sized
    ring = (n - 1) / n if n > 1 else 0.0
    stage = engine.stage

    cfg = getattr(engine.model, "config", None)
    cd_itemsize = (
        jnp.dtype(cfg.compute_dtype).itemsize if cfg is not None else 4
    )
    block_cd = nonblock_cd = block_deq = 0
    if stage == 3:
        block_deq = sum(
            int(np.prod(s.shape)) * cd_itemsize
            for name, s in shapes.items() if name.startswith("h.")
        )
        try:
            # what the per-layer gathers ACTUALLY move: the stacked compute
            # tree's own dtypes (compute dtype normally; f8 + f32 scales
            # under gather_quant="fp8" — pricing h.* at cd_itemsize would
            # overstate the quantized gathers ~2-4x)
            stacked = jax.eval_shape(
                engine.model.stacked_compute_params, shapes
            )
            block_cd = _bytes(stacked)
        except Exception:
            block_cd = sum(
                int(np.prod(s.shape)) * cd_itemsize
                for name, s in shapes.items() if name.startswith("h.")
            )
        nonblock_cd = sum(
            int(np.prod(s.shape)) * cd_itemsize
            for name, s in shapes.items() if not name.startswith("h.")
        )

    # Round 4, measured on the v5e:4x2 compile-only topology (PROFILE.md
    # "TPU topology HLO"): the replicated-grad all-reduce rides in COMPUTE
    # dtype — XLA commutes the reduction with the grad's f32 cast — so
    # DDP/ZeRO-1 reduction payloads are cd-priced (halves the bf16 bill vs
    # the old f32-grad pricing; exact on f32-compute models).  The sharded
    # -grad reduce-scatter of ZeRO-2/3 stays in PARAM dtype: the constraint
    # lands on the post-cast f32 grads and the partitioner keeps it.
    g_cd = sum(
        int(np.prod(s.shape)) * cd_itemsize for s in shapes.values()
    )
    # Microbatch accumulation: stage <= 1 keeps grads replicated and truly
    # pays ONE all-reduce after the local sum; stage >= 2 constrains the
    # f32 accumulator SHARDED, so every microbatch reduce-scatters into
    # the shard — accum_steps x the wire bytes (TPU topology measurement,
    # PROFILE.md zero2-accum4 row: 4x the single-step reduce-scatter).
    n_sync = int(getattr(engine, "accum_steps", 1)) if stage >= 2 else 1
    # grad_comm != fp32 (parallel/comm.py): the explicit quantized
    # schedule REPLACES the partitioner's gradient collective — one
    # error-fed int8/fp8 all-to-all reduce-scatter + quantized all-gather
    # per step (accumulation syncs once, so no n_sync multiplier), priced
    # by the same ring conventions via comm.modeled_wire_bytes
    quant = bool(getattr(engine, "_grad_comm_active", False))
    tmode = str(getattr(engine, "grad_comm_tail", "fp32"))
    # composed ZeRO-3: the non-block tail is RELEASED SEPARATELY from
    # the codec'd block syncs — through the differentiable gather's
    # fp32 transpose, or (grad_comm_tail != fp32) its own quantized
    # sync.  Price it under zero3_tail_release_bytes, not inside the
    # grad codec model (round-5 ledger finding: the old qt term billed
    # the tail to the block codec and missed the fp32 transpose).
    z3_split_tail = quant and stage == 3
    tail_elems_total = sum(
        int(np.prod(s.shape)) for nm, s in shapes.items()
        if not nm.startswith("h.")
    )
    quant_model = None
    if quant:
        from ..parallel.comm import modeled_wire_bytes
        n_elems = sum(int(np.prod(s.shape)) for s in shapes.values())
        if z3_split_tail:
            n_elems -= tail_elems_total
        quant_model = modeled_wire_bytes(
            n_elems, n, engine.grad_comm,
            block=engine.grad_comm_block,
            inner=engine.grad_comm_groups,
        )
        lay = getattr(engine, "_bucket_layout", None)
        if lay is not None:
            # bucketed release (grad_buckets > 1): K layer syncs + one
            # tail sync, each padded per-bucket — slightly more wire than
            # the monolithic schedule (the per-bucket padding/scale
            # overhead the acceptance tolerance prices).  The fp32
            # all-reduce baseline stays the monolithic model's — ONE
            # accounting site for the ring convention.
            qb = modeled_wire_bytes(
                lay["bucket_elems"], n, engine.grad_comm,
                block=engine.grad_comm_block,
                inner=engine.grad_comm_groups,
            )
            qt = modeled_wire_bytes(
                lay["tail_elems"], n, engine.grad_comm,
                block=engine.grad_comm_block,
                inner=engine.grad_comm_groups,
            ) if (lay["tail_elems"] and not z3_split_tail) else {
                "elems_padded": 0, "quant_wire_bytes": 0.0}
            k = lay["n_buckets"]
            quant_model = dict(
                quant_model,
                grad_buckets=k,
                elems_padded=k * qb["elems_padded"] + qt["elems_padded"],
                quant_wire_bytes=k * qb["quant_wire_bytes"]
                + qt["quant_wire_bytes"],
            )
    # the composed ZeRO-3 tail release itself (once per step, outside
    # the scans): fp32 = the transpose reduce-scatter on sharded leaves
    # (param dtype) + the explicit psum on replicated ones; quantized =
    # comm.modeled_wire_bytes on the tail's elems under the tail codec
    zero3_tail_release = 0.0
    if z3_split_tail:
        if tmode == "fp32":
            spec_rest = getattr(engine, "_param_spec_rest", {}) or {}
            for nm, s in shapes.items():
                if nm.startswith("h."):
                    continue
                b = int(np.prod(s.shape)) * int(jnp.dtype(s.dtype).itemsize)
                spec = spec_rest.get(nm)
                sharded = spec is not None and any(
                    d is not None for d in tuple(spec)
                )
                # reduce-scatter g*ring vs all-reduce 2g*ring
                zero3_tail_release += (1 if sharded else 2) * b * ring
        else:
            from ..parallel.comm import modeled_wire_bytes
            zero3_tail_release = modeled_wire_bytes(
                tail_elems_total, n, tmode,
                block=engine.grad_comm_block,
            )["quant_wire_bytes"]
    # hpZ secondary rebuild (qwZ): the once-per-step inter-granule
    # all-gather of this rank's resting shard — compute-dtype bytes at
    # fp32, fp8 blocks + scales under hpz_comm='fp8'
    hpz_rebuild = 0.0
    geom = getattr(getattr(engine, "_schedule", None), "hpz_geom", None)
    if getattr(engine, "hpz", False) and geom is not None and stage == 3:
        from ..parallel.comm import modeled_hpz_rebuild_bytes
        n_gran = geom[3]
        block_elems = sum(
            int(np.prod(s.shape)) for nm, s in shapes.items()
            if nm.startswith("h.")
        )
        hpz_rebuild = modeled_hpz_rebuild_bytes(
            block_cd // n, block_elems // n, n_gran,
            str(getattr(engine, "hpz_comm", "fp32")),
        )
    # gather_prefetch (parallel/schedule.GatherPrefetchScan): the explicit
    # prefetched schedule issues K-1 extra clamped end-of-scan gathers
    # per pass (fwd + remat bwd each run L+K-1 layer gathers), and
    # gather_groups reroutes each layer's gather through the 2-hop
    # shard_map (resting precision intra-group, compute dtype inter) —
    # priced by comm.modeled_gather_wire_bytes, the same accounting site
    # telemetry reads
    gp = int(getattr(engine, "gather_prefetch", 0) or 0)
    gg = getattr(engine, "gather_groups", None)
    gp_active = bool(getattr(engine, "_gather_prefetch_active", False))
    z3_gather = (2 * block_cd + nonblock_cd) * ring if stage == 3 else 0.0
    if stage == 3 and gp_active:
        from ..parallel.comm import modeled_gather_wire_bytes
        nl = int(getattr(cfg, "n_layer", 0) or 0)
        passes = 2.0 * (nl + gp - 1) / nl if nl else 2.0
        per_pass = modeled_gather_wire_bytes(
            block_cd, block_deq, n, inner=gg
        )
        z3_gather = passes * per_pass + nonblock_cd * ring

    report = {
        "devices": n,
        "param_bytes": g,
        "grad_comm": getattr(engine, "grad_comm", "fp32"),
        "grad_buckets": int(getattr(engine, "grad_buckets", 1)),
        "gather_prefetch": gp,
        "gather_groups": int(gg) if gg else 0,
        # full schedule model kept alongside the headline number so
        # downstream gauges (telemetry capture_compiled) read ONE
        # accounting site instead of re-deriving it
        "grad_comm_model": quant_model,
        "grad_quant_sync_bytes":
        quant_model["quant_wire_bytes"] if quant_model else 0.0,
        "grad_allreduce_bytes": 2 * g_cd * ring
        if stage <= 1 and n > 1 and not quant else 0.0,
        "grad_reduce_scatter_bytes": n_sync * g * ring
        if stage >= 2 and not quant else 0.0,
        "grad_reduce_scatter_is_upper_bounded_by_allreduce":
        stage >= 2 and not quant,
        "param_all_gather_bytes": g * ring if stage in (1, 2) else 0.0,
        # ZeRO-3: block params gathered per layer in fwd AND in the remat
        # bwd; non-block params once — all at compute precision (plus the
        # prefetch overshoot / 2-hop reroute when gather_prefetch is on)
        "zero3_layer_gather_bytes": z3_gather,
        # composed ZeRO-3 tail release + hpZ secondary rebuild — the
        # wire-agenda hops, modeled at the same ring conventions the
        # ledger measures (zero3_tail_wire_bytes /
        # hpz_rebuild_dcn_bytes gauges)
        "zero3_tail_release_bytes": zero3_tail_release,
        "hpz_rebuild_bytes": hpz_rebuild,
    }
    report["total_bytes_per_step"] = sum(
        v for k, v in report.items()
        if k.endswith("_bytes") and k != "param_bytes"
    )
    return report


class MetricsLogger:
    """Rank-0 structured metrics: JSONL file and/or stdout.

    Usable as a context manager so the file handle cannot leak when the
    training loop raises; `close()` keeps working for manual lifetimes.
    The record schema (step records + `kind`-tagged meta records from
    `log_meta`) is defined in `tiny_deepspeed_tpu/telemetry/schema.py` and
    validated by `scripts/report_run.py --check`.
    """

    def __init__(self, path: Optional[str] = None, stdout: bool = True):
        self.is_rank0 = jax.process_index() == 0
        self.stdout = stdout
        self._fh = None
        if path and self.is_rank0:
            os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
            self._fh = open(path, "a")

    def __enter__(self) -> "MetricsLogger":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.close()

    def log(self, step: int, **metrics) -> None:
        if not self.is_rank0:
            return
        rec = {"step": step, "ts": time.time(), **metrics}
        if self._fh:
            self._fh.write(json.dumps(rec) + "\n")
            self._fh.flush()
        if self.stdout:
            shown = " ".join(
                f"{k} {v:.4f}" if isinstance(v, float) else f"{k} {v}"
                for k, v in metrics.items()
            )
            print(f"step {step:5d} {shown}")

    def log_meta(self, kind: str = "run_meta", **fields) -> None:
        """One `kind`-tagged non-step record (run metadata, telemetry
        summaries) — JSONL only, never echoed to stdout."""
        if not self.is_rank0 or not self._fh:
            return
        rec = {"kind": kind, "ts": time.time(), **fields}
        self._fh.write(json.dumps(rec) + "\n")
        self._fh.flush()

    def close(self) -> None:
        if self._fh:
            self._fh.close()
            self._fh = None
