# Copyright 2026 tiny-deepspeed-tpu authors
# SPDX-License-Identifier: Apache-2.0

"""The `Telemetry` registry: counters/gauges/histograms, the instrumented
step wrapper, measured collective/memory gauges, and the anomaly tracer.

One object owns a run's telemetry:

    telem = Telemetry(trace_dir="traces")          # anomaly xprof capture
    eng   = Zero2(model, opt, telemetry=telem)     # health vector in-step
    ...
    with telem.step() as t:                        # timing + breakdown
        idx, tgt = loader.next();  t.mark("data")
        batch = device_put(...);   t.mark("h2d")
        state, loss = eng.step(state, batch)       # engine pushes the aux
    metrics.log(it, loss=telem.last_health["loss"], **telem.step_record())

The engine's health vector is observed as the step's sync barrier, so the
ONE device->host transfer that closes the step clock also delivers loss +
grad/update/param norms + non-finite counts — telemetry-on adds no
additional transfers per step over reading the loss alone.
"""

from __future__ import annotations

import contextlib
import os
import threading
from typing import Dict, Optional

import numpy as np

import jax

from . import live
from .health import HEALTH_FIELDS, health_dict
from ..utils.profiling import StepTimer, comm_report, _quantile

_GB = float(2 ** 30)


class Counter:
    __slots__ = ("value", "_lock")

    def __init__(self):
        self.value = 0
        # inc() is a read-modify-write: fleet replicas ticking on a
        # thread pool (fleet/router.py parallel=True) share one
        # registry, and unsynchronized increments LOSE counts — in a
        # repo whose telemetry exists to be exact.  One short-lived
        # lock per counter; the single-threaded paths pay nanoseconds.
        self._lock = threading.Lock()

    def inc(self, n: int = 1) -> int:
        with self._lock:
            self.value += n
            return self.value


class Histogram:
    __slots__ = ("values",)

    def __init__(self):
        self.values = []

    def observe(self, v: float) -> None:
        self.values.append(float(v))

    @property
    def count(self) -> int:
        return len(self.values)

    @property
    def mean(self) -> float:
        return sum(self.values) / max(1, len(self.values))

    @property
    def p50(self) -> float:
        return _quantile(self.values, 0.50)

    @property
    def p95(self) -> float:
        return _quantile(self.values, 0.95)

    @property
    def p99(self) -> float:
        return _quantile(self.values, 0.99)

    def snapshot(self) -> Dict[str, float]:
        return {
            "count": self.count,
            "mean": self.mean,
            "p50": self.p50,
            "p95": self.p95,
            "p99": self.p99,
            "max": max(self.values) if self.values else 0.0,
        }


class Telemetry:
    """Run-level telemetry registry + step instrumentation.

    anomaly capture: after `anomaly_min_steps` samples, a step slower than
    `anomaly_factor` x the rolling median ARMS the tracer; the next
    `telem.step()` runs under `jax.profiler` and writes ONE xprof trace
    into `trace_dir` — then never again this run (first anomalies are the
    interesting ones; a pathological run must not fill the disk with
    traces).  `tracer=(start_fn, stop_fn)` injects a fake pair for tests.
    """

    def __init__(
        self,
        timer: Optional[StepTimer] = None,
        trace_dir: Optional[str] = None,
        anomaly_factor: float = 2.5,
        anomaly_min_steps: int = 10,
        anomaly_window: int = 50,
        tracer=None,
        layers: bool = False,
        flight_steps: int = 64,
    ):
        self.timer = timer or StepTimer()
        self.timer.fetch_full = True
        self.trace_dir = trace_dir
        self.anomaly_factor = float(anomaly_factor)
        self.anomaly_min_steps = int(anomaly_min_steps)
        self.anomaly_window = int(anomaly_window)
        self._tracer = tracer or (
            jax.profiler.start_trace, jax.profiler.stop_trace,
        )
        # layers=True turns on the engine's per-layer health mode: the
        # compiled step additionally returns the (n_layer, 6) layer-health
        # matrix (telemetry/health.LAYER_FIELDS) the engine pushes into
        # on_step_output(layers=...)
        self.layers = bool(layers)
        # flight recorder (telemetry/flight.py): ring of the last N steps'
        # health + segments (+ layer matrices, un-synced), flushed as one
        # `flight` JSONL record when the anomaly detector fires on a slow
        # step or on non-finite health.  0 disables.
        from .flight import FlightRecorder
        self.flight = (
            FlightRecorder(flight_steps) if flight_steps else None
        )
        self.flight_pending: Optional[str] = None
        self._nonfinite_prev = False
        self.counters: Dict[str, Counter] = {}
        self.gauges: Dict[str, float] = {}
        self.histograms: Dict[str, Histogram] = {}
        self._engine = None
        self._last_aux = None
        self._last_health = None
        self._last_layers = None
        self._last_layers_host = None
        self._recent = []
        self._trace_armed = False
        self._trace_fired = False
        self.trace_path: Optional[str] = None
        self._trace_logged = False
        self._comm: Optional[Dict[str, object]] = None

    # -- registry -----------------------------------------------------------

    def counter(self, name: str) -> Counter:
        return self.counters.setdefault(name, Counter())

    def gauge(self, name: str, value=None, **labels):
        """Set/read a gauge.  Labels (e.g. ``replica=0``) qualify the
        storage KEY — ``serve_queue_depth{replica=0}`` — so parallel
        fleet replicas stop overwriting each other's values
        (the PR-16 last-writer-wins wart).  Call sites keep the literal
        base name; labels with None values are dropped, so single-engine
        paths (``replica=None``) keep their historical bare keys."""
        labels = {k: v for k, v in labels.items() if v is not None}
        key = live.gauge_key(name, **labels) if labels else name
        if value is not None:
            self.gauges[key] = float(value)
        return self.gauges.get(key)

    def histogram(self, name: str) -> Histogram:
        return self.histograms.setdefault(name, Histogram())

    def snapshot(self) -> Dict[str, object]:
        """JSON-safe registry dump for the `telemetry_summary` record."""
        return {
            "counters": {k: c.value for k, c in self.counters.items()},
            "gauges": dict(self.gauges),
            "histograms": {
                k: h.snapshot() for k, h in self.histograms.items()
            },
        }

    # -- engine wiring ------------------------------------------------------

    def attach(self, engine) -> None:
        """Called by `ZeroEngine.__init__(telemetry=...)`: watch the
        engine's jitted step for (re)compile counting and remember it for
        `capture_compiled`."""
        self._engine = engine
        self.timer.watch(engine)

    def on_step_output(self, aux, layers=None) -> None:
        """Engine push: the step's packed health vector — and, in layers
        mode, the (n_layer, 6) layer-health matrix (device arrays, NOT
        synced here)."""
        self._last_aux = aux
        self._last_health = None
        self._last_layers = layers
        self._last_layers_host = None

    def poll(self) -> Optional[Dict[str, float]]:
        """Host view of the latest health vector (one transfer, cached)."""
        if self._last_health is None and self._last_aux is not None:
            self._last_health = health_dict(np.asarray(self._last_aux))
        return self._last_health

    @property
    def last_health(self) -> Optional[Dict[str, float]]:
        return self.poll()

    def layer_health(self):
        """Host view of the latest (n_layer, 6) layer-health matrix
        (telemetry/health.LAYER_FIELDS columns), or None outside layers
        mode.  One transfer, cached — call at inspection cadence; the
        flight recorder keeps the un-synced device reference per step."""
        if self._last_layers_host is None and self._last_layers is not None:
            self._last_layers_host = np.asarray(self._last_layers)
        return self._last_layers_host

    # -- the instrumented step ----------------------------------------------

    @contextlib.contextmanager
    def step(self, index: Optional[int] = None):
        """Wrap one training step: timing + segment marks via the inner
        StepTimer handle, health-vector sync as the closing barrier, and
        the armed anomaly trace if one is pending.  `index` is the
        caller's training iteration — the flight record numbers its
        entries with it so a postmortem cross-references the step records
        in the same JSONL (a resumed run starts at start_iter, not 0);
        without it the internal steps counter is the fallback."""
        trace_now = (
            self._trace_armed and not self._trace_fired
            and self.trace_dir is not None
        )
        if trace_now:
            path = os.path.join(self.trace_dir, "anomaly")
            os.makedirs(path, exist_ok=True)
            self._tracer[0](path)
        try:
            with self.timer.step() as t:
                yield t
                if self._last_aux is not None:
                    t.observe(self._last_aux)
        finally:
            if trace_now:
                self._tracer[1]()
                self._trace_fired = True
                self._trace_armed = False
                self.trace_path = path
                self.counter("anomaly_traces").inc()
        # -- success-path bookkeeping (an exception skips all of it) --
        host = self.timer.last_host
        if host is not None and len(host) == len(HEALTH_FIELDS):
            self._last_health = health_dict(host)
        dt = self.timer.times[-1]
        n_step = self.counter("steps").inc()
        self.histogram("step_s").observe(dt)
        if self.timer.segments:
            for k, v in self.timer.segments[-1].items():
                self.histogram(k).observe(v)
        if self.timer.compiled_steps[-1]:
            self.counter("compiles").inc(self.timer.compiled_steps[-1])
        self.note_step_time(dt)
        h = self._last_health
        if self.flight is not None:
            # ring append only: host dicts (already paid for by the step's
            # own sync) + the layer matrix as an UN-SYNCED device ref
            self.flight.record(
                index if index is not None else n_step - 1,
                step_s=dt, health=h,
                segments=self.timer.segments[-1]
                if self.timer.segments else None,
                layers=self._last_layers,
            )
        bad = h is not None and (
            h["nonfinite_grads"] or not np.isfinite(h["loss"])
        )
        if bad and not self._nonfinite_prev:
            # a NaN step is not SLOW, so the rolling-median detector never
            # sees it — non-finite health arms the flight flush directly
            # (and outranks a pending slow_step: the NaN postmortem is the
            # more urgent record).  EDGE-triggered on the finite→bad
            # transition: a run that stays NaN flushes once per episode,
            # not one full ring per logging iteration
            self.counter("anomalies_nonfinite").inc()
            self.flight_pending = "nonfinite"
        self._nonfinite_prev = bad

    def note_step_time(self, s: float) -> bool:
        """Feed one step wall time to the anomaly detector.  Returns True
        exactly once per run: the first time a step exceeds
        `anomaly_factor` x the rolling median (after the warmup window).
        Firing arms BOTH postmortem channels: the one-shot xprof trace of
        the NEXT step and a flight-recorder flush of the PAST N steps
        (maybe_flush_flight) — the anomalous step itself is gone, so the
        trace covers what comes after and the flight record what led up
        to it."""
        fired = False
        if (
            len(self._recent) >= self.anomaly_min_steps
            and not self._trace_armed and not self._trace_fired
        ):
            med = _quantile(self._recent, 0.5)
            if s > self.anomaly_factor * med:
                self._trace_armed = True
                self.counter("anomalies").inc()
                self.gauge("anomaly_step_s", s)
                self.gauge("anomaly_threshold_s", self.anomaly_factor * med)
                if self.flight_pending is None:
                    self.flight_pending = "slow_step"
                fired = True
        self._recent.append(float(s))
        if len(self._recent) > self.anomaly_window:
            self._recent.pop(0)
        return fired

    # -- flight recorder ----------------------------------------------------

    def maybe_flush_flight(self, logger) -> Optional[str]:
        """Flush the flight ring to `logger` as a `flight` record iff an
        anomaly armed it (slow step or non-finite health).  Returns the
        flush reason, or None when nothing was pending.  Call at logging
        cadence (examples/common.py does, right after metrics.log) — the
        flush syncs any recorded layer matrices, so it must stay OFF the
        per-step hot path."""
        if self.flight is None or self.flight_pending is None:
            return None
        reason = self.flight_pending
        self.flight_pending = None
        self.flight.flush(logger, reason)
        self.counter("flight_flushes").inc()
        return reason

    # -- multi-host stragglers ----------------------------------------------

    def sample_stragglers(self, step_s: Optional[float] = None,
                          allgather=None,
                          quantity: str = "step_s") -> Dict[str, object]:
        """Per-host straggler attribution: all-gather one per-host wall
        quantity over the mesh, gauge how much the slowest host drags the
        others, and return the `straggler` record fields (schema.py).

        WHICH quantity matters: an SPMD program's collectives couple
        every host's DEVICE timeline, so whole-step wall converges to the
        slowest host's pace on all hosts and attributes nothing — pass an
        UNCOUPLED host-side measure for attribution (examples/common.py
        gathers each host's data-load + staging wall per step, which is
        pure host code and keeps the slow host visible).  `quantity`
        labels what was gathered in the record.  `step_s` defaults to
        this host's p50 step time (fine on one host; coupled on many).

        `straggler_frac` = (slowest - median) / slowest — the FRACTION
        of the slowest host's time the median host would not have spent:
        0 on a balanced mesh, 2/3 when the slowest host takes 3x the
        median, bounded [0, 1).  `allgather` injects the gather for
        tests; the real path uses
        jax.experimental.multihost_utils.process_allgather (single-
        process runs short-circuit to a local list)."""
        mine = float(
            step_s if step_s is not None else self.timer.p50_s
        )
        if allgather is not None:
            times = [float(v) for v in allgather(mine)]
        elif jax.process_count() > 1:
            from jax.experimental import multihost_utils
            times = [
                float(v) for v in np.asarray(
                    multihost_utils.process_allgather(
                        np.float32(mine)
                    )
                ).ravel()
            ]
        else:
            times = [mine]
        med = _quantile(sorted(times), 0.5)
        slowest = int(np.argmax(times))
        frac = (
            (times[slowest] - med) / times[slowest]
            if times[slowest] > 0 else 0.0
        )
        self.gauge("straggler_frac", frac)
        self.gauge("straggler_slowest_host", slowest)
        self.gauge("straggler_slowest_step_s", times[slowest])
        return {
            "hosts": len(times),
            "quantity": quantity,
            "step_s_by_host": [round(t, 6) for t in times],
            "slowest_host": slowest,
            "straggler_frac": round(frac, 6),
        }

    # -- measured gauges ----------------------------------------------------

    def sample_memory(self) -> Dict[str, float]:
        """Per-step HBM watermark from device memory stats (TPU runtime;
        the CPU backend reports none and this returns {})."""
        in_use = peak = 0
        seen = False
        for d in jax.local_devices():
            try:
                stats = d.memory_stats()
            except Exception:
                stats = None
            if not stats:
                continue
            seen = True
            in_use = max(in_use, int(stats.get("bytes_in_use", 0)))
            peak = max(peak, int(stats.get(
                "peak_bytes_in_use", stats.get("bytes_in_use", 0)
            )))
        if not seen:
            return {}
        out = {
            "hbm_gb_in_use": round(in_use / _GB, 4),
            "hbm_gb_peak": round(peak / _GB, 4),
        }
        self.gauge("hbm_gb_in_use", out["hbm_gb_in_use"])
        self.gauge(
            "hbm_gb_peak",
            max(self.gauge("hbm_gb_peak") or 0.0, out["hbm_gb_peak"]),
        )
        return out

    def sample_grad_residual(self, state) -> Optional[float]:
        """Error-feedback residual norm gauge (grad_comm int8/fp8,
        parallel/comm.py): the global L2 norm of
        TrainState.grad_residual — how much gradient signal is currently
        deferred to next step.  A healthy run keeps it bounded (the
        feedback loop re-injects it); monotone growth means quantization
        error is outrunning the gradient signal.  One host transfer —
        call at telemetry cadence, not every step.  Returns None when the
        state carries no residual."""
        res = getattr(state, "grad_residual", None)
        if res is None:
            return None
        norm = float(np.sqrt(np.sum(
            np.square(np.asarray(res, dtype=np.float64))
        )))
        self.gauge("grad_residual_norm", norm)
        return norm

    def capture_compiled(self, state, batch, engine=None,
                         granule_of=None):
        """Measured collective gauges: compile the engine's step for
        (state, batch) and read the REAL collective ledger off the post-
        SPMD HLO (utils/hlo_comm.py), next to the ring-model `comm_report`
        prediction — plus the AOT memory analysis when the backend
        provides one.

        On a hybrid ICI×DCN mesh (multiple slices / processes), the
        ledger additionally splits wire per LINK: collectives whose
        replica groups cross a granule boundary are billed to DCN
        (measured from the compiled replica_groups, not modeled), gauged
        as `dcn_wire_bytes`.  `granule_of` overrides the device→granule
        map for CPU-emulated multi-slice tests (default: derived from
        the engine mesh's slice/process indices,
        parallel/mesh.granule_map)."""
        from ..utils.hlo_comm import (
            collective_ledger, ledger_summary, overlap_report,
        )

        engine = engine or self._engine
        if engine is None:
            raise ValueError("no engine attached; pass engine=")
        if granule_of is None:
            mesh = getattr(engine, "mesh", None)
            if mesh is not None:
                from ..parallel.mesh import granule_map
                granule_of = granule_map(mesh.devices.flatten())
        compiled = engine._step.lower(state, batch).compile()
        compiled_text = compiled.as_text()
        led = collective_ledger(compiled_text)
        measured = ledger_summary(led, granule_of=granule_of)
        if granule_of is not None:
            self.gauge(
                "dcn_wire_bytes",
                measured["wire_bytes_by_link"]["dcn_wire_bytes"],
            )
        model_rep = comm_report(engine)
        # overlap window: how much of the reducing-collective wire is
        # issued inside while bodies (before the backward scan completes)
        # — the measured counterpart of the grad_buckets knob.  Reuses
        # the ledger above; only the async-window scan re-reads the text
        overlap = overlap_report(compiled_text, led=led)
        out: Dict[str, object] = {
            "comm_measured": measured,
            "comm_model": model_rep,
            "comm_overlap": overlap,
        }
        self.gauge(
            "grad_comm_overlap_frac", overlap["grad_comm_overlap_frac"]
        )
        # the gathering side (ZeRO-3 / gather_prefetch): loop-resident
        # all-gather wire — the measured placement of the per-layer
        # weight gathers (a hoist regression reads 0; ring/pipe
        # collective-permutes are deliberately excluded, hlo_comm.py)
        self.gauge(
            "gather_overlap_frac", overlap["gather_overlap_frac"]
        )
        out["gather_overlap"] = {
            k: overlap[k] for k in (
                "gather_wire_bytes_in_loops", "gather_wire_bytes_total",
                "gather_overlap_frac", "gather_async_windows",
                "gather_async_windows_overlapped",
            )
        }
        # composed scheduler (parallel/schedule.py): per-slot overlap
        # view of the MERGED program, plus the hpZ acceptance gauge —
        # loop-resident gather wire that crosses DCN (~zero when the
        # secondary weight partition keeps in-scan gathers intra-slice)
        if getattr(engine, "_lowering", "plain") == "composed":
            sched = engine._schedule
            if sched.gather is not None:
                self.gauge(
                    "sched_gather_overlap_frac",
                    overlap["gather_overlap_frac"],
                )
            if sched.grad is not None:
                self.gauge(
                    "sched_grad_overlap_frac",
                    overlap["grad_comm_overlap_frac"],
                )
            if sched.grad is not None and sched.grad.tail_mode != "fp32":
                # quantized ZeRO-3 tail release: the tail's sync runs
                # once per step OUTSIDE the scans (the bucket syncs are
                # the in-loop reduce wire), so outside-loop reduce wire
                # IS the tail release — comparable against the fp32
                # path's transpose reduce-scatter on the same number
                self.gauge(
                    "zero3_tail_wire_bytes",
                    overlap["reduce_wire_bytes_total"]
                    - overlap["reduce_wire_bytes_in_loops"],
                )
            if granule_of is not None:
                from ..utils.hlo_comm import (
                    gather_link_split_in_loops, group_wire_outside_loops,
                )
                in_scan = gather_link_split_in_loops(led, granule_of)
                measured["wire_bytes_by_link_in_scan_gather"] = in_scan
                if sched.gather is not None and sched.gather.hpz:
                    self.gauge(
                        "hpz_dcn_wire_bytes",
                        in_scan["dcn_wire_bytes"],
                    )
                    # the rebuild hop itself, isolated by exact group
                    # match on the scheduler's inter groups (qwZ fp8
                    # acceptance: ~4x lower than the fp32 rebuild)
                    if sched.hpz_geom is not None:
                        self.gauge(
                            "hpz_rebuild_dcn_bytes",
                            group_wire_outside_loops(
                                led, sched.hpz_geom[1]
                            ),
                        )
        # table-driven pipeline schedules (parallel/pipe_schedule.py):
        # the compiled (tick, stage) program's occupancy — bubble_frac is
        # the number the interleaved/zero-bubble lowerings exist to
        # shrink below 1F1B's (S-1)/(M+S-1)
        prog = getattr(
            getattr(engine, "_schedule", None), "pipe_program", None
        )
        if prog is not None:
            self.gauge("bubble_frac", float(prog.bubble_frac))
            self.gauge("pipe_ticks", int(prog.n_ticks))
        modeled = float(model_rep.get("total_bytes_per_step", 0.0))
        if modeled > 0:
            out["comm_delta"] = round(
                measured["total_wire_bytes"] / modeled, 4
            )
        self.gauge("measured_wire_bytes", measured["total_wire_bytes"])
        self.gauge("modeled_wire_bytes", modeled)
        mw = model_rep.get("grad_comm_model")
        if mw:
            # quantized gradient collectives (parallel/comm.py): modeled
            # wire saved vs the fp32 all-reduce this schedule replaces —
            # read off comm_report's model so there is ONE accounting site
            out["grad_comm"] = mw
            self.gauge("grad_comm_wire_bytes", mw["quant_wire_bytes"])
            self.gauge(
                "grad_comm_wire_saved_bytes",
                mw["fp32_allreduce_wire_bytes"] - mw["quant_wire_bytes"],
            )
        try:
            mem = compiled.memory_analysis()
            out["aot"] = {
                "temp_bytes": int(mem.temp_size_in_bytes),
                "argument_bytes": int(mem.argument_size_in_bytes),
                "output_bytes": int(mem.output_size_in_bytes),
            }
            self.gauge("aot_temp_bytes", mem.temp_size_in_bytes)
        except Exception:
            pass
        # compute/HBM cost ledger (utils/hlo_cost.py): the roofline's
        # other two axes, read off the SAME compiled text as the wire
        # ledger — post-hoc analysis only, the cached step is untouched
        from ..utils.hlo_cost import (
            cost_ledger, cost_summary, peak_flops_per_chip,
        )
        cled = cost_ledger(compiled_text)
        mesh = getattr(engine, "mesh", None)
        dev = (mesh.devices.flatten()[0] if mesh is not None
               else jax.devices()[0])
        dev_kind = getattr(dev, "device_kind", None)
        cost = cost_summary(
            cled, device_kind=dev_kind,
            wire_bytes=float(measured.get("total_wire_bytes", 0.0)),
        )
        out["hlo_cost"] = cost
        self.gauge("hlo_flops", cost["total_flops"])
        self.gauge("hlo_hbm_bytes", cost["hbm_bytes"])
        self.gauge("arithmetic_intensity", cost["arithmetic_intensity"])
        # a utilization needs the device's peak: on a device the table
        # does not know (the CPU mesh) the gauge is not emitted
        peak = peak_flops_per_chip(dev_kind)
        if peak is not None and self.timer.times:
            step_s = float(np.median(np.asarray(self.timer.times)))
            if step_s > 0:
                self.gauge("step_mfu_hlo",
                           cost["total_flops"] / step_s / peak)
        self._comm = out
        return out

    def run_meta(self, state, sample_batch, engine=None, **extra):
        """Assemble the run_meta record: engine identity + comm gauges +
        caller extras (model name, n_params, batch geometry, ...).
        `sample_batch` only provides shapes for the AOT lowering."""
        from .schema import SCHEMA_VERSION

        engine = engine or self._engine
        meta: Dict[str, object] = {"schema_version": SCHEMA_VERSION}
        try:
            meta.update(self.capture_compiled(
                state, sample_batch, engine=engine,
            ))
        except Exception as e:  # CPU backends missing pieces stay best-effort
            meta["comm_error"] = repr(e)[:200]
        if engine is not None:
            meta.update(
                engine=engine.describe(),
                stage=engine.stage,
                devices=engine.n_dev,
            )
        meta.update(extra)
        return meta

    # -- sinks --------------------------------------------------------------

    def step_record(self) -> Dict[str, object]:
        """Per-step JSONL fields beyond loss/step_s/tokens_per_s: health,
        wall-segment breakdown, compile attribution, HBM watermarks, and
        (once) the anomaly trace path."""
        rec: Dict[str, object] = {}
        h = self.poll()
        if h is not None:
            rec.update({k: h[k] for k in HEALTH_FIELDS if k != "loss"})
        if self.timer.segments:
            rec.update(self.timer.segments[-1])
        if self.timer.compiled_steps:
            rec["compiled"] = int(self.timer.compiled_steps[-1])
        rec.update(self.sample_memory())
        if self.trace_path and not self._trace_logged:
            rec["anomaly_trace"] = self.trace_path
            self._trace_logged = True
        return rec

    def flush(self, logger) -> None:
        """Write the registry snapshot as a `telemetry_summary` record to a
        MetricsLogger (no-op without a JSONL sink)."""
        logger.log_meta(kind="telemetry_summary", **self.snapshot())
