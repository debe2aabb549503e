# Copyright 2026 tiny-deepspeed-tpu authors
# SPDX-License-Identifier: Apache-2.0

"""In-step training telemetry: the run-observability subsystem.

The reference's entire observability surface is a wall-clock timer and
rank-0 loss prints (SURVEY §2.8, utils/profiling.py docstring).  This
package instruments a training run end to end:

  * `health` — on-device health metrics (grad/update/param global norms,
    non-finite counts, loss) computed INSIDE the compiled step and returned
    as one small auxiliary vector, so they ride the existing step output
    with zero extra host syncs.  Wired into `ZeroEngine` behind the opt-in
    `telemetry=` engine knob; with `telemetry=None` the compiled step is
    byte-identical (tests/test_telemetry.py pins the HLO).
  * `Telemetry` (registry.py) — counters / gauges / histograms, the
    step-time breakdown wrapper (data-wait vs host-to-device vs device
    compute, recompile detection), measured collective gauges from the
    compiled step's HLO ledger (utils/hlo_comm.py), per-step HBM watermarks
    from device memory stats, and an anomaly-triggered `jax.profiler`
    trace capture (one xprof trace when step time exceeds a rolling
    threshold).
  * `schema` — the JSONL metrics schema shared with
    `utils.profiling.MetricsLogger`; `scripts/report_run.py --check`
    validates files against it and `scripts/report_run.py RUN.jsonl`
    renders the markdown run report.
  * `trace` — the serving timeline: request windows and each tick's
    measured parts, exported as Chrome-trace JSON by
    `scripts/trace_view.py`.
  * `flight` (FlightRecorder) — ring buffer of the last N steps' health
    (+ per-layer health in layers mode), flushed as one `flight` JSONL
    record when the anomaly detector fires on a slow step or non-finite
    health.  `Telemetry(layers=True)` turns on the engine's per-layer
    health mode (grad/activation norms + non-finite counts INSIDE the
    block scan — the first-NaN layer localized in one step).
  * `live` — the serving fleet's live plane: streaming aggregation of
    registry snapshots into per-replica ring-buffered time series
    (windowed quantiles, rates) and the opt-in stdlib HTTP exporter
    serving /metrics (Prometheus text), /healthz and /slo — host-side
    only, strictly off the compiled path.
  * `slo` — per-tenant SLO objectives and multi-window error-budget
    burn-rate accounting; the engine observes every terminal request
    into an attached `SLOTracker`, fast-burn alerts flush the flight
    ring, and the fleet router reads `advise()` as a routing signal.
"""

from .health import (
    HEALTH_FIELDS, LAYER_FIELDS, first_nonfinite_layer, health_dict,
    health_vector,
)
from .flight import FlightRecorder
from .registry import Telemetry
from . import live
from . import schema
from . import slo
from . import trace

__all__ = [
    "HEALTH_FIELDS",
    "LAYER_FIELDS",
    "health_vector",
    "health_dict",
    "first_nonfinite_layer",
    "FlightRecorder",
    "Telemetry",
    "live",
    "schema",
    "slo",
    "trace",
]
