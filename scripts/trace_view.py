# Copyright 2026 tiny-deepspeed-tpu authors
# SPDX-License-Identifier: Apache-2.0

"""Export a serving run's metrics JSONL as a Chrome-trace timeline.

    python scripts/trace_view.py RUN.jsonl [-o TRACE.json]

Load TRACE.json in chrome://tracing or https://ui.perfetto.dev.

SERVING runs (`request`/`tick` records — the `serve_bench.py` sidecar or
any ServingEngine with a logger) show the scheduler ticks with their
parts at their measured starts, a queue track of request wait windows,
and one track per decode slot with each request's active windows —
preemptions, quarantines, and watchdog warm restarts visible as span
boundaries and instant markers.  Where a TRAINING step's time goes is
read from a profiler trace (`--profile`; `python benchmarks/run.py
--trace 1`), which carries the program's own `tds.*` spans and scopes.

FLEET serving files (records carrying `replica_id`) lay out one process
per replica, each with the full tick/queue/slot track set; a request
that crossed engines (disagg prefill->decode migration, failover) gets
its windows on every replica it touched, correlated by the `trace_id`
in their span args.  Ambiguous coordinates in such a shared stream
resolve by ONE rule (telemetry/trace.py::serving_chrome_trace): records
carrying an explicit key — replica_id on ticks/flights, per-event
replica stamps on request lifecycles — route by it; records without one
anchor by file order (last matching record written before, else first
after), which is how flight flushes land on the right engine lifetime
when two lifetimes' tick counters both start at 0.

Span assembly lives in `tiny_deepspeed_tpu/telemetry/trace.py`; the
input comes from `scripts/serve_bench.py`'s sidecar.

Exit codes: 0 ok; 1 parse errors in the JSONL; 2 missing/empty input or
no tick/request records to lay out.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_trace_module():
    """telemetry/trace.py loaded by file path: the module is pure-python
    (json + typing), but importing it through the package would pull the
    whole jax stack in — a multi-second tax on a viewer that only
    reshuffles JSONL."""
    spec = importlib.util.spec_from_file_location(
        "tiny_deepspeed_tpu_trace_standalone",
        os.path.join(_REPO, "tiny_deepspeed_tpu", "telemetry", "trace.py"),
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


trace = _load_trace_module()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("jsonl", help="metrics JSONL from a serving run")
    ap.add_argument("-o", "--out", default=None,
                    help="write the Chrome-trace JSON here "
                         "(default: <input>.trace.json)")
    args = ap.parse_args(argv)
    if not os.path.exists(args.jsonl):
        print(f"{args.jsonl}: no such file", file=sys.stderr)
        return 2
    metas, steps, errs = trace.load_run(args.jsonl)
    for e in errs:
        print(f"warning: {args.jsonl}: {e}", file=sys.stderr)
    if not metas and not steps:
        print(f"{args.jsonl}: no records (empty or fully truncated "
              "metrics file)", file=sys.stderr)
        return 2
    if not trace.has_serving_records(metas):
        print(f"{args.jsonl}: no serving tick/request records (a "
              "training step's timeline is a profiler trace: --profile)",
              file=sys.stderr)
        return 2
    doc = trace.serving_chrome_trace(metas, source=args.jsonl)
    laid_out = "tick(s)/request(s)"
    n_laid = doc["otherData"]["ticks"] + doc["otherData"]["requests"]
    n_spans = sum(1 for e in doc["traceEvents"] if e.get("ph") == "X")
    if not n_spans:
        print(f"{args.jsonl}: no timed serving tick/request records",
              file=sys.stderr)
        return 2
    out = args.out or (os.path.splitext(args.jsonl)[0] + ".trace.json")
    with open(out, "w") as f:
        json.dump(doc, f)
    reps = doc.get("otherData", {}).get("replicas") or []
    fleet = (f" across {len(reps)} replicas" if len(reps) > 1 else "")
    print(f"wrote {out}: {n_spans} spans over {n_laid} {laid_out}"
          f"{fleet} — open in chrome://tracing or "
          "https://ui.perfetto.dev")
    return 1 if errs else 0


if __name__ == "__main__":
    sys.exit(main())
