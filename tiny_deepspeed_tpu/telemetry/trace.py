# Copyright 2026 tiny-deepspeed-tpu authors
# SPDX-License-Identifier: Apache-2.0

"""Serving timeline: Chrome-trace / Perfetto span assembly from a
run's metrics JSONL, every span a host-clock measurement.

`serving_chrome_trace`: the request-lifecycle `events` on each `request`
record and the per-tick `tick` records (serving/engine.py) lay out as
scheduler-tick spans, the parts of each tick (`spans` on the tick record:
the engine's `tick_records` segments, the same instants its `tds.tick.*`
profiler spans carry) at their measured starts, a queue track (one span
per wait window, labeled with WHY the request waited: queue / preempted /
restart), and one track per decode slot (one span per active window,
closed with how it ended — finished, preempted, quarantined, expired).
Quarantines and watchdog restarts are instant markers, so "what led up
to that restart" is visible at a glance.  All serving stamps share one
monotonic clock, so the tracks align exactly.

`scripts/trace_view.py` turns a serving run's JSONL into Chrome-trace
JSON (chrome://tracing, https://ui.perfetto.dev) using this module.
WHERE inside a training step the device's time goes is read from a
profiler trace (`jax.profiler`; the program's `tds.*` spans and scopes,
utils/profiling.TABLE), not drawn from a model of it: the schematic
train timeline that stood here (collective and compute spans sized by
wire bytes and FLOPs) went with PR 26.
"""

from __future__ import annotations

import json
from typing import Dict, List, Optional, Tuple

def _quantile(xs, q: float) -> float:
    """Linear-interpolated quantile, mirror of
    utils/profiling._quantile — duplicated HERE (and only here) because
    this module is the pure-python loader the standalone scripts
    (trace_view.py, serve_report.py) path-import to avoid the jax tax;
    scripts must share THIS copy rather than growing their own."""
    if not xs:
        return 0.0
    ys = sorted(xs)
    if len(ys) == 1:
        return ys[0]
    pos = q * (len(ys) - 1)
    lo = int(pos)
    hi = min(lo + 1, len(ys) - 1)
    return ys[lo] + (ys[hi] - ys[lo]) * (pos - lo)


def load_run(path: str) -> Tuple[List[dict], List[dict], List[str]]:
    """(meta records, step records, parse errors) from a metrics JSONL —
    the report_run.py loader contract, shared here so trace_view.py and
    report_run.py read files identically."""
    metas, steps, errs = [], [], []
    with open(path) as f:
        for i, line in enumerate(f, 1):
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except ValueError as e:
                errs.append(f"line {i}: invalid JSON ({e})")
                continue
            (metas if isinstance(rec, dict) and "kind" in rec
             else steps).append(rec)
    return metas, steps, errs


def _find(metas: List[dict], kind: str) -> Optional[dict]:
    for m in metas:
        if m.get("kind") == kind:
            return m
    return None


def _json_safe(v):
    """Non-finite floats become their string names: Python's json happily
    writes bare `NaN`, but chrome://tracing and Perfetto parse STRICT
    JSON and would reject the whole file — exactly on the NaN-postmortem
    runs this timeline exists for."""
    if isinstance(v, float) and not (v == v and abs(v) != float("inf")):
        return str(v)
    if isinstance(v, dict):
        return {k: _json_safe(x) for k, x in v.items()}
    if isinstance(v, list):
        return [_json_safe(x) for x in v]
    return v

# -- serving timeline ---------------------------------------------------------

# serving Chrome-trace track (tid) layout, pid 1 (pid 0 is training).
# Fleet files (records carrying replica_id, schema v8+) get one PROCESS
# per replica — pid _PID_REPLICA0 + replica — each with this same tid
# layout inside, so one request's spans land on correlated per-replica
# track groups under its trace_id (schema v15).
_PID_SERVE = 1       # single-engine serving / records with no replica
_PID_REPLICA0 = 2    # replica r -> pid _PID_REPLICA0 + r
_TID_TICK = 0        # scheduler ticks
_TID_TICK_SEG = 1    # the parts of each tick (the tick record's `spans`)
_TID_QUEUE = 2       # request wait windows
_TID_SLOT0 = 3       # decode slot s -> tid _TID_SLOT0 + s

_WAIT_LABELS = {"queue": "queue wait", "preempt": "preempted wait",
                "restart": "restart wait",
                # disagg prefill->decode handoff (schema v15): the
                # export->import window, billed to comp_migrate_s
                "migrate": "migration wait"}

# Cross-engine lifecycle markers (schema v15) and their attribution —
# ONE rule, stated here and restated by Request.event's docstring: a
# marker that LEAVES an engine (`exported`, `engine_lost`) attributes
# every event since the previous marker to its replica; a marker that
# ARRIVES (`imported`, `recovered`) attributes the events after it;
# whatever trails the last marker belongs to the record's own
# `replica_id` (the engine that wrote the terminal).
_LEAVE_MARKERS = ("exported", "engine_lost")
_ARRIVE_MARKERS = ("imported", "recovered")


def _event_replicas(events: List[list], record_replica) -> List[object]:
    """Per-event replica attribution for one request's lifecycle events
    under the marker rule above (None throughout for pre-fleet records
    that carry no replica stamps).  Events serialize as [name, t],
    [name, t, slot] or [name, t, slot, replica] — slot may be null when
    only the replica is stamped (a queued request's engine_lost)."""
    n = len(events)
    reps: List[object] = [None] * n
    pending: List[int] = []
    cur = None
    for i, e in enumerate(events):
        name = e[0]
        rep = e[3] if len(e) > 3 and e[3] is not None else None
        if name in _LEAVE_MARKERS and rep is not None:
            reps[i] = rep
            for j in pending:
                reps[j] = rep
            pending = []
            cur = None
        elif name in _ARRIVE_MARKERS and rep is not None:
            reps[i] = cur = rep
        elif cur is not None:
            reps[i] = cur
        else:
            pending.append(i)
    for j in pending:
        reps[j] = record_replica
    return reps


def has_serving_records(metas: List[dict]) -> bool:
    """True when the file carries serving-tier records a timeline can be
    built from (request records with lifecycle events, or tick records)."""
    return any(
        m.get("kind") == "tick"
        or (m.get("kind") == "request" and m.get("events"))
        for m in metas
    )


def _request_windows(rec: dict) -> List[dict]:
    """Fold one request record's lifecycle `events` into closed windows:
    {"track": "queue" | ("slot", i), "label", "t0", "t1", "why",
     "replica", "trace"}.  Every wait window closes at the admission
    (or terminal) that ends it; every active window closes at the
    preemption / migration / quarantine / expiry / terminal that
    vacates the slot — the same timestamps the engine's
    latency-component partition uses, so track walls and `comp_*_s`
    agree by construction.  A window's `replica` is the attribution of
    the event that OPENED it (`_event_replicas`; None on single-engine
    records), which routes it onto the right per-replica track group in
    a fleet file; `trace` is the record's trace_id, the key that
    correlates one request's windows ACROSS those groups."""
    rid = rec.get("request_id", "?")
    trace = rec.get("trace_id")
    out: List[dict] = []
    events = rec.get("events") or []
    reps = _event_replicas(events, rec.get("replica_id"))
    wait_t = wait_kind = wait_rep = None
    active = None  # (slot, t_admitted, replica)

    def close_wait(t):
        nonlocal wait_t
        if wait_t is not None and t > wait_t:
            out.append({"track": "queue",
                        "label": f"req {rid}", "t0": wait_t, "t1": t,
                        "why": _WAIT_LABELS.get(wait_kind, wait_kind),
                        "replica": wait_rep, "trace": trace})
        wait_t = None

    def close_active(t, why):
        nonlocal active
        if active is not None:
            slot, t_adm, rep = active
            out.append({"track": ("slot", slot),
                        "label": f"req {rid}", "t0": t_adm, "t1": t,
                        "why": why, "replica": rep, "trace": trace})
        active = None

    for i, e in enumerate(events):
        name, t = e[0], float(e[1])
        slot = int(e[2]) if len(e) > 2 and e[2] is not None else None
        rep = reps[i]
        if name in ("submitted", "recovered"):
            wait_t = t
            wait_kind = "queue" if name == "submitted" else "restart"
            wait_rep = rep
        elif name == "admitted":
            close_wait(t)
            active = (slot if slot is not None else 0, t, rep)
        elif name in ("preempted", "restart_requeued"):
            close_active(t, "preempted" if name == "preempted"
                         else "warm restart")
            wait_t = t
            wait_kind = ("preempt" if name == "preempted" else "restart")
            wait_rep = rep
        elif name in ("quarantined", "expired"):
            close_active(t, name)
        elif name == "exported":
            # disagg handoff out of this engine: the active window
            # closes at the export and the migration wait opens —
            # billed to comp_migrate_s, drawn on the SOURCE replica's
            # queue track (the export stamp is the source's)
            close_active(t, "exported")
            wait_t = t
            wait_kind = "migrate"
            wait_rep = rep
        elif name == "imported":
            # ...and closes when the destination engine seats the slot;
            # the decode-side active window opens HERE, on the
            # destination replica's slot track
            close_wait(t)
            active = (slot if slot is not None else 0, t, rep)
        elif name == "engine_lost":
            # the replica died with this request queued or active: both
            # window kinds close at the death stamp (on the DEAD
            # replica's tracks); the sibling's `recovered` re-opens the
            # wait on its own
            close_active(t, "engine lost")
            close_wait(t)
        elif name == "admission_aborted":
            # a real prefill failure bounced the admission: the aborted
            # sliver closes here and the request re-queues (the engine
            # re-opened its wait window at the admission stamp)
            close_active(t, "aborted")
            wait_t = t
            wait_rep = rep
        elif name.startswith("terminal:"):
            close_active(t, name.split(":", 1)[1])
            close_wait(t)
    return out


def serving_chrome_trace(metas: List[dict],
                         source: str = "") -> Dict[str, object]:
    """Chrome-trace JSON for a serving run's records: scheduler-tick
    spans + their measured wall split, one queue track, one track per
    decode slot, quarantine/restart instant markers.  Timestamps are
    microseconds from the earliest serving stamp (every serving record
    shares one in-process monotonic clock, so tracks align exactly —
    across replicas too).

    Fleet files (records carrying replica_id) lay out one PROCESS per
    replica, each with the full tick/queue/slot tid set; a request that
    crossed engines (disagg migration, failover) gets its windows on
    EVERY replica it touched, correlated by the `trace_id` in their
    span args — the Perfetto view the cross-engine tail postmortem
    reads.

    Shared-stream disambiguation is ONE rule, applied to every
    coordinate collision in a multi-lifetime / multi-replica file:
      * a record that carries an explicit track key routes by it —
        replica_id on tick records picks the replica's process, and
        lifecycle windows carry the (trace_id, replica) attribution of
        the event that opened them (`_event_replicas`);
      * a record WITHOUT one anchors by FILE ORDER: the last matching
        record written before it, else the first after.  Flight flushes
        are the canonical without-case — one sidecar can carry two
        engine lifetimes (pre-kill, then recovered) whose tick counters
        both restart at 0, and the engine emits the tick record ahead
        of its flush (while recover() flushes before the fresh engine's
        tick 0 exists), which is exactly what before-else-after
        encodes.  A flight that DOES carry replica_id restricts its
        candidate ticks to that replica first."""
    ticks = [m for m in metas if m.get("kind") == "tick"
             and isinstance(m.get("t_s"), (int, float))]
    reqs = [m for m in metas if m.get("kind") == "request"]
    windows = [w for r in reqs for w in _request_windows(r)]
    run = _find(metas, "run_meta") or {}
    serve = run.get("serve") or {}
    n_slots = serve.get("max_active")
    if not isinstance(n_slots, int) or n_slots < 1:
        n_slots = 1 + max(
            (w["track"][1] for w in windows
             if isinstance(w["track"], tuple)), default=-1)

    replicas = sorted({
        r for r in ([t.get("replica_id") for t in ticks]
                    + [w.get("replica") for w in windows])
        if isinstance(r, int) and not isinstance(r, bool)})

    def pid_of(rep) -> int:
        if not replicas or not isinstance(rep, int) \
                or isinstance(rep, bool):
            return _PID_SERVE
        return _PID_REPLICA0 + rep

    stamps = ([t["t_s"] for t in ticks]
              + [w["t0"] for w in windows])
    t0 = min(stamps, default=0.0)

    def us(seconds: float) -> float:
        return round(seconds * 1e6, 3)

    events: List[dict] = []
    used_pids = sorted({pid_of(t.get("replica_id")) for t in ticks}
                       | {pid_of(w.get("replica")) for w in windows}
                       ) or [_PID_SERVE]
    for pid in used_pids:
        pname = (f"serving run {source}".strip() if pid == _PID_SERVE
                 else f"serving replica {pid - _PID_REPLICA0} "
                      f"{source}".strip())
        events.append({"ph": "M", "pid": pid, "name": "process_name",
                       "args": {"name": pname}})
        events.append({"ph": "M", "pid": pid, "tid": _TID_TICK,
                       "name": "thread_name",
                       "args": {"name": "scheduler ticks"}})
        events.append({"ph": "M", "pid": pid, "tid": _TID_TICK_SEG,
                       "name": "thread_name",
                       "args": {"name": "tick wall split"}})
        events.append({"ph": "M", "pid": pid, "tid": _TID_QUEUE,
                       "name": "thread_name",
                       "args": {"name": "queue"}})
        for s in range(n_slots):
            events.append({"ph": "M", "pid": pid, "tid": _TID_SLOT0 + s,
                           "name": "thread_name",
                           "args": {"name": f"slot {s}"}})

    for rec in ticks:
        pid = pid_of(rec.get("replica_id"))
        start = rec["t_s"] - t0
        wall = float(rec.get("wall_s") or 0.0)
        events.append({
            "ph": "X", "pid": pid, "tid": _TID_TICK,
            "name": f"tick {rec.get('tick', '?')}",
            "ts": us(start), "dur": us(wall),
            "args": _json_safe({
                k: rec[k] for k in
                ("occupancy", "pool_util", "queue_depth", "admitted",
                 "evicted", "preempted", "shed", "expired",
                 "quarantined", "restarted", "produced", "emit")
                if k in rec
            }),
        })
        # the tick's parts at their measured starts (`spans`: [name,
        # seconds from the tick's start, seconds])
        for name, rel, dur in rec.get("spans") or ():
            events.append({
                "ph": "X", "pid": pid, "tid": _TID_TICK_SEG,
                "name": name, "ts": us(start + rel), "dur": us(dur),
                "args": {"seconds": dur},
            })
        if rec.get("restarted"):
            events.append({
                "ph": "i", "pid": pid, "tid": _TID_TICK, "s": "p",
                "name": "watchdog warm restart", "ts": us(start + wall),
            })

    for w in windows:
        pid = pid_of(w.get("replica"))
        tid = (_TID_QUEUE if w["track"] == "queue"
               else _TID_SLOT0 + w["track"][1])
        args = {"window": w["why"]}
        if w.get("trace") is not None:
            args["trace_id"] = w["trace"]
        if w.get("replica") is not None:
            args["replica"] = w["replica"]
        events.append({
            "ph": "X", "pid": pid, "tid": tid, "name": w["label"],
            "ts": us(w["t0"] - t0), "dur": us(w["t1"] - w["t0"]),
            "args": args,
        })
        if w["why"] == "quarantined":
            events.append({
                "ph": "i", "pid": pid, "tid": tid, "s": "t",
                "name": f"quarantine ({w['label']})",
                "ts": us(w["t1"] - t0),
            })

    # flight markers: the file-order half of the shared-stream rule
    # (docstring above) — last matching tick written before the flush,
    # else first after; same-replica ticks preferred when the flight
    # carries a replica_id
    for fi, fl in enumerate(metas):
        if fl.get("kind") != "flight" or not str(
                fl.get("reason", "")).startswith(("serve_", "slo_")):
            continue
        at = fl.get("at_step")
        frep = fl.get("replica_id")
        matches = [(mi, m) for mi, m in enumerate(metas)
                   if m.get("kind") == "tick" and m.get("tick") == at
                   and isinstance(m.get("t_s"), (int, float))
                   and (frep is None or m.get("replica_id") == frep)]
        before = [m for mi, m in matches if mi < fi]
        after = [m for mi, m in matches if mi > fi]
        anchor = before[-1] if before else (after[0] if after else None)
        if anchor is not None:
            events.append({
                "ph": "i", "pid": pid_of(anchor.get("replica_id")),
                "tid": _TID_TICK, "s": "p",
                "name": f"flight flush ({fl['reason']})",
                "ts": us(anchor["t_s"] - t0
                         + float(anchor.get("wall_s") or 0.0)),
            })

    return {
        "traceEvents": events,
        "displayTimeUnit": "ms",
        "otherData": {
            "source": source,
            "serving": True,
            "slots": n_slots,
            "ticks": len(ticks),
            "requests": len(reqs),
            "replicas": replicas,
        },
    }
