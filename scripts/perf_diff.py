#!/usr/bin/env python3
# Copyright 2026 tiny-deepspeed-tpu authors
# SPDX-License-Identifier: Apache-2.0

"""Cross-run perf regression sentinel over BENCH_*.json rounds.

The repo commits one BENCH_rNN.json per growth round but nothing ever
COMPARED them — a silent 10% throughput loss would ride along forever.
This script diffs two or more rounds per fingerprint key and exits
nonzero for CI when something regressed:

  * **throughput regression** — the newest fresh value of a fingerprint
    vs the best of its (up to 3) most recent prior fresh values, flagged
    only beyond a noise threshold = max(--noise-floor, the relative
    spread of those prior values).  Best-of-3 spread IS the measured
    noise: a delta inside it proves nothing.
  * **modeled-vs-measured MFU drift** — bench stamps both the analytic
    `matmul_mfu` (hand formula) and `hlo_cost.mfu_hlo` (FLOPs counted
    from the compiled HLO, utils/hlo_cost.py).  When they diverge beyond
    --drift-tol the FORMULA rotted (a model change the hand accounting
    missed — exactly how the MoE dispatch einsums went uncounted for ten
    rounds).
  * **program growth** (informational) — when telemetry sidecars are
    reachable, a >2% jump in HLO-counted FLOPs for the same fingerprint
    is printed as a NOTE: the program changed, whether or not the clock
    noticed yet.
  * **SLO-attainment regression** — serve records stamp
    `extra.slo.attainment` (higher is better); a drop vs the best prior
    round beyond the noise floor flags a service regression that raw
    tokens/s can mask (tail latency traded for batch occupancy).

Records are usable only when fresh: value > 0 and not marked as a
replay (`extra.cached_result` / top-level `stale`: older round files
carry them).  With zero usable fingerprints
the verdict is OK (nothing to compare), exit 0.

Pure python (no jax): runs anywhere, including tier-1 CI.

Usage:
    python scripts/perf_diff.py BENCH_r04.json BENCH_r05.json
    python scripts/perf_diff.py --check BENCH_*.json     # CI gate
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Dict, List, Optional, Tuple

# extra keys that define a comparable measurement — same metric at a
# different chip count or sequence length is a different experiment,
# not a regression
_FINGERPRINT_KEYS = ("chips", "seq_len")

# metric-name substrings meaning lower-is-better; everything else in the
# bench vocabulary (tokens/s, requests/s, speedup) is higher-is-better
_LOWER_IS_BETTER = ("time", "latency", "_ms", "_s_", "ttft")

# per-fingerprint ledger wire-byte fields (extra.sched, stamped by the
# scheduler bench arms): dotted path -> short label.  Wire bytes are
# measured from the compiled HLO and deterministic per program, so a
# newest-vs-best increase beyond the noise floor is a COMM regression —
# the program started moving more bytes — even when the clock (step
# time on a CPU mesh) never noticed
_WIRE_KEYS = (
    ("sched.gather_wire_bytes_in_loops", "loop gather wire"),
    ("sched.reduce_wire_bytes_in_loops", "loop reduce wire"),
    ("sched.zero3_tail_wire_bytes", "zero3 tail wire"),
    ("sched.hpz_rebuild_dcn_bytes", "hpz rebuild DCN wire"),
    ("sched.wire_bytes_by_link.ici_wire_bytes", "ICI wire"),
    ("sched.wire_bytes_by_link.dcn_wire_bytes", "DCN wire"),
    # not wire, but the same deterministic-per-program contract: the
    # compiled pipeline tick program's idle fraction (pipe-schedule
    # arms) — a bubble creeping back up is a schedule regression the
    # clock on a CPU mesh never notices
    ("sched.bubble_frac", "pipeline bubble frac"),
)

# per-fingerprint HIGHER-is-better extras (the wire keys above are all
# lower-is-better): serve records stamp extra.slo.attainment (fraction
# of requests meeting the default SLO objective, telemetry/slo.py) —
# a drop vs the best prior round beyond the noise floor is a SERVICE
# regression even when tokens/s held (tail latency traded away for
# throughput).  Rounds that predate the stamp simply don't participate.
_ATTAIN_KEYS = (
    ("slo.attainment", "SLO attainment"),
)


def _wire_of(rec: dict, dotted: str) -> Optional[float]:
    """Numeric field at a dotted path under extra, or None."""
    node = rec.get("extra") or {}
    for part in dotted.split("."):
        if not isinstance(node, dict) or part not in node:
            return None
        node = node[part]
    try:
        return float(node)
    except (TypeError, ValueError):
        return None


def _records_of(obj) -> List[dict]:
    """Bench records inside one loaded JSON value: a driver wrapper
    {"n","cmd","rc","tail","parsed"} yields its parsed record, a bare
    record yields itself, a list flattens recursively."""
    if isinstance(obj, list):
        return [r for o in obj for r in _records_of(o)]
    if not isinstance(obj, dict):
        return []
    if "parsed" in obj and "rc" in obj:
        return _records_of(obj["parsed"]) if obj["parsed"] else []
    if "metric" in obj and "value" in obj:
        return [obj]
    return []


def load_round(path: str) -> List[dict]:
    """All bench records in one round file (JSON value or JSONL)."""
    with open(path) as f:
        text = f.read()
    try:
        return _records_of(json.loads(text))
    except ValueError:
        recs: List[dict] = []
        for line in text.splitlines():
            line = line.strip()
            if not line:
                continue
            try:
                recs.extend(_records_of(json.loads(line)))
            except ValueError:
                pass
        return recs


def is_fresh(rec: dict) -> bool:
    """Usable for comparison: a positive live measurement, not an error
    record and not a last-good-cache replay of an older round."""
    try:
        v = float(rec.get("value", 0.0))
    except (TypeError, ValueError):
        return False
    if v <= 0.0:
        return False
    if rec.get("stale"):
        return False
    extra = rec.get("extra") or {}
    if extra.get("cached_result") or extra.get("stale_cached_result"):
        return False
    if extra.get("error"):
        return False
    return True


def fingerprint(rec: dict) -> str:
    extra = rec.get("extra") or {}
    parts = [str(rec.get("metric", "?"))]
    for k in _FINGERPRINT_KEYS:
        if k in extra:
            parts.append(f"{k}={extra[k]}")
    return " ".join(parts)


def _higher_is_better(metric: str) -> bool:
    m = metric.lower()
    return not any(s in m for s in _LOWER_IS_BETTER)


def _sidecar_flops(rec: dict, round_dir: str) -> Optional[float]:
    """HLO-counted FLOPs for a record: from extra.hlo_cost directly, else
    from the telemetry sidecar's run_meta (best effort — sidecars are
    working-tree artifacts and are usually gone for old rounds)."""
    extra = rec.get("extra") or {}
    cost = extra.get("hlo_cost") or {}
    if isinstance(cost, dict) and cost.get("total_flops"):
        return float(cost["total_flops"])
    path = extra.get("telemetry_jsonl")
    if not path:
        return None
    if not os.path.isabs(path):
        path = os.path.join(round_dir, path)
    try:
        with open(path) as f:
            for line in f:
                try:
                    m = json.loads(line)
                except ValueError:
                    continue
                if isinstance(m, dict) and m.get("kind") == "run_meta":
                    c = m.get("hlo_cost") or {}
                    if c.get("total_flops"):
                        return float(c["total_flops"])
    except OSError:
        return None
    return None


def diff_rounds(rounds: List[Tuple[str, List[dict]]],
                noise_floor: float = 0.03,
                drift_tol: float = 0.15) -> Dict[str, object]:
    """Compare rounds (in given order; last = newest).  Returns
    {"regressions": [...], "drifts": [...], "notes": [...],
     "compared": n, "usable": n} — each flag a printable string naming
    the metric + fingerprint."""
    regressions: List[str] = []
    drifts: List[str] = []
    notes: List[str] = []

    # fingerprint -> [(round_name, rec)] in round order, fresh only
    series: Dict[str, List[Tuple[str, dict]]] = {}
    usable = 0
    for rname, recs in rounds:
        for rec in recs:
            if not is_fresh(rec):
                continue
            usable += 1
            series.setdefault(fingerprint(rec), []).append((rname, rec))

    compared = 0
    for fp, entries in sorted(series.items()):
        # modeled-vs-measured drift: every fresh record that carries both
        for rname, rec in entries:
            extra = rec.get("extra") or {}
            cost = extra.get("hlo_cost") or {}
            mm = extra.get("matmul_mfu")
            mh = cost.get("mfu_hlo") if isinstance(cost, dict) else None
            if mm and mh:
                rel = abs(float(mm) - float(mh)) / max(float(mh), 1e-12)
                if rel > drift_tol:
                    drifts.append(
                        f"DRIFT {fp} [{rname}]: analytic matmul_mfu "
                        f"{float(mm):.3f} vs HLO-counted mfu_hlo "
                        f"{float(mh):.3f} ({rel:.0%} apart > "
                        f"{drift_tol:.0%}) — the hand formula and the "
                        f"compiled program disagree"
                    )
        if len(entries) < 2:
            continue
        compared += 1
        newest_name, newest = entries[-1]
        prior = entries[:-1][-3:]  # up to the 3 most recent prior rounds
        vals = [float(r["value"]) for _, r in prior]
        newest_v = float(newest["value"])
        higher = _higher_is_better(str(newest.get("metric", "")))
        best = max(vals) if higher else min(vals)
        spread = (max(vals) - min(vals)) / max(abs(best), 1e-12)
        threshold = max(noise_floor, spread)
        delta = ((best - newest_v) if higher else (newest_v - best)) \
            / max(abs(best), 1e-12)
        if delta > threshold:
            regressions.append(
                f"REGRESSION {fp} [{newest_name}]: {newest_v:,.1f} vs "
                f"best-of-{len(vals)} {best:,.1f} "
                f"({-delta:+.1%} > noise {threshold:.1%} = "
                f"max(floor {noise_floor:.1%}, spread {spread:.1%}))"
            )
        # comm regression: per-fingerprint ledger wire bytes — newest vs
        # the best (lowest) prior value carrying the same field.  Both
        # sides must stamp the field: a round that predates the
        # scheduler arms (no extra.sched) simply does not participate,
        # so the committed trajectory stays comparable
        for dotted, label in _WIRE_KEYS:
            w_new = _wire_of(newest, dotted)
            w_prior = [w for w in (_wire_of(r, dotted) for _, r in prior)
                       if w is not None]
            if w_new is None or not w_prior:
                continue
            best_w = min(w_prior)
            if best_w <= 0.0:
                continue
            rel = (w_new - best_w) / best_w
            if rel > noise_floor:
                regressions.append(
                    f"REGRESSION {fp} [{newest_name}]: {label} "
                    f"{w_new:,.0f} B vs best-of-{len(w_prior)} "
                    f"{best_w:,.0f} B ({rel:+.1%} > {noise_floor:.1%}) "
                    f"— the compiled step moves more bytes"
                )
        # service regression: SLO attainment (higher is better) —
        # newest vs the best (highest) prior value carrying the field
        for dotted, label in _ATTAIN_KEYS:
            a_new = _wire_of(newest, dotted)
            a_prior = [a for a in (_wire_of(r, dotted) for _, r in prior)
                       if a is not None]
            if a_new is None or not a_prior:
                continue
            best_a = max(a_prior)
            if best_a <= 0.0:
                continue
            rel = (best_a - a_new) / best_a
            if rel > noise_floor:
                regressions.append(
                    f"REGRESSION {fp} [{newest_name}]: {label} "
                    f"{a_new:.3f} vs best-of-{len(a_prior)} "
                    f"{best_a:.3f} ({-rel:+.1%} > {noise_floor:.1%}) "
                    f"— fewer requests met their SLO objective"
                )
        # program growth: HLO-counted FLOPs for the same fingerprint
        f_old = _sidecar_flops(prior[-1][1],
                               os.path.dirname(prior[-1][0]) or ".")
        f_new = _sidecar_flops(newest,
                               os.path.dirname(newest_name) or ".")
        if f_old and f_new:
            rel = (f_new - f_old) / f_old
            if abs(rel) > 0.02:
                notes.append(
                    f"NOTE {fp}: HLO-counted FLOPs changed {rel:+.1%} "
                    f"({f_old:.3e} -> {f_new:.3e}) — the compiled "
                    f"program itself changed"
                )

    return {"regressions": regressions, "drifts": drifts, "notes": notes,
            "compared": compared, "usable": usable,
            "fingerprints": len(series)}


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        description="noise-aware cross-round bench diff (see module "
                    "docstring)")
    ap.add_argument("files", nargs="+",
                    help="BENCH_*.json round files, oldest first "
                         "(sorted by name unless --no-sort)")
    ap.add_argument("--check", action="store_true",
                    help="CI mode: same comparison, documented gate — "
                         "exit 1 on any REGRESSION/DRIFT flag, 0 "
                         "otherwise (including nothing-to-compare)")
    ap.add_argument("--no-sort", action="store_true",
                    help="take files in the order given instead of "
                         "sorting by name")
    ap.add_argument("--noise-floor", type=float, default=0.03,
                    help="minimum relative delta to flag (default 3%%)")
    ap.add_argument("--drift-tol", type=float, default=0.15,
                    help="modeled-vs-measured MFU divergence to flag "
                         "(default 15%%)")
    args = ap.parse_args(argv)

    files = list(args.files) if args.no_sort else sorted(args.files)
    rounds = [(f, load_round(f)) for f in files]
    out = diff_rounds(rounds, noise_floor=args.noise_floor,
                      drift_tol=args.drift_tol)

    print(f"perf_diff: {len(rounds)} round(s), {out['usable']} fresh "
          f"record(s), {out['fingerprints']} fingerprint(s), "
          f"{out['compared']} compared")
    for line in out["notes"]:
        print(line)
    for line in out["drifts"]:
        print(line)
    for line in out["regressions"]:
        print(line)
    flags = len(out["regressions"]) + len(out["drifts"])
    if flags:
        print(f"FAIL: {flags} flag(s)")
        return 1
    if not out["compared"] and not out["usable"]:
        print("OK (no fresh records to compare — cached/error rounds "
              "are excluded)")
    else:
        print("OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
