# Copyright 2026 tiny-deepspeed-tpu authors
# SPDX-License-Identifier: Apache-2.0

"""Render a training run's metrics JSONL into a markdown dashboard — or
validate it against the telemetry schema.

    python scripts/report_run.py RUN.jsonl [-o REPORT.md]
    python scripts/report_run.py --check RUN.jsonl

The JSONL comes from `utils.profiling.MetricsLogger` (examples/common.py
`--telemetry --metrics RUN.jsonl`); the
schema is `tiny_deepspeed_tpu/telemetry/schema.py`.  `--check` exits
non-zero on any drift (unknown fields, wrong types, malformed lines) so CI
catches schema breakage (tests/test_telemetry.py smoke-runs it in tier-1).

The report covers: throughput (p50/p95 step time, tokens/s, MFU when the
meta record carries FLOPs context), the step-time breakdown (data-wait vs
host->device vs device compute), measured (HLO-ledger) collective bytes
next to the `comm_report` ring model, HBM watermarks vs the AOT prediction,
and health flags (non-finite grads, loss spikes, recompiles, anomaly
traces).
"""

from __future__ import annotations

import argparse
import math
import os
import sys
from typing import List, Optional

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from tiny_deepspeed_tpu.telemetry import schema  # noqa: E402
# ONE loader for both views of a metrics file: trace_view.py reads the
# same records through the same function, so the two tools can never
# disagree on record classification
from tiny_deepspeed_tpu.telemetry.trace import load_run  # noqa: E402
from tiny_deepspeed_tpu.utils.profiling import _quantile  # noqa: E402


def _fmt_bytes(n: float) -> str:
    for unit, div in (("GB", 2 ** 30), ("MB", 2 ** 20), ("KB", 2 ** 10)):
        if abs(n) >= div:
            return f"{n / div:.2f} {unit}"
    return f"{n:.0f} B"


def _col(steps: List[dict], key: str) -> List[float]:
    return [
        r[key] for r in steps
        if isinstance(r.get(key), (int, float))
        and not isinstance(r.get(key), bool)
        and math.isfinite(r[key])
    ]


def _meta(metas: List[dict], kind: str) -> Optional[dict]:
    for m in metas:
        if m.get("kind") == kind:
            return m
    return None


def render_report(metas: List[dict], steps: List[dict],
                  source: str = "") -> str:
    run = _meta(metas, "run_meta") or {}
    summary = _meta(metas, "telemetry_summary") or {}
    out: List[str] = []
    title = run.get("model") or os.path.basename(source) or "training run"
    out.append(f"# Run report — {title}\n")
    if source:
        out.append(f"Source: `{source}`\n")

    # -- run identity -------------------------------------------------------
    if run:
        out.append("## Run\n")
        for label, key in (("engine", "engine"), ("devices", "devices"),
                           ("params", "n_params"), ("batch", "batch"),
                           ("seq len", "seq_len"),
                           ("tokens/step", "tokens_per_step")):
            if key in run:
                v = run[key]
                if key == "n_params":
                    v = f"{v / 1e6:.1f}M"
                out.append(f"- {label}: {v}")
        out.append("")

    # -- throughput ---------------------------------------------------------
    times = _col(steps, "step_s")
    # drop the first step once there are more: it pays the compile
    warm = times[1:] if len(times) > 1 else times
    toks = _col(steps, "tokens_per_s")
    out.append("## Throughput\n")
    out.append(f"- steps recorded: {len(steps)}")
    if warm:
        out.append(
            f"- step time: mean {sum(warm) / len(warm) * 1e3:.1f} ms, "
            f"p50 {_quantile(warm, 0.5) * 1e3:.1f} ms, "
            f"p95 {_quantile(warm, 0.95) * 1e3:.1f} ms, "
            f"p99 {_quantile(warm, 0.99) * 1e3:.1f} ms, "
            f"max {max(warm) * 1e3:.1f} ms"
        )
    if toks:
        warm_toks = toks[1:] if len(toks) > 1 else toks
        mean_tps = sum(warm_toks) / len(warm_toks)
        out.append(f"- tokens/s: mean {mean_tps:,.0f}")
        peak = run.get("peak_flops_per_chip")
        n_params = run.get("n_params")
        devices = run.get("devices", 1) or 1
        # MFU accounting preference: HLO-counted (measured numerator,
        # utils/hlo_cost) > analytic matmul (bench's honest formula) >
        # 6N naive (self-flattering: prices embedding gathers as
        # matmul FLOPs) — always labeled with which one was used
        cost = run.get("hlo_cost") or {}
        tok_step = run.get("tokens_per_step")
        fptm = run.get("flops_per_token_matmul")
        if peak and cost.get("total_flops") and tok_step:
            # per-device program FLOPs x steps/s / per-chip peak
            mfu = (float(cost["total_flops"]) * mean_tps
                   / float(tok_step) / peak)
            out.append(f"- MFU (HLO-counted): {mfu:.3f}")
        elif peak and fptm:
            mfu = float(fptm) * mean_tps / devices / peak
            out.append(f"- MFU (matmul accounting): {mfu:.3f}")
        elif peak and n_params:
            mfu = 6 * n_params * mean_tps / devices / peak
            out.append(f"- MFU (6N naive; no measured accounting "
                       f"in file): {mfu:.3f}")
    out.append("")

    # -- step-time breakdown ------------------------------------------------
    seg_keys = [k for k in ("data_s", "h2d_s", "compute_s")
                if _col(steps, k)]
    if seg_keys:
        out.append("## Step-time breakdown (mean, share of step)\n")
        out.append("| segment | mean | share |")
        out.append("|---|---|---|")
        total = sum(
            sum(_col(steps, k)) / max(1, len(_col(steps, k)))
            for k in seg_keys
        )
        names = {"data_s": "data wait", "h2d_s": "host->device",
                 "compute_s": "device compute (+sync)"}
        for k in seg_keys:
            xs = _col(steps, k)
            mean = sum(xs) / len(xs)
            share = mean / total if total else 0.0
            out.append(
                f"| {names[k]} | {mean * 1e3:.2f} ms | {share:.0%} |"
            )
        out.append("")

    # -- communication ------------------------------------------------------
    measured = run.get("comm_measured")
    model_rep = run.get("comm_model")
    if measured or model_rep:
        out.append("## Collective traffic (per device per step)\n")
        if model_rep:
            out.append("ring-model prediction (`comm_report`):\n")
            for k, v in sorted(model_rep.items()):
                if k.endswith("_bytes") and v:
                    out.append(f"- {k}: {_fmt_bytes(v)}")
            out.append("")
        if measured:
            out.append("measured from the compiled step's HLO ledger "
                       "(`utils/hlo_comm.py`):\n")
            out.append("| collective | wire bytes | ops/step |")
            out.append("|---|---|---|")
            counts = measured.get("count", {})
            for op, v in sorted(measured.get("wire_bytes", {}).items()):
                out.append(
                    f"| {op} | {_fmt_bytes(v)} | "
                    f"{counts.get(op, 0):.0f} |"
                )
            out.append(
                f"| **total** | **{_fmt_bytes(measured['total_wire_bytes'])}"
                f"** | |"
            )
            out.append("")
            if "comm_delta" in run:
                out.append(
                    f"measured / modeled = **{run['comm_delta']:.3f}** "
                    "(1.0 = the ring model is exact; >1 = the partitioner "
                    "emitted more wire traffic than the model predicts)\n"
                )
            unresolved = (measured.get("unresolved_loops", 0)
                          + measured.get("unresolved_groups", 0))
            if unresolved:
                out.append(
                    f"WARNING: {unresolved} collective(s)/loop(s) had "
                    "unresolved attribution — totals are a lower bound\n"
                )

    # -- roofline (HLO cost ledger) -----------------------------------------
    cost = run.get("hlo_cost")
    if cost:
        out.append("## Roofline (per device per step, "
                   "`utils/hlo_cost.py`)\n")
        out.append(f"- FLOPs: {cost.get('total_flops', 0.0):.3e} "
                   f"({cost.get('flops_in_loops', 0.0):.3e} in loops)")
        out.append(f"- HBM traffic (modeled): "
                   f"{_fmt_bytes(cost.get('hbm_bytes', 0.0))}")
        if cost.get("wire_bytes"):
            out.append(f"- wire traffic: "
                       f"{_fmt_bytes(cost['wire_bytes'])}")
        ai = cost.get("arithmetic_intensity", 0.0)
        if cost.get("bound"):
            out.append(f"- arithmetic intensity: {ai:.1f} FLOPs/byte "
                       f"(device ridge "
                       f"{cost.get('ridge_intensity', 0.0):.1f})")
            out.append(
                f"- bound verdict: **{cost['bound']}-bound** "
                f"(t_compute {cost.get('t_compute_s', 0.0) * 1e3:.2f} ms, "
                f"t_hbm {cost.get('t_hbm_s', 0.0) * 1e3:.2f} ms, "
                f"t_wire {cost.get('t_wire_s', 0.0) * 1e3:.2f} ms lower "
                f"bounds)"
            )
        else:
            # counts only: the run's device has no entry in the peak
            # tables (utils/hlo_cost.py), so no roofline was drawn
            out.append(f"- arithmetic intensity: {ai:.1f} FLOPs/byte")
            out.append("- bound verdict: not measured (no peak known "
                       "for this device)")
        centers = cost.get("top_cost_centers") or []
        if centers:
            out.append("\ntop cost centers:\n")
            out.append("| op (result <- operands) | FLOPs | ops/step "
                       "| share |")
            out.append("|---|---|---|---|")
            for c in centers:
                out.append(
                    f"| `{c.get('sig', '?')}` | "
                    f"{c.get('flops', 0.0):.3e} | "
                    f"{c.get('count', 0.0):.0f} | "
                    f"{c.get('share', 0.0):.0%} |"
                )
        out.append("")

    # -- memory -------------------------------------------------------------
    hbm_peak = _col(steps, "hbm_gb_peak")
    aot = run.get("aot") or {}
    if hbm_peak or aot:
        out.append("## Memory\n")
        if hbm_peak:
            out.append(
                f"- HBM peak watermark: {max(hbm_peak):.3f} GB "
                f"(first step {hbm_peak[0]:.3f} GB)"
            )
            in_use = _col(steps, "hbm_gb_in_use")
            if in_use:
                out.append(f"- HBM in use (last step): {in_use[-1]:.3f} GB")
        if aot.get("temp_bytes") is not None:
            out.append(
                f"- AOT-predicted step temp: "
                f"{_fmt_bytes(aot['temp_bytes'])}"
            )
            if hbm_peak:
                pred_gb = aot["temp_bytes"] / 2 ** 30
                out.append(
                    f"- predicted-vs-measured delta: "
                    f"{max(hbm_peak) - pred_gb:+.3f} GB "
                    "(live state + allocator slack)"
                )
        out.append("")

    # -- health -------------------------------------------------------------
    out.append("## Health\n")
    flags = []
    losses = _col(steps, "loss")
    if losses:
        out.append(
            f"- loss: first {losses[0]:.4f} -> last {losses[-1]:.4f} "
            f"(min {min(losses):.4f})"
        )
        if losses[-1] > losses[0]:
            flags.append("loss ended ABOVE its starting value")
    gn = _col(steps, "grad_norm")
    if gn:
        out.append(f"- grad norm: max {max(gn):.4f}, last {gn[-1]:.4f}")
        p50_gn = _quantile(gn, 0.5)
        if p50_gn and max(gn) > 10 * p50_gn:
            flags.append(
                f"grad-norm spike: max {max(gn):.3g} vs p50 {p50_gn:.3g}"
            )
    nf = [r for r in steps if r.get("nonfinite_grads")]
    if nf:
        flags.append(
            f"{len(nf)} step(s) with NON-FINITE gradients "
            f"(first at step {nf[0].get('step')})"
        )
    else:
        nonf = _col(steps, "nonfinite_grads")
        if nonf:
            out.append("- non-finite grads: none")
    # the first recorded step legitimately pays the first compile; any
    # compiled>0 after it is a shape-driven recompile worth flagging
    recompiles = [r for r in steps[1:] if r.get("compiled")]
    if recompiles:
        flags.append(
            f"{len(recompiles)} RECOMPILE step(s) beyond the first "
            f"(steps {[r.get('step') for r in recompiles][:8]})"
        )
    traces = [r["anomaly_trace"] for r in steps if r.get("anomaly_trace")]
    if traces:
        flags.append(f"anomaly trace captured: `{traces[0]}`")
    flight = _meta(metas, "flight")
    if flight is not None:
        fl = (f"flight record flushed (reason: "
              f"{flight.get('reason', '?')}, "
              f"{len(flight.get('steps') or [])} step(s) of history)")
        fnl = flight.get("first_nonfinite_layer")
        if fnl is not None:
            fl += f"; non-finiteness ORIGINATED at layer {fnl}"
        flags.append(fl)
    if warm:
        p50 = _quantile(warm, 0.5)
        slow = [t for t in warm if p50 and t > 2 * p50]
        if slow:
            flags.append(
                f"{len(slow)} step(s) slower than 2x the p50 step time"
            )
    if flags:
        out.append("\n### Flags\n")
        for fl in flags:
            out.append(f"- [!] {fl}")
    else:
        out.append("- no flags raised")
    out.append("")

    # -- multi-host stragglers ---------------------------------------------
    strag = _meta(metas, "straggler")
    if strag is not None and strag.get("hosts", 1) > 1:
        qty = strag.get("quantity", "step_s")
        out.append(f"## Stragglers (per-host {qty})\n")
        by_host = strag.get("step_s_by_host") or []
        out.append(f"- hosts: {strag['hosts']}")
        out.append(
            f"- slowest host: {strag.get('slowest_host')} "
            f"({max(by_host) * 1e3:.1f} ms vs median "
            f"{_quantile(sorted(by_host), 0.5) * 1e3:.1f} ms)"
        )
        frac = strag.get("straggler_frac", 0.0)
        out.append(
            f"- straggler_frac: {frac:.3f} — the fraction of the slowest "
            "host's time the median host would not have spent (every "
            "SPMD step runs at the slowest host's pace)"
        )
        out.append("")

    # -- serving tier -------------------------------------------------------
    req_recs = [m for m in metas if m.get("kind") == "request"]
    tick_recs = [m for m in metas if m.get("kind") == "tick"]
    if req_recs or tick_recs:
        out.append("## Serving\n")
        by_status = {}
        for r in req_recs:
            s = r.get("status", "?")
            by_status[s] = by_status.get(s, 0) + 1
        if req_recs:
            out.append(f"- requests: {len(req_recs)} (" + ", ".join(
                f"{k} {v}" for k, v in sorted(by_status.items())) + ")")
            ttfts = sorted(
                r["ttft_s"] for r in req_recs
                if isinstance(r.get("ttft_s"), (int, float)))
            if ttfts:
                out.append(
                    f"- TTFT: p50 {_quantile(ttfts, 0.5) * 1e3:.1f} ms, "
                    f"p99 {_quantile(ttfts, 0.99) * 1e3:.1f} ms"
                )
            lats = sorted(
                r["lat_s"] for r in req_recs
                if isinstance(r.get("lat_s"), (int, float))
                and r.get("status") != "shed")
            if lats:
                out.append(
                    f"- latency: p50 {_quantile(lats, 0.5) * 1e3:.1f} ms"
                    f", p99 {_quantile(lats, 0.99) * 1e3:.1f} ms"
                )
        if tick_recs:
            occ = [t["occupancy"] for t in tick_recs
                   if isinstance(t.get("occupancy"), (int, float))]
            if occ:
                out.append(
                    f"- ticks recorded: {len(tick_recs)}, mean "
                    f"occupancy {sum(occ) / len(occ):.2f}"
                )
        out.append(
            "\nFull dashboard (tail attribution, SLO headroom, shed "
            f"audit): `python scripts/serve_report.py "
            f"{source or 'RUN.jsonl'}`\n"
        )

    # -- telemetry registry summary ----------------------------------------
    if summary:
        out.append("## Telemetry registry\n")
        counters = summary.get("counters") or {}
        if counters:
            out.append("counters: " + ", ".join(
                f"{k}={v}" for k, v in sorted(counters.items())
            ) + "\n")
        hists = summary.get("histograms") or {}
        if hists:
            out.append(
                "| histogram | count | mean | p50 | p95 | p99 | max |"
            )
            out.append("|---|---|---|---|---|---|---|")
            for k, h in sorted(hists.items()):
                out.append(
                    f"| {k} | {h.get('count', 0)} | {h.get('mean', 0):.4g} "
                    f"| {h.get('p50', 0):.4g} | {h.get('p95', 0):.4g} "
                    f"| {h.get('p99', h.get('p95', 0)):.4g} "
                    f"| {h.get('max', 0):.4g} |"
                )
            out.append("")
    return "\n".join(out) + "\n"


def check(path: str) -> int:
    counts, errs = schema.validate_file(path)
    for e in errs:
        print(f"{path}: {e}", file=sys.stderr)
    if errs:
        print(
            f"{path}: SCHEMA DRIFT — {len(errs)} error(s) "
            f"({counts['step']} valid step + {counts['meta']} valid meta "
            "records)",
            file=sys.stderr,
        )
        return 1
    if counts["step"] + counts["meta"] == 0:
        print(f"{path}: no records (empty metrics file)", file=sys.stderr)
        return 2
    metas, _, _ = load_run(path)
    warn = schema.version_warning(metas)
    if warn:
        # advisory only: field validation above is the hard gate
        print(f"{path}: warning: {warn}", file=sys.stderr)
    print(
        f"{path}: ok — {counts['step']} step record(s), "
        f"{counts['meta']} meta record(s)"
    )
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("jsonl", help="metrics JSONL from a training run")
    ap.add_argument("-o", "--out", default=None,
                    help="write the markdown report here (default: stdout)")
    ap.add_argument("--check", action="store_true",
                    help="validate the schema instead of rendering; "
                         "exit non-zero on drift")
    args = ap.parse_args(argv)
    if not os.path.exists(args.jsonl):
        print(f"{args.jsonl}: no such file", file=sys.stderr)
        return 2
    if args.check:
        return check(args.jsonl)
    metas, steps, errs = load_run(args.jsonl)
    for e in errs:
        # a truncated final line (crashed writer) is the common case:
        # say so clearly, render what parsed, and exit non-zero below
        print(f"warning: {args.jsonl}: {e}", file=sys.stderr)
    if not metas and not steps:
        print(
            f"{args.jsonl}: no records (empty or fully truncated metrics "
            "file — nothing to report)", file=sys.stderr,
        )
        return 2
    report = render_report(metas, steps, source=args.jsonl)
    if args.out:
        with open(args.out, "w") as f:
            f.write(report)
        print(f"wrote {args.out}")
    else:
        print(report)
    if errs:
        print(
            f"{args.jsonl}: {len(errs)} unparseable line(s) — the report "
            "above covers only the valid records", file=sys.stderr,
        )
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
