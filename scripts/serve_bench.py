#!/usr/bin/env python3
# Copyright 2026 tiny-deepspeed-tpu authors
# SPDX-License-Identifier: Apache-2.0

"""Serving-tier load driver: synthetic Poisson arrivals through the
continuous-batching engine, reporting aggregate tokens/s at p50/p99
per-token latency — the serving headline the ROADMAP asks for.

    # max-pressure (closed-loop) smoke on the CPU backend:
    python scripts/serve_bench.py --model tiny --cpu --requests 16 \
        --max-active 4 --closed-loop

    # open-loop Poisson at 2 req/s, with the serial generate() baseline:
    python scripts/serve_bench.py --model tiny --cpu --rate 2 --serial

    # quantized KV blocks:
    python scripts/serve_bench.py --model tiny --cpu --kv-quant int8

    # goodput under faults: slot-poison + tick-delay chaos, A/B'd
    # against the same trace fault-free (--chaos runs both passes):
    python scripts/serve_bench.py --model tiny --cpu --requests 12 \
        --closed-loop --chaos "nan@6,nan@7,delay@10" --deadline 30

Prints a human summary plus ONE machine-readable JSON line.

Every run writes a telemetry JSONL SIDECAR (default
artifacts/serve_run.jsonl; --jsonl PATH moves it, --jsonl none disables):
a run_meta record carrying the serve config,
per-tick `tick` records, per-request `request` records with lifecycle
events + latency components, flight records on faults, and the
telemetry summary — so every bench run replays in the dashboard
(`scripts/serve_report.py`, `scripts/report_run.py`) and the trace
viewer (`scripts/trace_view.py` -> Perfetto slot/queue tracks).  With
--chaos the faulted pass writes its OWN sidecar next to the clean one
(<path>.chaos.jsonl) with its own telemetry registry, so the A/B is two
replayable files, and the JSON summary carries both passes plus the
terminal-status counts (ok/shed/expired/failed) and p99 TTFT with and
without faults."""

import argparse
import dataclasses
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def serve(argv=None, cfg_overrides=None) -> dict:
    """Run the load driver; returns the JSON summary it prints plus
    `outputs` (each request's tokens, in submission order) and, with
    --serial, `serial_outputs` (the same trace through generate()).
    `cfg_overrides` (model config fields) is for programmatic callers
    that need the preset at another precision (chip_smoke.py)."""
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--model", default="tiny")
    p.add_argument("--cpu", action="store_true",
                   help="run on the CPU backend (without it the TPU is "
                        "required)")
    p.add_argument("--requests", type=int, default=16)
    p.add_argument("--rate", type=float, default=None, metavar="RPS",
                   help="Poisson arrival rate (default: closed loop — "
                        "all requests arrive at t=0)")
    p.add_argument("--closed-loop", action="store_true",
                   help="ignore arrival times; keep the engine saturated")
    p.add_argument("--prompt-lens", default="8,16,32",
                   help="comma list the trace samples prompts from")
    p.add_argument("--max-new-tokens", type=int, default=32)
    p.add_argument("--max-active", type=int, default=4)
    p.add_argument("--num-blocks", type=int, default=64)
    p.add_argument("--block-tokens", type=int, default=16)
    p.add_argument("--max-seq-tokens", type=int, default=0,
                   help="per-request length ceiling sizing the compiled "
                        "decode panel (0 = auto: max prompt + max new, "
                        "rounded to a block)")
    p.add_argument("--kv-quant", default=None, choices=("int8", "fp8"))
    p.add_argument("--temperature", type=float, default=0.0)
    p.add_argument("--top-k", type=int, default=None)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--deadline", type=float, default=None, metavar="S",
                   help="per-request completion SLO in seconds; the "
                        "engine sheds unmeetable queued requests and "
                        "expires active ones that blow it")
    p.add_argument("--max-queue", type=int, default=None,
                   help="admission watermark: submissions beyond this "
                        "queue depth are shed at the door")
    p.add_argument("--shed-pool-util", type=float, default=None,
                   help="pool-pressure watermark in [0,1]: shed "
                        "submissions while the paged pool is this full "
                        "with a backlog")
    p.add_argument("--chaos", default=None, metavar="SPEC",
                   help="tick-fault spec, e.g. 'nan@6,delay@10,nan%%0.02'"
                        " (kinds: nan, delay, prefill, journal_kill); "
                        "runs the SAME trace fault-free first and "
                        "reports the goodput A/B")
    p.add_argument("--chaos-delay-s", type=float, default=0.25,
                   help="tick-delay fault duration")
    p.add_argument("--journal", default=None, metavar="PATH",
                   help="append the crash-recovery request journal here "
                        "(fleet mode derives per-replica paths "
                        "PATH.rN from it; default for --replicas: "
                        "artifacts/fleet_journal.jsonl)")
    p.add_argument("--replicas", type=int, default=1, metavar="N",
                   help="fleet mode: N engine replicas behind the "
                        "SLO-aware FleetRouter (fleet/router.py); each "
                        "replica gets its own journal so a chaos "
                        "engine_kill@T (which kills replica 0) fails "
                        "over onto a sibling mid-trace")
    p.add_argument("--disagg", action="store_true",
                   help="disaggregated mode: prefill and decode on "
                        "separate engines with priced paged-KV "
                        "migration between their pools "
                        "(fleet/disagg.py); per-request migration "
                        "bytes/link land on the request records")
    p.add_argument("--spec-draft", default=None, metavar="DRAFTER",
                   help="speculative decoding drafter: 'ngram' "
                        "(model-free prompt lookup), 'model:self', or "
                        "'model:<preset>' (serving/drafter.py); greedy "
                        "output stays token-exact, committed tokens/s "
                        "is the number to compare")
    p.add_argument("--spec-k", type=int, default=4,
                   help="draft span width: up to this many tokens "
                        "proposed+verified per slot per tick")
    p.add_argument("--prefix-cache", action="store_true",
                   help="shared-prefix KV reuse: radix tree over the "
                        "refcounted pool — matched prompt blocks alias "
                        "copy-on-write, only the suffix prefills "
                        "(serving/prefix.py)")
    p.add_argument("--prefix-pool", type=int, default=0, metavar="P",
                   help="shared-prefix TRACE: draw each prompt's "
                        "leading --prefix-len tokens from P distinct "
                        "system prompts, Zipf-weighted (0 = plain "
                        "uniform trace)")
    p.add_argument("--prefix-len", type=int, default=32,
                   help="system-prompt length for --prefix-pool traces")
    p.add_argument("--zipf-a", type=float, default=1.2,
                   help="Zipf exponent over the --prefix-pool prompts")
    p.add_argument("--tenants", default=None, metavar="SPEC",
                   help="multi-tenant mode: comma list of "
                        "name[:weight[:tokens_per_tick[:max_queue]]] "
                        "policies (serving/tenancy.py); arrivals are "
                        "tagged by weight-proportional draw and "
                        "admission turns weighted-fair")
    p.add_argument("--tenant-weights", default=None, metavar="W",
                   help="override the ARRIVAL mix only: comma weights "
                        "aligned with --tenants order (default: the "
                        "tenants' scheduling weights)")
    p.add_argument("--slo", default=None, metavar="SPEC",
                   help="SLO objective 'target=0.99,ttft=0.5,latency=5' "
                        "(telemetry/slo.py grammar; keys optional): "
                        "terminal requests feed multi-window error-"
                        "budget burn accounting, the summary gains the "
                        "budget snapshot, and a fast-burn alert flushes "
                        "the flight recorder")
    p.add_argument("--live-port", type=int, default=None, metavar="PORT",
                   help="start the live observability exporter on this "
                        "port (0 = OS-assigned; printed to stderr): "
                        "/metrics Prometheus text, /healthz per-replica "
                        "state, /slo budget JSON — host-side only, "
                        "scrape while the bench runs")
    p.add_argument("--serial", action="store_true",
                   help="also run the one-at-a-time generate() baseline "
                        "on the same trace and report the ratio")
    p.add_argument("--jsonl", default=None, metavar="PATH",
                   help="telemetry JSONL sidecar (run_meta + tick + "
                        "request records + flight/telemetry summary; "
                        "default: artifacts/serve_run.jsonl beside the "
                        "repo, 'none' disables)")
    args = p.parse_args(argv)

    jsonl_path = args.jsonl
    if jsonl_path is None:
        jsonl_path = os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "..",
            "artifacts", "serve_run.jsonl")
    elif jsonl_path.lower() == "none":
        jsonl_path = None

    import jax

    from tiny_deepspeed_tpu.utils.startup import select_platform
    select_platform(cpu=args.cpu, cpu_flag="--cpu")

    from tiny_deepspeed_tpu.models import ALL_PRESETS, build_model
    from tiny_deepspeed_tpu.serving import ServeConfig, ServingEngine
    from tiny_deepspeed_tpu.serving.driver import poisson_trace, run_trace
    from tiny_deepspeed_tpu.telemetry import Telemetry

    cfg = dataclasses.replace(ALL_PRESETS[args.model],
                              **(cfg_overrides or {}))
    model = build_model(cfg)
    # one compiled program, not one dispatch per initializer op
    params = jax.jit(model.init)(jax.random.PRNGKey(args.seed))
    prompt_lens = [int(x) for x in args.prompt_lens.split(",")]

    tenants = None
    tenant_mix = None
    if args.tenants:
        from tiny_deepspeed_tpu.serving import parse_tenant_spec
        tenants = parse_tenant_spec(args.tenants)
        tenant_mix = {n: pol.weight for n, pol in tenants.items()}
        if args.tenant_weights:
            ws = [float(x) for x in args.tenant_weights.split(",")]
            names = [e.split(":")[0] for e in args.tenants.split(",")
                     if e.strip()]
            if len(ws) != len(names):
                p.error("--tenant-weights must match --tenants count")
            tenant_mix = dict(zip(names, ws))

    if args.prefix_pool:
        from tiny_deepspeed_tpu.serving.driver import shared_prefix_trace
        suffix_lens = [max(1, pl - args.prefix_len)
                       for pl in prompt_lens]
        trace = shared_prefix_trace(
            args.requests, rate_rps=args.rate,
            prefix_pool=args.prefix_pool, prefix_len=args.prefix_len,
            suffix_lens=suffix_lens, zipf_a=args.zipf_a,
            max_new_tokens=args.max_new_tokens,
            vocab_size=cfg.vocab_size, seed=args.seed,
            deadline_s=args.deadline, tenants=tenant_mix,
        )
        prompt_lens = sorted({args.prefix_len + s for s in suffix_lens})
    else:
        trace = poisson_trace(
            args.requests, rate_rps=args.rate,
            prompt_lens=prompt_lens,
            max_new_tokens=args.max_new_tokens,
            vocab_size=cfg.vocab_size,
            seed=args.seed, deadline_s=args.deadline,
        )
        if tenant_mix:
            import numpy as _np
            trng = _np.random.default_rng(args.seed + 1)
            names = sorted(tenant_mix)
            tw = _np.asarray([tenant_mix[n] for n in names])
            tw = tw / tw.sum()
            trace = [a._replace(tenant=str(trng.choice(names, p=tw)))
                     for a in trace]

    tel = Telemetry()

    bt = args.block_tokens
    max_seq = args.max_seq_tokens or min(
        cfg.block_size,
        -(-(max(prompt_lens) + args.max_new_tokens) // bt) * bt,
    )

    serve_cfg = ServeConfig(
        max_active=args.max_active, num_blocks=args.num_blocks,
        block_tokens=bt, quant=args.kv_quant,
        temperature=args.temperature, top_k=args.top_k,
        seed=args.seed, max_seq_tokens=max_seq,
        max_queue=args.max_queue, shed_pool_util=args.shed_pool_util,
        spec_draft=args.spec_draft, spec_k=args.spec_k,
        prefix_cache=args.prefix_cache, tenants=tenants,
    )
    realtime = not args.closed_loop and args.rate is not None

    def make_logger(path):
        """Sidecar writer: run_meta first (schema stamp + the serve
        geometry trace_view.py lays slot tracks out from), the engine
        streams tick/request/flight records behind it."""
        if not path:
            return None
        from tiny_deepspeed_tpu.telemetry.schema import SCHEMA_VERSION
        from tiny_deepspeed_tpu.utils.profiling import MetricsLogger
        if os.path.exists(path):
            os.remove(path)
        lg = MetricsLogger(path, stdout=False)
        lg.log_meta(schema_version=SCHEMA_VERSION,
                    engine=f"serve:{args.model}",
                    model=args.model, devices=jax.device_count(),
                    serve=dict(
                        max_active=args.max_active,
                        num_blocks=args.num_blocks, block_tokens=bt,
                        max_seq_tokens=max_seq,
                        quant=args.kv_quant or "off",
                        spec_draft=args.spec_draft or "off",
                        spec_k=args.spec_k,
                        replicas=args.replicas,
                        disagg=bool(args.disagg),
                        prefix_cache=bool(args.prefix_cache),
                        tenants={n: {"weight": pol.weight,
                                     "tokens_per_tick":
                                         pol.tokens_per_tick,
                                     "max_queue": pol.max_queue}
                                 for n, pol in (tenants or {}).items()},
                    ))
        return lg

    # CLI validation BEFORE the sidecar writer truncates anything: an
    # invalid invocation must not destroy the previous run's records
    if args.replicas < 1:
        p.error("--replicas must be >= 1")
    if args.disagg and args.replicas > 1:
        p.error("--disagg and --replicas are separate modes (a fleet "
                "of disagg pairs is not wired yet)")
    if args.disagg and args.chaos:
        p.error("--chaos targets a single engine or fleet replica 0; "
                "not supported with --disagg")
    if args.disagg and args.spec_draft:
        p.error("--disagg does not compose with --spec-draft (drafter "
                "state only rebuilds through the prefill admission "
                "path)")
    if args.prefix_cache and args.spec_draft:
        p.error("--prefix-cache does not compose with --spec-draft "
                "(the suffix prefill and the draft span both own the "
                "span program)")
    if (args.chaos and "journal_kill" in args.chaos
            and not args.journal and args.replicas == 1):
        p.error("--chaos journal_kill@N needs --journal PATH (the kill "
                "fires inside the journal's commit, and recovery "
                "replays it); fleet mode auto-assigns journals")
    slo_obj = None
    if args.slo:
        from tiny_deepspeed_tpu.telemetry.slo import SLOObjective
        try:
            slo_obj = SLOObjective.parse(args.slo)
        except ValueError as e:
            p.error(f"--slo: {e}")

    logger = make_logger(jsonl_path)

    # the live plane attaches to the MEASURED pass only (warm requests
    # pollute neither the aggregator nor the SLO budget, same contract
    # as telemetry/logger); the exporter is a loopback daemon thread —
    # strictly host-side, so serving HLO and tick cadence are untouched
    slo_tracker = None
    live_agg = None
    exporter = None
    if args.slo or args.live_port is not None:
        from tiny_deepspeed_tpu.telemetry.slo import SLOTracker
        from tiny_deepspeed_tpu.telemetry.slo import SLOObjective as _Obj
        slo_tracker = SLOTracker(default=slo_obj or _Obj())
    if args.live_port is not None:
        from tiny_deepspeed_tpu.telemetry.live import (
            LiveAggregator, LiveExporter,
        )
        live_agg = LiveAggregator()
        exporter = LiveExporter(live_agg, slo=slo_tracker,
                                port=args.live_port)
        port = exporter.start()
        print(f"live exporter -> http://127.0.0.1:{port}/metrics "
              "(also /healthz, /slo)", file=sys.stderr)

    # warm run on the SAME engine (each engine owns fresh jit closures,
    # so warming a throwaway one buys nothing): one request per DISTINCT
    # prompt length covers every power-of-two prefill bucket, closed-loop
    # covers the decode step — the measured pass then reports serving
    # throughput, not XLA compile time.  Telemetry/logger/journal attach
    # after, so warm requests pollute neither counters, the JSONL, nor
    # the crash-recovery write-ahead log.
    from tiny_deepspeed_tpu.serving import RequestJournal
    from tiny_deepspeed_tpu.serving.driver import Arrival

    warm_trace = [
        Arrival(0.0, [0] * plen, min(2, args.max_new_tokens))
        for plen in sorted(set(prompt_lens))
    ]
    if args.prefix_cache:
        # a SECOND identical-prompt request per length hits the tree
        # and compiles the suffix-prefill bucket — without it the
        # measured pass pays that XLA compile on its first cache hit
        warm_trace = [a for a in warm_trace for _ in range(2)]

    def warmed_engine(journal_path=None, replica_id=None):
        e = ServingEngine(model, params, serve_cfg,
                          replica_id=replica_id)
        run_trace(e, warm_trace, realtime=False)
        if e._prefix is not None:
            # warm requests compiled the suffix program (and may sit
            # warm in the tree), but the measured pass's hit-rate
            # stats must price the TRACE only
            e._prefix.reset_stats()
        if journal_path:
            e.journal = RequestJournal(journal_path)
        return e

    def replica_journal(i, tag=""):
        base = args.journal or os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "..",
            "artifacts", "fleet_journal.jsonl")
        root, ext = os.path.splitext(base)
        path = f"{root}{tag}.r{i}{ext or '.jsonl'}"
        # per-run scratch, like the sidecar: journals open in APPEND
        # mode (recovery continues one file), so a stale file from the
        # previous invocation would resurrect ITS interrupted requests
        # at this run's first failover — and pin this run to its
        # geometry stamp
        if os.path.exists(path):
            os.remove(path)
        return path

    def build_target(telemetry, logger, chaos=None, tag=""):
        """The measured object for this pass: a single engine, a fleet
        router over N warmed replicas, or a disaggregated pair —
        telemetry/logger attached AFTER warm in every mode, so warm
        requests pollute neither counters nor the sidecar."""
        if args.disagg:
            from tiny_deepspeed_tpu.fleet import DisaggEngine
            dis = DisaggEngine(model, params, serve_cfg)
            run_trace(dis, warm_trace, realtime=False)
            # warm requests migrated too — zero the counters so the
            # summary prices the MEASURED trace only (their records
            # never reached the sidecar: logger AND journal attach
            # after warm, same as the other modes — warm requests
            # must not enter the crash-recovery WAL either)
            dis.migrations = 0
            dis.migrated_bytes = 0
            dis.bytes_by_link = {}
            j = RequestJournal(args.journal) if args.journal else None
            for e in (dis.prefill, dis.decode):
                e.telemetry, e.logger = telemetry, logger
                e.journal = j  # shared WAL (geometry stamped per attach)
            dis.telemetry = telemetry
            return dis
        if args.replicas > 1:
            from tiny_deepspeed_tpu.fleet import FleetRouter
            from tiny_deepspeed_tpu.resilience import ChaosServingEngine
            engines = []
            for i in range(args.replicas):
                e = warmed_engine(replica_journal(i, tag), replica_id=i)
                e.telemetry, e.logger = telemetry, logger
                engines.append(e)
            if chaos is not None:
                # chaos faults target replica 0 — an engine_kill there
                # exercises the failover path while siblings keep
                # serving
                engines[0] = ChaosServingEngine(engines[0], chaos)
            # parallel ticks: replicas are independent engines and XLA
            # releases the GIL mid-program — on a multi-core host this
            # is where replica-count scaling comes from
            return FleetRouter(engines, telemetry=telemetry,
                               logger=logger, parallel=True)
        e = warmed_engine(args.journal)
        e.telemetry, e.logger = telemetry, logger
        return e

    eng = build_target(tel, logger)
    res = run_trace(eng, trace, realtime=realtime,
                    slo=slo_tracker, live=live_agg)
    # returned, not printed (ids are global across engines: submission
    # order is the stable key)
    returned = {"outputs": list(res.pop("outputs").values())}
    res.pop("requests")
    if slo_tracker is not None and logger is not None:
        # final budget snapshot as an `slo` record: the engine only
        # emits one when an alert fires, but serve_report's "SLO
        # budgets" section needs the end-of-run state on clean runs too
        slo_tracker.record(logger)

    summary = {
        "model": args.model,
        "requests": args.requests,
        "rate_rps": args.rate,
        "max_active": args.max_active,
        "kv_quant": args.kv_quant,
        "deadline_s": args.deadline,
        "tokens_per_s": res["tokens_per_s"],
        "ok_tokens_per_s": res["ok_tokens_per_s"],
        "status_counts": res["status_counts"],
        "restarts": res["restarts"],
        "token_latency": res["token_latency"],
        "ttft": res["ttft"],
        "latency_components_s": res["latency_components_s"],
        "mean_occupancy": res["mean_occupancy"],
        "mean_pool_utilization": res["mean_pool_utilization"],
        "evictions": res["evictions"],
        "preemptions": res["preemptions"],
        "pool": eng.pool.kv_bytes(),
    }
    if "spec" in res:
        summary["spec"] = dict(res["spec"], drafter=args.spec_draft,
                               k=args.spec_k)
    if args.replicas > 1:
        summary["fleet"] = {
            "replicas": args.replicas,
            "replicas_live": len(eng._live()),
            "failovers": eng.failovers,
            "dispatch": {str(k): v
                         for k, v in eng.dispatch_counts().items()},
        }
    if args.disagg:
        summary["disagg"] = eng.migration_summary()
    if "prefix_cache" in res:
        summary["prefix_cache"] = res["prefix_cache"]
    if "tenants" in res:
        summary["tenants"] = res["tenants"]
    if "slo" in res:
        # the budget snapshot rides the machine-readable line:
        # slo.attainment is the higher-is-better key perf_diff.py's
        # sentinel watches
        summary["slo"] = res["slo"]

    if args.chaos:
        # goodput under faults, A/B on the SAME trace: the clean pass
        # above is the baseline; this pass injects the tick faults
        from tiny_deepspeed_tpu.resilience import (
            ChaosServingEngine, parse_serving_chaos,
        )
        from tiny_deepspeed_tpu.serving import ServingKilled
        chaos = parse_serving_chaos(args.chaos, seed=args.seed,
                                    delay_s=args.chaos_delay_s)
        # the faulted pass gets its OWN sidecar + telemetry registry:
        # two replayable files (clean vs chaos) make the A/B a pair of
        # serve_report.py dashboards instead of one entangled stream
        chaos_jsonl = None
        if jsonl_path:
            root, ext = os.path.splitext(jsonl_path)
            chaos_jsonl = f"{root}.chaos{ext or '.jsonl'}"
        tel2 = Telemetry()
        logger2 = make_logger(chaos_jsonl)
        if args.replicas > 1:
            # fleet: the router ITSELF absorbs replica death (incl.
            # engine_kill / journal_kill on replica 0) by journal-replay
            # failover — the A/B shows the goodput cost of losing and
            # recovering a whole engine mid-trace
            ceng = build_target(tel2, logger2, chaos=chaos,
                                tag=".chaos")
        else:
            ceng = ChaosServingEngine(build_target(tel2, logger2),
                                      chaos)
        try:
            cres = run_trace(ceng, trace, realtime=realtime)
        except ServingKilled:
            # In fleet mode the router absorbs replica deaths by
            # failover; a ServingKilled escaping run_trace means the
            # LAST live replica died — total fleet loss is a real
            # outcome, and a FleetRouter has no recover() to pretend
            # otherwise with
            if args.replicas > 1:
                raise
            # the journal_kill fault "killed" the engine mid-commit;
            # demonstrate the recovery recipe end-to-end: a fresh
            # engine replays the journal and finishes the in-flight
            # requests (arrivals not yet submitted died with the
            # process, exactly as a real crash loses them)
            reng = build_target(tel2, logger2)
            rec = reng.recover()
            reng.drain()
            cres = None
            summary["chaos"] = {
                "spec": args.chaos,
                "journal_killed": True,
                "recovered": len(rec),
                "recovered_ok": sum(1 for r in rec
                                    if r.status == "ok"),
            }
        n_faults = len(chaos.injected)
        if logger2 is not None:
            chaos.log_faults(logger2)
            tel2.flush(logger2)
            logger2.close()
            print(f"chaos-pass records -> {chaos_jsonl}",
                  file=sys.stderr)
        if cres is not None:
            summary["chaos"] = {
                "spec": args.chaos,
                "faults_injected": n_faults,
                "tokens_per_s": cres["tokens_per_s"],
                "ok_tokens_per_s": cres["ok_tokens_per_s"],
                "status_counts": cres["status_counts"],
                "restarts": cres["restarts"],
                "ttft_p99_ms": cres["ttft"]["p99_ms"],
                "ttft_p99_ms_clean": res["ttft"]["p99_ms"],
                "goodput_frac": round(
                    cres["ok_tokens_per_s"]
                    / max(res["ok_tokens_per_s"], 1e-9), 3),
            }
            if args.replicas > 1:
                summary["chaos"]["failovers"] = ceng.failovers
                summary["chaos"]["replicas_live"] = len(ceng._live())
    if args.serial:
        from tiny_deepspeed_tpu.serving.driver import run_serial
        ser = run_serial(model, params, trace,
                         temperature=args.temperature, top_k=args.top_k)
        returned["serial_outputs"] = ser["outputs"]
        summary["serial_tokens_per_s"] = ser["tokens_per_s"]
        summary["vs_serial"] = round(
            res["tokens_per_s"] / max(ser["tokens_per_s"], 1e-9), 3)

    sc = res["status_counts"]
    print(f"served {args.requests} requests, {res['tokens']} tokens in "
          f"{res['wall_s']}s -> {res['tokens_per_s']} tok/s "
          f"(occupancy {res['mean_occupancy']:.2f}, "
          f"p50 {res['token_latency']['p50_ms']}ms / "
          f"p99 {res['token_latency']['p99_ms']}ms per token)")
    print(f"outcomes: ok {sc['ok']} / shed {sc['shed']} / "
          f"expired {sc['expired']} / failed {sc['failed']} "
          f"(goodput {res['ok_tokens_per_s']} tok/s)")
    if args.replicas > 1:
        fl = summary["fleet"]
        print(f"fleet: {fl['replicas_live']}/{fl['replicas']} replicas "
              f"live, dispatch {fl['dispatch']}, "
              f"failovers {fl['failovers']}")
    if args.disagg:
        dg = summary["disagg"]
        print(f"disagg: {dg['migrations']} prefill->decode migrations, "
              f"{dg['migrated_bytes'] / 1024:.1f} KiB KV moved "
              f"({dg['bytes_by_link']})")
    if "spec" in summary:
        sp = summary["spec"]
        print(f"speculation [{sp['drafter']} k={sp['k']}]: "
              f"accept rate {sp['accept_rate']} "
              f"({sp['accepted']}/{sp['proposed']} drafts)")
    if "prefix_cache" in summary:
        pc = summary["prefix_cache"]
        print(f"prefix cache: hit rate {pc['hit_rate']} "
              f"({pc['blocks_aliased']} blocks aliased, "
              f"{pc['prefill_tokens_avoided']} prefill tokens avoided, "
              f"{pc['cached_blocks']} warm, "
              f"{pc['tree_evictions']} tree evictions)")
    if "tenants" in summary:
        for name, td in sorted(summary["tenants"].items()):
            sc_t = td["status_counts"]
            bu = td.get("scheduler", {}).get("budget_utilization")
            print(f"tenant {name}: {td['requests']} req "
                  f"(ok {sc_t['ok']} / shed {sc_t['shed']} / expired "
                  f"{sc_t['expired']}), goodput "
                  f"{td['ok_tokens_per_s']} tok/s, p99 TTFT "
                  f"{td['ttft']['p99_ms']}ms"
                  + (f", budget util {bu}" if bu is not None else ""))
    if args.chaos:
        ch = summary["chaos"]
        if ch.get("journal_killed"):
            print(f"chaos [{ch['spec']}]: engine killed between "
                  f"journal append and commit; recovered "
                  f"{ch['recovered']} in-flight request(s) from "
                  f"{args.journal} -> {ch['recovered_ok']} ok")
        else:
            cc = ch["status_counts"]
            fo = (f", {ch['failovers']} failover(s) "
                  f"({ch['replicas_live']}/{args.replicas} replicas "
                  "left)" if "failovers" in ch else "")
            print(f"chaos [{ch['spec']}]: {ch['faults_injected']} "
                  f"faults, {ch['restarts']} restarts{fo} -> ok "
                  f"{cc['ok']} "
                  f"/ shed {cc['shed']} / expired {cc['expired']} / "
                  f"failed {cc['failed']}; goodput "
                  f"{ch['ok_tokens_per_s']} tok/s "
                  f"({ch['goodput_frac']}x clean), p99 TTFT "
                  f"{ch['ttft_p99_ms']}ms vs {ch['ttft_p99_ms_clean']}"
                  "ms clean")
    if args.serial:
        print(f"serial generate() baseline: "
              f"{summary['serial_tokens_per_s']} tok/s -> "
              f"{summary['vs_serial']}x")
    if "slo" in summary:
        sl = summary["slo"]
        print(f"slo: attainment {sl['attainment']}, "
              f"{len(sl['alerts'])} alert(s) "
              f"(windows {sl['windows_s']}s)")
    if exporter is not None:
        agg_snap = live_agg.snapshot()
        print(f"live exporter served {live_agg.scrapes} scrape(s), "
              f"aggregated {sum(agg_snap['ticks'].values())} tick "
              f"snapshot(s) across {len(agg_snap['ticks'])} replica "
              "stream(s)", file=sys.stderr)
        exporter.stop()
    print(json.dumps(summary))

    if logger is not None:
        tel.flush(logger)
        logger.close()
        print(
            f"sidecar -> {jsonl_path}  (dashboard: python "
            f"scripts/serve_report.py {jsonl_path}; timeline: python "
            f"scripts/trace_view.py {jsonl_path})", file=sys.stderr,
        )
    return {**summary, **returned}


def main(argv=None) -> int:
    serve(argv)
    return 0


if __name__ == "__main__":
    sys.exit(main())
