# Copyright 2026 tiny-deepspeed-tpu authors
# SPDX-License-Identifier: Apache-2.0

"""Synthetic serving load: Poisson arrivals through the engine, and the
serial `generate()` baseline the continuous-batching numbers are judged
against.  Shared by `scripts/serve_bench.py` and tests/test_serving.py
so the two never measure different things.
"""

from __future__ import annotations

import time
from typing import List, NamedTuple, Optional, Sequence

import numpy as np


class Arrival(NamedTuple):
    """One trace entry: when (seconds from trace start; 0.0 everywhere
    = closed-loop max-pressure mode), what prompt, how many tokens —
    plus an optional per-request completion deadline (seconds from
    submission; the engine's SLO machinery sheds/expires around it)
    and an optional tenant tag (multi-tenant scheduling)."""

    at_s: float
    prompt: List[int]
    max_new_tokens: int
    deadline_s: Optional[float] = None
    tenant: Optional[str] = None


def poisson_trace(n_requests: int, *, rate_rps: Optional[float],
                  prompt_lens: Sequence[int], max_new_tokens: int,
                  vocab_size: int, seed: int = 0,
                  deadline_s: Optional[float] = None) -> List[Arrival]:
    """Exponential inter-arrivals at `rate_rps` (None = all at t=0),
    prompts drawn uniformly from `prompt_lens` / the vocab.  Seeded —
    the same trace replays against every engine configuration.
    `deadline_s` stamps every arrival with the same completion SLO."""
    rng = np.random.default_rng(seed)
    t = 0.0
    trace = []
    for _ in range(n_requests):
        if rate_rps is not None:
            t += float(rng.exponential(1.0 / rate_rps))
        plen = int(rng.choice(np.asarray(prompt_lens)))
        prompt = rng.integers(0, vocab_size, size=plen).tolist()
        trace.append(Arrival(t, prompt, max_new_tokens, deadline_s))
    return trace


def shared_prefix_trace(n_requests: int, *,
                        rate_rps: Optional[float],
                        prefix_pool: int, prefix_len: int,
                        suffix_lens: Sequence[int],
                        max_new_tokens: int, vocab_size: int,
                        zipf_a: float = 1.2, seed: int = 0,
                        deadline_s: Optional[float] = None,
                        tenants: Optional[dict] = None) -> List[Arrival]:
    """The millions-of-users workload shape: `prefix_pool` distinct
    system prompts of `prefix_len` tokens, each arrival picking one
    Zipf-weighted (a few prompts dominate, a long tail exists — the
    regime prefix caching exists for) and appending a random suffix
    drawn from `suffix_lens`.  `tenants` maps tenant name -> arrival
    weight; each arrival is tagged with a tenant drawn from the
    normalized weights (None = untagged traffic).  Seeded — the same
    trace replays against every engine configuration, which is what
    makes the cache-on/off A/B one workload."""
    if prefix_pool < 1 or prefix_len < 1:
        raise ValueError("prefix_pool and prefix_len must be >= 1")
    rng = np.random.default_rng(seed)
    prefixes = [rng.integers(0, vocab_size, size=prefix_len).tolist()
                for _ in range(prefix_pool)]
    # Zipf over the prefix pool: rank r with weight 1/(r+1)^a
    w = 1.0 / np.arange(1, prefix_pool + 1, dtype=np.float64) ** zipf_a
    w /= w.sum()
    names, tw = None, None
    if tenants:
        names = sorted(tenants)
        tw = np.asarray([float(tenants[n]) for n in names])
        tw = tw / tw.sum()
    t = 0.0
    trace = []
    for _ in range(n_requests):
        if rate_rps is not None:
            t += float(rng.exponential(1.0 / rate_rps))
        i = int(rng.choice(prefix_pool, p=w))
        slen = int(rng.choice(np.asarray(suffix_lens)))
        prompt = prefixes[i] + rng.integers(
            0, vocab_size, size=slen).tolist()
        tenant = (str(rng.choice(names, p=tw))
                  if names is not None else None)
        trace.append(Arrival(t, prompt, max_new_tokens, deadline_s,
                             tenant))
    return trace


def _latency_stats(lats: List[float]) -> dict:
    if not lats:
        return {"p50_ms": 0.0, "p99_ms": 0.0, "mean_ms": 0.0}
    a = np.asarray(lats) * 1e3
    return {
        "p50_ms": round(float(np.percentile(a, 50)), 3),
        "p99_ms": round(float(np.percentile(a, 99)), 3),
        "mean_ms": round(float(a.mean()), 3),
    }


def run_trace(engine, trace: Sequence[Arrival], *,
              realtime: bool = True, max_ticks: int = 200_000,
              no_progress_ticks: int = 2_000,
              slo=None, live=None) -> dict:
    """Drive `engine` (serving.ServingEngine or a ChaosServingEngine
    wrapper) through the trace.

    realtime=True honors arrival times with wall-clock waits (what the
    latency percentiles mean under open-loop load); realtime=False
    submits each arrival as soon as the engine drains ahead of it
    (closed-loop — tests use it to avoid sleeping).  Returns outputs
    per request plus aggregate metrics; per-token latency covers every
    produced token (first token = TTFT).  `status_counts` and
    `ok_tokens_per_s` (goodput: tokens of requests that finished "ok")
    summarize the terminal outcomes under faults/SLOs.

    `no_progress_ticks` bounds LIVELOCK, which `max_ticks` alone cannot:
    an engine that can never admit its queue (e.g. every prompt refused
    after the pool shrank) ticks forever producing nothing.  After that
    many CONSECUTIVE zero-token ticks with work still pending, raise
    with the queue/pool state named instead of spinning to max_ticks."""
    if slo is not None:
        # SLO error budgets (telemetry/slo.py): attached through the
        # engine's own hook so fleet/disagg/chaos wrappers fan the
        # tracker out to every underlying engine
        engine.attach_slo(slo)
    if live is not None:
        engine.attach_live(live)
    requests = []
    pending = list(trace)
    occupancy = []
    pool_util = []
    t0 = time.monotonic()
    ticks = 0
    idle_ticks = 0
    while pending or engine.queue_depth or engine.n_active:
        now = time.monotonic() - t0
        while pending and (not realtime or pending[0].at_s <= now):
            if not realtime:
                # closed-loop feed target: enough queued to fill every
                # free slot next tick (a one-per-spin feed starves a
                # multi-slot fleet's occupancy), capped at the engine's
                # own queue watermark and checked BEFORE submitting —
                # pushing the queue TO the watermark and then feeding
                # into it would shed arrivals that the engine could
                # serve one tick later, turning max-pressure mode into
                # a shed artifact whenever max_queue < max_active
                free = engine.config.max_active - engine.n_active
                target = max(1, free)
                cap = getattr(engine.config, "max_queue", None)
                if cap is not None:
                    target = max(1, min(target, cap))
                if engine.queue_depth >= target:
                    break
            a = pending.pop(0)
            req = engine.submit(
                a.prompt, a.max_new_tokens, deadline_s=a.deadline_s,
                tenant=a.tenant)
            requests.append(req)
            if not realtime and req.status is not None:
                # a TENANT-scoped door shed refuses one tenant, not the
                # engine — other tenants' arrivals must keep feeding or
                # the abuser's sheds would inflate the well-behaved
                # tenants' measured TTFT (the isolation A/B's number)
                if str(req.finish_reason or "").endswith(
                        "tenant_queue_watermark"):
                    continue
                break  # engine-level watermark: it is refusing load
        if (realtime and not engine.queue_depth and not engine.n_active
                and pending):
            # open-loop idle: nothing in flight, next arrival is in the
            # future — wait for it instead of spinning
            time.sleep(max(0.0, pending[0].at_s - (
                time.monotonic() - t0)))
            continue
        if engine.queue_depth or engine.n_active:
            produced = engine.tick()
            occupancy.append(engine.n_active / engine.config.max_active)
            pool_util.append(
                engine.pool.blocks_in_use / engine.pool.num_usable)
            idle_ticks = 0 if produced else idle_ticks + 1
            if idle_ticks >= no_progress_ticks:
                raise RuntimeError(
                    f"engine made no progress for {idle_ticks} "
                    f"consecutive ticks: queue_depth="
                    f"{engine.queue_depth}, active={engine.n_active}, "
                    f"pool blocks_free={engine.pool.blocks_free}/"
                    f"{engine.pool.num_usable} — every queued request "
                    "is unadmittable (pool too small for its prompt, "
                    "or blocks leaked)"
                )
        ticks += 1
        if ticks > max_ticks:
            raise RuntimeError(f"trace did not drain in {max_ticks} ticks")
    wall = time.monotonic() - t0
    toks = sum(len(r.tokens) for r in requests)
    lats = [lat for r in requests for lat in r.token_lat]
    status_counts = {"ok": 0, "shed": 0, "expired": 0, "failed": 0}
    for r in requests:
        status_counts[r.status] = status_counts.get(r.status, 0) + 1
    ok_toks = sum(len(r.tokens) for r in requests if r.status == "ok")
    # aggregate latency attribution (the per-request partition summed
    # across the trace): where the trace's total request-seconds went —
    # the bench-JSON view of what serve_report.py breaks down per tail
    comp_totals = {
        k: round(sum(r.lat_components[k] for r in requests), 4)
        for k in ("queue", "prefill", "decode", "preempt", "restart",
                  "migrate")
    }
    # per-tenant aggregates (absent on untagged traffic): goodput,
    # p99 TTFT / end-to-end latency, and terminal outcomes per tenant
    # — the ONE surface the bench, the report, and the isolation pin
    # all read
    by_tenant: dict = {}
    for r in requests:
        if r.tenant is not None:
            by_tenant.setdefault(r.tenant, []).append(r)
    tenants_out = None
    if by_tenant:
        tenants_out = {}
        for name in sorted(by_tenant):
            rs = by_tenant[name]
            ttfts = [r.t_first - r.t_arrival for r in rs
                     if r.t_first is not None]
            lats_t = [r.t_done - r.t_arrival for r in rs
                      if r.t_done is not None]
            sc = {"ok": 0, "shed": 0, "expired": 0, "failed": 0}
            for r in rs:
                sc[r.status] = sc.get(r.status, 0) + 1
            tenants_out[name] = {
                "requests": len(rs),
                "status_counts": sc,
                "tokens": sum(len(r.tokens) for r in rs),
                "ok_tokens_per_s": round(
                    sum(len(r.tokens) for r in rs
                        if r.status == "ok") / max(wall, 1e-9), 2),
                "ttft": _latency_stats(ttfts),
                "latency": _latency_stats(lats_t),
            }
        ts = getattr(engine, "tenant_stats", lambda: None)()
        if ts:
            for name, st in ts.items():
                if name in tenants_out:
                    tenants_out[name]["scheduler"] = st
    # shared-prefix cache aggregate (absent with the cache off)
    prefix_out = getattr(engine, "prefix_stats", lambda: None)()
    # speculative-decoding aggregate (zeros stay absent: a spec-off
    # trace reports exactly the pre-spec dict)
    spec_proposed = sum(r.spec_proposed for r in requests)
    spec = None
    if spec_proposed:
        spec_accepted = sum(r.spec_accepted for r in requests)
        spec = {
            "proposed": spec_proposed,
            "accepted": spec_accepted,
            "accept_rate": round(
                spec_accepted / max(1, spec_proposed), 4),
        }
    out = {
        "outputs": {r.id: list(r.tokens) for r in requests},
        "requests": requests,
        "tokens": toks,
        "wall_s": round(wall, 4),
        "tokens_per_s": round(toks / max(wall, 1e-9), 2),
        # goodput: only tokens delivered to requests that finished OK
        # count — shed/expired/failed work is wasted capacity
        "ok_tokens_per_s": round(ok_toks / max(wall, 1e-9), 2),
        "status_counts": status_counts,
        "restarts": engine.restarts,
        "token_latency": _latency_stats(lats),
        "ttft": _latency_stats(
            [r.t_first - r.t_arrival for r in requests
             if r.t_first is not None]),
        "latency_components_s": comp_totals,
        "mean_occupancy": round(float(np.mean(occupancy)), 4)
        if occupancy else 0.0,
        "mean_pool_utilization": round(float(np.mean(pool_util)), 4)
        if pool_util else 0.0,
        "evictions": engine._evictions,
        "preemptions": sum(r.preemptions for r in requests),
    }
    if spec is not None:
        out["spec"] = spec
    if tenants_out is not None:
        out["tenants"] = tenants_out
    if prefix_out is not None:
        out["prefix_cache"] = prefix_out
    if slo is not None:
        out["slo"] = slo.snapshot()
    return out


def run_serial(model, params, trace: Sequence[Arrival], *,
               temperature: float = 0.0,
               top_k: Optional[int] = None) -> dict:
    """The one-at-a-time baseline: the SAME trace through
    `GPT2Model.generate`, each request starting when the previous
    finishes (or when it arrives, whichever is later).  Its per-request
    tokens are also the greedy-parity reference for the batched path."""
    import jax

    outputs = []
    lats: List[float] = []
    t0 = time.monotonic()
    for i, a in enumerate(trace):
        now = time.monotonic() - t0
        if a.at_s > now:
            time.sleep(a.at_s - now)
        t_req = time.monotonic()
        out = model.generate(
            params, np.asarray(a.prompt, np.int32)[None, :],
            a.max_new_tokens, temperature=temperature, top_k=top_k,
            key=jax.random.PRNGKey(i) if temperature != 0.0 else None,
        )
        toks = np.asarray(out)[0, len(a.prompt):].tolist()
        dt = time.monotonic() - t_req
        outputs.append(toks)
        # serial tokens surface all at once: attribute the request wall
        # evenly (the honest per-token number a one-shot script delivers)
        lats.extend([dt / max(len(toks), 1)] * len(toks))
    wall = time.monotonic() - t0
    n = sum(len(o) for o in outputs)
    return {
        "outputs": outputs,
        "tokens": n,
        "wall_s": round(wall, 4),
        "tokens_per_s": round(n / max(wall, 1e-9), 2),
        "token_latency": _latency_stats(lats),
    }
