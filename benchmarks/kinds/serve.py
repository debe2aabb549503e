"""kind = serve: an open loop of seeded arrivals against `ServingEngine`,
replayed against a schedule and timed from each request's DUE instant.

One thread: arrivals that have become due are submitted between ticks
(the engine admits only at the start of a tick, so a second thread would
move a request from the generator's wait into the engine's queue and change
nothing a user sees; how late submit() ran is reported as
`gen_late_ms_p95`).  Phases, all at the mix's one rate: a ramp that fills
the engine to its steady occupancy (set-up), the window of --seconds whose
arrivals are the MEASURED SET, and a tail that keeps offering until the
last measured request is done, so none finishes in a draining engine.

A mix's data file gives the traffic (generator.py) and: param_dtype,
block_tokens, check {prompt_lens, logit_tolerance}, trace_seconds.  A cell
gives sizes.slots (the pool is slots x context / block_tokens blocks: every
slot can reach the model's context, so nothing is ever preempted).
"""

from __future__ import annotations

import time

import numpy as np


def _check(engine, model, params, cfg, mix, env):
    """Warm-up and correctness in one tick: one seeded prompt per prefill
    bucket the traffic uses, two tokens each.  The tick prefills each (its
    first token) and runs one decode step; that step's logits, computed
    through the paged KV cache, must agree with the plain reference's full
    forward over prompt + first token."""
    import jax
    from benchmarks.reference import gpt2 as reference

    rng = np.random.default_rng([env.seed, 0xC4EC])
    lens = [int(n) for n in mix["check"]["prompt_lens"]]
    reqs = [engine.submit(rng.integers(0, cfg.vocab_size, n).tolist(), 2)
            for n in lens]
    engine.tick()
    logits = np.asarray(engine.last_logits)
    width = max(lens) + 1
    idx = np.zeros((len(reqs), width), np.int32)
    for j, r in enumerate(reqs):
        idx[j, :len(r.prompt) + 1] = r.prompt + r.tokens[:1]
    ref = np.asarray(jax.jit(reference.logits_at, static_argnums=3)(
        params, idx, np.asarray(lens, np.int32), cfg.n_head))
    got = np.stack([logits[r.last_slot] for r in reqs])
    diff = np.abs(got - ref)
    tol = float(mix["check"]["logit_tolerance"])
    ok = (bool(np.isfinite(got).all()) and float(diff.max()) <= tol
          and all(r.status == "ok" and len(r.tokens) == 2 for r in reqs)
          and engine.restarts == 0)
    env.say(f"check: decode logits through the cache vs float32 reference, "
            f"prompts {lens}: max|d|={diff.max():.5f} rms="
            f"{np.sqrt(np.mean(diff ** 2)):.5f} at sigma {ref.std():.3f} "
            f"(tol {tol}): {'ok' if ok else 'FAIL'}")
    return ok


def run(cell, env):
    import jax
    from tiny_deepspeed_tpu.models import build_model
    from tiny_deepspeed_tpu.ops.dispatch import kernels_noted
    from tiny_deepspeed_tpu.serving import ServeConfig, ServingEngine

    from benchmarks import serve_arith as sa
    from benchmarks import generator
    from benchmarks.harness import Outcome, annotate

    mix, sizes = cell.mix, cell.sizes
    cfg = cell.model_config(param_dtype=mix["param_dtype"])
    model = build_model(cfg)
    with jax.default_device(env.devices[0]):
        params = jax.jit(model.init)(jax.random.PRNGKey(env.seed))
    env.lap("imports, backend, weights")
    slots, bt = int(sizes["slots"]), int(mix["block_tokens"])
    engine = ServingEngine(model, params, ServeConfig(
        max_active=slots, num_blocks=slots * cfg.block_size // bt,
        block_tokens=bt, temperature=0.0, eos_id=None, prefix_cache=False,
        spec_draft=None, paged_kernel="auto", seed=env.seed % 2**31))
    env.say(engine.describe())
    env.lap("engine, pool")
    arrivals = generator.schedule(mix, env.seed, env.seconds, cfg.vocab_size)
    env.lap("schedule")
    ok_check = _check(engine, model, params, cfg, mix, env)
    env.lap("warm-up tick + check (compile or cache load)")
    env.say(f"kernels {kernels_noted()}; schedule: "
            + ", ".join(f"{sum(a.phase == p for a in arrivals)} {p}"
                        for p in ("ramp", "window", "tail")))

    clock = time.monotonic
    t0 = clock() + float(mix["ramp_s"])      # the window's first due instant
    t_end = t0 + env.seconds
    trace_from = t_end - float(mix["trace_seconds"])
    env.laps.append(("ramp", t0))
    records, handles, in_window = [], [], []
    ticks, occupancy = [], []
    units = 0
    nxt = 0
    marked = False

    def measured_done():
        """Every window arrival submitted, and each of them done."""
        return (nxt < len(arrivals) and arrivals[nxt].phase == "tail"
                and all(h.done for h in in_window))

    while nxt < len(arrivals) or engine.queue_depth or engine.n_active:
        now = clock()
        if not marked and now >= t0:
            env.monitor.mark("window")
            marked = True
        if measured_done():
            break
        if nxt < len(arrivals) and t0 + arrivals[nxt].due_s <= now:
            with annotate("bench.submit"):
                while (nxt < len(arrivals)
                       and t0 + arrivals[nxt].due_s <= clock()):
                    a = arrivals[nxt]
                    handles.append(engine.submit(a.prompt, a.max_new_tokens))
                    if a.phase == "window":
                        in_window.append(handles[-1])
                    records.append(sa.Record(
                        due=t0 + a.due_s, submitted=clock(),
                        want_tokens=a.max_new_tokens))
                    nxt += 1
        if env.trace and not env.tracer.active and env.tracer.path is None \
                and now >= trace_from:
            env.tracer.start()
        if engine.queue_depth or engine.n_active:
            t_tick = clock()
            with annotate("bench.tick"):
                engine.tick()
            t_done = clock()
            if t0 <= t_tick < t_end:
                ticks.append(t_done - t_tick)
                occupancy.append(engine.n_active / slots)
            if env.trace and env.tracer.active:
                units += 1
                if t_done >= t_end:
                    env.tracer.stop()
        elif nxt < len(arrivals):
            time.sleep(max(0.0, min(
                0.05, t0 + arrivals[nxt].due_s - clock())))
    offered_out = nxt >= len(arrivals)
    if env.trace and env.tracer.active:
        env.tracer.stop()
    env.monitor.mark("after")

    for rec, h in zip(records, handles):
        rec.admitted, rec.first, rec.done = h.t_admitted, h.t_first, h.t_done
        rec.tokens, rec.gaps = len(h.tokens), list(h.token_lat[1:])
        rec.status, rec.preemptions = h.status, h.preemptions
    measured = sa.measured_set(records, t0, env.seconds)
    failed = sum(not r.whole for r in measured)
    preempted = sum(r.preemptions for r in measured)
    compiles = env.monitor.requests["window"]
    tpot, ttft = sa.tpot_p95_ms(measured), sa.ttft_p95_ms(measured)
    env.say(f"window: {len(measured)} measured requests, {failed} not whole, "
            f"{preempted} preemptions, restarts {engine.restarts}, "
            f"{len(ticks)} ticks, tpot_p95 {tpot:.3f} ms, ttft_p95 "
            f"{ttft:.3f} ms, tail ran out: {offered_out}, compile requests "
            f"in window {compiles}; memory_stats "
            f"{env.devices[0].memory_stats()}")
    return Outcome(
        t_window=t0,
        end_to_end={"tpot_p95_ms": tpot},
        correct=(ok_check and failed == 0 and preempted == 0
                 and engine.restarts == 0 and compiles == 0
                 and not offered_out and len(measured) > 0),
        attempted=len(measured), failed=failed,
        host={"measured": measured, "tick_s": ticks,
              "occupancy": occupancy},
        units=units)
