"""kind = train: the real training loop, timed by the host's clock.

`TokenLoader.next()` -> `jnp.asarray` -> `engine.step`, as
`examples/common.run` does (bench.py's `measure()` replays one batch that
already sits on the device, so it bypasses the loader and the staging
hop).  At most `in_flight` steps are dispatched ahead: the loss of step
i - in_flight is read before step i is dispatched, so the loop ends near
--seconds and every loss is seen.  The rate is tokens of the completed
steps over the time that really passed, closed by the last loss's arrival
on the host.

A mix's data file gives: engine (SingleDevice | DDP | Zero1 | Zero2 |
Zero3), seq_len, param_dtype, moment_dtype, optimizer {lr, weight_decay},
in_flight, warmup_steps, trace_skip_steps, trace_steps, sync_steps,
loss_tolerance.  A cell gives sizes.batch_per_chip and sizes.model
(non-size fields of the model config: remat, scan_unroll, fused_xent..).
"""

from __future__ import annotations

import collections
import functools
import math
import time

ENGINES = ("SingleDevice", "DDP", "Zero1", "Zero2", "Zero3")


def _resting_bytes(state) -> int:
    """Bytes of the train state on the fullest chip, from the arrays' own
    shards (chip_smoke.py's resting_bytes)."""
    import jax
    per = collections.Counter()
    for leaf in jax.tree.leaves(state):
        for shard in leaf.addressable_shards:
            per[shard.device.id] += shard.data.nbytes
    return max(per.values())


def run(cell, env):
    import jax
    import jax.numpy as jnp
    import tiny_deepspeed_tpu as tds
    from tiny_deepspeed_tpu.data import TokenLoader
    from tiny_deepspeed_tpu.models import build_model
    from tiny_deepspeed_tpu.ops.dispatch import kernels_noted
    from tiny_deepspeed_tpu.utils.hlo_comm import hlo_comm_report

    from benchmarks.harness import Outcome, annotate, memory_peak_bytes
    from benchmarks.reference import gpt2 as reference

    mix, sizes = cell.mix, cell.sizes
    if mix["engine"] not in ENGINES:
        raise ValueError(f"unknown engine {mix['engine']!r}")
    chips = cell.chips
    seq = int(mix["seq_len"])
    batch = int(sizes["batch_per_chip"]) * chips
    cfg = cell.model_config(param_dtype=mix["param_dtype"])
    model = build_model(cfg)
    opt = tds.AdamW(lr=mix["optimizer"]["lr"],
                    weight_decay=mix["optimizer"]["weight_decay"],
                    state_dtype=jnp.dtype(mix["moment_dtype"]))
    engine = getattr(tds, mix["engine"])(
        model, opt, mesh=tds.make_mesh(devices=env.devices))
    env.say(engine.describe())
    env.lap("imports, backend, engine")
    state = engine.init(jax.random.PRNGKey(env.seed))
    resting = _resting_bytes(state)
    env.lap("weights")
    loader = TokenLoader(None, batch=batch, seq=seq,
                         vocab_size=cfg.vocab_size, seed=env.seed)
    env.say(f"params={model.num_params() / 1e6:.1f}M global_batch={batch} "
            f"T={seq} loader={loader.backend} resting="
            f"{resting / 2**30:.3f}GiB/chip")

    def load():
        with annotate("bench.load"):
            idx, tgt = loader.next()
            return jnp.asarray(idx), jnp.asarray(tgt)

    def step(state, batch):
        with annotate("bench.step"):
            return engine.step(state, batch)

    # -- correctness, outside the window: the reference at the engine's own
    # initial parameters on the seeded first batch, against the loss the
    # first step reports (which is computed before that step's update)
    first = load()
    t_ref = time.monotonic()
    ref_loss = float(jax.jit(reference.loss, static_argnums=3)(
        state.params, first[0], first[1], cfg.n_head))
    t_ref = time.monotonic() - t_ref
    env.lap("reference")
    t_first = time.monotonic()
    state, loss = step(state, first)
    first_loss = float(loss)
    t_first = time.monotonic() - t_first
    env.lap("first step (compile or cache load)")
    tol = float(mix["loss_tolerance"])
    ok_ref = abs(first_loss - ref_loss) <= tol
    env.say(f"check: first step loss {first_loss:.6f} vs float32 reference "
            f"{ref_loss:.6f} (|d|={abs(first_loss - ref_loss):.2e}, tol "
            f"{tol}): {'ok' if ok_ref else 'FAIL'}; reference {t_ref:.2f}s, "
            f"first step {t_first:.2f}s; kernels {kernels_noted()}")
    for _ in range(int(mix["warmup_steps"])):
        state, loss = step(state, load())
    float(loss)
    env.lap("warm-up steps")

    # -- the window
    in_flight = int(mix["in_flight"])
    skip, n_trace = int(mix["trace_skip_steps"]), int(mix["trace_steps"])
    pending = collections.deque()
    losses, waits = [], []
    units = 0
    paused = 0.0   # a traced run's clock stops while the profiler turns
    env.monitor.mark("window")
    t0 = time.monotonic()
    i = 0
    while time.monotonic() - t0 - paused < env.seconds:
        if env.trace and i == skip:
            while pending:  # a clean edge: whole steps inside the trace
                losses.append(float(pending.popleft()))
            t_pause = time.monotonic()
            env.tracer.start()
            paused += time.monotonic() - t_pause
        if len(pending) >= in_flight:
            with annotate("bench.sync"):
                losses.append(float(pending.popleft()))
        t_load = time.monotonic()
        b = load()
        waits.append(time.monotonic() - t_load)
        state, loss = step(state, b)
        pending.append(loss)
        i += 1
        if env.trace and env.tracer.active and i == skip + n_trace:
            while pending:
                losses.append(float(pending.popleft()))
            t_pause = time.monotonic()
            env.tracer.stop()
            paused += time.monotonic() - t_pause
            units = n_trace
    while pending:
        losses.append(float(pending.popleft()))
    elapsed = time.monotonic() - t0 - paused
    if env.trace and env.tracer.active:  # window too short for the plan
        env.tracer.stop()
        units = i - skip
    rate = i * batch * seq / elapsed / chips

    # -- per-step time with a sync, traced run only
    step_sync = []
    if env.trace:
        for _ in range(int(mix["sync_steps"])):
            b = load()
            t = time.monotonic()
            state, loss = step(state, b)
            losses.append(float(loss))
            step_sync.append(time.monotonic() - t)
    env.monitor.mark("after")
    loader.close()

    bad = sum(not math.isfinite(x) for x in losses)
    in_window = env.monitor.requests["window"]
    peak = memory_peak_bytes(env.devices)
    env.say(f"window: {i} steps in {elapsed:.3f}s, {rate:.2f} tokens/s/chip, "
            f"loss {losses[0]:.4f} -> {losses[-1]:.4f}, non-finite {bad}, "
            f"compile requests in window {in_window}, peak "
            f"{peak / 2**30:.3f}GiB; memory_stats "
            f"{env.devices[0].memory_stats()}")
    return Outcome(
        t_window=t0,
        end_to_end={"tokens_per_s_chip": rate,
                    "peak_hbm_gib": peak / 2**30},
        correct=ok_ref and bad == 0 and in_window == 0,
        attempted=len(losses), failed=bad,
        host={"tokens_per_s_chip": rate, "input_wait_s": waits,
              "step_sync_s": step_sync, "resting_bytes": resting,
              "seq_len": seq, "batch": batch,
              # the compiled step's collective ledger, only if a reader asks
              "comm_report": functools.partial(
                  hlo_comm_report, engine, state, b)},
        units=units)
