# Copyright 2026 tiny-deepspeed-tpu authors
# SPDX-License-Identifier: Apache-2.0

"""Benchmark: GPT-2 training throughput on the real chip(s).

Prints ONE JSON line: {"metric": ..., "value": N, "unit": ..., "vs_baseline": N}.
Runs on the TPU only (utils/startup.select_platform): with no chip it exits
non-zero before compiling, and any failed phase is a traceback and a
non-zero exit — never a record.

The reference publishes no numbers (BASELINE.md), so `vs_baseline` is measured
against this repo's own previous round (BENCH_r*.json if present, else 1.0).
Headline metric: GPT-2 124M tokens/sec/chip on the reference demo workload
shape (T=1024, AdamW — reference example/ddp/train.py:23-35), batch size
scaled to fill the chip.

MFU is reported two ways (round-1 verdict: the 6N formula flatters itself by
counting embedding params whose forward is a gather):
  * `matmul_mfu` — honest: 6 * non-embedding params (wte/wpe excluded,
    lm_head kept: it is a matmul) + 12*L*T*d attention FLOPs per token
    (PaLM-appendix convention, no causal discount).
  * `mfu_6n` — the naive 6 * total-params number, for comparability.

`python bench.py --sweep` measures every single-chip row of the BASELINE.md
matrix (GPT-2 124M / 350M / 774M / 1.5B) plus a Llama-160M datapoint, one
JSON line per config.
"""

import dataclasses
import glob
import json
import os
import re
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


def _peak_flops_per_chip(device) -> float:
    """bf16 peak by device kind (used only for the MFU context numbers).
    Delegates to the cost ledger's table (utils/hlo_cost.py) so the MFU
    denominator and the roofline verdict can never disagree.  A device
    the table does not know is refused: an MFU priced at another chip's
    peak is not a measurement."""
    from tiny_deepspeed_tpu.utils.hlo_cost import peak_flops_per_chip
    kind = getattr(device, "device_kind", "")
    peak = peak_flops_per_chip(kind)
    if peak is None:
        raise SystemExit(
            f"bench: no peak FLOP/s known for device_kind={kind!r} "
            "(utils/hlo_cost._PEAK_FLOPS_TABLE); refusing to report MFU")
    return peak


def measure(engine, state, batch, warmup=5, iters=30):
    # float(loss) (device->host transfer) closes each timed window
    for _ in range(warmup):
        state, loss = engine.step(state, batch)
    float(loss)
    t0 = time.perf_counter()
    for _ in range(iters):
        state, loss = engine.step(state, batch)
    float(loss)
    dt = time.perf_counter() - t0
    return dt / iters, state


def _bench_config(model_name: str):
    """Per-model single-chip bench settings, measured on v5e-1 (16 GB):
    124M fits without remat (fastest); 1.5B only fits fully-bf16 (params +
    AdamW moments) with remat=nothing + the chunked fused lm_head/xent."""
    import jax.numpy as jnp
    table = {
        # bf16 resting params beat f32 across the matrix (measured r2:
        # 124m 88.3k vs 86.8k, 350m 32.0k vs 31.7k, 774m 16.1k vs 15.4k):
        # the per-step f32->bf16 cast of every weight disappears and weight
        # HBM traffic halves.  AdamW moments: bf16 wherever measured
        # faster or needed to fit (124m/774m/1.5b/moe/llama-1b), f32 on
        # 350m; update math is f32 either way.
        # 124m (round-4 live-chip grid, /tmp/mfu_sweep):
        # b12 + bf16 moments = 92.3k tok/s / 0.401 matmul MFU vs b10+f32
        # 90.0k / 0.392 — bf16 moments halve the optimizer-state HBM
        # traffic that dominates the small model's update.  fused_xent
        # LOSES at this size (b12: 86.6k, b10: 84.5k) — the full-logits
        # matmul rides the MXU better than the chunked head; it's a
        # memory knob, needed only from 774m up.  b13/b14 regress
        # (90.3k/89.6k).  A compile OOM, if the envelope moves again,
        # steps down b12->b11 (91.8k) via the guard below.
        # scan_unroll=True wherever it measured faster (round-4 chip runs):
        # it deletes the layer-scan's activation-stash slice traffic (the
        # 124m profile priced it at ~16 ms of a 132 ms step) — 124m 92.0k
        # -> 106.5k (+16%), 350m 32.5k -> 33.9k, 774m 15.4k -> 17.1k,
        # llama-160m 94.1k -> 105.4k.  1.5b stays SCANNED: it remats with
        # policy "nothing" (no stash to delete) and unroll=4/8 measured
        # 7.5k/6.9k vs 8.0k scanned; full unroll fails to compile at 48
        # layers (remote_compile 500).
        "gpt2-124m": dict(batch=12,
                          overrides=dict(remat=False,
                                         param_dtype=jnp.bfloat16,
                                         scan_unroll=True),
                          state_dtype=jnp.bfloat16),
        "gpt2-350m": dict(batch=8,
                          overrides=dict(param_dtype=jnp.bfloat16,
                                         scan_unroll=True),
                          state_dtype=jnp.float32),
        "gpt2-774m": dict(batch=4,
                          overrides=dict(param_dtype=jnp.bfloat16,
                                         fused_xent=True,
                                         scan_unroll=True),
                          state_dtype=jnp.bfloat16),
        "gpt2-1.5b": dict(
            batch=4,
            overrides=dict(param_dtype=jnp.bfloat16, remat_policy="nothing",
                           fused_xent=True),
            state_dtype=jnp.bfloat16,
        ),
        # ~0.9B total params, top-2 routed (~2/8 active per token); batch
        # kept small — expert tensors carry the (E,) axis so weight HBM is
        # the bound, not activations
        "moe-8x124m": dict(
            batch=4,
            overrides=dict(param_dtype=jnp.bfloat16, fused_xent=True,
                           scan_unroll=True),
            state_dtype=jnp.bfloat16,
        ),
        # round-4 live-chip grid (/tmp/llama_sweep): bf16 params + bf16
        # moments + remat OFF at b=12 = 94.1k tok/s / 0.381 matmul MFU vs
        # the old untuned f32 defaults 89.4k / 0.362; b=16 regresses
        "llama-160m": dict(
            batch=12,
            overrides=dict(param_dtype=jnp.bfloat16, remat=False,
                           scan_unroll=True),
            state_dtype=jnp.bfloat16,
        ),
        # ~1.2B params: same squeeze as gpt2-1.5b (f32 state = 17.9 GB
        # compiled, over the 16 GB chip — round-4 AOT measurement)
        "llama-1b": dict(
            batch=4,
            overrides=dict(param_dtype=jnp.bfloat16, fused_xent=True),
            state_dtype=jnp.bfloat16,
        ),
    }
    return table.get(model_name,
                     dict(batch=8, overrides={}, state_dtype=None))


def _effective_xent_impl(cfg, n_chips: int, tokens=None) -> str:
    """The loss-head implementation a step with this config actually runs
    — delegates to the ONE predicate gpt2.head itself consults
    (models/gpt2.effective_xent_impl, mirroring moe.effective_dispatch),
    so the A/B label can never drift from the gate."""
    from tiny_deepspeed_tpu.models.gpt2 import effective_xent_impl
    return effective_xent_impl(cfg, multi_device=n_chips > 1,
                               tokens=tokens)


def _sched_extra(engine, compiled_step, hpz_gran=None):
    """extra.sched for the scheduler-composed / hpZ bench arms: the live
    composition string, the merged program's per-slot overlap fractions,
    and (under hpZ) the measured per-link wire split with the in-scan
    gather slice — the before/after ledger rows the ROADMAP hpZ item
    asks for come from running the legacy arm next to this one."""
    from tiny_deepspeed_tpu.utils.hlo_comm import (
        collective_ledger, gather_link_split_in_loops, overlap_report,
        wire_link_split,
    )
    txt = compiled_step.as_text()
    led = collective_ledger(txt)
    rep = overlap_report(txt, led=led)
    out = {
        "describe": engine._schedule.describe(),
        "lowering": engine._lowering,
        "sched_gather_overlap_frac": round(
            rep["gather_overlap_frac"], 4),
        "sched_grad_overlap_frac": round(
            rep["grad_comm_overlap_frac"], 4),
        "gather_wire_bytes_in_loops": rep["gather_wire_bytes_in_loops"],
        "reduce_wire_bytes_in_loops": rep["reduce_wire_bytes_in_loops"],
    }
    sched = engine._schedule
    if sched.pipe_program is not None:
        # table pipeline arms: the compiled tick program's occupancy —
        # perf_diff.py sentinel-flags bubble_frac like the wire keys, so
        # a schedule regression (bubble creeping back up) reads as a
        # diff line, not silence
        out["pipe"] = sched.pipe_program.describe()
        out["bubble_frac"] = round(
            float(sched.pipe_program.bubble_frac), 6)
        out["pipe_ticks"] = int(sched.pipe_program.n_ticks)
    elif getattr(engine, "_use_1f1b", False):
        # the 1f1b baseline arm has no tick table; its bubble is the
        # closed form — stamped so the three-arm A/B reads side by side
        from tiny_deepspeed_tpu.parallel.pipe_schedule import (
            analytic_1f1b_bubble,
        )
        s = int(engine.mesh.shape.get("pipe", 0) or 0)
        m = int(engine.pctx.pipe_microbatches or s)
        if s >= 2:
            out["pipe"] = f"pipe=1f1b[s={s} m={m} analytic]"
            out["bubble_frac"] = round(analytic_1f1b_bubble(s, m), 6)
    if sched.grad is not None and sched.grad.tail_mode != "fp32":
        # quantized tail release: its sync is the once-per-step
        # OUTSIDE-loop reduce wire (buckets are the in-loop wire)
        out["tail_comm"] = sched.grad.tail_mode
        out["zero3_tail_wire_bytes"] = round(
            rep["reduce_wire_bytes_total"]
            - rep["reduce_wire_bytes_in_loops"])
    if sched.auto_plan is not None:
        # the DCN-aware policy's resolved assignment + modeled bytes
        out["auto_plan"] = sched.auto_plan
    if hpz_gran is not None:
        out["wire_bytes_by_link"] = wire_link_split(led, hpz_gran)
        out["in_scan_gather_link"] = gather_link_split_in_loops(
            led, hpz_gran)
        if (sched.gather is not None and sched.gather.hpz
                and sched.hpz_geom is not None):
            from tiny_deepspeed_tpu.utils.hlo_comm import (
                group_wire_outside_loops,
            )
            out["hpz_comm"] = sched.gather.hpz_mode
            out["hpz_rebuild_dcn_bytes"] = round(
                group_wire_outside_loops(led, sched.hpz_geom[1]))
    return {"sched": out}


def _gather_prefetch_extra(engine, compiled_step, gather_prefetch,
                           gather_quant):
    """Round-8 A/B labeling: the gather-prefetch config that actually ran
    plus the compiled ledger's LOOP-RESIDENT gather wire (the measured
    placement of the per-layer weight gathers — a hoist regression reads
    0 here while the step still 'works')."""
    from tiny_deepspeed_tpu.utils.hlo_comm import collective_ledger
    led = collective_ledger(compiled_step.as_text())
    return {
        "gather_prefetch": int(gather_prefetch),
        "gather_prefetch_active": bool(engine._gather_prefetch_active),
        **({"gather_quant": gather_quant} if gather_quant else {}),
        **({"gather_groups": int(engine.gather_groups)}
           if getattr(engine, "gather_groups", None) else {}),
        "gather_loop_wire_bytes": round(
            led["wire_bytes_in_loops"].get("all-gather", 0.0)),
        "gather_total_wire_bytes": round(
            led["wire_bytes"].get("all-gather", 0.0)),
    }


def run_one(model_name: str, b=None, t=1024, iters=30):
    import jax
    import jax.numpy as jnp
    from tiny_deepspeed_tpu import AdamW, SingleDevice, make_mesh
    from tiny_deepspeed_tpu.models import ALL_PRESETS, build_model
    from tiny_deepspeed_tpu.models.llama import LlamaConfig

    bc = _bench_config(model_name)
    b = b or bc["batch"]
    cfg = dataclasses.replace(ALL_PRESETS[model_name], **bc["overrides"])
    md = os.environ.get("BENCH_MOE_DISPATCH")
    if md and hasattr(cfg, "moe_dispatch"):
        # round-4 A/B knob: sort vs einsum dispatch (MoEConfig.moe_dispatch)
        cfg = dataclasses.replace(cfg, moe_dispatch=md)
    if os.environ.get("BENCH_XENT") == "pallas":
        # round-5 A/B knob: the Pallas fused lm_head+xent kernel
        # (ops/xent_pallas.py) vs whatever head the config default runs
        cfg = dataclasses.replace(cfg, fused_xent=True,
                                  fused_xent_impl="pallas")
    gather_quant = os.environ.get("BENCH_GATHER_QUANT")
    if gather_quant and hasattr(cfg, "gather_quant"):
        # round-8 A/B axis: fp8 weight gather under the zero3 prefetch A/B
        cfg = dataclasses.replace(cfg, gather_quant=gather_quant)
    if t > cfg.block_size:
        # long-context invocation (BENCH_SEQ=4096/8192): widen the position
        # table and drop the short-context speed knobs — remat back on and
        # the chunked fused head, or the activation/logit memory at long T
        # swamps the chip
        # scan_unroll back to scanned too: a fully unrolled 12-36 layer
        # stack at T>=4096 inflates compile time and re-stashes per-layer
        # activations that the re-enabled remat exists to avoid
        cfg = dataclasses.replace(cfg, block_size=t, remat=True,
                                  fused_xent=True, scan_unroll=1)

    if os.environ.get("BENCH_AUTOTUNE"):
        # per-shape candidate timing at trace time (linear layouts, flash
        # attention blocks, layernorm kernels) — winners baked into the step
        from tiny_deepspeed_tpu.autotuner import (
            RuntimeAutoTuner, set_default_tuner,
        )
        set_default_tuner(RuntimeAutoTuner(verbose=bool(
            os.environ.get("BENCH_AUTOTUNE_VERBOSE"))))

    model = build_model(cfg)
    devices = jax.devices()
    n_chips = len(devices)
    mesh = make_mesh()
    opt = AdamW(lr=1e-5, weight_decay=0.1,
                state_dtype=bc["state_dtype"] or jnp.float32)
    ek = {}
    if os.environ.get("BENCH_OFFLOAD"):
        ek["offload_opt_state"] = True  # moments to pinned_host (TPU only)
        if os.environ.get("BENCH_OFFLOAD_PREFETCH"):
            # round-5 A/B knob: in-flight window of streamed moment leaves
            ek["offload_prefetch"] = int(os.environ["BENCH_OFFLOAD_PREFETCH"])
    grad_comm = os.environ.get("BENCH_GRAD_COMM")
    if grad_comm:
        # round-6 A/B knob: quantized gradient collectives
        # (parallel/comm.py) — int8/fp8 error-fed reduce-scatter.  Inert
        # (engine warns) on a single chip, where there is no gradient
        # collective; the record below labels what actually ran.
        ek["grad_comm"] = grad_comm
        if os.environ.get("BENCH_GRAD_COMM_GROUPS"):
            # hierarchical 2-hop schedule: inner group size
            ek["grad_comm_groups"] = int(os.environ["BENCH_GRAD_COMM_GROUPS"])
    grad_buckets = os.environ.get("BENCH_GRAD_BUCKETS")
    if grad_buckets:
        # round-7 A/B knob: bucketed backward-overlapped gradient release
        # (engine grad_buckets=) — per-layer-bucket collectives inside the
        # backward scan vs the monolithic after-backward sync.  Inert
        # (engine warns) on a single chip; must divide n_layer.
        ek["grad_buckets"] = int(grad_buckets)
    gather_prefetch = os.environ.get("BENCH_GATHER_PREFETCH")
    if gather_prefetch:
        # round-8 A/B knob: ZeRO-3 layer-ahead weight-gather prefetch
        # (engine gather_prefetch=, parallel/schedule.GatherPrefetchScan).
        # Setting the env var selects the Zero3 engine (the stage whose
        # per-layer gathers the knob schedules); K=1 is the byte-
        # identical on-demand baseline so the A/B pair shares a stage.
        ek["gather_prefetch"] = int(gather_prefetch)
        if os.environ.get("BENCH_GATHER_GROUPS"):
            # hierarchical 2-hop gather: inner group size
            ek["gather_groups"] = int(os.environ["BENCH_GATHER_GROUPS"])
    sched_compose = os.environ.get("BENCH_SCHED_COMPOSE")
    bench_hpz = os.environ.get("BENCH_HPZ")
    hpz_gran = None
    if os.environ.get("BENCH_COMM_AUTO"):
        # wire-agenda arm: DCN-aware "auto" sizing — the engine resolves
        # codec / bucket count / inner-group factor from the mesh's
        # granule map (parallel/schedule.auto_comm_plan); the record's
        # extra.sched carries the resolved plan for the A/B against the
        # hand-set arms
        ek["grad_comm"] = "auto"
        ek["grad_buckets"] = "auto"
        ek["gather_groups"] = "auto"
    if os.environ.get("BENCH_TAIL_QUANT"):
        # wire-agenda arm: quantized ZeRO-3 tail release — rides the
        # grad codec (defaults int8 when no explicit BENCH_GRAD_COMM)
        ek["grad_comm"] = os.environ.get("BENCH_GRAD_COMM") or "int8"
        ek["grad_comm_tail"] = os.environ["BENCH_TAIL_QUANT"]
    pipe_sched_arm = os.environ.get("BENCH_PIPE_SCHED")
    if pipe_sched_arm:
        # pipeline-schedule A/B arm: "1f1b" vs "interleaved:V" vs
        # "zbub[:V]" at FIXED stages and microbatches — the schedule is
        # the only variable across the three rows (extra.sched.pipe names
        # it), and extra.sched.bubble_frac carries the compiled tick
        # program's occupancy for perf_diff's sentinel
        stages = int(os.environ.get("BENCH_PIPE_STAGES") or 0) or \
            min(4, n_chips)
        if n_chips % stages:
            raise SystemExit(
                f"bench: BENCH_PIPE_STAGES={stages} must divide the "
                f"chip count {n_chips}"
            )
        ek["pipeline_parallel"] = stages
        ek["pipeline_schedule"] = pipe_sched_arm
        ek["pipeline_microbatches"] = int(
            os.environ.get("BENCH_PIPE_MB") or 2 * stages)
    if sched_compose:
        # round-9 A/B: the scheduler-composed FULL STACK (ZeRO-3 +
        # gather prefetch + bucketed quantized grads + per-layer
        # health) vs the legacy single-feature arms — the legacy arm is
        # a separate invocation (e.g. BENCH_GATHER_PREFETCH alone);
        # extra.sched.describe keeps the rows apart
        ek["gather_prefetch"] = int(
            os.environ.get("BENCH_GATHER_PREFETCH") or 2)
        ek["grad_buckets"] = int(
            os.environ.get("BENCH_GRAD_BUCKETS") or 2)
        ek["grad_comm"] = os.environ.get("BENCH_GRAD_COMM") or "int8"
        from tiny_deepspeed_tpu.telemetry import Telemetry
        ek["telemetry"] = Telemetry(layers=True)
    if bench_hpz:
        # hpZ secondary weight partitioning: real multi-slice granule
        # map when the pod has one, else the emulated 2-slice split (the
        # same emulation the wire_link_split tests pin).  A BENCH_HPZ
        # row that cannot actually run hpz is REFUSED, not silently
        # measured plain — a mislabeled row would poison the
        # before/after ledger A/B
        from tiny_deepspeed_tpu.parallel.mesh import granule_map
        hpz_gran = granule_map(mesh.devices.flatten())
        if hpz_gran is None and n_chips > 1 and n_chips % 2 == 0:
            hpz_gran = {i: i // (n_chips // 2) for i in range(n_chips)}
        if hpz_gran is None:
            raise SystemExit(
                "bench: BENCH_HPZ=1 needs a real multi-slice mesh or an "
                f"even chip count >= 2 to emulate one (got {n_chips} "
                "chips, single granule); refusing to record a plain row "
                "under the hpz fingerprint"
            )
        ek["hpz"] = True
        ek["hpz_granule_of"] = hpz_gran
        if os.environ.get("BENCH_HPZ_COMM"):
            # wire-agenda arm: qwZ — the secondary rebuild's
            # inter-granule all_gather moves fp8 blocks + scales
            ek["hpz_comm"] = os.environ["BENCH_HPZ_COMM"]
    if pipe_sched_arm:
        # the engine carves the (data, pipe) mesh itself — the premade
        # flat mesh above has no pipe axis.  Zero1 keeps the optimizer
        # sharded without pulling in the gather/grad slots the table
        # schedules refuse to compose with.
        from tiny_deepspeed_tpu import Zero1
        engine = Zero1(model, opt, **ek)
        b *= n_chips
    elif (gather_prefetch or sched_compose or bench_hpz
            or os.environ.get("BENCH_TAIL_QUANT")
            or os.environ.get("BENCH_COMM_AUTO")):
        from tiny_deepspeed_tpu import Zero3
        engine = Zero3(model, opt, mesh=mesh, **ek)
        b *= n_chips
    elif n_chips == 1:
        engine = SingleDevice(model, opt, mesh=mesh, **ek)
    else:
        from tiny_deepspeed_tpu import Zero2
        engine = Zero2(model, opt, mesh=mesh, **ek)
        b *= n_chips
    # Effective MoE dispatch: moe.py's ONE fallback predicate, so the
    # record can never claim a knob value that fell back (sort runs
    # shard-local under pure DP since round 5; einsum under ep/tp/sp/pipe)
    moe_eff = None
    if hasattr(cfg, "moe_dispatch"):
        from tiny_deepspeed_tpu.models.moe import effective_dispatch
        moe_eff = effective_dispatch(cfg, engine.pctx)
        if moe_eff != cfg.moe_dispatch:
            print(f"bench: moe_dispatch={cfg.moe_dispatch!r} is INERT on "
                  f"this mesh; the measurement below is the {moe_eff} path",
                  file=sys.stderr)

    state = engine.init(jax.random.PRNGKey(0))
    # Compile-OOM guard: the memory envelope moves with the XLA version
    # (round 4: the b=10 124M config that RAN on-chip in round 2 at
    # 13.88 GB OOMs the compile-only v5e topology at 16.0/15.75 GB —
    # BASELINE.md "124m note").  Step the batch down until the step
    # COMPILES, and label the reduction in `extra`.
    b_requested = b
    while True:
        idx = jax.random.randint(jax.random.PRNGKey(1), (b, t), 0,
                                 cfg.vocab_size, jnp.int32)
        tgt = jax.random.randint(jax.random.PRNGKey(2), (b, t), 0,
                                 cfg.vocab_size, jnp.int32)
        try:
            # kept for the peak-HBM accounting below: the AOT compile does
            # not populate the jit call cache, so reusing it there keeps
            # run_one at two compiles (guard + measure), same as before
            compiled_step = engine._step.lower(state, (idx, tgt)).compile()
            break
        except Exception as e:
            if "RESOURCE_EXHAUSTED" in repr(e) and b > n_chips:
                print(f"bench: compile OOM at batch {b}, retrying "
                      f"{b - n_chips}: {e!r:.200}", file=sys.stderr)
                b -= n_chips
                continue
            raise

    if os.environ.get("BENCH_AUTOTUNE"):
        # first trace records candidate requests; retune times them on the
        # device and re-jits with winners baked (engine.retune docstring).
        # Guardrail for the standalone-timing hazard (adamw_pallas.py saw a
        # standalone winner LOSE in-graph): measure the whole step both
        # ways and keep the faster program.
        state, _ = engine.step(state, (idx, tgt))
        base_time, state = measure(engine, state, (idx, tgt), warmup=2,
                                   iters=8)
        tuned = engine.retune()
        tuned_time, state = measure(engine, state, (idx, tgt), warmup=2,
                                    iters=8)
        if tuned_time > base_time * 1.005:
            engine.revert_tune()
            print(
                f"bench: autotune REVERTED ({tuned} sites; tuned step "
                f"{tuned_time * 1e3:.2f}ms > default "
                f"{base_time * 1e3:.2f}ms)", file=sys.stderr,
            )
        else:
            print(
                f"bench: autotuned {tuned} sites ({base_time * 1e3:.2f}ms "
                f"-> {tuned_time * 1e3:.2f}ms)", file=sys.stderr,
            )

    step_time, state = measure(engine, state, (idx, tgt), iters=iters)
    tokens_per_sec_chip = b * t / step_time / n_chips

    # peak HBM/chip: live state + XLA temp from the compiled step
    mem = compiled_step.memory_analysis()
    state_bytes = sum(
        x.size * x.dtype.itemsize for x in jax.tree.leaves(state)
        if getattr(x.sharding, "memory_kind", None) != "pinned_host"
    )  # host-resident (offloaded) leaves are not chip memory
    hbm_gb = round(
        (state_bytes + mem.temp_size_in_bytes) / n_chips / 2**30, 3
    )

    # MFU, both accountings (module docstring).
    n_params = model.num_params()
    d, l, v = cfg.n_embd, cfg.n_layer, cfg.vocab_size
    # wte (+ wpe for gpt2; llama has no position table) — gathers, not matmuls
    embed_params = v * d + (
        0 if isinstance(cfg, LlamaConfig) else cfg.block_size * d
    )
    n_active = n_params
    from tiny_deepspeed_tpu.models.moe import MoEConfig
    if isinstance(cfg, MoEConfig):
        # routed experts: only top_k of n_expert run per token — but the
        # capacity-padded dispatch feeds every expert its FULL C slots
        # (round 16, HLO-counted: E*C = cf*k*S slot-rows of compute, a
        # capacity_factor more than the k/E accounting claimed — both
        # dispatch paths pad to (E, C, D))
        import math as _math
        expert = sum(
            int(_math.prod(s.shape))
            for n, s in model.param_shapes().items()
            if ".moe." in n and "router" not in n
        )
        _cap = max(1, int(cfg.capacity_factor * cfg.expert_top_k
                          * b * t / cfg.n_expert))
        # E*C slot-rows each through expert/E params: per token the
        # expert params "active" are expert * C / S
        n_active = n_params - expert + expert * _cap // (b * t)
    flops_tok_matmul = 6 * (n_active - embed_params) + 12 * l * t * d
    if isinstance(cfg, MoEConfig) and moe_eff == "einsum":
        # round 16: the GShard dispatch/combine einsums are real model
        # matmuls (~2/3 of the expert FLOPs at this shape) that the
        # formula above ignored — the HLO counter demonstrated the
        # undercount (tests/test_hlo_cost.py) and this corrects it
        from tiny_deepspeed_tpu.models.moe import (
            dispatch_combine_flops_per_token,
        )
        flops_tok_matmul += dispatch_combine_flops_per_token(cfg, b * t)
    peak = _peak_flops_per_chip(devices[0])
    toks_per_sec_total = b * t / step_time
    matmul_mfu = flops_tok_matmul * toks_per_sec_total / n_chips / peak
    mfu_6n = 6 * n_params * toks_per_sec_total / n_chips / peak

    # HLO cost ledger (utils/hlo_cost.py): measured FLOPs/HBM + roofline
    # verdict off the ALREADY-compiled step — stamped on the record so
    # every future round is self-describing (perf_diff reads mfu_hlo to
    # flag modeled-vs-measured drift)
    from tiny_deepspeed_tpu.utils.hlo_comm import collective_ledger
    from tiny_deepspeed_tpu.utils.hlo_cost import cost_ledger, cost_summary
    _ctext = compiled_step.as_text()
    hlo_cost_extra = cost_summary(
        cost_ledger(_ctext),
        device_kind=getattr(devices[0], "device_kind", None),
        wire_bytes=float(collective_ledger(_ctext).get(
            "total_wire_bytes", 0.0)),
    )
    # per-device program FLOPs over the measured step wall
    hlo_cost_extra["mfu_hlo"] = round(
        hlo_cost_extra["total_flops"] / step_time / peak, 3)

    # telemetry sidecar: measured collective ledger + a few instrumented
    # steps, so scripts/report_run.py can render this bench run
    tel_path = os.environ.get("BENCH_TELEMETRY_JSONL") or os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "artifacts",
        f"bench_telemetry_{model_name}.jsonl",
    )
    tel_dir = os.path.dirname(tel_path)
    if tel_dir:  # BENCH_TELEMETRY_JSONL may be a bare filename
        os.makedirs(tel_dir, exist_ok=True)
    _write_bench_telemetry(
        tel_path, engine, state, (idx, tgt), _ctext,
        model_name, n_chips, b, t, peak,
        flops_tok_matmul=flops_tok_matmul, hlo_cost=hlo_cost_extra,
    )

    return {
        "metric": f"{model_name}_train_tokens_per_sec_per_chip",
        "value": round(tokens_per_sec_chip, 1),
        "unit": "tokens/s/chip",
        "extra": {
            "chips": n_chips,
            "batch": b,
            **({"batch_reduced_from": b_requested}
               if b != b_requested else {}),
            "seq_len": t,
            "step_time_s": round(step_time, 4),
            "matmul_mfu": round(matmul_mfu, 3),
            "mfu_6n": round(mfu_6n, 3),
            "hlo_cost": hlo_cost_extra,
            "peak_hbm_gb_per_chip": hbm_gb,
            "n_params_m": round(n_params / 1e6, 1),
            # what actually ran, so an A/B record can't claim a knob value
            # it never measured: moe_dispatch post-fallback, plus the knobs
            # the long-context branch silently overrides (the `config` dict
            # below is the PRE-override _bench_config table)
            **({"moe_dispatch_effective": moe_eff} if moe_eff else {}),
            **({"grad_comm": grad_comm,
                "grad_comm_active": bool(engine._grad_comm_active)}
               if grad_comm else {}),
            **({"grad_buckets": int(grad_buckets),
                "grad_buckets_active": bool(engine._bucketed_active)}
               if grad_buckets else {}),
            **(_gather_prefetch_extra(engine, compiled_step,
                                      gather_prefetch, gather_quant)
               if gather_prefetch else {}),
            **(_sched_extra(engine, compiled_step, hpz_gran)
               if (sched_compose or bench_hpz or pipe_sched_arm
                   or os.environ.get("BENCH_TAIL_QUANT")
                   or os.environ.get("BENCH_COMM_AUTO")) else {}),
            "effective": {
                "remat": str(cfg.remat),
                "fused_xent": str(cfg.fused_xent),
                # the IMPL THAT RAN, mirroring gpt2.head's gate (pallas
                # needs fused_xent + TPU kernels + a single device) — not
                # the knob verbatim, which would mislabel fallback runs
                "fused_xent_impl": _effective_xent_impl(
                    cfg, n_chips, tokens=b * t // n_chips),
                "scan_unroll": str(cfg.scan_unroll),
            },
            "config": {
                k: str(v) for k, v in _bench_config(model_name).items()
            },
            "telemetry_jsonl": tel_path,
        },
    }


def run_decode(model_name: str, b=8, prompt_t=128, new_tokens=256):
    """KV-cache decode throughput: tokens/s of model.generate() (greedy,
    prefill + one cached single-position pass per token).  BENCH_DECODE=1
    selects this mode; the reference has no sampling loop at all."""
    import dataclasses as _dc

    import jax
    import jax.numpy as jnp
    from tiny_deepspeed_tpu.models import ALL_PRESETS, build_model

    # scan_unroll on the decode loop: per-token work is tiny, so the layer
    # scan's slice overhead is proportionally huge — unrolling measured
    # 4,455 vs 3,051 tok/s (+46%) on v5e-1 124m b=8 (round 4).  Depth-
    # gated: full unroll of the 48-layer 1.5b failed to compile in the
    # training sweep (remote_compile 500), so deep presets stay scanned.
    base = ALL_PRESETS[model_name]
    cfg = _dc.replace(base, param_dtype=jnp.bfloat16, remat=False,
                      scan_unroll=base.n_layer <= 24)
    model = build_model(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(0))
    idx = jax.random.randint(jax.random.PRNGKey(1), (b, prompt_t), 0,
                             cfg.vocab_size, jnp.int32)
    out = model.generate(params, idx, new_tokens, temperature=0.0)
    float(out[0, -1])  # warm + sync (compile both prefill and decode jits)
    iters = 5
    t0 = time.perf_counter()
    for _ in range(iters):
        out = model.generate(params, idx, new_tokens, temperature=0.0)
    float(out[0, -1])
    dt = (time.perf_counter() - t0) / iters
    return {
        "metric": f"{model_name}_decode_tokens_per_sec",
        "value": round(b * new_tokens / dt, 1),
        "unit": "tokens/s",
        "extra": {
            "batch": b, "prompt_t": prompt_t, "new_tokens": new_tokens,
            "latency_ms_per_token": round(dt / new_tokens * 1e3, 3),
        },
    }


def _kernel_stamp(paged_mode=None) -> dict:
    """The RESOLVED kernel-arm choices for this invocation — stamped
    into serve/spec/tune extras so a record can never claim a kernel it
    fell back from: the paged-attention mode and what it dispatches on
    this backend, the fp8 matmul mode, and the applied tuned-plan hash
    (empty when no plan was consumed)."""
    from tiny_deepspeed_tpu.ops.matmul_fp8 import fp8_matmul_mode
    from tiny_deepspeed_tpu.ops.paged_attn_pallas import (
        effective_paged_kernel, paged_kernel_forced,
    )
    mode = (paged_mode if paged_mode is not None
            else os.environ.get("BENCH_PAGED_KERNEL", "auto"))
    with paged_kernel_forced(mode):
        eff = effective_paged_kernel()
    return {
        "paged_kernel": mode,
        "paged_kernel_effective": eff,
        "fp8_matmul": fp8_matmul_mode(),
        "tune_plan": os.environ.get("BENCH_TUNE_PLAN", ""),
    }


def _tune_cache_path() -> str:
    """Where run_tune_e2e WRITES its plan (BENCH_TUNE_CACHE, default
    artifacts/autotune_cache.json)."""
    return os.environ.get("BENCH_TUNE_CACHE", os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "artifacts", "autotune_cache.json"))


def _mesh_desc():
    import jax
    return f"{jax.device_count()}dev", jax.default_backend()


def _tuned_plan(model_name: str):
    """The persisted tune_e2e plan entry for (model, mesh, backend), or
    None.  Read ONLY from a cache passed explicitly (BENCH_TUNE_CACHE):
    a generated file left under artifacts/ by an earlier run must not
    change what a default invocation measures.  Consumers that take a
    knob from it must export the plan hash into BENCH_TUNE_PLAN so the
    record's kernel stamp names the plan."""
    from tiny_deepspeed_tpu.autotuner import RuntimeAutoTuner, plan_key
    path = os.environ.get("BENCH_TUNE_CACHE")
    if not path or not os.path.exists(path):
        return None
    tuner = RuntimeAutoTuner()
    tuner.load(path)
    mesh, backend = _mesh_desc()
    return tuner.get_plan(plan_key(model_name, mesh, backend))


def run_serve(model_name: str, b=None, t=None):
    """Serving-tier throughput: continuous batching over the paged KV
    pool under the synthetic arrivals driver (serving/driver.py — the
    same code path scripts/serve_bench.py and the tests drive), tokens/s
    with p50/p99 per-token latency and batch occupancy in extra.
    BENCH_SERVE=1 selects this mode.  BENCH_PAGED_KERNEL=auto|on|off is
    the Pallas paged-attention A/B arm (ServeConfig.paged_kernel);
    extra.kernels stamps the RESOLVED choices."""
    import dataclasses as _dc

    import jax
    import jax.numpy as jnp
    from tiny_deepspeed_tpu.models import ALL_PRESETS, build_model
    from tiny_deepspeed_tpu.serving import ServeConfig, ServingEngine
    from tiny_deepspeed_tpu.serving.driver import (
        Arrival, poisson_trace, run_trace,
    )
    from tiny_deepspeed_tpu.telemetry.slo import SLOObjective, SLOTracker

    del b, t
    n_req = int(os.environ.get("BENCH_SERVE_REQUESTS", "12"))
    max_new = int(os.environ.get("BENCH_SERVE_NEW_TOKENS", "64"))
    max_active = int(os.environ.get("BENCH_SERVE_ACTIVE", "4"))
    quant = os.environ.get("BENCH_SERVE_QUANT") or None
    rate = os.environ.get("BENCH_SERVE_RATE")
    rate = float(rate) if rate else None  # default: closed-loop capacity
    prompt_lens = [int(x) for x in os.environ.get(
        "BENCH_SERVE_PROMPTS", "32,64,128").split(",")]

    base = ALL_PRESETS[model_name]
    cfg = _dc.replace(base, param_dtype=jnp.bfloat16, remat=False,
                      scan_unroll=base.n_layer <= 24)
    model = build_model(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(0))
    bt = 16
    # full capacity for max_active worst-case requests (+1 slack block):
    # occupancy, not preemption, is what this record measures; the
    # decode panel sizes to the workload, not the model context
    worst = -(-(max(prompt_lens) + max_new) // bt)
    serve_cfg = ServeConfig(
        max_active=max_active, num_blocks=max_active * worst + 1,
        block_tokens=bt, quant=quant, temperature=0.0,
        max_seq_tokens=min(worst * bt, cfg.block_size),
        paged_kernel=os.environ.get("BENCH_PAGED_KERNEL", "auto"),
    )

    eng = ServingEngine(model, params, serve_cfg)
    # warm on the SAME engine (fresh engines own fresh jit closures):
    # one request per distinct prompt length covers every prefill
    # bucket, closed-loop covers the decode step — compiles stay out of
    # the measured wall, and no Poisson sleeps during warmup
    run_trace(eng, [Arrival(0.0, [0] * p, min(2, max_new))
                    for p in sorted(set(prompt_lens))], realtime=False)
    trace = poisson_trace(
        n_req, rate_rps=rate, prompt_lens=prompt_lens,
        max_new_tokens=max_new, vocab_size=cfg.vocab_size, seed=0,
    )
    # SLO attainment rides the record (extra.slo.attainment): with a
    # latency objective matched to the closed-loop run it is a
    # higher-is-better service-quality fingerprint perf_diff.py's
    # sentinel watches — tokens/s can hold while attainment rots (e.g.
    # a scheduler change that trades tail latency for batch occupancy)
    slo = SLOTracker(default=SLOObjective(target=0.99, latency_s=120.0))
    res = run_trace(eng, trace, realtime=rate is not None, slo=slo)
    return {
        "metric": f"{model_name}_serve_tokens_per_sec",
        "value": res["tokens_per_s"],
        "unit": "tokens/s",
        "extra": {
            "requests": n_req, "max_new_tokens": max_new,
            "max_active": max_active, "rate_rps": rate,
            "kv_quant": quant, "prompt_lens": prompt_lens,
            "p50_token_latency_ms": res["token_latency"]["p50_ms"],
            "p99_token_latency_ms": res["token_latency"]["p99_ms"],
            "ttft_p50_ms": res["ttft"]["p50_ms"],
            # where the trace's request-seconds went (queue/prefill/
            # decode/preempt/restart — serving/driver.py aggregate of
            # the per-request latency partition)
            "latency_components_s": res["latency_components_s"],
            "occupancy": res["mean_occupancy"],
            "pool_utilization": res["mean_pool_utilization"],
            "pool_kv_bytes": eng.pool.kv_bytes()["kv_block_bytes"],
            # terminal outcomes (all "ok" on this fault-free record;
            # anything else means the bench itself mis-served)
            "status_counts": res["status_counts"],
            # resolved kernel arms: the record can never claim a
            # kernel choice that fell back on this backend
            "kernels": _kernel_stamp(serve_cfg.paged_kernel),
            # service-quality fingerprint (schema v15 SLO accounting):
            # fraction of requests that met the default objective
            "slo": {"attainment": res["slo"]["attainment"],
                    "alerts": len(res["slo"]["alerts"])},
        },
    }


def resolve_spec_k(model_name: str, env=None, plan_entry=None):
    """(spec_k, source) for a spec serving run: BENCH_SPEC_K when set
    ("env"), else the persisted tune_e2e plan's spec_k ("plan"), else
    the hand-set default 4 ("default").  Consuming a plan knob exports
    the plan's hash into BENCH_TUNE_PLAN so the record's kernel stamp
    (`_kernel_stamp`) distinguishes runs under different tuned plans —
    the round-trip tests/test_paged_kernel.py pins."""
    env = os.environ if env is None else env
    raw = env.get("BENCH_SPEC_K")
    if raw:
        return int(raw), "env"
    if plan_entry is None:
        plan_entry = _tuned_plan(model_name)
    if plan_entry and "spec_k" in plan_entry.get("plan", {}):
        env.setdefault("BENCH_TUNE_PLAN", plan_entry["hash"])
        return int(plan_entry["plan"]["spec_k"]), "plan"
    return 4, "default"


def run_spec_ab(model_name: str):
    """Speculative-decoding A/B: the SAME closed-loop trace through the
    serving engine with speculation OFF then ON (BENCH_SPEC=1 selects
    this mode; BENCH_SPEC_DRAFT default "ngram", BENCH_SPEC_K default
    4).  The headline value is the spec-on COMMITTED tokens/s; extra
    carries the plain baseline, the speedup ratio, the acceptance rate
    both as a number and as the serve_spec_accept_rate gauge in the
    telemetry sidecar, and a greedy token-parity check between the two
    passes (speculation must change throughput, never tokens).

    Workload: a RANDOM-INIT model's greedy output is aperiodic, so no
    drafter can predict it and any spec A/B on it measures only the
    adversarial floor.  BENCH_SPEC therefore first trains the model
    briefly (BENCH_SPEC_TRAIN_STEPS, default 400 AdamW steps on
    synthetic periodic sequences — ~15 s for the tiny preset on the
    CPU mesh): a partially-trained model's greedy decode collapses
    into self-repetition, which is exactly the context-echoing regime
    (templates, code, retrieval paste-ins) prompt-lookup drafting
    exists for.  BENCH_SPEC_PROMPT="repeat" (default) tiles each
    prompt from a short random motif; "random" draws uniform prompts;
    BENCH_SPEC_TRAIN_STEPS=0 skips training and measures the
    random-init floor."""
    import dataclasses as _dc

    import jax
    import numpy as np
    from tiny_deepspeed_tpu import AdamW, SingleDevice
    from tiny_deepspeed_tpu.models import ALL_PRESETS, build_model
    from tiny_deepspeed_tpu.serving import ServeConfig, ServingEngine
    from tiny_deepspeed_tpu.serving.driver import Arrival, run_trace
    from tiny_deepspeed_tpu.telemetry import Telemetry
    from tiny_deepspeed_tpu.telemetry.schema import SCHEMA_VERSION
    from tiny_deepspeed_tpu.utils.profiling import MetricsLogger

    n_req = int(os.environ.get("BENCH_SPEC_REQUESTS", "8"))
    max_new = int(os.environ.get("BENCH_SPEC_NEW_TOKENS", "48"))
    max_active = int(os.environ.get("BENCH_SPEC_ACTIVE", "4"))
    drafter = os.environ.get("BENCH_SPEC_DRAFT", "ngram")
    # spec_k resolution: explicit env > the persisted tune_e2e plan for
    # this (model, mesh, backend) > the hand-set default.  A plan-chosen
    # spec_k exports the plan hash into BENCH_TUNE_PLAN FIRST, so the
    # record's kernel stamp names the plan the value came from
    spec_k, spec_k_source = resolve_spec_k(model_name)
    prompt_mode = os.environ.get("BENCH_SPEC_PROMPT", "repeat")
    plen = int(os.environ.get("BENCH_SPEC_PROMPT_TOKENS", "32"))
    train_steps = int(os.environ.get("BENCH_SPEC_TRAIN_STEPS", "400"))

    base = ALL_PRESETS[model_name]
    cfg = _dc.replace(base, remat=False)
    model = build_model(cfg)
    # training consumes its own rng: the PROMPT stream must be
    # identical whatever BENCH_SPEC_TRAIN_STEPS is, or the "same A/B
    # over the untrained model" would quietly be a different workload
    rng = np.random.default_rng(1)
    prompt_rng = np.random.default_rng(2)
    if train_steps:
        eng_t = SingleDevice(model, AdamW(lr=1e-3))
        state = eng_t.init(jax.random.PRNGKey(0))
        t_train = min(64, cfg.block_size)

        def train_batch():
            xs = []
            for _ in range(8):
                m = rng.integers(2, 5)
                motif = rng.integers(0, cfg.vocab_size, m)
                xs.append(np.tile(
                    motif, -(-(t_train + 1) // m))[:t_train + 1])
            a = np.asarray(xs, np.int32)
            return a[:, :-1], a[:, 1:]

        for _ in range(train_steps):
            state, _loss = eng_t.step(state, train_batch())
        params = state.params
    else:
        params = jax.jit(model.init)(jax.random.PRNGKey(0))

    prompts = []
    for _ in range(n_req):
        if prompt_mode == "repeat":
            motif = prompt_rng.integers(0, cfg.vocab_size, size=4)
            prompts.append(np.tile(motif, -(-plen // 4))[:plen].tolist())
        else:
            prompts.append(
                prompt_rng.integers(0, cfg.vocab_size,
                                    size=plen).tolist())
    trace = [Arrival(0.0, pr, max_new) for pr in prompts]

    bt = 16
    worst = -(-(plen + max_new) // bt)
    serve_kw = dict(
        max_active=max_active, num_blocks=max_active * worst + 1,
        block_tokens=bt, temperature=0.0,
        max_seq_tokens=min(worst * bt, cfg.block_size),
        paged_kernel=os.environ.get("BENCH_PAGED_KERNEL", "auto"),
    )

    passes = int(os.environ.get("BENCH_SPEC_PASSES", "3"))

    def measure(spec):
        eng = ServingEngine(model, params, ServeConfig(
            **serve_kw,
            spec_draft=drafter if spec else None, spec_k=spec_k))
        # warm the SAME engine's jits (prefill bucket + decode/verify
        # + drafter rollout) so the measured pass is serving, not XLA
        run_trace(eng, [Arrival(0.0, prompts[0], min(4, max_new))],
                  realtime=False)
        tel = logger = None
        if spec:
            tel = Telemetry()
            path = os.path.join(
                os.path.dirname(os.path.abspath(__file__)),
                "artifacts", "bench_spec_run.jsonl")
            os.makedirs(os.path.dirname(path), exist_ok=True)
            if os.path.exists(path):
                os.remove(path)
            logger = MetricsLogger(path, stdout=False)
            logger.log_meta(schema_version=SCHEMA_VERSION,
                            engine=f"spec:{model_name}",
                            model=model_name,
                            devices=jax.device_count(),
                            serve=dict(**serve_kw, spec_draft=drafter,
                                       spec_k=spec_k))
            eng.telemetry, eng.logger = tel, logger
        # best-of-N on the warm engine, SAME treatment for both arms:
        # single-shot walls on the shared 2-vCPU box swing several x
        # between back-to-back runs, which would let scheduler noise
        # decide the A/B's sign (greedy tokens are identical each
        # pass, so the best pass measures the same work)
        res = None
        for _ in range(max(1, passes)):
            r = run_trace(eng, trace, realtime=False)
            if res is None or r["tokens_per_s"] > res["tokens_per_s"]:
                res = r
        if logger is not None:
            tel.flush(logger)
            logger.close()
        return res

    plain = measure(spec=False)
    spec = measure(spec=True)
    # outputs key on GLOBAL request ids (fresh per engine) — parity is
    # positional over the shared trace's submission order
    parity = (list(plain["outputs"].values())
              == list(spec["outputs"].values()))
    rec = {
        "metric": f"{model_name}_spec_tokens_per_sec",
        "value": spec["tokens_per_s"],
        "unit": "tokens/s",
        "extra": {
            "drafter": drafter, "spec_k": spec_k,
            "spec_k_source": spec_k_source,
            "kernels": _kernel_stamp(serve_kw["paged_kernel"]),
            "prompt_mode": prompt_mode, "requests": n_req,
            "prompt_tokens": plen, "max_new_tokens": max_new,
            "max_active": max_active,
            "passes": passes,
            "plain_tokens_per_s": plain["tokens_per_s"],
            "speedup": round(spec["tokens_per_s"]
                             / max(plain["tokens_per_s"], 1e-9), 3),
            "accept_rate": spec.get("spec", {}).get("accept_rate", 0.0),
            "drafts_proposed": spec.get("spec", {}).get("proposed", 0),
            "drafts_accepted": spec.get("spec", {}).get("accepted", 0),
            # greedy parity between the two passes: speculation may only
            # change the speed, never the tokens
            "token_parity": parity,
            "status_counts": spec["status_counts"],
            "telemetry_jsonl": "artifacts/bench_spec_run.jsonl",
        },
    }
    return rec


def run_prefix_ab(model_name: str):
    """Shared-prefix KV-reuse A/B: the SAME Zipf shared-prefix trace
    through the serving engine with the prefix cache OFF then ON
    (BENCH_PREFIX=1 selects this mode).  The workload is the
    millions-of-users shape: BENCH_PREFIX_POOL distinct system prompts
    (default 4) of BENCH_PREFIX_LEN tokens (default 64), Zipf-weighted
    (BENCH_PREFIX_ZIPF, default 1.2), short random suffixes — so most
    admissions re-prefill a prompt the pool already holds.  The
    headline value is the cache-ON tokens/s; extra carries the OFF
    baseline, TTFT p50/p99 both ways, the measured
    prefill-tokens-avoided / hit rate, and a greedy token-parity check
    between the passes (aliasing changes where K/V is READ from, never
    the tokens)."""
    import dataclasses as _dc

    import jax
    import jax.numpy as jnp
    from tiny_deepspeed_tpu.models import ALL_PRESETS, build_model
    from tiny_deepspeed_tpu.serving import ServeConfig, ServingEngine
    from tiny_deepspeed_tpu.serving.driver import (
        Arrival, run_trace, shared_prefix_trace,
    )

    n_req = int(os.environ.get("BENCH_PREFIX_REQUESTS", "16"))
    max_new = int(os.environ.get("BENCH_PREFIX_NEW_TOKENS", "32"))
    max_active = int(os.environ.get("BENCH_PREFIX_ACTIVE", "4"))
    pool_n = int(os.environ.get("BENCH_PREFIX_POOL", "4"))
    plen = int(os.environ.get("BENCH_PREFIX_LEN", "64"))
    zipf = float(os.environ.get("BENCH_PREFIX_ZIPF", "1.2"))
    slens = [int(x) for x in os.environ.get(
        "BENCH_PREFIX_SUFFIX", "8,16").split(",")]
    passes = int(os.environ.get("BENCH_PREFIX_PASSES", "3"))

    base = ALL_PRESETS[model_name]
    cfg = _dc.replace(base, param_dtype=jnp.bfloat16, remat=False,
                      scan_unroll=base.n_layer <= 24)
    model = build_model(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(0))
    trace = shared_prefix_trace(
        n_req, rate_rps=None, prefix_pool=pool_n, prefix_len=plen,
        suffix_lens=slens, zipf_a=zipf, max_new_tokens=max_new,
        vocab_size=cfg.vocab_size, seed=0,
    )
    bt = 16
    worst = -(-(plen + max(slens) + max_new) // bt)
    serve_kw = dict(
        max_active=max_active,
        # headroom for the warm tree on top of the active worst case —
        # the A/B measures reuse, not pressure-eviction behavior
        num_blocks=(max_active + 2) * worst + 1,
        block_tokens=bt, temperature=0.0,
        max_seq_tokens=min(worst * bt, cfg.block_size),
    )

    def measure(prefix_on):
        eng = ServingEngine(model, params, ServeConfig(
            **serve_kw, prefix_cache=prefix_on))
        # warm the SAME engine's jits: two identical-prompt requests
        # cover the full-prefill bucket, the decode step, AND (cache
        # on) the suffix-bucket program via the second request's hit —
        # both arms then measure serving, not XLA compiles.  Passes
        # run on the warm engine, so the cache-on arm measures the
        # steady state a long-lived server actually serves from.
        warm = [Arrival(0.0, list(trace[0].prompt), min(2, max_new)),
                Arrival(0.0, list(trace[0].prompt), min(2, max_new))]
        run_trace(eng, warm, realtime=False)
        best = None
        for _ in range(max(1, passes)):
            if eng._prefix is not None:
                # per-pass hit-rate stats: the best pass's numbers
                # must describe ONE traversal of the trace, not the
                # warmup plus every earlier pass
                eng._prefix.reset_stats()
            r = run_trace(eng, trace, realtime=False)
            if best is None or r["tokens_per_s"] > best["tokens_per_s"]:
                best = r
        return best

    off = measure(prefix_on=False)
    on = measure(prefix_on=True)
    parity = (list(off["outputs"].values())
              == list(on["outputs"].values()))
    pc = on.get("prefix_cache") or {}
    rec = {
        "metric": f"{model_name}_prefix_tokens_per_sec",
        "value": on["tokens_per_s"],
        "unit": "tokens/s",
        "extra": {
            "requests": n_req, "prefix_pool": pool_n,
            "prefix_len": plen, "zipf_a": zipf,
            "suffix_lens": slens, "max_new_tokens": max_new,
            "max_active": max_active, "passes": passes,
            "off_tokens_per_s": off["tokens_per_s"],
            "speedup": round(on["tokens_per_s"]
                             / max(off["tokens_per_s"], 1e-9), 3),
            "ttft_p50_ms_off": off["ttft"]["p50_ms"],
            "ttft_p50_ms_on": on["ttft"]["p50_ms"],
            "ttft_p99_ms_off": off["ttft"]["p99_ms"],
            "ttft_p99_ms_on": on["ttft"]["p99_ms"],
            "prefill_tokens_avoided": pc.get(
                "prefill_tokens_avoided", 0),
            "hit_rate": pc.get("hit_rate", 0.0),
            "blocks_aliased": pc.get("blocks_aliased", 0),
            "token_parity": parity,
        },
    }
    return rec


def _ratio(num, den):
    """round(num/den, 3), or None when either side is None (a failed
    tune_e2e baseline records score None, not a number)."""
    if num is None or den is None:
        return None
    return round(num / max(den, 1e-9), 3)


def run_tune_e2e(model_name: str):
    """ONE autotune over the whole knob space against END-TO-END
    objectives (BENCH_TUNE_E2E=1): greedy coordinate descent
    (autotuner.tune_e2e) over {scan_unroll, fp8 matmul, flash kernel
    blocks} against the MEASURED training step time, and over {spec_k,
    paged-attention kernel arm} against the MEASURED serving committed
    tok/s — closing the standalone-timing gap the per-op tuner has been
    caught in twice (adamw_pallas, the xent chunk ladder).  The winning
    joint plan persists per (model, mesh, backend) in the AOT autotune
    cache (BENCH_TUNE_CACHE, default artifacts/autotune_cache.json);
    later invocations consume it (run_spec_ab's spec_k resolution) with
    the plan hash exported into BENCH_TUNE_PLAN.  The record carries
    the full A/B evidence: default-plan and tuned-plan scores for both
    objectives plus every trial."""
    import dataclasses as _dc

    import jax
    import jax.numpy as jnp
    import numpy as np
    from tiny_deepspeed_tpu import AdamW, SingleDevice
    from tiny_deepspeed_tpu.autotuner import (
        RuntimeAutoTuner, plan_hash, plan_key, tune_e2e,
    )
    from tiny_deepspeed_tpu.models import ALL_PRESETS, build_model
    from tiny_deepspeed_tpu.ops import matmul_fp8
    from tiny_deepspeed_tpu.ops.attention_pallas import (
        FLASH_VARIANTS, promote_flash_variant,
    )
    from tiny_deepspeed_tpu.ops.dispatch import kernel_target
    from tiny_deepspeed_tpu.serving import ServeConfig, ServingEngine
    from tiny_deepspeed_tpu.serving.driver import Arrival, run_trace

    b = int(os.environ.get("BENCH_TUNE_BATCH", "4"))
    base = ALL_PRESETS[model_name]
    t = min(int(os.environ.get("BENCH_TUNE_SEQ", "256")), base.block_size)
    iters = int(os.environ.get("BENCH_TUNE_ITERS", "8"))

    # -- training objective: measured step seconds -------------------------
    train_space = {
        "scan_unroll": [base.scan_unroll, True],
        "fp8_matmul": ["off", "on"],
    }
    if kernel_target() == "tpu":
        # kernel block sizes: whole-step A/B per flash variant (the
        # promote seam), not standalone kernel timings
        train_space["flash_block"] = [f.__name__ for f in FLASH_VARIANTS[:3]]

    # restore the PROCESS-ENTRY fp8 mode after every trial (a
    # BENCH_FP8_MATMUL=on invocation must not have its mode clobbered
    # to "off" by the search — the kernel stamp still claims "on")
    fp8_entry_mode = matmul_fp8.fp8_matmul_mode()

    def measure_train(plan):
        cfg = _dc.replace(base, scan_unroll=plan["scan_unroll"])
        if "flash_block" in plan:
            promote_flash_variant(plan["flash_block"])
        matmul_fp8.set_fp8_matmul(plan["fp8_matmul"])
        try:
            eng = SingleDevice(build_model(cfg), AdamW(lr=1e-4))
            state = eng.init(jax.random.PRNGKey(0))
            idx = jax.random.randint(jax.random.PRNGKey(1), (b, t), 0,
                                     cfg.vocab_size, jnp.int32)
            step_s, _ = measure(eng, state, (idx, idx), warmup=2,
                                iters=iters)
            return step_s
        finally:
            matmul_fp8.set_fp8_matmul(fp8_entry_mode)

    train_plan, train_s, train_trials = tune_e2e(
        measure_train, train_space, objective="min")
    if "flash_block" in train_plan:
        # coordinate descent leaves FLASH_VARIANTS ordered by the LAST
        # trial measured — re-promote the WINNER so the serve phase and
        # everything after runs the plan, not an arbitrary leftover
        promote_flash_variant(train_plan["flash_block"])

    # -- serving objective: measured committed tokens/s --------------------
    model = build_model(_dc.replace(base, remat=False))
    params = jax.jit(model.init)(jax.random.PRNGKey(0))
    n_req = int(os.environ.get("BENCH_TUNE_REQUESTS", "6"))
    max_new = int(os.environ.get("BENCH_TUNE_NEW_TOKENS", "24"))
    plen = 16
    rng = np.random.default_rng(2)
    prompts = []
    for _ in range(n_req):  # repeat-motif prompts: the ngram regime
        motif = rng.integers(0, base.vocab_size, size=4)
        prompts.append(np.tile(motif, -(-plen // 4))[:plen].tolist())
    bt = 8
    worst = -(-(plen + max_new) // bt)
    serve_kw = dict(
        max_active=4, num_blocks=4 * worst + 1, block_tokens=bt,
        temperature=0.0,
        max_seq_tokens=min(worst * bt, base.block_size),
    )
    serve_space = {"spec_k": [4, 2, 8]}
    # the kernel A/B arm exists only where "off" differs from "auto"
    # (TPU targets); on the CPU mesh auto already IS the XLA path
    serve_space["paged_kernel"] = (
        ["auto", "off"] if kernel_target() == "tpu" else ["auto"])

    def measure_serve(plan):
        eng = ServingEngine(model, params, ServeConfig(
            **serve_kw, spec_draft="ngram", spec_k=plan["spec_k"],
            paged_kernel=plan["paged_kernel"]))
        run_trace(eng, [Arrival(0.0, prompts[0], 4)], realtime=False)
        res = run_trace(eng, [Arrival(0.0, p, max_new) for p in prompts],
                        realtime=False)
        return res["tokens_per_s"]

    serve_plan, serve_tok, serve_trials = tune_e2e(
        measure_serve, serve_space, objective="max")

    # -- comm objective: measured step time + measured ledger wire ---------
    # The wire-agenda phase (multi-chip only — a single chip runs no
    # gradient collective): coordinate descent over the comm knob space
    # {codec, bucket count, tail codec, hpz on/off + codec, "auto"},
    # each trial scored by MEASURED step seconds plus the compiled
    # step's MEASURED loop-resident wire priced at an assumed 100 GB/s
    # — the wire term breaks step-time ties toward the plan that also
    # moves fewer bytes (on the CPU mesh step time barely sees wire;
    # on a real pod both terms pull the same way).  Infeasible combos
    # (tail codec without a quantized grad slot) raise inside the
    # engine and score worst — tune_e2e's standard failure handling.
    comm_plan, comm_trials = {}, []
    comm_s = None
    n_chips = len(jax.devices())
    if n_chips > 1:
        from tiny_deepspeed_tpu import Zero3, make_mesh
        from tiny_deepspeed_tpu.parallel.mesh import granule_map
        from tiny_deepspeed_tpu.parallel.schedule import (
            comm_plan_engine_kwargs,
        )
        from tiny_deepspeed_tpu.utils.hlo_comm import (
            collective_ledger, overlap_report,
        )
        cmesh = make_mesh()
        hgran = granule_map(cmesh.devices.flatten())
        if hgran is None and n_chips % 2 == 0:
            # the emulated 2-slice split the wire_link_split tests pin
            hgran = {i: i // (n_chips // 2) for i in range(n_chips)}
        nl = int(base.n_layer)
        comm_space = {
            "grad_comm": ["auto", "int8", "fp8", "fp32"],
            "grad_buckets": [1] + [k for k in (2, 4)
                                   if nl % k == 0 and k <= nl],
            "grad_comm_tail": ["fp32", "int8"],
        }
        if hgran is not None:
            comm_space["hpz"] = [False, True]
            comm_space["hpz_comm"] = ["fp32", "fp8"]
        wire_bw = 100e9  # assumed link GB/s for the tie-break term

        def measure_comm(plan):
            kw = comm_plan_engine_kwargs(plan)
            if not kw.get("hpz"):
                kw.pop("hpz_comm", None)
            elif hgran is not None:
                kw["hpz_granule_of"] = hgran
            eng = Zero3(build_model(base), AdamW(lr=1e-4), mesh=cmesh,
                        **kw)
            state = eng.init(jax.random.PRNGKey(0))
            idx = jax.random.randint(jax.random.PRNGKey(1),
                                     (b * n_chips, t), 0,
                                     base.vocab_size, jnp.int32)
            step_s, _ = measure(eng, state, (idx, idx), warmup=2,
                                iters=iters)
            rep = overlap_report(
                eng._step.lower(state, (idx, idx)).compile().as_text())
            wire = (rep["reduce_wire_bytes_total"]
                    + rep["gather_wire_bytes_total"])
            return step_s + wire / wire_bw

        comm_plan, comm_s, comm_trials = tune_e2e(
            measure_comm, comm_space, objective="min")
        if not comm_plan.get("hpz"):
            comm_plan.pop("hpz_comm", None)

    # -- persist + record --------------------------------------------------
    plan = {**train_plan, **serve_plan, **comm_plan}
    mesh, backend = _mesh_desc()
    key = plan_key(model_name, mesh, backend)
    record = {
        "train_step_s_default": train_trials[0]["score"],
        "train_step_s_tuned": train_s,
        "serve_tok_s_default": serve_trials[0]["score"],
        "serve_tok_s_tuned": serve_tok,
        "train_trials": len(train_trials),
        "serve_trials": len(serve_trials),
        "batch": b, "seq": t, "backend": backend, "mesh": mesh,
    }
    if comm_trials:
        record.update(
            comm_score_default=comm_trials[0]["score"],
            comm_score_tuned=comm_s,
            comm_trials=len(comm_trials),
            comm_plan={k: comm_plan[k] for k in sorted(comm_plan)},
        )
    path = _tune_cache_path()
    os.makedirs(os.path.dirname(path), exist_ok=True)
    tuner = RuntimeAutoTuner()
    if os.path.exists(path):
        try:
            tuner.load(path)  # other configs' winners/plans survive
        except (OSError, ValueError):
            pass
    # merge: a partial re-tune (e.g. a comm-only sweep on a new mesh
    # window) folds into the stored plan instead of dropping the other
    # phases' winners
    tuner.store_plan(key, plan, record, merge=True)
    tuner.save(path)
    # the produced plan governs THIS record's kernel stamp too
    os.environ["BENCH_TUNE_PLAN"] = plan_hash(plan)

    # autotune decisions as run_meta records (the telemetry-path
    # satellite applied to the e2e tuner's own output)
    side = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "artifacts", "bench_tune_e2e.jsonl")
    try:
        from tiny_deepspeed_tpu.telemetry.schema import SCHEMA_VERSION
        from tiny_deepspeed_tpu.utils.profiling import MetricsLogger
        if os.path.exists(side):
            os.remove(side)
        with MetricsLogger(side, stdout=False) as ml:
            ml.log_meta(schema_version=SCHEMA_VERSION, model=model_name,
                        autotune={
                            "event": "tune_e2e", "plan": plan,
                            "plan_hash": plan_hash(plan), "record": record,
                            "train_trials": train_trials,
                            "serve_trials": serve_trials,
                            "comm_trials": comm_trials,
                        })
    except OSError:
        pass

    return {
        "metric": f"{model_name}_tune_e2e_tokens_per_sec",
        "value": serve_tok,
        "unit": "tokens/s",
        "extra": {
            "plan": plan, "plan_hash": plan_hash(plan), "plan_key": key,
            "cache_path": os.path.relpath(
                path, os.path.dirname(os.path.abspath(__file__))),
            **record,
            # None-safe: a failed DEFAULT measurement records score None
            # (tune_e2e's infeasible marker) — the speedup is then
            # unknown, not a crash after the whole search already ran
            "train_speedup": _ratio(record["train_step_s_default"],
                                    record["train_step_s_tuned"]),
            "serve_speedup": _ratio(record["serve_tok_s_tuned"],
                                    record["serve_tok_s_default"]),
            "kernels": _kernel_stamp(serve_plan.get("paged_kernel")),
            "telemetry_jsonl": "artifacts/bench_tune_e2e.jsonl",
        },
    }


def _round_number(path: str) -> int:
    m = re.search(r"BENCH_r(\d+)\.json$", path)
    return int(m.group(1)) if m else -1


def _prev_round_value():
    """Latest prior round's nonzero headline value, or None on a fresh
    cycle (no usable BENCH_r*.json — the trajectory is []).  Rounds order
    NUMERICALLY: from round 10 on, a lexicographic sort would put r9
    ahead of r10 and compare against the wrong round."""
    for path in sorted(
            glob.glob(os.path.join(
                os.path.dirname(os.path.abspath(__file__)),
                "BENCH_r*.json")),
            key=_round_number, reverse=True):
        try:
            with open(path) as f:
                rec = json.load(f)
            prev_val = rec.get("value")
            if prev_val is None and isinstance(rec.get("parsed"), dict):
                prev_val = rec["parsed"].get("value")
            if prev_val:
                return prev_val
        except Exception:
            continue
    return None


def _vs_prev_round(value: float) -> float:
    prev = _prev_round_value()
    return round(value / prev, 3) if prev else 1.0


def _write_bench_telemetry(path, engine, state, batch, compiled_text,
                           model_name, n_chips, b, t, peak_flops,
                           steps=5, flops_tok_matmul=None, hlo_cost=None):
    """Telemetry sidecar for the bench record: a run_meta line (measured
    HLO-ledger collective bytes next to the comm_report model, AOT-known
    geometry) plus a few instrumented per-step records — written AFTER the
    headline measurement so the per-step sync barriers cannot perturb it.
    The JSONL renders with scripts/report_run.py; the record's
    extra.telemetry_jsonl points here."""
    from tiny_deepspeed_tpu.telemetry.schema import SCHEMA_VERSION
    from tiny_deepspeed_tpu.utils.hlo_comm import (
        collective_ledger, ledger_summary, overlap_report,
    )
    from tiny_deepspeed_tpu.utils.profiling import (
        MetricsLogger, StepTimer, comm_report,
    )

    if os.path.exists(path):
        os.remove(path)  # one run per file: the report reads a single run
    led = collective_ledger(compiled_text)
    measured = ledger_summary(led)
    overlap = overlap_report(compiled_text, led=led)
    timer = StepTimer()
    timer.watch(engine)
    with MetricsLogger(path, stdout=False) as ml:
        ml.log_meta(
            schema_version=SCHEMA_VERSION,
            engine=engine.describe(), model=model_name, devices=n_chips,
            n_params=engine.model.num_params(), batch=b, seq_len=t,
            tokens_per_step=b * t, peak_flops_per_chip=peak_flops,
            comm_model=comm_report(engine), comm_measured=measured,
            comm_overlap=overlap,
            # measured vs analytic compute accounting side by side —
            # report_run prefers the measured one for MFU, perf_diff
            # flags their divergence (formula rot)
            **({"flops_per_token_matmul": float(flops_tok_matmul)}
               if flops_tok_matmul is not None else {}),
            **({"hlo_cost": hlo_cost} if hlo_cost else {}),
        )
        for i in range(steps):
            with timer.step() as tm:
                state, loss = engine.step(state, batch)
                tm.observe(loss)
            ml.log(i, loss=timer.last_value, step_s=timer.times[-1],
                   tokens_per_s=b * t / max(timer.times[-1], 1e-9))
    return state


def main():
    from tiny_deepspeed_tpu.utils.startup import select_platform
    select_platform()  # TPU or exit non-zero, before anything compiles

    if "--sweep" in sys.argv:
        models = ["gpt2-124m", "gpt2-350m", "gpt2-774m", "gpt2-1.5b",
                  "llama-160m", "llama-1b", "moe-8x124m"]
        for name in models:
            rec = run_one(name, iters=10 if "1.5b" in name
                          or "774m" in name or "1b" in name else 30)
            rec["vs_baseline"] = 1.0
            print(json.dumps(rec), flush=True)
        return

    model_name = os.environ.get("BENCH_MODEL", "gpt2-124m")
    b = os.environ.get("BENCH_BATCH")
    t = int(os.environ.get("BENCH_SEQ", "1024"))
    if os.environ.get("BENCH_FP8_MATMUL"):
        # fp8 matmul arm (ops/matmul_fp8.py): applies to every mode's
        # traces in this process — run_one's training step, the serve
        # family's decode programs, and the fused-xent head
        from tiny_deepspeed_tpu.ops.matmul_fp8 import set_fp8_matmul
        set_fp8_matmul(os.environ["BENCH_FP8_MATMUL"])
    if os.environ.get("BENCH_TUNE_E2E"):
        rec = run_tune_e2e(model_name)
        rec["vs_baseline"] = rec["extra"]["serve_speedup"] or 1.0
    elif os.environ.get("BENCH_PREFIX"):
        rec = run_prefix_ab(model_name)
        rec["vs_baseline"] = rec["extra"]["speedup"]
    elif os.environ.get("BENCH_SPEC"):
        rec = run_spec_ab(model_name)
        rec["vs_baseline"] = rec["extra"]["speedup"]
    elif os.environ.get("BENCH_SERVE"):
        rec = run_serve(model_name)
        rec["vs_baseline"] = 1.0
    elif os.environ.get("BENCH_DECODE"):
        rec = run_decode(model_name, b=int(b) if b else 8)
        rec["vs_baseline"] = 1.0
    else:
        rec = run_one(model_name, b=int(b) if b else None, t=t)
        prev = _prev_round_value()
        if prev is None:
            # fresh cycle (trajectory []): emit the neutral baseline
            # ratio EXPLICITLY and label it, so the driver's trajectory
            # starts at a defined 1.0 instead of an accidental default
            rec["vs_baseline"] = 1.0
            rec.setdefault("extra", {})["fresh_cycle"] = True
        else:
            rec["vs_baseline"] = round(rec["value"] / prev, 3)
    print(json.dumps(rec))


if __name__ == "__main__":
    main()
