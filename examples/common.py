# Copyright 2026 tiny-deepspeed-tpu authors
# SPDX-License-Identifier: Apache-2.0

"""Shared harness for the five train entry points.

Parity with the reference example scripts (example/{single_device,ddp,zero1,
zero2,zero3}/train.py): seed, random token batches of (B, T=1024), model +
engine construction, a 100-iteration loop printing per-iter loss from process
0.  Differences, deliberate:

  * one global batch sharded over the mesh replaces per-rank private batches
    (the reference seeds *differently per rank* — quirk #14 — so its global
    batch is implicit; here it is explicit);
  * `jax.distributed.initialize`/mesh replaces torchrun env:// rendezvous;
  * hyperparameters mirror the reference: AdamW lr=1e-5, wd=0.1, 100 iters
    (reference ddp/train.py:27-29).
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from tiny_deepspeed_tpu import (
    AdamW,
    init_distributed,
    make_mesh,
)
from tiny_deepspeed_tpu.models import ALL_PRESETS, build_model
from tiny_deepspeed_tpu.utils.profiling import span
from tiny_deepspeed_tpu.utils.startup import select_platform


def parse_args(default_model="gpt2-124m", argv=None, **defaults):
    """`defaults` overrides any flag's default (explicit flags still win).
    `argv` (default: the process's own) lets a programmatic caller build
    the same Namespace the command line does."""
    p = argparse.ArgumentParser()
    p.add_argument(
        "--cpu-devices", type=int, default=0, metavar="N",
        help="debug: run on N virtual CPU devices instead of the TPU "
             "(JAX host-platform trick; lets every ZeRO mode run without "
             "a pod — the reference has no such story, SURVEY §4)",
    )
    p.add_argument(
        "--model", default=None, choices=sorted(ALL_PRESETS),
        help=f"default {default_model}; under --cpu-devices the default "
             "drops to 'tiny' so every entry point smoke-tests in seconds "
             "(XLA-CPU compile of a full-size step takes minutes)",
    )
    p.add_argument("--iters", type=int, default=100)
    p.add_argument("--batch-per-device", type=int, default=1)
    p.add_argument("--seq-len", type=int, default=None,
                   help="default min(1024, model block_size)")
    p.add_argument("--lr", type=float, default=1e-5)
    p.add_argument(
        "--lr-schedule", default="constant",
        choices=("constant", "warmup_linear", "warmup_cosine",
                 "inverse_sqrt"),
        help="learning-rate schedule over --iters with --lr as the peak "
             "(optim/schedule.py; the reference hard-codes a constant lr, "
             "reference ddp/train.py:27)",
    )
    p.add_argument("--warmup-steps", type=int, default=0,
                   help="linear warmup steps for --lr-schedule")
    p.add_argument("--weight-decay", type=float, default=0.1)
    p.add_argument(
        "--wd-exclude", default=None, metavar="PAT[,PAT]",
        help="comma-separated name substrings exempt from weight decay "
             "(e.g. '.b,ln_' = biases + layernorms; default: decay all, "
             "the reference's behavior)",
    )
    p.add_argument(
        "--grad-clip", type=float, default=0.0, metavar="NORM",
        help="clip gradients to this global L2 norm (0 = off)",
    )
    p.add_argument(
        "--dropout", type=float, default=0.0, metavar="P",
        help="residual/embedding dropout rate (the reference's config knob, "
             "implemented working — its own wiring is dead code, reference "
             "model.py:79-81)",
    )
    p.add_argument(
        "--scan-unroll", action="store_true",
        help="fully unroll the transformer layer stack instead of "
             "lax.scan-ning it — deletes the scan's activation-stash "
             "slice traffic (round-4 chip profile: +16%% on gpt2-124m; "
             "BASELINE.md).  Avoid with ZeRO-3 (the scan bounds live "
             "gathered weights; the engine warns) and with very deep "
             "models (compile time grows with depth)",
    )
    p.add_argument(
        "--moe-dispatch", choices=("einsum", "sort"), default=None,
        help="MoE families only: token dispatch mechanism "
             "(MoEConfig.moe_dispatch — 'sort' skips the dense one-hot "
             "dispatch matmuls on single device)",
    )
    p.add_argument(
        "--gather-quant", choices=("fp8",), default=None,
        help="ZeRO++-style quantized weight gather: block weights stack "
             "as float8_e4m3 + stop-gradiented per-channel scales so the "
             "ZeRO-3 per-layer gathers move f8 bytes (TPU HLO: net -23%% "
             "wire vs unquantized, PROFILE.md finding 5; lossy — the CPU "
             "backend upcasts and gains nothing)",
    )
    def _loss_scale(v):
        if v == "dynamic":
            return v
        try:
            return float(v)
        except ValueError:
            raise argparse.ArgumentTypeError(
                f"{v!r} is not a number or 'dynamic'"
            )

    p.add_argument(
        "--loss-scale", type=_loss_scale, default=None, metavar="S",
        help="loss scaling: a number (static) or 'dynamic' (fp16 AMP; "
             "halve on overflow + skip the step, grow on a clean streak)",
    )
    p.add_argument("--seed", type=int, default=0)
    p.add_argument(
        "--tensor-parallel", type=int, default=1, metavar="TP",
        help="Megatron-style intra-layer sharding over a 'model' mesh axis",
    )
    p.add_argument(
        "--seq-parallel", type=int, default=1, metavar="SP",
        help="sequence/context parallelism over a 'seq' mesh axis",
    )
    p.add_argument(
        "--seq-impl", default="ring", choices=("ring", "ulysses"),
        help="sequence-parallel attention: ppermute ring (O(T/n) memory) "
             "or DeepSpeed-Ulysses all-to-all head/seq reshard",
    )
    p.add_argument(
        "--expert-parallel", type=int, default=1, metavar="EP",
        help="MoE expert sharding over an 'expert' mesh axis (use with the "
             "moe-* presets)",
    )
    p.add_argument(
        "--pipeline-parallel", type=int, default=1, metavar="PP",
        help="GPipe microbatch pipeline over a 'pipe' mesh axis "
             "(stacked blocks partition into PP stages)",
    )
    p.add_argument(
        "--pipeline-microbatches", type=int, default=0, metavar="M",
        help="in-flight pipeline microbatches (default PP; raise to "
             "amortize the (PP-1)/(M+PP-1) bubble)",
    )
    def _pipeline_schedule_arg(v):
        kind = v.partition(":")[0]
        if kind not in ("gpipe", "1f1b", "interleaved", "zbub"):
            raise argparse.ArgumentTypeError(
                f"{v!r}: schedule must be gpipe, 1f1b, interleaved or "
                f"zbub, optionally with a ':V' virtual-stage suffix "
                f"(e.g. interleaved:2)"
            )
        return v

    p.add_argument(
        "--pipeline-schedule",
        type=_pipeline_schedule_arg, default="gpipe", metavar="KIND[:V]",
        help="gpipe (autodiff, O(M) in-flight activations), 1f1b "
             "(combined fwd/bwd tick scan, O(PP) — raise M freely), or "
             "the table-driven schedules: interleaved (each stage holds "
             "V virtual chunks, --pipeline-virtual) and zbub "
             "(interleaved + zero-bubble backward split: dgrad on the "
             "critical path, wgrad fills the cooldown bubble) — both "
             "shrink the measured bubble_frac below 1f1b's "
             "(PP-1)/(M+PP-1)",
    )
    p.add_argument(
        "--pipeline-virtual", type=int, default=1, metavar="V",
        help="virtual chunks per stage for "
             "--pipeline-schedule interleaved/zbub (n_layer must divide "
             "by PP*V; the `--sched pipe=interleaved:V` spelling sets "
             "this too)",
    )
    p.add_argument(
        "--offload-opt-state", action="store_true",
        help="ZeRO-Offload-style placement: optimizer moments rest in "
             "host memory (pinned_host) instead of HBM; TPU runtime only",
    )
    p.add_argument(
        "--offload-prefetch", type=int, default=2, metavar="W",
        help="with --offload-opt-state: in-flight window of streamed "
             "moment leaves (>= 1; 1 = serial streaming, no double "
             "buffer; default 2; widening measured peak-HBM cost "
             "without schedule benefit at leaf granularity — PROFILE.md "
             "round-5 offload study)",
    )
    p.add_argument(
        "--grad-comm", choices=("fp32", "int8", "fp8"), default="fp32",
        help="gradient-collective precision (parallel/comm.py): int8/fp8 "
             "quantize the grad reduce-scatter/all-reduce blockwise with "
             "an error-feedback residual (~4x less gradient wire; pure "
             "data-parallel meshes, ZeRO stages 0-2)",
    )
    p.add_argument(
        "--grad-comm-groups", type=int, default=None, metavar="M",
        help="with --grad-comm int8/fp8: hierarchical 2-hop schedule — "
             "low-precision reduce-scatter inside M-rank groups, bf16 "
             "across groups (M must divide the data-axis size)",
    )
    p.add_argument(
        "--grad-buckets", type=int, default=1, metavar="K",
        help="bucketed backward-overlapped gradient release: split the "
             "gradient into K layer buckets (+ a non-block tail) and "
             "emit each bucket's collective INSIDE the backward scan, "
             "so its wire time overlaps the remaining backward compute "
             "(works with --grad-comm fp32/int8/fp8; K must divide "
             "n_layer; 1 = the monolithic schedule)",
    )
    p.add_argument(
        "--gather-prefetch", type=int, default=0, metavar="K",
        help="ZeRO-3 layer-ahead weight-gather prefetch "
             "(parallel/schedule.GatherPrefetchScan): the block scan issues "
             "layer k+(K-1)'s parameter all-gather while layer k "
             "computes, holding at most K layers' gathered weights (2 = "
             "double buffer), on the forward AND the remat backward; "
             "composes with --gather-quant fp8.  0/1 = the on-demand "
             "gather (byte-identical program); zero3 only",
    )
    p.add_argument(
        "--gather-groups", type=int, default=None, metavar="M",
        help="with --gather-prefetch >= 2: hierarchical 2-hop gather — "
             "resting precision (f8 under --gather-quant) within M-rank "
             "groups, compute dtype across groups (mirrors "
             "--grad-comm-groups; M must divide the data-axis size)",
    )
    p.add_argument(
        "--sched", default=None, metavar="SPEC",
        help="in-scan collective scheduler composition "
             "(parallel/schedule.py), e.g. "
             "'gather_prefetch=2,grad_buckets=4,grad_comm=int8,health,"
             "hpz': each element declares one scheduler slot; 'health' "
             "upgrades --telemetry to layers, 'hpz' holds a secondary "
             "compute-dtype weight replica per slice so ZeRO-3's "
             "in-scan gathers never cross DCN (ZeRO++).  Wire-agenda "
             "keys: 'grad_comm_tail=int8' quantizes the ZeRO-3 "
             "non-block tail release, 'hpz_comm=fp8' moves the hpZ "
             "secondary rebuild as fp8 blocks + scales (qwZ), and "
             "'grad_comm=auto'/'grad_buckets=auto'/'gather_groups="
             "auto' size the codec/K/m from the mesh's granule map "
             "(schedule.auto_comm_plan).  Legacy flags "
             "(--grad-comm/--grad-buckets/--gather-prefetch/...) keep "
             "working and merge with this spec; --sched wins on "
             "conflict",
    )
    p.add_argument(
        "--fused-xent", choices=("chunked", "pallas"), default=None,
        help="fused lm_head+cross-entropy head: 'chunked' (XLA scan over "
             "(B,chunk,V) slabs) or 'pallas' (round-5 kernel — logit "
             "tiles live only in VMEM; TPU single-device, falls back to "
             "chunked elsewhere).  Default: full-logits head",
    )
    p.add_argument(
        "--data", default=None, metavar="TOKENS.bin",
        help="binary uint16 token corpus (nanoGPT .bin convention); "
             "default: synthetic random tokens, the reference demo workload",
    )
    p.add_argument(
        "--eval-every", type=int, default=0, metavar="N",
        help="every N iters, report mean validation loss over "
             "--eval-batches forward-only batches (deterministic: no "
             "dropout, no update)",
    )
    p.add_argument("--eval-batches", type=int, default=8, metavar="K")
    p.add_argument(
        "--val-data", default=None, metavar="VAL.bin",
        help="held-out token corpus for --eval-every (default: a "
             "differently-seeded synthetic stream)",
    )
    p.add_argument(
        "--autotune", nargs="?", const="", default=None, metavar="CACHE.json",
        help="runtime-autotune kernel candidates (flash-attention blocks, "
             "linear layouts, layernorm Pallas-vs-XLA): first step records "
             "requests, they are timed on device, the step re-jits with "
             "winners baked.  With a path, winners persist across runs "
             "(ahead-of-time cache)",
    )
    p.add_argument(
        "--profile", default=None, metavar="LOGDIR",
        help="capture a jax.profiler device trace (XPlane/TensorBoard) of "
             "iters 2-4 into LOGDIR (utils/profiling.trace)",
    )
    p.add_argument(
        "--metrics", default=None, metavar="FILE.jsonl",
        help="append per-iter structured metrics (loss, step seconds, "
             "tokens/s) as JSONL (utils/profiling.MetricsLogger)",
    )
    p.add_argument(
        "--telemetry", nargs="?", const="on", default=None,
        choices=("on", "layers"),
        help="full run telemetry (tiny_deepspeed_tpu/telemetry/): "
             "on-device health metrics computed inside the compiled step "
             "(grad/update/param norms, non-finite counts), step-time "
             "breakdown (data wait / host->device / compute) with "
             "recompile detection, HBM watermarks, measured HLO-ledger "
             "collective bytes in the meta "
             "records, a flight recorder flushed on anomalies, and "
             "straggler gauges.  '--telemetry layers' additionally "
             "computes PER-LAYER health inside the block scan "
             "(grad/activation norms + non-finite counts; the first-NaN "
             "layer localized in one step — plain-scan engines, "
             "GPT-2/Llama).  Pairs with --metrics; render with "
             "scripts/report_run.py (a step's timeline is a profiler "
             "trace: --profile)",
    )
    p.add_argument(
        "--telemetry-trace", default=None, metavar="DIR",
        help="with --telemetry: capture ONE jax.profiler trace into DIR "
             "the first time a step exceeds 2.5x the rolling median step "
             "time (anomaly capture; off without a directory)",
    )
    p.add_argument(
        "--flight-steps", type=int, default=64, metavar="N",
        help="with --telemetry: flight-recorder ring size — the last N "
             "steps' health (+ per-layer health under 'layers') flushed "
             "as one JSONL 'flight' record when the anomaly detector "
             "fires on a slow step or non-finite health (0 disables)",
    )
    p.add_argument(
        "--save-every", type=int, default=0, metavar="N",
        help="legacy alias of --checkpoint-every",
    )
    p.add_argument("--save-dir", default="checkpoints", metavar="DIR",
                   help="legacy alias of --checkpoint-dir")
    p.add_argument(
        "--checkpoint-every", type=int, default=0, metavar="N",
        help="commit a sharded Orbax checkpoint of the TrainState every N "
             "iters into --checkpoint-dir — atomically (tmp-dir + rename "
             "+ COMMITTED marker: a crash mid-save can never corrupt the "
             "resume chain), asynchronously (the Orbax write overlaps the "
             "next steps), with retry/backoff on transient I/O failure, "
             "and ADAPTIVELY: with --telemetry, an anomaly (step-time "
             "spike or non-finite health) checkpoints immediately — "
             "non-finite states go to <dir>/postmortem/, outside the "
             "resume chain.  SIGTERM (preemption notice) drains one final "
             "committed checkpoint before exit "
             "(tiny_deepspeed_tpu/resilience/)",
    )
    p.add_argument("--checkpoint-dir", default=None, metavar="DIR",
                   help="checkpoint directory (default: --save-dir, i.e. "
                        "'checkpoints')")
    p.add_argument(
        "--checkpoint-sync", action="store_true",
        help="write checkpoints synchronously (the async writer overlaps "
             "Orbax I/O with training steps; sync trades that overlap "
             "for a strict save-then-step ordering)",
    )
    p.add_argument(
        "--resume", action="store_true",
        help="resume from the latest COMMITTED checkpoint in "
             "--checkpoint-dir (restores params+optimizer state into the "
             "engine's shardings and fast-forwards the data stream to the "
             "saved global sample offset, so the loss trajectory matches "
             "an uninterrupted run).  Elastic: a checkpoint saved on a "
             "DIFFERENT device count restores onto this run's mesh — "
             "partition tables and shardings are re-derived for the new "
             "topology (data-axis reshaping only; pipeline/expert/TP/SP "
             "configs are refused loudly)",
    )
    if defaults:
        p.set_defaults(**defaults)
    args = p.parse_args(argv)
    if args.model is None:
        args.model = "tiny" if args.cpu_devices else default_model
    if args.seq_len is None:
        args.seq_len = min(1024, ALL_PRESETS[args.model].block_size)
    return args


def run(engine_cls, args, single_device=False, cfg_overrides=None,
        state_dtype=None):
    """Build model + engine from `args` and train `args.iters` steps;
    returns (engine, state).

    Runs on the TPU unless `--cpu-devices N` asks for the virtual CPU
    mesh (utils/startup.select_platform: no chip and no flag exits
    non-zero before anything compiles).  `cfg_overrides` (model config
    fields) and `state_dtype` (AdamW moment dtype) are for programmatic
    callers that need a preset at another precision or attention path —
    chip_smoke.py runs gpt2-124m at the benchmark's width through them."""
    select_platform(getattr(args, "cpu_devices", 0),
                    cpu_flag="--cpu-devices N")
    init_distributed()
    import dataclasses as _dc
    model_cfg = ALL_PRESETS[args.model]

    def _cfg_override(field, value):
        if not any(f.name == field for f in _dc.fields(type(model_cfg))):
            raise SystemExit(
                f"--{field.replace('_', '-')}: the "
                f"{type(model_cfg).__name__} family has no {field} knob"
            )
        return _dc.replace(model_cfg, **{field: value})

    if getattr(args, "dropout", 0.0):
        model_cfg = _cfg_override("dropout", args.dropout)
    if getattr(args, "gather_quant", None):
        model_cfg = _cfg_override("gather_quant", args.gather_quant)
    if getattr(args, "scan_unroll", False):
        model_cfg = _cfg_override("scan_unroll", True)
    if getattr(args, "moe_dispatch", None):
        model_cfg = _cfg_override("moe_dispatch", args.moe_dispatch)
    if getattr(args, "fused_xent", None):
        model_cfg = _cfg_override("fused_xent", True)
        model_cfg = _cfg_override("fused_xent_impl", args.fused_xent)
    for field, value in (cfg_overrides or {}).items():
        model_cfg = _cfg_override(field, value)
    model = build_model(model_cfg)

    lr = args.lr
    sched_name = getattr(args, "lr_schedule", "constant")
    if sched_name != "constant" or getattr(args, "warmup_steps", 0):
        from tiny_deepspeed_tpu.optim import schedule as _sched
        kw = {"warmup_steps": args.warmup_steps}
        if sched_name == "constant":
            sched_name, kw = "warmup_linear", dict(kw, min_lr=args.lr)
        elif sched_name == "inverse_sqrt":
            kw["warmup_steps"] = max(1, args.warmup_steps)
        if sched_name in ("warmup_linear", "warmup_cosine"):
            kw["total_steps"] = args.iters
        lr = _sched.SCHEDULES[sched_name](args.lr, **kw)
    opt = AdamW(
        lr=lr, weight_decay=args.weight_decay,
        decay_exclude=tuple(
            p for p in (getattr(args, "wd_exclude", None) or "").split(",")
            if p
        ),
        state_dtype=state_dtype or jnp.float32,
    )
    # --sched: ONE translation site — the composition spec parses into
    # scheduler-slot engine kwargs (parallel/schedule.parse_sched_spec)
    # and merges over the legacy per-knob flags ('health' upgrades the
    # telemetry to layers mode)
    sched_kw = {}
    if getattr(args, "sched", None):
        from tiny_deepspeed_tpu.parallel.schedule import parse_sched_spec
        sched_kw = parse_sched_spec(args.sched)
    telem = None
    # pop BEFORE the or: a short-circuit would leak the key into the
    # engine kwargs when --telemetry layers is also set
    sched_layers = sched_kw.pop("telemetry_layers", False)
    want_layers = (getattr(args, "telemetry", None) == "layers"
                   or sched_layers)
    if getattr(args, "telemetry", None) or want_layers:
        from tiny_deepspeed_tpu.telemetry import Telemetry
        telem = Telemetry(
            trace_dir=getattr(args, "telemetry_trace", None),
            layers=want_layers,
            flight_steps=getattr(args, "flight_steps", 64),
        )
    train_kw = dict(
        grad_clip=getattr(args, "grad_clip", 0.0) or None,
        loss_scale=getattr(args, "loss_scale", None),
        offload_opt_state=getattr(args, "offload_opt_state", False),
        offload_prefetch=getattr(args, "offload_prefetch", 2),
        telemetry=telem,
        grad_comm=getattr(args, "grad_comm", "fp32"),
        grad_comm_groups=getattr(args, "grad_comm_groups", None),
        grad_buckets=getattr(args, "grad_buckets", 1),
        gather_prefetch=getattr(args, "gather_prefetch", 0),
        gather_groups=getattr(args, "gather_groups", None),
    )
    train_kw.update(sched_kw)
    # `--sched pipe=KIND:V` lands in sched_kw as pipeline_schedule /
    # pipeline_virtual — pop them so they win over the legacy flags
    # without colliding with the explicit ctor kwargs below
    pipe_sched = train_kw.pop(
        "pipeline_schedule", getattr(args, "pipeline_schedule", "gpipe")
    )
    pipe_virtual = train_kw.pop(
        "pipeline_virtual", getattr(args, "pipeline_virtual", 1)
    )
    if single_device:
        engine = engine_cls(
            model, opt, mesh=make_mesh(devices=[jax.devices()[0]]),
            pipeline_schedule=pipe_sched, pipeline_virtual=pipe_virtual,
            **train_kw,
        )
        n_dev = 1
    else:
        # engine builds the (data[, seq][, model]) mesh from the flags
        engine = engine_cls(
            model, opt,
            seq_parallel=getattr(args, "seq_parallel", 1),
            seq_impl=getattr(args, "seq_impl", "ring"),
            tensor_parallel=getattr(args, "tensor_parallel", 1),
            expert_parallel=getattr(args, "expert_parallel", 1),
            pipeline_parallel=getattr(args, "pipeline_parallel", 1),
            pipeline_microbatches=getattr(args, "pipeline_microbatches", 0)
            or None,
            pipeline_schedule=pipe_sched, pipeline_virtual=pipe_virtual,
            **train_kw,
        )
        n_dev = engine.n_dev
    if jax.process_index() == 0:
        print(engine.describe())
        print(f"model={args.model} params={model.num_params()/1e6:.1f}M "
              f"global_batch={args.batch_per_device * n_dev} T={args.seq_len}")

    b = args.batch_per_device * n_dev
    vocab = model.config.vocab_size

    ckpt_dir = getattr(args, "checkpoint_dir", None) or args.save_dir
    ckpt_every = getattr(args, "checkpoint_every", 0) \
        or getattr(args, "save_every", 0)

    start_iter = 0
    resume_step = None
    resume_info = None
    if getattr(args, "resume", False):
        from tiny_deepspeed_tpu.utils.checkpoint import latest_step
        resume_step = latest_step(ckpt_dir)
    if resume_step is not None:
        # restore INSTEAD of init — materializing a fresh TrainState first
        # would double peak state memory exactly on the near-HBM-limit runs
        # checkpointing exists for.  elastic_load tolerates a different
        # device count than the checkpoint was saved on (data-axis only;
        # pipeline/expert/TP/SP configs are refused with both shapes).
        from tiny_deepspeed_tpu.resilience import elastic_load
        state, resume_info = elastic_load(ckpt_dir, engine,
                                          step=resume_step)
        start_iter = resume_step
        if jax.process_index() == 0:
            el = " (elastic: mesh changed)" if resume_info["elastic"] \
                else ""
            print(f"resumed from {ckpt_dir} at iter {resume_step}{el}")
    else:
        state = engine.init(jax.random.PRNGKey(args.seed))

    # Native prefetching pipeline (C++ producer threads): batches are ready
    # before the device asks — the reference rebuilds tensors on the host
    # inside the loop (example/ddp/train.py:23-24).
    from tiny_deepspeed_tpu.data import TokenLoader
    indexed = False
    seek = 0
    if start_iter:
        # replay position -> trajectory continuity.  With an UNCHANGED
        # global batch the per-batch stream replays bit-exactly from the
        # saved sample offset; legacy checkpoints without meta fall back
        # to step-count replay (same stream iff the batch is unchanged).
        # A CHANGED global batch has no per-batch continuation at all —
        # that stream is keyed by (batch counter, batch size) — so the
        # run switches to the per-sample indexed stream at the saved
        # offset: deterministic, batch-size invariant from here on, and
        # recorded in the meta so later resumes stay on it.
        from tiny_deepspeed_tpu.resilience import data_offset_batches
        data = (resume_info or {}).get("data") or {}
        saved_b = data.get("global_batch")
        if data.get("indexed") or (saved_b is not None
                                   and int(saved_b) != b):
            seek = int(data["samples_seen"])
            indexed = True
            if jax.process_index() == 0 and not data.get("indexed"):
                print(f"resume: global batch changed {int(saved_b)} -> "
                      f"{b}; continuing on the indexed per-sample "
                      f"stream at offset {seek}")
        else:
            try:
                off = (data_offset_batches(resume_info, b)
                       if resume_info else None)
                seek = (off if off is not None else start_iter) * b
            except ValueError:
                # same nominal batch but a misaligned offset (e.g. a
                # checkpoint hand-written mid-batch): the indexed stream
                # accepts any offset
                seek = int(data["samples_seen"])
                indexed = True
                if jax.process_index() == 0:
                    print(f"resume offset {seek} samples not divisible "
                          f"by global batch {b}: using indexed loader")
    loader = TokenLoader(args.data, batch=b, seq=args.seq_len,
                         vocab_size=vocab, seed=args.seed, indexed=indexed)
    if seek:
        loader.seek_samples(seek)
    if jax.process_index() == 0:
        # "numpy" on a machine with a compiler means the native build
        # failed (data/loader.py keeps the reason) — say so, don't hide it
        print(f"loader backend: {loader.backend}")

    if getattr(args, "autotune", None) is not None:
        if jax.process_count() > 1:
            # per-host timing could pick DIVERGENT winners -> the hosts
            # would compile different SPMD programs and hang at the next
            # collective; tune single-host, ship the cache file instead
            if jax.process_index() == 0:
                print("autotune skipped: multi-host run (tune on one host "
                      "and pass the saved cache file)")
        else:
            from tiny_deepspeed_tpu.autotuner import (
                RuntimeAutoTuner, set_default_tuner,
            )
            import os as _os
            tuner = RuntimeAutoTuner(verbose=True)
            if args.autotune and _os.path.exists(args.autotune):
                tuner.load(args.autotune)
            set_default_tuner(tuner)
            # lifecycle: trace once (records candidate requests), time them
            # on device, re-jit with winners baked (engine.retune
            # docstring).  Probe batch is synthetic — shapes are all that
            # matter.
            probe = jax.random.randint(
                jax.random.PRNGKey(7), (b, args.seq_len), 0, vocab, jnp.int32
            )
            state, _ = engine.step(state, (probe, probe))
            n = engine.retune()
            print(f"autotuned {n} site(s)")
            if args.autotune:
                tuner.save(args.autotune)
            # re-create training state so the probe step does not advance
            # it; drop the probe state FIRST (holding both would double
            # peak state memory exactly on near-HBM-limit runs)
            state = None
            if resume_step is not None:
                from tiny_deepspeed_tpu.resilience import elastic_load
                state, _ = elastic_load(ckpt_dir, engine, step=resume_step)
            else:
                state = engine.init(jax.random.PRNGKey(args.seed))

    metrics = None
    if getattr(args, "metrics", None):
        from tiny_deepspeed_tpu.utils.profiling import MetricsLogger
        metrics = MetricsLogger(args.metrics, stdout=False)
    profile_dir = getattr(args, "profile", None)

    # preemption-safe checkpoint cadence (tiny_deepspeed_tpu/resilience/):
    # async atomic saves on the interval + immediately on a telemetry
    # anomaly; a SIGTERM (the preemption notice) drains one final
    # committed checkpoint between steps instead of dying mid-save
    manager = guard = None
    if ckpt_every:
        from tiny_deepspeed_tpu.resilience import (
            CheckpointManager, PreemptionGuard,
        )
        manager = CheckpointManager(
            ckpt_dir, every=ckpt_every, engine=engine, telemetry=telem,
            async_save=not getattr(args, "checkpoint_sync", False),
        )
        guard = PreemptionGuard()
    if metrics is not None and resume_info is not None:
        metrics.log_meta(kind="resume", checkpoint_dir=ckpt_dir,
                         **resume_info)

    def _data_meta():
        return {"samples_seen": loader.samples_seen, "global_batch": b,
                "seed": args.seed, "indexed": loader.indexed}

    eval_every = getattr(args, "eval_every", 0)
    val_loader = None
    if eval_every:
        val_loader = TokenLoader(
            getattr(args, "val_data", None), batch=b, seq=args.seq_len,
            vocab_size=vocab, seed=args.seed + 1,
        )

    rank0 = jax.process_index() == 0
    trace_started = False
    t0 = time.perf_counter()
    ran = 0
    # per-host straggler signal: data-load + staging wall, pure host code
    # — collectives couple the DEVICE timelines across hosts (whole-step
    # wall converges to the slowest host on every host), so only an
    # uncoupled host-side measure can attribute a straggler
    host_prep_s = 0.0
    try:
        for it in range(start_iter, args.iters):
            it_t0 = time.perf_counter()
            flight_reason = None
            if profile_dir is not None and it == start_iter + 2:
                jax.profiler.start_trace(profile_dir)
                trace_started = True
            if telem is not None and rank0:
                # instrumented step: wall segments (data wait / host->device /
                # compute), recompile attribution, and the health-vector sync
                # as the closing barrier — ONE device->host transfer delivers
                # loss + grad/update/param norms + non-finite counts.  Rank 0
                # only: the barrier would cost the other ranks the run-ahead
                # overlap the plain path preserves (their engine.step still
                # pushes the aux un-synced; the compiled program is identical
                # on every rank)
                with telem.step(index=it) as t:
                    idx, tgt = loader.next()      # its own tds.load span
                    t.mark("data")
                    with span("tds.h2d"):
                        batch = (jnp.asarray(idx), jnp.asarray(tgt))
                    t.mark("h2d")
                    host_prep_s += time.perf_counter() - it_t0
                    state, loss = engine.step(state, batch)
                ran += 1
                health = telem.last_health
                loss_f = (health["loss"] if health is not None
                          else float(loss))
                it_dt = telem.timer.times[-1]
                print(f"iter {it:3d} loss {loss_f:.4f}")
                if metrics is not None:
                    metrics.log(
                        it, loss=loss_f, step_s=it_dt,
                        tokens_per_s=b * args.seq_len / max(it_dt, 1e-9),
                        **telem.step_record(),
                    )
                    # anomaly-armed flight flush (slow step or non-finite
                    # health): the last N steps' history lands as ONE
                    # 'flight' record; syncs any per-layer matrices, so it
                    # stays here at logging cadence, off the step hot path
                    flight_reason = telem.maybe_flush_flight(metrics)
                    if flight_reason is not None:
                        print(f"iter {it:3d} flight record flushed "
                              f"(reason: {flight_reason})")
            else:
                idx, tgt = loader.next()
                # NB (both branches): jnp.asarray stages the whole batch on
                # device 0 and the step's in_shardings reshard it — correct,
                # but one extra device-to-device hop per step on a
                # multi-chip host (PERF.md "Open questions")
                with span("tds.h2d"):
                    batch = (jnp.asarray(idx), jnp.asarray(tgt))
                host_prep_s += time.perf_counter() - it_t0
                state, loss = engine.step(state, batch)
                ran += 1
                if rank0:
                    # device->host sync only where the value is
                    # consumed — other ranks run ahead and overlap
                    # loader.next() with device compute (MetricsLogger.log is
                    # rank-0 gated too)
                    with span("tds.sync"):
                        loss_f = float(loss)
                    it_dt = time.perf_counter() - it_t0
                    print(f"iter {it:3d} loss {loss_f:.4f}")
                    if metrics is not None:
                        metrics.log(it, loss=loss_f, step_s=it_dt,
                                    tokens_per_s=b * args.seq_len
                                    / max(it_dt, 1e-9))
            if trace_started and it == start_iter + 4:
                jax.profiler.stop_trace()
                trace_started = False
                if rank0:
                    print(f"profiler trace written to {profile_dir}")
            if eval_every and (it + 1) % eval_every == 0:
                vals = []
                for _ in range(args.eval_batches):
                    vix, vtg = val_loader.next()
                    vals.append(engine.eval_loss(
                        state, (jnp.asarray(vix), jnp.asarray(vtg))
                    ))
                vloss = sum(float(v) for v in vals) / len(vals)
                if rank0:
                    print(f"iter {it:3d} val_loss {vloss:.4f}")
                    if metrics is not None:
                        metrics.log(it, val_loss=vloss)
            if manager is not None:
                manager.note_step()
                saved = manager.maybe_save(
                    state, it + 1, anomaly=flight_reason,
                    data_meta=_data_meta(),
                )
                if saved is not None and rank0:
                    print(f"saved checkpoint at iter {it + 1} ({saved})")
                if guard.agreed():
                    # preemption notice: drain ONE final committed
                    # checkpoint from between steps (never mid-step — the
                    # jitted step has donated the previous state's
                    # buffers).  agreed(), not triggered: the flag is
                    # rank-local and a drain only some hosts enter would
                    # deadlock the final save's collective barriers
                    # against the other hosts' next step
                    drained = manager.maybe_save(
                        state, it + 1, data_meta=_data_meta(), force=True,
                    )
                    manager.close()
                    if rank0:
                        print(f"preempted (signal "
                              f"{guard.signum or 'on another host'}); "
                              f"drained final checkpoint at iter {it + 1} "
                              f"({drained or 'already committed'})")
                    break
    finally:
        # drain the async writer and restore signal handlers even when
        # the loop raised: a daemon writer thread killed mid-Orbax-write
        # would silently drop a save already announced as kicked off.
        # Capture the in-flight exception BEFORE calling close() — inside
        # the except handler below, exc_info() would report the handled
        # RuntimeError itself and a clean-exit save failure would be
        # silently swallowed
        import sys as _sys
        _loop_exc = _sys.exc_info()[0]
        if manager is not None:
            try:
                manager.close()
            except RuntimeError:
                if _loop_exc is None:
                    raise  # do not mask the loop's own exception
        if guard is not None:
            guard.uninstall()
    if trace_started:  # run ended inside the trace window
        jax.profiler.stop_trace()
    elif profile_dir is not None and args.iters - start_iter <= 2 and rank0:
        print(f"--profile: run too short (< 3 iters past {start_iter}) — "
              f"no trace captured in {profile_dir}")
    loader.close()
    if val_loader is not None:
        val_loader.close()
    if telem is not None and metrics is not None:
        if jax.process_count() == 1 and ran:
            # run_meta: measured collective ledger off the compiled step's
            # HLO (single-controller only — a one-host AOT compile of a
            # multi-host program would diverge) next to the comm_report
            # ring model.  Captured AFTER the loop: the AOT compile is a
            # second full compile of the step program (the jit dispatch
            # cache is separate), so doing it up front would double
            # time-to-first-step on big models
            probe = jnp.zeros((b, args.seq_len), jnp.int32)
            metrics.log_meta(**telem.run_meta(
                state, (probe, probe), model=args.model,
                n_params=model.num_params(), batch=b,
                seq_len=args.seq_len, tokens_per_step=b * args.seq_len,
            ))
        if ran:
            # per-host straggler attribution over the UNCOUPLED host-side
            # prep wall (data load + staging): collectives equalize the
            # device timelines across hosts, so whole-step wall cannot
            # name a straggler — host-side wait can.  Every rank must
            # reach this call (process_allgather is a collective);
            # log_meta itself is rank-0-gated.  Degenerate but
            # schema-complete on one host.
            metrics.log_meta(
                kind="straggler",
                **telem.sample_stragglers(
                    step_s=host_prep_s / ran, quantity="host_prep_s",
                ),
            )
        telem.flush(metrics)  # registry snapshot -> telemetry_summary record
    if metrics is not None:
        metrics.close()
    dt = time.perf_counter() - t0
    if jax.process_index() == 0:
        toks = ran * b * args.seq_len
        print(f"done: {ran} iters in {dt:.1f}s "
              f"({toks / dt:.0f} tokens/s)")
        if telem is not None and telem.timer.times:
            tm = telem.timer
            print(f"step time p50 {tm.p50_s * 1e3:.1f}ms "
                  f"p95 {tm.p95_s * 1e3:.1f}ms "
                  f"p99 {tm.p99_s * 1e3:.1f}ms "
                  f"max {tm.max_s * 1e3:.1f}ms; "
                  f"compiles {tm.compile_count}")
            if getattr(args, "metrics", None):
                print("run report: python scripts/report_run.py "
                      f"{args.metrics}")
    return engine, state
