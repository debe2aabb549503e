#!/usr/bin/env python3
# Copyright 2026 tiny-deepspeed-tpu authors
# SPDX-License-Identifier: Apache-2.0

"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the trainer and the server once, on the TPU, through the functions
their entry points call (examples/common.run, scripts/serve_bench.serve), at
the full width of gpt2-124m (random weights from a seed), and checks what
comes out by the repo's own means:

  train    SingleDevice, B=12 T=1024 bf16 params+moments, unrolled (the
           benchmark's gpt2-124m.train-1chip), 6 steps at lr 1e-3: first loss near ln(vocab), finite,
           falling; the trainer's own --profile yields an .xplane.pb with a
           TPU plane that has events
  parity   the same first two steps with every kernel gate forced to XLA
           (kernel_target_forced("cpu") + standard_attention) on the same
           device: losses agree within LOSS_TOL
  serve    ServingEngine through serve_bench, closed loop, mixed prompt
           lengths, 32 new tokens: every request ok, zero restarts; greedy
           tokens identical across paged_kernel on / off / generate() in
           float32 at highest matmul precision (where identity is the
           correct expectation); in the preset's bf16 the kernel's decode
           logits agree with the XLA path within LOGIT_TOL and token
           agreement is reported (one near-tie argmax flip diverges a
           greedy sequence, so bf16 identity is a coin, not a check)
  4 chips  (when exactly four devices are visible) DDP / ZeRO-1/2/3 against
           SingleDevice on gpt2-124m at one global batch: two-step loss
           parity, every state leaf on four devices in the stage's layout,
           ZeRO-3 resting bytes about a quarter of DDP's, the stage's
           collectives in the compiled step; then gpt2-1.5b under ZeRO-3
           and ZeRO-2 (examples/zero{3,2}/train.py shapes) take steps

It exits non-zero, and prints no result, unless jax.default_backend() is
"tpu".  One process for every phase: a chip belongs to one process.  The last
stdout line is the result JSON.

`--rehearse-cpu N` is a labeled dress rehearsal at tiny size on N virtual CPU
devices with the Pallas kernels interpreted — for debugging this script in a
sandbox, never what it does on finding no chip.
"""

from __future__ import annotations

import argparse
import contextlib
import gc
import glob
import importlib.util
import json
import math
import os
import sys
import tempfile
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, ROOT)

# stated tolerances (bf16 compute): mean token loss over >= 4k tokens
# (measured on the chip: 3e-5), and the largest of one decode step's 150k
# logits (sigma 0.55 at random init; measured 0.025 — a wrong mask or block
# moves logits by sigma, not by a twentieth of it)
LOSS_TOL = 0.05
LOGIT_TOL = 0.1
# first loss vs ln(vocab): logits at init have sigma = 0.02 * sqrt(d), which
# lifts the loss ~sigma^2/2 above ln(vocab) (0.15 at d=768, 0.32 at d=1600)
FIRST_LOSS_TOL = {"gpt2-124m": 0.2, "gpt2-1.5b": 0.5, "tiny": 0.2}


def _load(relpath: str):
    """Import an entry-point file by path (examples/ and scripts/ are not
    packages)."""
    path = os.path.join(ROOT, relpath)
    name = "_smoke_" + os.path.splitext(os.path.basename(path))[0]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


class Smoke:
    def __init__(self, args):
        import jax

        from tiny_deepspeed_tpu.utils import startup

        self.jax = jax
        self.rehearsal = args.rehearse_cpu
        # names the platform or exits non-zero, before anything compiles
        startup.select_platform(self.rehearsal, cpu_flag="--rehearse-cpu N")
        self.cache_dir = startup.compile_cache_dir()
        self.out = os.path.abspath(args.out)
        os.makedirs(self.out, exist_ok=True)
        self.failed: list = []
        self.summary: dict = {}
        self.t_start = time.perf_counter()
        # compile accounting across every phase (jax.monitoring): XLA
        # backend compiles, persistent-cache retrievals included; tracing
        # and lowering count as run time (their events nest)
        self.compile_s = 0.0
        self.cache_hits = 0
        self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_dur)
        jax.monitoring.register_event_listener(self._on_event)

        self.model = "tiny" if self.rehearsal else "gpt2-124m"
        self.seq = 128 if self.rehearsal else 1024
        self.common = _load("examples/common.py")
        self.serve_bench = _load("scripts/serve_bench.py")

    # -- accounting ---------------------------------------------------------

    def _on_dur(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += duration

    def _on_event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def hbm(self):
        """Per-device memory_stats() in GiB, or None where the backend
        reports none (CPU).  On the TPU `peak_bytes_in_use` counts live
        arrays only; a compiled step's temp memory shows up under
        `peak_bytes_reserved` (7.9 GB for the 124M bench step, whose
        memory_analysis() says 7.4 GB temp)."""
        out = []
        for d in self.jax.devices():
            st = d.memory_stats()
            if not st:
                return None
            out.append({
                name: round(st.get(key, 0) / 2**30, 3)
                for name, key in (
                    ("peak_in_use_gb", "peak_bytes_in_use"),
                    ("peak_reserved_gb", "peak_bytes_reserved"),
                    ("in_use_gb", "bytes_in_use"))})
        return out

    @contextlib.contextmanager
    def phase(self, name):
        """Time one phase (compile seconds apart from the rest), print the
        kernels its traces noted and the HBM peaks; record any failure and
        keep going so one run reports every broken phase."""
        from tiny_deepspeed_tpu.ops.dispatch import kernels_noted
        kernels_noted(clear=True)
        facts: dict = {}
        c0, h0, m0 = self.compile_s, self.cache_hits, self.cache_misses
        t0 = time.perf_counter()
        print(f"\n=== phase {name} ===", flush=True)
        try:
            yield facts
            facts["verdict"] = "pass"
        except Exception as e:  # noqa: BLE001 - report every phase
            traceback.print_exc()
            facts["verdict"] = "FAIL"
            facts["error"] = f"{type(e).__name__}: {e}"[:400]
            self.failed.append(name)
        wall = time.perf_counter() - t0
        comp = self.compile_s - c0
        facts.update(
            wall_s=round(wall, 1), compile_s=round(comp, 1),
            run_s=round(wall - comp, 1),
            cache_hits=self.cache_hits - h0,
            cache_misses=self.cache_misses - m0,
            kernels=kernels_noted(clear=True), hbm=self.hbm(),
        )
        self.summary[name] = facts
        print(f"--- phase {name}: {json.dumps(facts)}", flush=True)
        gc.collect()

    def check(self, ok: bool, what: str):
        print(f"  [{'ok' if ok else 'FAIL'}] {what}", flush=True)
        if not ok:
            raise AssertionError(what)

    # -- helpers ------------------------------------------------------------

    def jsonl(self, tag):
        return os.path.join(self.out, f"{tag}.jsonl")

    def train_args(self, tag, **kw):
        """The Namespace the trainer's own command line would build."""
        argv = ["--model", kw.pop("model", self.model),
                "--seq-len", str(self.seq), "--lr", "1e-3",
                "--metrics", self.jsonl(tag)]
        if self.rehearsal:
            argv += ["--cpu-devices", str(self.rehearsal)]
        for k, v in kw.items():
            flag = "--" + k.replace("_", "-")
            argv += [flag] if v is True else [flag, str(v)]
        if os.path.exists(self.jsonl(tag)):
            os.remove(self.jsonl(tag))  # MetricsLogger appends
        return self.common.parse_args(argv=argv)

    def losses(self, tag):
        with open(self.jsonl(tag)) as f:
            recs = [json.loads(ln) for ln in f if ln.strip()]
        return [r["loss"] for r in recs if "loss" in r and "step" in r]

    def bench_width(self):
        """gpt2-124m as the benchmark's gpt2-124m.train-1chip measures
        it: B=12, bf16 params and moments, no remat, unrolled."""
        import jax.numpy as jnp
        if self.rehearsal:
            return 2, dict(remat=False, scan_unroll=True), None
        over = dict(remat=False, param_dtype=jnp.bfloat16, scan_unroll=True)
        return 12, over, jnp.bfloat16

    def check_losses(self, ls, model, facts):
        import tiny_deepspeed_tpu.models as M
        ln_v = math.log(M.ALL_PRESETS[model].vocab_size)
        facts.update(losses=[round(x, 6) for x in ls], ln_vocab=round(ln_v, 3))
        self.check(all(math.isfinite(x) for x in ls), f"losses finite: {ls}")
        self.check(abs(ls[0] - ln_v) < FIRST_LOSS_TOL[model],
                   f"first loss {ls[0]:.4f} within "
                   f"{FIRST_LOSS_TOL[model]} of ln(vocab) {ln_v:.3f}")
        if self.rehearsal:
            # d=64 starts 0.01 above ln(vocab) on random tokens: nothing
            # to gain, so the rehearsal reports the direction only
            print(f"  [rehearsal] loss {ls[0]:.4f} -> {ls[-1]:.4f}")
            return
        self.check(ls[-1] < ls[0], f"loss falls: {ls[0]:.4f} -> {ls[-1]:.4f}")

    def gate(self, noted, gate):
        """On the chip a default path must have traced the Pallas kernel
        at this gate (and nothing else); the rehearsal only reports."""
        got = noted.get(gate, [])
        if self.rehearsal:
            print(f"  [rehearsal] gate {gate}: {got}")
            return
        self.check(bool(got) and all(g.startswith("pallas:") for g in got),
                   f"gate {gate} ran pallas: {got}")

    # -- one chip -----------------------------------------------------------

    def phase_train(self):
        from tiny_deepspeed_tpu import SingleDevice
        from tiny_deepspeed_tpu.data.loader import native_build_error
        from tiny_deepspeed_tpu.ops.dispatch import kernels_noted
        batch, over, sdt = self.bench_width()
        with self.phase("train") as facts, \
                tempfile.TemporaryDirectory() as trace_dir:
            args = self.train_args("train", iters=6, batch_per_device=batch,
                                   profile=trace_dir)
            engine, state = self.common.run(
                SingleDevice, args, single_device=True,
                cfg_overrides=over, state_dtype=sdt)
            del engine, state
            err = native_build_error()
            facts["loader"] = "native" if err is None else f"numpy ({err})"
            ls = self.losses("train")
            self.check(len(ls) == 6, f"6 steps logged ({len(ls)})")
            self.check_losses(ls, self.model, facts)
            noted = kernels_noted()
            self.gate(noted, "attention")
            self.gate(noted, "layernorm")
            facts["trace"] = self.read_trace(trace_dir)
        self.train_losses = self.summary["train"].get("losses")

    def read_trace(self, trace_dir):
        """The trainer's --profile wrote an XPlane: find the device plane
        and count its events (S0's trace reduction depends on this)."""
        from jax.profiler import ProfileData
        paths = sorted(glob.glob(os.path.join(
            trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
        self.check(bool(paths), f"--profile wrote an .xplane.pb ({paths})")
        prof = ProfileData.from_file(paths[-1])
        planes = {pl.name: sum(1 for ln in pl.lines for _ in ln.events)
                  for pl in prof.planes}
        want = "/host:CPU" if self.rehearsal else "TPU"
        dev = {n: c for n, c in planes.items() if want in n}
        self.check(any(c > 0 for c in dev.values()),
                   f"a {want} plane with events: {planes}")
        return {"xplane_mb": round(os.path.getsize(paths[-1]) / 2**20, 1),
                "planes": planes}

    def phase_parity(self):
        from tiny_deepspeed_tpu import SingleDevice
        from tiny_deepspeed_tpu.ops.dispatch import kernel_target_forced
        batch, over, sdt = self.bench_width()
        # remat on: same math, and the materialized (B,H,T,T) attention
        # of 12 unrolled layers would otherwise sit at 13.3 of 15.75 GB
        over = dict(over, attn_impl="standard_attention", remat=True)
        with self.phase("parity") as facts:
            args = self.train_args("parity", iters=2,
                                   batch_per_device=batch)
            with kernel_target_forced("cpu"):
                engine, state = self.common.run(
                    SingleDevice, args, single_device=True,
                    cfg_overrides=over, state_dtype=sdt)
            del engine, state
            ref = self.losses("parity")
            got = (self.train_losses or [])[:2]
            facts.update(kernel_path=got, xla_path=[round(x, 6) for x in ref],
                         tol=LOSS_TOL)
            self.check(len(got) == 2 and len(ref) == 2,
                       "two losses from each path")
            for i, (a, b) in enumerate(zip(got, ref)):
                self.check(abs(a - b) <= LOSS_TOL,
                           f"step {i}: kernel path {a:.6f} vs XLA path "
                           f"{b:.6f} (|d|={abs(a - b):.6f} <= {LOSS_TOL})")

    def serve_once(self, tag, paged, serial=False, f32=False):
        """One pass of scripts/serve_bench through its own function."""
        import jax.numpy as jnp

        from tiny_deepspeed_tpu.ops.paged_attn_pallas import (
            paged_kernel_forced,
        )
        lens = "8,20,40" if self.rehearsal else "24,100,300"
        blocks = "32" if self.rehearsal else "96"
        argv = ["--model", self.model, "--requests", "6", "--closed-loop",
                "--prompt-lens", lens, "--max-new-tokens", "32",
                "--max-active", "4", "--num-blocks", blocks,
                "--jsonl", self.jsonl(f"serve_{tag}")]
        if self.rehearsal:
            argv.append("--cpu")
        if serial:
            argv.append("--serial")
        prec = (self.jax.default_matmul_precision("highest") if f32
                else contextlib.nullcontext())
        over = {"compute_dtype": jnp.float32} if f32 else None
        with prec, paged_kernel_forced(paged):
            res = self.serve_bench.serve(argv, cfg_overrides=over)
        self.check(res["status_counts"]["ok"] == 6
                   and sum(res["status_counts"].values()) == 6,
                   f"{tag}: every request ok ({res['status_counts']})")
        self.check(res["restarts"] == 0, f"{tag}: serve_restarts == 0")
        self.check(all(len(o) == 32 for o in res["outputs"]),
                   f"{tag}: 32 new tokens per request")
        return res

    @staticmethod
    def agreement(a, b):
        """(identical requests, first diverging token index per request)."""
        first = []
        for x, y in zip(a, b):
            i = next((i for i, (p, q) in enumerate(zip(x, y)) if p != q),
                     None)
            first.append(i)
        return sum(f is None for f in first), first

    def phase_serve(self):
        from tiny_deepspeed_tpu.ops.dispatch import kernels_noted
        from tiny_deepspeed_tpu.ops.paged_attn_pallas import (
            effective_paged_kernel,
        )
        with self.phase("serve") as facts:
            if not self.rehearsal:
                self.check(effective_paged_kernel() == "pallas",
                           'paged_kernel="auto" dispatches the Pallas '
                           "kernel on this backend")
            # float32 at highest matmul precision: the three paths differ
            # by f32 reassociation only, so greedy tokens must be identical
            on = self.serve_once("f32_on", "on", serial=True, f32=True)
            self.gate(kernels_noted(), "paged_attention")
            off = self.serve_once("f32_off", "off", f32=True)
            facts["f32_identical"] = {
                "on_vs_off": self.agreement(on["outputs"], off["outputs"]),
                "on_vs_generate": self.agreement(
                    on["outputs"], on["serial_outputs"]),
            }
            self.check(on["outputs"] == off["outputs"],
                       "f32: tokens identical, paged_kernel on vs off")
            self.check(on["outputs"] == on["serial_outputs"],
                       "f32: tokens identical, engine vs model.generate()")
            # the preset's own bf16: liveness through the same path, token
            # agreement REPORTED (a near-tie flip diverges greedy decode)
            on16 = self.serve_once("bf16_on", "on", serial=True)
            off16 = self.serve_once("bf16_off", "off")
            facts["bf16_agreement"] = {
                "on_vs_off": self.agreement(
                    on16["outputs"], off16["outputs"]),
                "on_vs_generate": self.agreement(
                    on16["outputs"], on16["serial_outputs"]),
                # two XLA programs, no kernel: the noise floor
                "off_vs_generate": self.agreement(
                    off16["outputs"], on16["serial_outputs"]),
            }
            facts["bf16_logits"] = self.decode_logit_parity()

    def decode_logit_parity(self):
        """One decode tick of two ServingEngines (paged_kernel on / off)
        over the same prompts: the kernel's logits against the XLA
        path's, in the preset's own compute dtype."""
        import numpy as np

        from tiny_deepspeed_tpu.models import build_model
        from tiny_deepspeed_tpu.serving import ServeConfig, ServingEngine
        jax = self.jax
        model = build_model(self.model)
        params = jax.jit(model.init)(jax.random.PRNGKey(0))
        rng = np.random.default_rng(0)
        plens = (8, 20, 40) if self.rehearsal else (24, 100, 300)
        prompts = [rng.integers(0, model.config.vocab_size, n).tolist()
                   for n in plens]
        logits = {}
        for mode in ("on", "off"):
            eng = ServingEngine(model, params, ServeConfig(
                max_active=4, num_blocks=32 if self.rehearsal else 96,
                block_tokens=16, max_seq_tokens=64 if self.rehearsal
                else 336, paged_kernel=mode))
            for p in prompts:
                eng.submit(p, 4)
            eng.tick()  # admit + prefill + ONE decode step
            logits[mode] = np.asarray(eng.last_logits)[:len(prompts)]
            self.check(eng.restarts == 0, f"logit parity {mode}: no restart")
        diff = logits["on"] - logits["off"]
        d = float(np.max(np.abs(diff)))
        rms = float(np.sqrt(np.mean(np.square(diff))))
        sigma = float(np.std(logits["off"]))
        self.check(np.isfinite(logits["on"]).all(), "kernel logits finite")
        self.check(d <= LOGIT_TOL,
                   f"decode logits, kernel vs XLA: max|d|={d:.5f} <= "
                   f"{LOGIT_TOL} (rms {rms:.5f}, logit sigma {sigma:.3f})")
        return {"max_abs_diff": round(d, 6), "rms_diff": round(rms, 6),
                "sigma": round(sigma, 4), "tol": LOGIT_TOL}

    # -- four chips ---------------------------------------------------------

    def resting_bytes(self, state):
        """Bytes of TrainState resident on each device, from the arrays'
        own shards."""
        per = {}
        for leaf in self.jax.tree.leaves(state):
            for sh in leaf.addressable_shards:
                per[sh.device.id] = per.get(sh.device.id, 0) + sh.data.nbytes
        return per

    def phase_stages(self):
        """DDP / ZeRO-1 / 2 / 3 against SingleDevice at one global batch."""
        import jax.numpy as jnp

        from tiny_deepspeed_tpu import (
            DDP, SingleDevice, Zero1, Zero2, Zero3,
        )
        from tiny_deepspeed_tpu.utils.hlo_comm import collective_ledger
        from tiny_deepspeed_tpu.utils.profiling import comm_report
        jax = self.jax
        n = len(jax.devices())
        # what each stage promises at rest (params, optimizer moments) and
        # on the wire (the collectives carrying most of the step's bytes)
        promise = {
            "ddp": (False, False, ("all-reduce",)),
            "zero1": (False, True, ("reduce-scatter", "all-gather")),
            "zero2": (False, True, ("reduce-scatter", "all-gather")),
            "zero3": (True, True, ("reduce-scatter", "all-gather")),
        }
        with self.phase("stages") as facts:
            args = self.train_args("stage_single", iters=2,
                                   batch_per_device=n)
            engine, state = self.common.run(SingleDevice, args,
                                            single_device=True)
            del engine, state
            ref = self.losses("stage_single")
            facts["single"] = [round(x, 4) for x in ref]
            resting = {}
            for name, cls in (("ddp", DDP), ("zero1", Zero1),
                              ("zero2", Zero2), ("zero3", Zero3)):
                tag = f"stage_{name}"
                engine, state = self.common.run(
                    cls, self.train_args(tag, iters=2, batch_per_device=1))
                ls = self.losses(tag)
                for i, (a, b) in enumerate(zip(ls, ref)):
                    self.check(abs(a - b) <= LOSS_TOL,
                               f"{name} step {i}: {a:.4f} vs single "
                               f"{b:.4f} (<= {LOSS_TOL})")
                p_sh, o_sh, wire_ops = promise[name]
                for part, tree, want in (
                        ("params", state.params, p_sh),
                        ("opt_state", state.opt_state, o_sh)):
                    big = [x for x in jax.tree.leaves(tree) if x.size > n]
                    self.check(all(len({s.device for s in
                                        x.addressable_shards}) == n
                                   for x in big),
                               f"{name} {part}: every leaf on {n} devices")
                    sharded = [not x.sharding.is_fully_replicated
                               for x in big]
                    self.check(all(sharded) if want else not any(sharded),
                               f"{name} {part}: "
                               f"{'sharded' if want else 'replicated'} "
                               f"({sum(sharded)}/{len(big)} leaves sharded)")
                per = self.resting_bytes(state)
                resting[name] = max(per.values())
                self.check(len(per) == n and min(per.values()) > 0,
                           f"{name}: resting state on all {n} devices "
                           f"({ {k: v >> 20 for k, v in per.items()} } MiB)")
                probe = jnp.zeros((n, self.seq), jnp.int32)
                led = collective_ledger(engine._step.lower(
                    state, (probe, probe)).compile().as_text())
                total = led["total_wire_bytes"] or 1.0
                share = {k: round(v / total, 3)
                         for k, v in led["wire_bytes"].items()}
                model_total = comm_report(engine)["total_bytes_per_step"]
                facts[name] = {
                    "losses": [round(x, 4) for x in ls],
                    "resting_mib_per_chip": resting[name] >> 20,
                    "wire_share": share,
                    "wire_mb": round(total / 1e6, 1),
                    "comm_report_mb": round(model_total / 1e6, 1),
                }
                if self.rehearsal:
                    # the CPU partitioner emits other collectives than
                    # the TPU's (all-reduce + slice for reduce-scatter)
                    print(f"  [rehearsal] {name} wire shares: {share}")
                else:
                    self.check(
                        all(share.get(op, 0) > 0.2 for op in wire_ops),
                        f"{name}: compiled step carries {wire_ops} "
                        f"({share})")
                    if name == "ddp":
                        self.check(share.get("reduce-scatter", 0) == 0,
                                   "ddp: no reduce-scatter")
                    self.check(
                        0.5 <= total / model_total <= 1.5,
                        f"{name}: measured wire {total / 1e6:.0f} MB vs "
                        f"comm_report {model_total / 1e6:.0f} MB")
                del engine, state
                gc.collect()
            mem = self.hbm()
            if mem is not None:
                self.check(all(m["peak_in_use_gb"] > 0 for m in mem),
                           f"memory_stats: bytes on every device ({mem})")
            ratio = resting["zero3"] / resting["ddp"]
            facts["zero3_over_ddp_resting"] = round(ratio, 3)
            self.check(0.8 / n <= ratio <= 1.4 / n,
                       f"ZeRO-3 resting bytes/chip are ~1/{n} of DDP's "
                       f"(ratio {ratio:.3f})")

    def phase_big(self, name, cls, model="gpt2-1.5b"):
        """examples/zero3/train.py at its default (gpt2-1.5b, one sequence
        per chip, T=1024), and examples/zero2/train.py --model gpt2-1.5b.
        A stage whose f32 state does not fit the chips says so and runs
        again with bf16 parameters."""
        import jax.numpy as jnp
        if self.rehearsal:
            model = self.model
        with self.phase(name) as facts:
            over = None
            for attempt in ("f32 params", "bf16 params"):
                tag = f"{name}_{attempt.split()[0]}"
                try:
                    engine, state = self.common.run(
                        cls, self.train_args(tag, model=model, iters=4,
                                             batch_per_device=1),
                        cfg_overrides=over)
                    break
                except Exception as e:  # noqa: BLE001 - only OOM retries
                    if "RESOURCE_EXHAUSTED" not in repr(e) or over:
                        raise
                    oom = repr(e)[:300]
                # outside the handler: the traceback's frames (and the
                # arrays they hold) are released before the next attempt
                print(f"  FINDING: {name} {model} with {attempt} does not "
                      f"fit: {oom}", flush=True)
                facts["does_not_fit"] = attempt
                over = {"param_dtype": jnp.bfloat16}
                gc.collect()
            facts["params"] = attempt
            per = self.resting_bytes(state)
            facts["resting_gib_per_chip"] = round(
                max(per.values()) / 2**30, 2)
            del engine, state
            self.check_losses(self.losses(tag), model, facts)

    # -- driver -------------------------------------------------------------

    def interpret_switches(self):
        from tiny_deepspeed_tpu.ops import (
            flash_fa2, layernorm_pallas, paged_attn_pallas, quant_pallas,
            xent_pallas,
        )
        from tiny_deepspeed_tpu.optim import adamw_pallas
        return [(flash_fa2, "_INTERPRET"), (xent_pallas, "_INTERPRET"),
                (quant_pallas, "_INTERPRET"),
                (paged_attn_pallas, "INTERPRET"),
                (layernorm_pallas, "INTERPRET"), (adamw_pallas, "INTERPRET")]

    def run(self) -> int:
        jax = self.jax
        import jaxlib
        from importlib import metadata
        try:
            libtpu = metadata.version("libtpu")
        except metadata.PackageNotFoundError:
            libtpu = "unknown"
        dev = jax.devices()[0]
        device = {"platform": dev.platform, "kind": dev.device_kind,
                  "count": len(jax.devices())}
        print(f"device: platform={dev.platform} device_kind="
              f"{dev.device_kind!r} count={device['count']} "
              f"jax={jax.__version__} jaxlib={jaxlib.__version__} "
              f"libtpu={libtpu}", flush=True)
        if self.rehearsal:
            print("*** REHEARSAL on the CPU at tiny size with interpreted "
                  "kernels: NOT a chip result ***", flush=True)
        env = {k: v for k, v in os.environ.items()
               if k.startswith(("TPU_", "JAX_", "MEGASCALE", "XLA_"))}
        print(f"env: {env}")
        entries = (len(os.listdir(self.cache_dir))
                   if os.path.isdir(self.cache_dir) else 0)
        print(f"compile cache: {self.cache_dir} ({entries} entries at "
              f"start: {'warm' if entries else 'cold'})", flush=True)

        switches = self.interpret_switches()
        if self.rehearsal:
            from tiny_deepspeed_tpu.ops.dispatch import force_kernel_target
            for mod, attr in switches:
                setattr(mod, attr, True)
            force_kernel_target("tpu")  # trace the kernel arms, interpreted
        else:
            on = [f"{m.__name__}.{a}" for m, a in switches if getattr(m, a)]
            if on:
                print(f"interpret mode is ON on the chip: {on}",
                      file=sys.stderr)
                return 1
            print("interpret mode: off at every kernel")

        self.phase_train()
        self.phase_parity()
        self.phase_serve()
        n = len(jax.devices())
        if n == 4:
            from tiny_deepspeed_tpu import Zero2, Zero3
            self.phase_stages()
            self.phase_big("zero3_1p5b", Zero3)
            self.phase_big("zero2_1p5b", Zero2)
        else:
            print(f"\nfour-chip phases: not run ({n} devices)")

        wall = time.perf_counter() - self.t_start
        totals = {
            "wall_s": round(wall, 1), "compile_s": round(self.compile_s, 1),
            "cache_dir": self.cache_dir,
            "cache_hits": self.cache_hits,
            "cache_misses": self.cache_misses,
            "cache": "warm" if entries else "cold",
            "hbm": self.hbm(),
            "verdicts": {k: v["verdict"] for k, v in self.summary.items()},
        }
        print(f"\ntotals: {json.dumps(totals)}", flush=True)
        print(f"memory_stats(device 0): {dev.memory_stats()}")
        with open(os.path.join(self.out, "summary.json"), "w") as f:
            json.dump({"device": device, "rehearsal": bool(self.rehearsal),
                       "totals": totals, "phases": self.summary}, f,
                      indent=1, default=str)
        result = {"ok": not self.failed, "device": device}
        if self.rehearsal:
            result["rehearsal"] = True
        if self.failed:
            result["failed"] = self.failed
        print(json.dumps(result), flush=True)
        return 1 if self.failed else 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument(
        "--rehearse-cpu", type=int, default=0, metavar="N",
        help="labeled rehearsal at tiny size on N virtual CPU devices with "
             "interpreted kernels (N=4 also rehearses the four-chip phases)")
    ap.add_argument(
        "--out", default=os.path.join(ROOT, "chiprun_out", "chip_smoke"),
        metavar="DIR", help="where the per-phase JSONL and summary.json go")
    return Smoke(ap.parse_args()).run()


if __name__ == "__main__":
    sys.exit(main())
