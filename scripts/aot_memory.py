# Copyright 2026 tiny-deepspeed-tpu authors
# SPDX-License-Identifier: Apache-2.0

"""AOT memory + roofline analysis of the bench configs on a v5e topology.

Compiles each BASELINE.md single-chip bench configuration (bench.py
_bench_config: model preset + dtype/remat/batch knobs) against a
compile-only single-chip v5e topology — no hardware, libtpu compiles
locally — and reports, per config:

  * compiled peak HBM: live TrainState bytes + XLA temp allocation
    (the same accounting bench.py reports from the real chip);
  * ANALYTIC roofline floors — compute: matmul FLOPs (bench.py's honest
    MFU accounting) / 197 bf16 TF/s; memory: a weight/optimizer traffic
    LOWER bound (weights read 3x per step [fwd + dx + dw passes], moments
    read+written, params written) / 819 GB/s.  Deliberately NOT
    `compiled.cost_analysis()`: XLA's flops/bytes counters count a
    while-loop body ONCE, so remat scans understate true work L-fold
    (the same trip-count trap utils/hlo_comm.py handles for collectives).

The floors are the CEILING ANALYSIS for the throughput numbers: measured
step time can approach but not beat max(compute_floor, hbm_floor); the
gap between measured step time and the binding floor is the optimization
headroom (round-4 verdict #3 for gpt2-124m).

Usage: python scripts/aot_memory.py [--topology v5e:1x1] [--json OUT]
"""

import argparse
import dataclasses
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np
import jax

jax.config.update("jax_platforms", "cpu")  # trace locally; libtpu compiles

import jax.numpy as jnp
from jax.experimental import topologies
from jax.sharding import Mesh

from tiny_deepspeed_tpu.ops.dispatch import kernel_target_forced

V5E_PEAK_FLOPS = 197e12  # bf16
V5E_HBM_BW = 819e9       # bytes/s
V5E_HBM_GB = 16.0


def _matmul_flops_per_token(model, cfg, t):
    """bench.py's honest MFU accounting: 6 x non-embedding (active) params
    + 12*L*T*d attention FLOPs per token (wte/wpe gathers excluded)."""
    from tiny_deepspeed_tpu.models.llama import LlamaConfig
    from tiny_deepspeed_tpu.models.moe import MoEConfig
    import math

    n_params = model.num_params()
    embed = cfg.vocab_size * cfg.n_embd + (
        0 if isinstance(cfg, LlamaConfig) else cfg.block_size * cfg.n_embd
    )
    n_active = n_params
    if isinstance(cfg, MoEConfig):
        expert = sum(
            int(math.prod(s.shape))
            for n, s in model.param_shapes().items()
            if ".moe." in n and "router" not in n
        )
        n_active = (n_params - expert
                    + expert * cfg.expert_top_k // cfg.n_expert)
    return 6 * (n_active - embed) + 12 * cfg.n_layer * t * cfg.n_embd


def _traffic_floor_bytes(state):
    """Per-step HBM traffic LOWER bound from the live state alone:
    params read 3x (fwd, dx pass, dw pass) + written once; optimizer
    state read + written.  Ignores activations, logits, and grads — a
    true floor, so the implied tokens/s is an upper bound."""
    params_b = opt_b = 0
    for path, x in jax.tree_util.tree_flatten_with_path(state)[0]:
        b = int(np.prod(x.shape)) * x.dtype.itemsize
        if any(getattr(p, "name", None) == "params"
               or getattr(p, "key", None) == "params" for p in path):
            params_b += b
        else:
            opt_b += b
    return 4 * params_b + 2 * opt_b


def _bench_engine(model_name: str, mesh, t=1024, offload=False):
    """Mirror bench.py run_one's single-chip engine construction."""
    import bench
    from tiny_deepspeed_tpu import AdamW, SingleDevice
    from tiny_deepspeed_tpu.models import ALL_PRESETS, build_model

    bc = bench._bench_config(model_name)
    cfg = dataclasses.replace(ALL_PRESETS[model_name], **bc["overrides"])
    if t > cfg.block_size:
        cfg = dataclasses.replace(cfg, block_size=t, remat=True,
                                  fused_xent=True)
    model = build_model(cfg)
    opt = AdamW(lr=1e-5, weight_decay=0.1,
                state_dtype=bc["state_dtype"] or jnp.float32)
    eng = SingleDevice(model, opt, mesh=mesh,
                       offload_opt_state=offload)
    return eng, bc["batch"], cfg


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--topology", default="v5e:2x2",
                    help="smallest v5e topology libtpu accepts is 2x2; the "
                         "single-chip engines compile on a 1-device mesh "
                         "carved from it")
    ap.add_argument("--json", default="/tmp/aot_memory.json")
    ap.add_argument("--seq", type=int, default=0,
                    help="override T for every config (long-context rows)")
    args = ap.parse_args()

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name=args.topology)
    devs = np.array(topo.devices)
    mesh = Mesh(devs[:1], ("data",))  # single-chip bench configs
    print(f"topology {args.topology}: {devs.size}x "
          f"{topo.devices[0].device_kind} (using 1 device)", flush=True)

    # import the sibling script for the shared abstract-state builders
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "aot_topology_script",
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "aot_topology.py"),
    )
    aot = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(aot)

    cases = [
        ("gpt2-124m", {}),
        ("gpt2-350m", {}),
        ("gpt2-774m", {}),
        ("gpt2-1.5b", {}),
        ("moe-8x124m", {}),
        ("llama-160m", {}),
        ("gpt2-124m", {"t": 4096, "b": 2}),
        ("gpt2-124m", {"t": 8192, "b": 1}),
        ("gpt2-1.5b", {"offload": True}),
        ("llama-1b", {"b": 4}),
    ]
    results = []
    for model_name, kw in cases:
        t = kw.get("t", args.seq or 1024)
        label = model_name + (f"-t{t}" if t != 1024 else "") \
            + ("-offload" if kw.get("offload") else "")
        try:
            eng, b_dflt, cfg = _bench_engine(
                model_name, mesh, t=t, offload=kw.get("offload", False)
            )
            b = kw.get("b", b_dflt)
            state = aot._state_structs(eng)
            compiled = None
            while True:
                try:
                    with kernel_target_forced("tpu"):
                        compiled = eng._step.lower(
                            state, aot._batch_structs(eng, b, t)
                        ).compile()
                    break
                except Exception as e:
                    # compile-time HBM OOM: step the batch down and label
                    # it — the fitting envelope is itself a result
                    if "RESOURCE_EXHAUSTED" in repr(e) and b > 1:
                        b -= 1
                        continue
                    raise
            mem = compiled.memory_analysis()
            state_bytes = sum(
                int(np.prod(x.shape)) * x.dtype.itemsize
                for x in jax.tree.leaves(state)
                if getattr(x.sharding, "memory_kind", None) != "pinned_host"
            )
            temp = int(mem.temp_size_in_bytes)
            hbm_gb = (state_bytes + temp) / 2**30
            toks = b * t
            flops = _matmul_flops_per_token(eng.model, cfg, t) * toks
            traffic = _traffic_floor_bytes(state)
            compute_floor_ms = flops / V5E_PEAK_FLOPS * 1e3
            hbm_floor_ms = traffic / V5E_HBM_BW * 1e3
            floor_ms = max(compute_floor_ms, hbm_floor_ms)
            rec = {
                "label": label, "batch": b, "seq": t,
                "batch_reduced_from": (None if b == kw.get("b", b_dflt)
                                       else kw.get("b", b_dflt)),
                "state_gb": round(state_bytes / 2**30, 3),
                "temp_gb": round(temp / 2**30, 3),
                "peak_hbm_gb": round(hbm_gb, 3),
                "fits_16gb": hbm_gb < V5E_HBM_GB,
                "matmul_flops_per_step": flops,
                "traffic_floor_bytes": traffic,
                "compute_floor_ms": round(compute_floor_ms, 3),
                "hbm_floor_ms": round(hbm_floor_ms, 3),
                "bound": ("compute" if compute_floor_ms >= hbm_floor_ms
                          else "hbm"),
                "roofline_tokens_per_sec": (
                    round(toks / (floor_ms / 1e3), 1) if floor_ms else None
                ),
            }
            note = (f" (b {rec['batch_reduced_from']}->{b})"
                    if rec["batch_reduced_from"] else "")
            print(f"{label}{note}: peak_hbm={rec['peak_hbm_gb']:.2f}GB "
                  f"floors(compute={compute_floor_ms:.1f}ms, "
                  f"hbm={hbm_floor_ms:.1f}ms) -> {rec['bound']}-bound, "
                  f"roofline {rec['roofline_tokens_per_sec']:.0f} tok/s",
                  flush=True)
        except Exception as e:
            rec = {"label": label,
                   "error": f"{type(e).__name__}: {e}"[:400]}
            print(f"{label}: ERROR {rec['error'][:160]}", flush=True)
        results.append(rec)

    # ---- north-star shape (BASELINE.md): 1.5B ZeRO-2/3 on a 16-chip v5e
    # pod slice — per-chip compiled memory of the REAL-size multi-chip
    # program (the dryrun covers tiny shapes only; this is the full model)
    import dataclasses as _dc
    from tiny_deepspeed_tpu import AdamW, Zero2, Zero3
    from tiny_deepspeed_tpu.models import ALL_PRESETS, build_model

    # ---- long context at real scale: ring attention over a seq=8 mesh,
    # GPT-2 124M widened to T=32k/64k — per-chip compiled memory is the
    # O(T/n) claim at sizes one chip cannot hold (round-3 CPU evidence
    # stopped at T=16k)
    for t_long in (32768, 65536):
        label = f"ring-sp8-124m-t{t_long}"
        try:
            topo8 = topologies.get_topology_desc(platform="tpu",
                                                 topology_name="v5e:4x2")
            d8 = np.array(topo8.devices)
            mesh8 = Mesh(d8.reshape(1, 8), ("data", "seq"))
            cfgL = _dc.replace(
                ALL_PRESETS["gpt2-124m"], block_size=t_long,
                param_dtype=jnp.bfloat16, remat=True,
            )
            eng = Zero2(build_model(cfgL), AdamW(lr=1e-5), mesh=mesh8,
                        seq_parallel=8)
            state = aot._state_structs(eng)
            with kernel_target_forced("tpu"):
                compiled = eng._step.lower(
                    state, aot._batch_structs(eng, 1, t_long)
                ).compile()
            mem = compiled.memory_analysis()
            state_b = sum(
                int(np.prod(x.sharding.shard_shape(x.shape)))
                * x.dtype.itemsize
                for x in jax.tree.leaves(state)
            )
            temp = int(mem.temp_size_in_bytes)
            rec = {"label": label, "devices": 8, "batch": 1, "seq": t_long,
                   "state_gb_per_chip": round(state_b / 2**30, 3),
                   "temp_gb_per_chip": round(temp / 2**30, 3),
                   "peak_hbm_gb_per_chip": round(
                       (state_b + temp) / 2**30, 3)}
            print(f"{label}: per-chip state={rec['state_gb_per_chip']}GB "
                  f"temp={rec['temp_gb_per_chip']}GB "
                  f"peak={rec['peak_hbm_gb_per_chip']}GB", flush=True)
        except Exception as e:
            rec = {"label": label,
                   "error": f"{type(e).__name__}: {e}"[:400]}
            print(f"{label}: ERROR {repr(e)[:200]}", flush=True)
        results.append(rec)

    for label, eng_cls in (("northstar-zero2-1.5b-dp16", Zero2),
                           ("northstar-zero3-1.5b-dp16", Zero3)):
        try:
            topo16 = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:4x4"
            )
            d16 = np.array(topo16.devices)
            mesh16 = Mesh(d16.reshape(d16.size), ("data",))
            cfg15 = _dc.replace(
                ALL_PRESETS["gpt2-1.5b"],
                param_dtype=jnp.bfloat16, fused_xent=True,
            )  # f32 moments SHARDED across chips replace the single-chip
            #    bf16-moment squeeze (BASELINE.md fitting note)
            eng = eng_cls(build_model(cfg15),
                          AdamW(lr=1e-5, weight_decay=0.1), mesh=mesh16)
            state = aot._state_structs(eng)
            b16 = 4 * d16.size  # per-chip batch 4, the bench 1.5b setting
            while True:
                try:
                    with kernel_target_forced("tpu"):
                        compiled = eng._step.lower(
                            state, aot._batch_structs(eng, b16, 1024)
                        ).compile()
                    break
                except Exception as e:
                    if "RESOURCE_EXHAUSTED" in repr(e) and \
                            b16 > d16.size:
                        b16 -= d16.size
                        continue
                    raise
            mem = compiled.memory_analysis()
            # per-chip: sharded leaves already count 1/N via shard_shape
            state_b = sum(
                int(np.prod(x.sharding.shard_shape(x.shape)))
                * x.dtype.itemsize
                for x in jax.tree.leaves(state)
            )
            temp = int(mem.temp_size_in_bytes)  # per device
            rec = {
                "label": label, "devices": int(d16.size),
                "batch_global": b16, "seq": 1024,
                "state_gb_per_chip": round(state_b / 2**30, 3),
                "temp_gb_per_chip": round(temp / 2**30, 3),
                "peak_hbm_gb_per_chip": round(
                    (state_b + temp) / 2**30, 3),
            }
            print(f"{label}: per-chip state={rec['state_gb_per_chip']}GB "
                  f"temp={rec['temp_gb_per_chip']}GB "
                  f"peak={rec['peak_hbm_gb_per_chip']}GB", flush=True)
        except Exception as e:
            rec = {"label": label,
                   "error": f"{type(e).__name__}: {e}"[:400]}
            print(f"{label}: ERROR {repr(e)[:200]}", flush=True)
        results.append(rec)

    out = {"topology": args.topology,
           "device_kind": topo.devices[0].device_kind,
           "assumptions": {"peak_flops": V5E_PEAK_FLOPS,
                           "hbm_bw": V5E_HBM_BW},
           "results": results}
    with open(args.json, "w") as f:
        json.dump(out, f, indent=1)
    print(f"wrote {args.json}")


if __name__ == "__main__":
    main()
