# Copyright 2026 tiny-deepspeed-tpu authors
# SPDX-License-Identifier: Apache-2.0

"""AOT-compile the engines against a REAL TPU topology (no hardware).

Round-3 verdict: every multi-chip claim was audited on XLA-CPU HLO, which
provably differs from the TPU partitioner's output (all-reduce where
reduce-scatter is intended; f8 collectives upcast to f16; no async
-start/-done pairs).  JAX can lower + compile against a *compile-only* TPU
topology via `jax.experimental.topologies` — libtpu compiles locally, no
devices needed.  This script does exactly that for each engine stage and
feeds the TPU-partitioned HLO to `utils.hlo_comm.collective_ledger`,
settling three questions one chip cannot answer:

  1. Does the TPU partitioner emit TRUE reduce-scatter for ZeRO-2/3 grads
     (XLA CPU emits all-reduce instead — PROFILE.md caveat 1)?
  2. Does the fp8 weight gather (gather_quant="fp8") move f8 bytes on the
     wire, or is the feature dead on TPU too (CPU: +1.34x bytes)?
  3. Do async `-start`/`-done` pairs appear — the first compiled evidence
     for the "XLA latency-hides the collectives" overlap claim
     (engine.py:14-18 vs reference ddp/module.py:36-78)?

Usage:  python scripts/aot_topology.py [--topology v5e:4x2] [--json OUT]
Writes a JSON summary; PROFILE.md's "TPU topology HLO" section is the
human-readable digest.
"""

import argparse
import json
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np
import jax

# Trace/constant-fold on the local CPU whatever the session's platform;
# the TPU compilation happens via the compile-only topology client
# (libtpu), no chip involved.
jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp
from jax.experimental import topologies
from jax.sharding import Mesh

from tiny_deepspeed_tpu import (
    AdamW, DDP, GPT2Model, GPTConfig, Zero1, Zero2, Zero3,
)
from tiny_deepspeed_tpu.parallel.engine import TrainState
from tiny_deepspeed_tpu.ops.dispatch import kernel_target_forced
from tiny_deepspeed_tpu.utils.hlo_comm import collective_ledger
from tiny_deepspeed_tpu.utils.profiling import comm_report

# real async op pairs (ppermute compiles to these on TPU)
_COLLECTIVE_START_RE = re.compile(
    r"(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all)"
    r"-start\("
)
# collectives the TPU backend scheduled async WITHOUT renaming the op: the
# frontend attribute records the start half of the pair
_ASYNC_ATTR_RE = re.compile(r'async_collective_name="([\w\.\-]+)"')
# every all-gather result shape, to split gathered bytes by dtype (the fp8
# question: do the ZeRO-3 layer gathers move f8 on the TPU wire?)
_GATHER_RESULT_RE = re.compile(r"=\s*((?:\([^)]*\)|\S+))\s*all-gather\(")
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_DTYPE_BYTES = {"f32": 4, "bf16": 2, "f16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
                "f8e4m3": 1, "s32": 4, "u32": 4}


def _state_structs(engine):
    """Abstract TrainState + batch matching the engine's jit shardings —
    engine.init() would need executable devices; a topology has none.
    (Shared with tests/test_aot_topology.py — keep the single copy here.)"""
    params = jax.eval_shape(engine.model.init, jax.random.PRNGKey(0))
    opt = jax.eval_shape(engine.optimizer.init, params)

    def attach(avals, shardings):
        return jax.tree.map(
            lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
            avals, shardings,
        )

    dropout_base = None
    if engine._dropout_shardings is not None:
        key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
        dropout_base = jax.ShapeDtypeStruct(
            key.shape, key.dtype, sharding=engine._dropout_shardings
        )
    scaler = None
    if engine._scaler_shardings is not None:  # loss_scale="dynamic"
        scaler = {
            "scale": jax.ShapeDtypeStruct(
                (), jnp.float32,
                sharding=engine._scaler_shardings["scale"]),
            "good": jax.ShapeDtypeStruct(
                (), jnp.int32, sharding=engine._scaler_shardings["good"]),
        }
    return TrainState(
        params=attach(params, engine._param_shardings),
        opt_state=attach(opt, engine._opt_shardings),
        scaler=scaler,
        dropout_base=dropout_base,
    )


def _batch_structs(engine, b, t):
    shape = (b, t)
    if engine.accum_steps > 1:  # microbatched step: (accum, B, T)
        shape = (engine.accum_steps,) + shape
    s = jax.ShapeDtypeStruct(shape, jnp.int32,
                             sharding=engine._batch_sharding)
    return (s, s)


def analyze(engine, b, t, label, dump_dir=None):
    state = _state_structs(engine)
    batch = _batch_structs(engine, b, t)
    # trace with the TPU kernel gates ON: the process backend is CPU, but
    # the program targets TPU — without the force every Pallas gate picks
    # the XLA fallback and the compiled program differs from the chip's
    # (ops/dispatch.py; found in round 4 via chip-vs-AOT memory mismatch)
    with kernel_target_forced("tpu"):
        compiled = engine._step.lower(state, batch).compile()
    text = compiled.as_text()
    if dump_dir:
        os.makedirs(dump_dir, exist_ok=True)
        with open(os.path.join(dump_dir, f"{label}.hlo"), "w") as f:
            f.write(text)
    ledger = collective_ledger(text)
    starts = {}
    for m in _COLLECTIVE_START_RE.finditer(text):
        starts[m.group(1)] = starts.get(m.group(1), 0) + 1
    gather_by_dtype = {}
    for m in _GATHER_RESULT_RE.finditer(text):
        for dt, dims in _SHAPE_RE.findall(m.group(1)):
            if dt not in _DTYPE_BYTES:
                continue
            nel = 1
            for d in dims.split(","):
                if d:
                    nel *= int(d)
            gather_by_dtype[dt] = (gather_by_dtype.get(dt, 0)
                                   + nel * _DTYPE_BYTES[dt])
    predicted = comm_report(engine)
    return {
        "label": label,
        "ledger": {
            k: ledger[k] for k in
            ("payload_bytes", "wire_bytes", "count", "total_wire_bytes",
             "unresolved_loops", "unresolved_groups")
        },
        "async_start_pairs": starts,
        "async_attr_collectives": len(_ASYNC_ATTR_RE.findall(text)),
        "gather_result_bytes_by_dtype": gather_by_dtype,
        "comm_report_total": predicted.get("total_bytes_per_step"),
        "comm_report": predicted,
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--topology", default="v5e:4x2")
    ap.add_argument("--json", default="/tmp/aot_topology.json")
    ap.add_argument("--dump-hlo", default=None, metavar="DIR",
                    help="also write each config's compiled HLO text to "
                         "DIR/<label>.hlo (the PROFILE.md evidence files)")
    args = ap.parse_args()

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name=args.topology)
    devs = np.array(topo.devices)
    n = devs.size
    print(f"topology {args.topology}: {n}x {topo.devices[0].device_kind}",
          flush=True)

    cfg = GPTConfig(block_size=128, vocab_size=512, n_layer=4, n_head=8,
                    n_embd=256)
    b, t = n, cfg.block_size
    opt = lambda: AdamW(lr=1e-3)

    mesh_dp = Mesh(devs.reshape(n), ("data",))
    mesh_tp = Mesh(devs.reshape(n // 2, 2), ("data", "model"))
    mesh_sp = Mesh(devs.reshape(n // 2, 2), ("data", "seq"))
    mesh_pp = Mesh(devs.reshape(n // 2, 2), ("data", "pipe"))
    mesh_ep = Mesh(devs.reshape(n // 2, 2), ("data", "expert"))

    def _moe_ep_engine():
        from tiny_deepspeed_tpu import MoEConfig, MoEGPT
        mcfg = MoEConfig(block_size=128, vocab_size=512, n_layer=2,
                         n_head=4, n_embd=64, n_expert=4, expert_top_k=2)
        return Zero2(MoEGPT(mcfg), opt(), mesh=mesh_ep, expert_parallel=2)

    cases = [
        ("ddp", lambda: DDP(GPT2Model(cfg), opt(), mesh=mesh_dp)),
        ("zero1", lambda: Zero1(GPT2Model(cfg), opt(), mesh=mesh_dp)),
        ("zero2", lambda: Zero2(GPT2Model(cfg), opt(), mesh=mesh_dp)),
        ("zero3", lambda: Zero3(GPT2Model(cfg), opt(), mesh=mesh_dp)),
        ("zero3-fp8", lambda: Zero3(
            GPT2Model(GPTConfig(**{**cfg.__dict__, "gather_quant": "fp8"})),
            opt(), mesh=mesh_dp)),
        ("zero3-tp2", lambda: Zero3(GPT2Model(cfg), opt(), mesh=mesh_tp,
                                    tensor_parallel=2)),
        ("zero2-ring-sp2", lambda: Zero2(GPT2Model(cfg), opt(), mesh=mesh_sp,
                                         seq_parallel=2)),
        ("zero1-pipe2-1f1b", lambda: Zero1(
            GPT2Model(cfg), opt(), mesh=mesh_pp, pipeline_parallel=2,
            pipeline_microbatches=4, pipeline_schedule="1f1b")),
        # sharded f32 accumulator across microbatches: each microbatch's
        # grads reduce-scatter into the shard (engine.py round-1 design)
        ("zero2-accum4", lambda: Zero2(GPT2Model(cfg), opt(),
                                       mesh=mesh_dp, accum_steps=4)),
        # expert parallelism: capacity-bucketed dispatch over the "expert"
        # axis — does the TPU partitioner emit real all-to-all?
        ("moe-zero2-ep2", lambda: _moe_ep_engine()),
    ]

    results = []
    for label, make in cases:
        try:
            engine = make()
            res = analyze(engine, b, t, label, dump_dir=args.dump_hlo)
            rs = res["ledger"]["wire_bytes"].get("reduce-scatter", 0)
            ar = res["ledger"]["wire_bytes"].get("all-reduce", 0)
            print(f"{label}: total_wire={res['ledger']['total_wire_bytes']:.3e}"
                  f" (predicted {res['comm_report_total']:.3e})"
                  f" rs={rs:.3e} ar={ar:.3e}"
                  f" starts={res['async_start_pairs']}"
                  f" async_attrs={res['async_attr_collectives']}"
                  f" gathers={res['gather_result_bytes_by_dtype']}",
                  flush=True)
        except Exception as e:  # keep going: one failed case != no report
            res = {"label": label, "error": f"{type(e).__name__}: {e}"[:500]}
            print(f"{label}: ERROR {res['error'][:200]}", flush=True)
        results.append(res)

    out = {"topology": args.topology, "n_devices": n,
           "device_kind": topo.devices[0].device_kind,
           "model": "gpt2 L4/H8/D256/V512", "batch": [b, t],
           "results": results}
    with open(args.json, "w") as f:
        json.dump(out, f, indent=1)
    print(f"wrote {args.json}")


if __name__ == "__main__":
    main()
