# Copyright 2026 tiny-deepspeed-tpu authors
# SPDX-License-Identifier: Apache-2.0

"""Capture a per-op TPU profile of the default bench step and bucket it.

Automates the round-4 analysis behind PROFILE.md "chip profile": traces 5
steps of the default single-chip config with `jax.profiler.trace`, parses
the XPlane with `jax.profiler.ProfileData` (no TensorBoard needed), and
prints a JSON bucket table (ms/step by op family).  Run on a live TPU:

    python scripts/profile_step.py [--model gpt2-124m] [--out DIR]

The buckets are the ceiling-analysis vocabulary: attention kernels, vocab
head (50304-shaped), MLP (4d-shaped), QKV (3d-shaped), scan stash
slices, copies, other.  Sum of buckets reproduces the device step time
(the `%while` wrappers are skipped; their children are counted).
"""

import argparse
import dataclasses
import glob
import json
import os
import sys
from collections import defaultdict

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

STEPS = 5


def bucket_for(name: str, d_model: int, vocab: int) -> str:
    head = name.split(" = ")[0]
    if head.startswith("%while"):
        return "SKIP"
    if "_xent_" in name:
        return "fused xent kernels"  # ops/xent_pallas.py (BENCH_XENT=pallas)
    if ("flash" in name or "_fwd_kernel" in name or "_bwd_dkv" in name
            or "_bwd_dq" in name):
        return "attention kernels"
    if str(vocab) in name:
        return "vocab head/xent/embed"
    if str(4 * d_model) in name:
        return "MLP fusions"
    if str(3 * d_model) in name:
        return "QKV fusions"
    if "dynamic-update-slice" in name or "dynamic-slice" in name:
        return "scan stash/slices"
    if "copy-start" in head or "copy-done" in head:
        # the offload stream's async host<->HBM transfers (and any other
        # async copies) — the bucket VERDICT r4 #5 asked for: on an
        # offload_opt_state run this is the moments traffic, and its
        # size vs the update/other buckets says what the streaming hides
        return "async copies (offload stream)"
    if "copy" in head:
        return "copies"
    return "other"


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="gpt2-124m")
    ap.add_argument("--out", default="/tmp/profile_step")
    ap.add_argument("--offload", action="store_true",
                    help="profile the offload_opt_state step (adds the "
                         "async-copy bucket attribution for the moments "
                         "stream)")
    ap.add_argument("--offload-prefetch", type=int, default=4)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from tiny_deepspeed_tpu.utils.startup import select_platform
    select_platform()  # a device trace needs the chip

    from bench import _bench_config
    from tiny_deepspeed_tpu import AdamW, SingleDevice, make_mesh
    from tiny_deepspeed_tpu.models import ALL_PRESETS, build_model

    bc = _bench_config(args.model)
    cfg = dataclasses.replace(ALL_PRESETS[args.model], **bc["overrides"])
    model = build_model(cfg)
    opt = AdamW(lr=1e-5, weight_decay=0.1,
                state_dtype=bc["state_dtype"] or jnp.float32)
    ek = {}
    if args.offload:
        ek = dict(offload_opt_state=True,
                  offload_prefetch=args.offload_prefetch)
    # ONE chip even on a four-chip host: SingleDevice over every visible
    # device would replicate the step, not profile the single-chip config
    engine = SingleDevice(model, opt,
                          mesh=make_mesh(devices=[jax.devices()[0]]), **ek)
    state = engine.init(jax.random.PRNGKey(0))
    b, t = bc["batch"], 1024
    idx = jax.random.randint(jax.random.PRNGKey(1), (b, t), 0,
                             cfg.vocab_size, jnp.int32)
    tgt = jax.random.randint(jax.random.PRNGKey(2), (b, t), 0,
                             cfg.vocab_size, jnp.int32)
    for _ in range(5):
        state, loss = engine.step(state, (idx, tgt))
    float(loss)
    with jax.profiler.trace(args.out):
        for _ in range(STEPS):
            state, loss = engine.step(state, (idx, tgt))
        float(loss)

    from jax.profiler import ProfileData
    xplane = sorted(glob.glob(
        os.path.join(args.out, "plugins/profile/*/*.xplane.pb")))[-1]
    p = ProfileData.from_file(xplane)
    tpu = next((pl for pl in p.planes if "TPU" in pl.name), None)
    if tpu is None:
        raise SystemExit(
            f"no TPU plane in {xplane} (planes: "
            f"{[pl.name for pl in p.planes]})")
    ops = next(ln for ln in tpu.lines if ln.name == "XLA Ops")
    tot = defaultdict(float)
    for e in ops.events:
        bk = bucket_for(e.name, cfg.n_embd, cfg.vocab_size)
        if bk != "SKIP":
            tot[bk] += e.duration_ns / 1e6 / STEPS
    print(json.dumps({
        "model": args.model, "batch": b, "offload": bool(args.offload),
        "xplane": xplane,
        "step_ms": round(sum(tot.values()), 2),
        "buckets_ms": {k: round(v, 2) for k, v in
                       sorted(tot.items(), key=lambda x: -x[1])},
    }))


if __name__ == "__main__":
    main()
