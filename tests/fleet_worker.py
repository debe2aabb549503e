# Copyright 2026 tiny-deepspeed-tpu authors
# SPDX-License-Identifier: Apache-2.0

"""Worker for tests/test_fleet.py's real-SIGKILL fleet recovery test —
NOT a pytest module.

Run as:  python fleet_worker.py <mode> <journal_base>

Modes:
  straight — the fixed 4-request trace through a 2-replica fleet,
             uninterrupted; print {"outputs": {id: [tokens]}}.
  serve    — the same trace through a 2-replica fleet whose replicas
             journal to <base>.r0 / <base>.r1; at the Nth router tick,
             SIGKILL ourselves from replica 0's journal commit hook —
             a REAL process death takes the WHOLE fleet (no in-process
             failover possible; both WALs survive on disk).
  recover  — ONE fresh engine with its own journal (<base>.new)
             replays BOTH dead replicas' journals through the
             cross-journal `recover()` path (the "sibling" here is a
             fresh process's replica), drains, prints
             {"recovered": [ids], "outputs": {...}, "statuses": {...}}.

The parent asserts every recovered request's FINAL sequence equals the
straight run's — journal-replay failover is token-exact even when the
failover target lives in another process.
"""

import json
import os
import sys

mode, base = sys.argv[1], sys.argv[2]

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

jax.config.update("jax_platforms", "cpu")

from tiny_deepspeed_tpu import GPT2Model, GPTConfig  # noqa: E402
from tiny_deepspeed_tpu.fleet import FleetRouter  # noqa: E402
from tiny_deepspeed_tpu.serving import ServeConfig, ServingEngine  # noqa: E402

CFG = GPTConfig(block_size=64, vocab_size=128, n_layer=2, n_head=2,
                n_embd=32, compute_dtype=jnp.float32)
SCFG = ServeConfig(max_active=2, num_blocks=16, block_tokens=8,
                   max_seq_tokens=40)
SPECS = [(1, 7, 12), (2, 13, 12), (3, 7, 12), (4, 13, 12)]
KILL_AT_TICK = 4


def _prompt(seed, n):
    return np.asarray(
        jax.random.randint(jax.random.PRNGKey(seed), (n,), 0, 128),
        np.int32,
    ).tolist()


model = GPT2Model(CFG)
params = model.init(jax.random.PRNGKey(0))

if mode == "straight":
    router = FleetRouter([
        ServingEngine(model, params, SCFG, replica_id=i)
        for i in range(2)
    ])
    reqs = [router.submit(_prompt(s, n), new) for s, n, new in SPECS]
    router.drain(max_ticks=500)
    print(json.dumps({"outputs": {r.id: r.tokens for r in reqs}}),
          flush=True)
elif mode == "serve":
    engines = [
        ServingEngine(model, params, SCFG, journal=f"{base}.r{i}",
                      replica_id=i)
        for i in range(2)
    ]
    router = FleetRouter(engines)
    for s, n, new in SPECS:
        router.submit(_prompt(s, n), new)
    for t in range(500):
        if t == KILL_AT_TICK:
            # a REAL kill between replica 0's journal append and its
            # fsync commit — the whole process (both replicas) dies
            engines[0].journal.arm_commit_hook(
                lambda: os.kill(os.getpid(), 9))
        router.tick()
    raise SystemExit("worker was supposed to be SIGKILLed")  # pragma: no cover
elif mode == "recover":
    eng = ServingEngine(model, params, SCFG, journal=f"{base}.new")
    rec = []
    for i in range(2):
        rec.extend(eng.recover(journal=f"{base}.r{i}"))
    eng.drain(max_ticks=500)
    print(json.dumps({
        "recovered": [r.id for r in rec],
        "outputs": {r.id: r.tokens for r in rec},
        "statuses": {r.id: r.status for r in rec},
    }), flush=True)
else:  # pragma: no cover
    raise SystemExit(f"unknown mode {mode!r}")
