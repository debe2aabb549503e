# Copyright 2026 tiny-deepspeed-tpu authors
# SPDX-License-Identifier: Apache-2.0

"""Worker for tests/test_serving.py's kill-mid-trace recovery test —
NOT a pytest module.

Run as:  python serving_worker.py <mode> <journal_path>

Modes:
  serve    — submit the fixed 4-request trace through an engine with a
             request journal; at the Nth scheduler tick, SIGKILL
             ourselves from the journal's commit hook — i.e. a REAL
             process death between journal-append and fsync, the worst
             write moment (no cleanup, no excepthook).
  recover  — build a FRESH engine on the same journal,
             `ServingEngine.recover()`, drain, print one JSON line
             {"recovered": [ids], "outputs": {id: [tokens]}}.
  straight — the same 4 submissions through a journal-less engine,
             uninterrupted; print {"outputs": {id: [tokens]}}.

The parent asserts: the kill left in-flight requests in the journal;
recovery re-queues them front-of-line with their committed prefix; and
every recovered request's FINAL token sequence equals the straight
run's (greedy — the (seed, position) sampling keys make it exact).
"""

import json
import os
import sys

mode, journal_path = sys.argv[1], sys.argv[2]

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

jax.config.update("jax_platforms", "cpu")

from tiny_deepspeed_tpu import GPT2Model, GPTConfig  # noqa: E402
from tiny_deepspeed_tpu.serving import ServeConfig, ServingEngine  # noqa: E402

CFG = GPTConfig(block_size=64, vocab_size=128, n_layer=2, n_head=2,
                n_embd=32, compute_dtype=jnp.float32)
SCFG = ServeConfig(max_active=2, num_blocks=24, block_tokens=8)
# (prompt seed, prompt len, max_new): 2 admit immediately, 2 queue —
# the kill at tick 5 lands with requests in EVERY lifecycle state
SPECS = [(1, 7, 12), (2, 13, 12), (3, 7, 12), (4, 13, 12)]
KILL_AT_TICK = 5


def _prompt(seed, n):
    return np.asarray(
        jax.random.randint(jax.random.PRNGKey(seed), (n,), 0, 128),
        np.int32,
    ).tolist()


model = GPT2Model(CFG)
params = model.init(jax.random.PRNGKey(0))

if mode == "straight":
    eng = ServingEngine(model, params, SCFG)
    reqs = [eng.submit(_prompt(s, n), new) for s, n, new in SPECS]
    eng.drain(max_ticks=500)
    print(json.dumps({"outputs": {r.id: r.tokens for r in reqs}}),
          flush=True)
elif mode == "serve":
    eng = ServingEngine(model, params, SCFG, journal=journal_path)
    for s, n, new in SPECS:
        eng.submit(_prompt(s, n), new)
    for t in range(500):
        if t == KILL_AT_TICK:
            # a REAL kill between the tick's journal append and its
            # fsync commit: the journal hook fires inside commit()
            eng.journal.arm_commit_hook(
                lambda: os.kill(os.getpid(), 9))
        eng.tick()
    raise SystemExit("worker was supposed to be SIGKILLed")  # pragma: no cover
elif mode == "recover":
    eng = ServingEngine(model, params, SCFG, journal=journal_path)
    rec = eng.recover()
    eng.drain(max_ticks=500)
    print(json.dumps({
        "recovered": [r.id for r in rec],
        "outputs": {r.id: r.tokens for r in rec},
        "statuses": {r.id: r.status for r in rec},
    }), flush=True)
else:  # pragma: no cover
    raise SystemExit(f"unknown mode {mode!r}")
