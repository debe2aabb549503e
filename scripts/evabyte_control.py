# Copyright 2026 tiny-deepspeed-tpu authors
# SPDX-License-Identifier: Apache-2.0

"""The two readings beside `evabyte-6.5b.docs-open`'s `logit_tolerance`
that the cell's own run does not make, at the cell's sizes and weights.

control (the default): the harness's own comparison (`kinds/serve._check`,
judged by `harness.within`) with THE REFERENCE ITSELF IN BFLOAT16 in the
program's place -- every activation, the residual stream and the softmax
in the nearest precision below the one the configuration states (bf16
operands, float32 residual stream, softmax and logits).  It stands where
the engine stands: it is handed the check's prompts, picks its first byte
greedily from head 0, and answers with the next step's logits.  The
tolerance has to refuse it: `correct` false at every seed.

--decode N: the served path itself, N decode steps from 8 bytes short of a
window boundary (one prompt in the first window, one in the third), every
step's logits against the reference's full forward.  The cell's check
compares ONE step straight after a prefill; this one also attends summary
rows that decode wrote and ring rows written again after the roll.

    chiprun -- python scripts/evabyte_control.py 2147483659 2147483660
    chiprun -- python scripts/evabyte_control.py --decode 26 2147483659

On the TPU, or with --cpu at whatever size the sandbox can hold.
"""

import argparse
import json
import os
import sys
import types

import numpy as np


class ReferenceInTheProgramsPlace:
    """What `kinds/serve._check` asks of an engine, answered by
    `reference.logits_at` in `dtype`."""

    restarts = 0

    def __init__(self, reference, params, cfg, dtype):
        import jax
        self.cfg, self.params, self.reqs = cfg, params, []
        self.forward = jax.jit(lambda p, idx, pos: reference.logits_at(
            p, idx, pos, cfg, dtype=dtype))

    def submit(self, prompt, max_new_tokens):
        self.reqs.append(types.SimpleNamespace(
            prompt=list(prompt), tokens=[], status="ok",
            last_slot=len(self.reqs)))
        return self.reqs[-1]

    def tick(self):
        """A prefill (the first byte) and one decode step, as the engine's
        first tick gives a request that asks for two."""
        lens = np.asarray([len(r.prompt) for r in self.reqs], np.int32)
        idx = np.zeros((len(lens), lens.max() + 1), np.int32)
        for j, r in enumerate(self.reqs):
            idx[j, :lens[j]] = r.prompt
        rows, vocab = np.arange(len(lens)), self.cfg.vocab_size
        first = np.asarray(self.forward(self.params, idx, lens - 1))
        idx[rows, lens] = first[:, :vocab].argmax(axis=1)
        self.last_logits = np.asarray(self.forward(self.params, idx, lens))
        second = self.last_logits[:, :vocab].argmax(axis=1)
        for j, r in enumerate(self.reqs):
            r.tokens += [int(idx[j, lens[j]]), int(second[j])]


def control(cell, seed, root, dtype, say=print):
    """-> {name: [number, its limit]} of the cell's check with the
    reference in `dtype` served in the engine's place."""
    import jax
    from benchmarks import harness
    from tiny_deepspeed_tpu.models import build_model
    cfg = cell.model_config(param_dtype=cell.mix["param_dtype"])
    params = jax.jit(build_model(cfg).init)(jax.random.PRNGKey(seed))
    reference = cell.reference()
    env = types.SimpleNamespace(seed=seed, say=say)
    return harness.load_kind(root, "serve")._check(
        ReferenceInTheProgramsPlace(reference, params, cfg, dtype),
        reference, params, cfg, cell.mix, env)


def decode_gaps(cfg, reference, seed, steps, slots, block_tokens,
                windows=(1, 3)):
    """Serve one prompt a window count in `windows`, each ending 8 bytes
    short of that window's boundary, for `steps` decode steps, through an
    engine built as `kinds/serve.py` builds the cell's.  -> one row a
    prompt: its length, the largest gap of the steps before the roll and
    of those from it on, the rms gap; and the windows rolled."""
    import jax
    from tiny_deepspeed_tpu.models import build_model
    from tiny_deepspeed_tpu.serving import ServeConfig, ServingEngine
    model = build_model(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(seed))
    engine = ServingEngine(model, params, ServeConfig(
        max_active=slots, num_blocks=slots * cfg.block_size // block_tokens,
        block_tokens=block_tokens, temperature=0.0, eos_id=None,
        prefix_cache=False, spec_draft=None, paged_kernel="auto",
        seed=seed % 2**31))
    rng = np.random.default_rng([seed, 0xDEC0])
    window = cfg.window_size
    reqs = [engine.submit(rng.integers(
        0, cfg.vocab_size, k * window - 8).tolist(), steps + 1)
        for k in windows]
    got = [[] for _ in reqs]          # (bytes the step saw, its logits)
    while not all(r.done for r in reqs):
        before = [len(r.tokens) for r in reqs]
        engine.tick()
        logits = np.asarray(engine.last_logits)
        for j, (r, n) in enumerate(zip(reqs, before)):
            if len(r.tokens) > max(n, 1):     # a decode step ran for it
                got[j].append((len(r.tokens) - 1, logits[r.last_slot]))
    rolled = sum(t.get("windows_rolled", 0) for t in engine.tick_records)
    forward = jax.jit(lambda p, idx, pos: reference.logits_at(
        p, idx, pos, cfg))
    rows = []
    for r, steps_seen in zip(reqs, got):
        assert r.status == "ok" and len(steps_seen) == steps, r.status
        seqs = [r.prompt + r.tokens[:n] for n, _ in steps_seen]
        idx = np.zeros((len(seqs), len(seqs[-1])), np.int32)
        for i, seq in enumerate(seqs):
            idx[i, :len(seq)] = seq
        pos = np.asarray([len(seq) - 1 for seq in seqs], np.int32)
        gap = np.abs(np.stack([g for _, g in steps_seen])
                     - np.asarray(forward(params, idx, pos)))
        boundary = -(-len(r.prompt) // window) * window
        rows.append({
            "prompt": len(r.prompt), "steps": steps,
            "gap_max_before_roll": float(gap[pos < boundary].max()),
            "gap_max_from_roll": float(gap[pos >= boundary].max()),
            "steps_from_roll": int((pos >= boundary).sum()),
            "rms": float(np.sqrt(np.mean(gap ** 2)))})
    return rows, rolled


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("seeds", type=int, nargs="+")
    ap.add_argument("--workload", default="evabyte-6.5b.docs-open")
    ap.add_argument("--decode", type=int, default=0, metavar="N")
    ap.add_argument("--cpu", action="store_true")
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    import jax.numpy as jnp

    from benchmarks import harness
    from tiny_deepspeed_tpu.utils.startup import select_platform
    select_platform(cpu=args.cpu)
    cell = harness.load_cell(args.workload)
    tol = float(cell.mix["check"]["logit_tolerance"])
    refused = 0
    for seed in args.seeds:
        if args.decode:
            rows, rolled = decode_gaps(
                cell.model_config(param_dtype=cell.mix["param_dtype"]),
                cell.reference(), seed, args.decode,
                int(cell.sizes["slots"]), int(cell.mix["block_tokens"]))
            checks = {"decode_gap_max": [max(
                max(r["gap_max_before_roll"], r["gap_max_from_roll"])
                for r in rows), tol]}
            print("decode " + json.dumps({
                "seed": seed, "correct": harness.within(checks),
                "checks": checks, "windows_rolled": rolled,
                "prompts": rows}), flush=True)
            refused += not harness.within(checks)
            continue
        said = []
        checks = control(cell, seed, harness.HERE, jnp.bfloat16,
                         say=said.append)
        print("control " + json.dumps({
            "seed": seed, "correct": harness.within(checks),
            "checks": checks, "said": said}), flush=True)
        refused += not harness.within(checks)
    # the control is sound where every seed is refused; the decode steps
    # where none is
    return 0 if refused == (0 if args.decode else len(args.seeds)) else 1


if __name__ == "__main__":
    sys.exit(main())
