# Copyright 2026 tiny-deepspeed-tpu authors
# SPDX-License-Identifier: Apache-2.0

"""The readings beside `mimo-v2-flash.reason-open`'s `logit_tolerance` that
the cell's own run does not make, at the cell's sizes and weights, every one
through the harness's own comparison (`kinds/serve._check`, judged by
`harness.within`).  A line a seed and a mode, `<mode> {json}`:

served    the engine itself, as the cell's set-up builds and checks it, with
          the rms gap beside the largest, and the routing at the compared
          positions: the choices of the program (its own full forward in the
          cell's precision) against the float32 reference's, 6 expert layers
          x 8 choices a position; how many differ, and how many of those
          name an expert held here on either side.
control   THE REFERENCE ITSELF IN BFLOAT16 in the program's place (every
          activation, the residual stream and the softmax in the nearest
          precision below the one the configuration states; the router
          float32 on what it is handed).  The tolerance should refuse it.
faults    the reference in float32 with ONE planted fault in the program's
          place, each of `reference.FAULTS` (a dropped held expert, gates
          left unnormalised, the selection bias added to the gate, the sink
          left out, a window of 127 or 129, theta swapped between kinds).
          The tolerance must refuse each.

run       the cell itself through `harness.run_cell`, as `benchmarks/run.py`
          runs it, with what run.py has no flag for: --rate offers another
          rate than the mix's (the knee's sweep), and beside the result
          line it prints how the queue wait of the measured requests grew
          over the window (PERF.md section 4's reading of a knee).  A
          manifest that does not list the cell yet is handed on with the
          one `workloads` entry the cell's own file gives.  --trace reads
          the per-layer metrics.

replay    NO CHIP, no JAX: the mix's schedule at each seed replayed against
          a clock made of chip readings (a decode tick by its live slots, a
          prefill by its bucket: `TICK_MS`, `PREFILL_MS`), admission as the
          engine's (every queued request that finds a slot, prefills one
          after another, then one decode step).  Reads the `tpot_p95_ms`
          that the SCHEDULE alone gives a seed, so that the spread a set of
          runs shows can be split into what the local order of one trace
          does and what the system and the seed's weights do.

    chiprun -- python scripts/mimo_control.py served control 2147483659 ..
    chiprun -- python scripts/mimo_control.py faults 2147483659
    chiprun -- python scripts/mimo_control.py run --rate 1.75 2147483659
    python scripts/mimo_control.py replay --rate 1.4 2147483659 2147483660 ..

On the TPU, or with --cpu at whatever size the sandbox can hold.  Exit 0
where every served seed is correct and every fault refused.
"""

import argparse
import gc
import json
import os
import sys
import types

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from evabyte_control import ReferenceInTheProgramsPlace  # noqa: E402


def _said_rms(said):
    """The rms gap `_check` prints beside the largest."""
    for line in said:
        if " rms=" in line:
            return float(line.split(" rms=")[1].split()[0])
    return None


def reference_in_place(cell, seed, root, dtype, fault=""):
    """The cell's check with the reference, in `dtype` and with `fault`,
    served in the engine's place -> (checks, rms)."""
    import jax
    from benchmarks import harness
    from tiny_deepspeed_tpu.models import build_model
    cfg = cell.model_config(param_dtype=cell.mix["param_dtype"])
    params = jax.jit(build_model(cfg).init)(jax.random.PRNGKey(seed))
    reference = cell.reference()
    wrong = types.SimpleNamespace(logits_at=lambda p, ix, pos, c, dtype: (
        reference.logits_at(p, ix, pos, c, dtype=dtype, fault=fault)))
    said = []
    checks = harness.load_kind(root, "serve")._check(
        ReferenceInTheProgramsPlace(wrong, params, cfg, dtype), reference,
        params, cfg, cell.mix, types.SimpleNamespace(seed=seed,
                                                     say=said.append))
    return checks, _said_rms(said)


class Routes:
    """The top-k choices of every expert layer at one position of a
    sequence, as the program routes (its own full forward in the cell's
    precision) and as the float32 reference does: two jitted functions,
    (params, seq (1, T), at) -> nothing, that report through a callback.
    Both are made to run their expert layer in one pass a sequence, so
    that row `at` is position `at`."""

    def __init__(self, cell):
        import jax
        import jax.numpy as jnp
        from tiny_deepspeed_tpu.models import build_model, mimo
        cfg = cell.model_config(param_dtype=cell.mix["param_dtype"])
        self.cfg, self.found = cfg, {}
        model, reference = build_model(cfg), cell.reference()
        self._patched = ((mimo, "_MOE_TOKENS", 1 << 30),
                         (reference, "_ROWS", 1 << 30))

        def note(layer, choice):
            self.found[int(layer)] = {int(e) for e in np.asarray(choice)}

        def program(params, seq, at):
            real = mimo.moe_layer

            def spy(h, rw, rb, wg, wu, wd, layer, *, top_k, **kw):
                choice, _ = mimo.moe_route(h, rw, rb, top_k)
                jax.debug.callback(note, layer, choice[at])
                return real(h, rw, rb, wg, wu, wd, layer, top_k=top_k, **kw)

            mimo.moe_layer = spy
            try:
                return model.apply(params, seq)
            finally:
                mimo.moe_layer = real

        def plain(params, seq, at):
            real = reference._experts

            def spy(rows, lp, held, c, dtype, fault):
                r = jax.nn.sigmoid(rows.astype(jnp.float32)
                                   @ lp["router.w"].astype(jnp.float32))
                _, choice = jax.lax.top_k(
                    r + lp["router.bias"].astype(jnp.float32),
                    c.n_experts_per_tok)
                jax.debug.callback(note, held[1] // c.experts_held,
                                   choice[at])
                return real(rows, lp, held, c, dtype, fault)

            reference._experts = spy
            try:
                return reference.logits_at(params, seq, at[None], cfg)
            finally:
                reference._experts = real

        self._sides = jax.jit(program), jax.jit(plain)

    def differences(self, reqs, params):
        """Over the check's requests (prompt + first token, compared at
        the last position): (choices compared, that differ, expert layers
        where a differing choice names a held expert on either side)."""
        import jax
        cfg = self.cfg
        lo, hi = cfg.experts_first, cfg.experts_first + cfg.experts_held
        compared = differ = differ_held = 0
        saved = [(mod, name, getattr(mod, name))
                 for mod, name, _ in self._patched]
        for mod, name, value in self._patched:
            setattr(mod, name, value)
        try:
            for r in reqs:
                seq = np.asarray([r.prompt + r.tokens[:1]], np.int32)
                at = np.int32(seq.shape[1] - 1)
                seq = np.pad(seq, ((0, 0), (0, -seq.shape[1] % 128)))
                sets = []
                for side in self._sides:
                    self.found = {}
                    jax.block_until_ready(side(params, seq, at))
                    jax.effects_barrier()
                    sets.append(self.found)
                for layer in sets[1]:
                    odd = sets[0][layer] ^ sets[1][layer]
                    compared += cfg.n_experts_per_tok
                    differ += len(odd) // 2
                    differ_held += any(lo <= e < hi for e in odd)
        finally:
            for mod, name, value in saved:
                setattr(mod, name, value)
        return compared, differ, differ_held


def served(cell, seed, root):
    """The engine as `kinds/serve.run` builds it, through `_check`."""
    import jax
    from benchmarks import harness
    from tiny_deepspeed_tpu.models import build_model
    from tiny_deepspeed_tpu.serving import ServeConfig, ServingEngine
    cfg = cell.model_config(param_dtype=cell.mix["param_dtype"])
    model = build_model(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(seed))
    slots, bt = int(cell.sizes["slots"]), int(cell.mix["block_tokens"])
    engine = ServingEngine(model, params, ServeConfig(
        max_active=slots, num_blocks=slots * cfg.block_size // bt,
        block_tokens=bt, temperature=0.0, eos_id=None, prefix_cache=False,
        spec_draft=None, paged_kernel="auto", seed=seed % 2**31))
    reqs, said = [], []
    submit = engine.submit
    engine.submit = lambda *a, **kw: (reqs.append(submit(*a, **kw)),
                                      reqs[-1])[1]
    checks = harness.load_kind(root, "serve")._check(
        engine, cell.reference(), params, cfg, cell.mix,
        types.SimpleNamespace(seed=seed, say=said.append))
    del engine
    return checks, _said_rms(said), reqs, params


def _lag1(x):
    """Correlation of a series with itself one step on; None where it
    does not vary."""
    if len(x) < 3 or np.std(x[:-1]) == 0 or np.std(x[1:]) == 0:
        return None
    return float(np.corrcoef(x[:-1], x[1:])[0, 1])


def _slowest(measured, ticks, plain_s, n=5):
    """The n measured requests with the largest time per token: tokens,
    ms per token, and of the ticks between its first and its last token:
    how many, how many requests they admitted and the ms that took over a
    plain tick each, the longest of them in ms, and the mean ms of those
    that admitted nobody."""
    from benchmarks.serve_arith import tpot_ms
    out = []
    for r in sorted(measured, key=lambda r: -(tpot_ms(r) or 0.0))[:n]:
        life = ticks[(ticks[:, 0] + ticks[:, 1] > r.first)
                     & (ticks[:, 0] < r.done)]
        plain = life[life[:, 3] == 0]
        if not len(plain):  # an answer of a tick or two
            continue
        out.append(dict(
            tokens=r.tokens, tpot_ms=round(tpot_ms(r), 2),
            due_s=round(r.due, 2), ticks=len(life),
            admitted=int(life[:, 3].sum()),
            admission_ms=round(1e3 * float(
                (life[life[:, 3] > 0][:, 1] - plain_s).sum()), 1),
            longest_tick_ms=round(1e3 * float(life[:, 1].max()), 1),
            plain_tick_ms_mean=round(1e3 * float(plain[:, 1].mean()), 2)))
    return out


def run_at_rate(name, root, manifest_path, seed, seconds, trace, rate=None,
                t_process=None):
    """One run of the cell `name` -> (the result as run.py prints it,
    how its measured requests' queue wait grew).  Growth is the
    least-squares slope of the wait against the due instant x the window,
    PERF.md section 4's reading of a knee."""
    import tempfile
    from benchmarks import harness
    from benchmarks.serve_arith import queue_ms
    with open(manifest_path) as f:
        manifest = json.load(f)
    with open(os.path.join(root, "cells", name + ".json")) as f:
        spec = json.load(f)
    if not any(w["name"] == name for w in manifest["workloads"]):
        manifest["workloads"].append({"name": name, **{
            k: spec[k] for k in ("config", "traffic", "chips", "why")}})
    kept = {}
    load_cell, load_kind = harness.load_cell, harness.load_kind

    def cell_at_rate(name, root):
        cell = kept["cell"] = load_cell(name, root)
        if rate is not None:
            cell.mix["arrival"]["rate_rps"] = rate
        return cell

    def kind_kept(root, kind):
        mod = load_kind(root, kind)
        run = mod.run
        mod.run = lambda cell, env: kept.setdefault("out", run(cell, env))
        return mod

    import tiny_deepspeed_tpu.serving as serving
    engine_class = serving.ServingEngine

    class Watched(engine_class):
        """The engine the kind builds, kept, so that its ticks' own
        records (`tick_records` holds the last 512 only) can be read
        after the run."""
        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            kept["ticks"] = []

        def tick(self, **kw):
            produced = super().tick(**kw)
            r = self.tick_records[-1]
            fetch = sum(end - start for name, start, end in r["segments"]
                        if name == "decode.fetch")
            kept["ticks"].append((
                r["t0"], r["t1"] - r["t0"], r["produced"], r["admitted"],
                r.get("pairs", 0), r.get("experts_touched", 0), fetch,
                max(r["buckets"], default=0)))
            return produced

    harness.load_cell, harness.load_kind = cell_at_rate, kind_kept
    serving.ServingEngine = Watched
    try:
        with tempfile.NamedTemporaryFile("w", suffix=".json") as f:
            json.dump(manifest, f)
            f.flush()
            result = harness.run_cell(name, seed, seconds, trace, root=root,
                                      manifest=f.name, t_process=t_process)
    finally:
        harness.load_cell, harness.load_kind = load_cell, load_kind
        serving.ServingEngine = engine_class
    host = kept["out"].host
    t0 = kept["out"].t_window
    ticks = np.asarray([t for t in kept["ticks"]
                        if t0 <= t[0] < t0 + seconds]).reshape(-1, 8)
    # ticks that admitted nobody: what they produced is a token a slot
    plain = ticks[ticks[:, 3] == 0]
    layers = sum(kept["cell"].model_config().moe_layers)
    due = np.asarray([r.due for r in host["measured"]])
    wait = np.asarray([queue_ms(r) for r in host["measured"]])
    third = max(1, len(due) // 3)
    grew = dict(
        rate=rate, seed=seed, measured=len(due),
        queue_growth_ms=float(np.polyfit(due - due.min(), wait, 1)[0]
                              * seconds) if len(due) > 2 else 0.0,
        queue_first_third_ms=float(wait[:third].mean()),
        queue_last_third_ms=float(wait[-third:].mean()),
        occupancy_mean=100 * float(np.mean(host["occupancy"])),
        tick_ms_p50=1e3 * float(np.median(host["tick_s"])),
        # what a seed's weights and tokens decide, beside what they cost:
        # (token, choice) pairs on the held experts a live slot and expert
        # layer (even routing: 8 x 16 / 256 = 0.5 at the cell's sizes),
        # held experts touched a tick, the ticks with no admission, and
        # the seconds of the window that admissions took over such a tick
        pairs_per_slot_layer=float(
            plain[:, 4].sum() / max(1.0, layers * plain[:, 2].sum())),
        experts_touched_per_tick=float(plain[:, 5].mean()),
        plain_tick_ms_p50=1e3 * float(np.median(plain[:, 1])),
        admission_s=float((ticks[ticks[:, 3] > 0][:, 1]
                           - np.median(plain[:, 1])).sum()),
        admissions=int(ticks[:, 3].sum()),
        # a plain tick split at the wait for the decode step's result (the
        # device, mostly) and the rest (the host); a tick that admitted
        # ONE request, less a plain tick, by its prefill bucket; and how
        # far a tick's pairs a slot follow those of the tick before (1: a
        # request's routing stands still; 0: every token routes anew)
        plain_fetch_ms_mean=1e3 * float(plain[:, 6].mean()),
        plain_host_ms_mean=1e3 * float((plain[:, 1] - plain[:, 6]).mean()),
        prefill_ms_by_bucket={
            int(b): round(1e3 * float(np.median(
                ticks[(ticks[:, 3] == 1) & (ticks[:, 7] == b)][:, 1]
                - np.median(plain[:, 1]))), 2)
            for b in np.unique(ticks[ticks[:, 3] == 1][:, 7])},
        pairs_lag1=_lag1(plain[:, 4] / np.maximum(1, plain[:, 2])),
        # where a tail comes from: the window's longest ticks (ms, requests
        # admitted, largest bucket), and the measured requests with the
        # largest time per token, each with what its life held
        longest_ticks=[[round(1e3 * t[1], 1), int(t[3]), int(t[7])]
                       for t in sorted(ticks, key=lambda t: -t[1])[:8]],
        slowest_requests=_slowest(host["measured"],
                                  np.asarray(kept["ticks"]).reshape(-1, 8),
                                  float(np.median(plain[:, 1]))))
    return result, grew


# `replay`'s clock (my chip runs, PR 34, the cell's sizes): a decode tick by
# the slots it decodes, a line through `tick_ms_p50` 16.5 at 24.3 live slots
# and 20.55 at 41, flatter to 23.6 with all 64 (every held expert is read by
# then); a prefill by its bucket, what a tick that admits one request takes
# over one that admits nobody (`run`'s `prefill_ms_by_bucket`, the same to
# 1 % in eight runs; 512 from the prefill program's 22 ms and the others'
# host share)
TICK_MS = ((0, 10.6), (41, 20.54), (64, 23.6))
PREFILL_MS = {512: 23.5, 1024: 43.5, 2048: 78.3, 4096: 171.5, 8192: 372.7}


def replay(mix, seed, seconds, vocab, slots, rate=None):
    """-> (`tpot_p95_ms` of the measured set, its size) had every tick and
    prefill taken what `TICK_MS` and `PREFILL_MS` say."""
    from benchmarks import generator
    from benchmarks.serve_arith import percentile
    if rate is not None:
        mix = dict(mix, arrival=dict(mix["arrival"], rate_rps=rate))
    arrivals = generator.schedule(mix, seed, seconds, vocab)
    live_x, tick_y = zip(*TICK_MS)
    buckets = sorted(PREFILL_MS)
    t, nxt, queue, live, tpot = -float(mix["ramp_s"]), 0, [], [], []
    left = sum(a.phase == "window" for a in arrivals)
    while left:
        while nxt < len(arrivals) and arrivals[nxt].due_s <= t:
            queue.append(arrivals[nxt])
            nxt += 1
        if not queue and not live:
            t = arrivals[nxt].due_s
            continue
        while queue and len(live) < slots:
            a = queue.pop(0)
            t += PREFILL_MS[next(b for b in buckets
                                 if b >= len(a.prompt))] / 1e3
            live.append([a, 1, t])  # its first token, at the prefill's end
        t += float(np.interp(len(live), live_x, tick_y)) / 1e3
        for slot in live:
            slot[1] += 1
        for a, n, first in [s for s in live if s[1] >= s[0].max_new_tokens]:
            if a.phase == "window":
                tpot.append((t - first) / (n - 1) * 1e3)
                left -= 1
        live = [s for s in live if s[1] < s[0].max_new_tokens]
    return percentile(tpot, 95), len(tpot)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("what", nargs="+",
                    help="modes (served, control, faults, run, replay) and seeds")
    ap.add_argument("--workload", default="mimo-v2-flash.reason-open")
    ap.add_argument("--rate", type=float, default=None,
                    help="with run or replay: requests/s offered, not the mix's")
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--trace", action="store_true")
    ap.add_argument("--routing", action="store_true",
                    help="with served: count the routing differences")
    ap.add_argument("--cpu", action="store_true")
    args = ap.parse_intermixed_args(argv)
    modes = [w for w in args.what if not w.isdigit()]
    seeds = [int(w) for w in args.what if w.isdigit()]
    from benchmarks import harness
    t_process = harness.process_start_monotonic()
    if "replay" in modes:
        cell = harness.load_cell(args.workload)
        for seed in seeds:
            tpot, n = replay(cell.mix, seed, args.seconds,
                             cell.config["vocab_size"],
                             int(cell.sizes["slots"]), args.rate)
            print("replay " + json.dumps(dict(
                seed=seed, rate=args.rate, measured=n, tpot_p95_ms=tpot)),
                flush=True)
        return 0
    import jax
    import jax.numpy as jnp

    from tiny_deepspeed_tpu.utils.startup import select_platform
    select_platform(cpu=args.cpu)
    if "run" in modes:  # one process a run, as run.py: its set-up is timed
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        result, grew = run_at_rate(
            args.workload, harness.HERE,
            os.path.join(harness.REPO, "BENCHMARK.json"), seeds[0],
            args.seconds, args.trace, args.rate, t_process)
        print("grew " + json.dumps(grew), flush=True)
        print(json.dumps(result), flush=True)
        return 0 if result["correct"] else 1
    cell = harness.load_cell(args.workload)
    routes = Routes(cell) if args.routing else None
    bad = 0

    def line(mode, seed, checks, rms, **more):
        ok = harness.within(checks)
        print(mode + " " + json.dumps(dict(
            seed=seed, correct=ok, logit_gap_max=checks["logit_gap_max"][0],
            logit_gap_rms=rms, checks=checks, **more)), flush=True)
        return ok

    for seed in seeds:
        gc.collect()  # the last seed's engine and pool, before the next's
        if "served" in modes:
            checks, rms, reqs, params = served(cell, seed, harness.HERE)
            more = {}
            if routes is not None:
                compared, differ, held = routes.differences(reqs, params)
                more = dict(choices_compared=compared,
                            choices_differ=differ,
                            layers_where_a_held_expert_differs=held)
            bad += not line("served", seed, checks, rms, **more)
            del reqs, params
            gc.collect()
        if "control" in modes:
            checks, rms = reference_in_place(cell, seed, harness.HERE,
                                             jnp.bfloat16)
            line("control", seed, checks, rms)
        if "faults" in modes:
            for fault in cell.reference().FAULTS:
                checks, rms = reference_in_place(
                    cell, seed, harness.HERE, jnp.float32, fault)
                bad += line("fault", seed, checks, rms, fault=fault)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
