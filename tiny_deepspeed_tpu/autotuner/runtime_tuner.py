# Copyright 2026 tiny-deepspeed-tpu authors
# SPDX-License-Identifier: Apache-2.0

"""RuntimeAutoTuner: measure candidate kernels, cache the winner per shape.

Capability parity with reference core/autotuner/runtime_tuner.py:7-39
(choose_function times each candidate with warmup+measured wall-clock calls
and caches the winner; final_tune freezes the choice), re-thought for XLA's
compilation model:

  * The reference times eagerly inside forward() because torch dispatches op
    by op.  Under jit everything is traced once — so candidates are timed at
    TRACE TIME: when `choose` is called with tracers, the tuner synthesizes
    concrete arrays of the same shape/dtype, jits each candidate, times it on
    the real device, and bakes the winner into the traced program.  Each
    (candidates, shapes, dtypes) key is timed once per process and cached.
  * Timing uses a device->host transfer as the sync barrier.
  * `final_tune()` freezes the cache (parity: reference :31-32): after
    freezing, unseen keys fall back to candidate[0] instead of timing.
"""

from __future__ import annotations

import hashlib
import json
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np


class RuntimeAutoTuner:
    def __init__(self, warmup: int = 2, iters: int = 5,
                 verbose: bool = False, telemetry=None, logger=None):
        self.warmup = warmup
        self.iters = iters
        self.verbose = verbose
        # diagnostics sinks (attach_diagnostics): decisions become
        # `run_meta` records on the MetricsLogger and candidate failures
        # a Telemetry counter + gauge — the bare stderr prints this
        # class used to emit were invisible to every dashboard
        self.telemetry = telemetry
        self.logger = logger
        self.cache: Dict[Tuple, Callable] = {}
        # key -> (candidates, arg signature, static kwargs): requests made
        # from inside a trace, to be timed by resolve_pending()
        self.pending: Dict[Tuple, Tuple] = {}
        self.frozen = False
        # bumped whenever TIMING produces a new winner (not on AOT-stored
        # hits, which the requesting trace already used); consumers compare
        # against the version they compiled with to decide whether a
        # re-trace would change anything (engine.retune)
        self.version = 0

    # -- key / input synthesis --------------------------------------------

    @staticmethod
    def _sig(args) -> Tuple:
        return tuple(
            None if a is None else (tuple(a.shape), str(a.dtype))
            for a in args
        )

    @classmethod
    def _key(cls, candidates: Sequence[Callable], args) -> Tuple:
        return (
            tuple(c.__module__ + "." + c.__name__ for c in candidates),
            cls._sig(args),
        )

    @staticmethod
    def _synthesize(args):
        """Concrete stand-ins for args (arrays, or (shape, dtype) sig
        entries from a pending record), same shape/dtype."""
        out = []
        key = jax.random.PRNGKey(0)
        for a in args:
            if a is None:
                out.append(None)
                continue
            shape, dtype = (
                a if isinstance(a, tuple) else (a.shape, a.dtype)
            )
            dtype = jnp.dtype(dtype)
            if jnp.issubdtype(dtype, jnp.integer):
                out.append(jnp.zeros(shape, dtype))
            else:
                key, sub = jax.random.split(key)
                out.append(jax.random.normal(sub, shape, jnp.float32)
                           .astype(dtype))
        return tuple(out)

    def attach_diagnostics(self, telemetry=None, logger=None) -> None:
        """Route tuner diagnostics into the run's observability surface:
        `telemetry` (a Telemetry registry) receives the
        autotune_candidate_failures counter/gauge, `logger` (a
        MetricsLogger) one `run_meta` record per timing decision."""
        if telemetry is not None:
            self.telemetry = telemetry
        if logger is not None:
            self.logger = logger

    def _diag_failure(self, fn: Callable, exc: BaseException) -> None:
        """One candidate refused these shapes: count it where dashboards
        look (an occasional failure is normal — FA2 past its T bound —
        a climbing counter means a rotten candidate list)."""
        if self.telemetry is not None:
            n = self.telemetry.counter("autotune_candidate_failures").inc()
            self.telemetry.gauge("autotune_candidate_failures", float(n))
        if self.logger is not None:
            self.logger.log_meta(
                kind="run_meta",
                autotune={"event": "candidate_failed",
                          "candidate": fn.__name__,
                          "error": type(exc).__name__},
            )
        elif self.verbose:
            print(f"autotuner: {fn.__name__} failed: {type(exc).__name__}")

    def _diag_decision(self, candidates, times, best: int) -> None:
        """One timing decision: the ranking becomes a `run_meta` record
        (and the stderr line only without a logger)."""
        if self.logger is not None:
            self.logger.log_meta(
                kind="run_meta",
                autotune={
                    "event": "decision",
                    "winner": candidates[best].__name__,
                    "ranking": [
                        {"candidate": c.__name__,
                         "us": None if t == float("inf")
                         else round(t * 1e6, 1)}
                        for c, t in zip(candidates, times)
                    ],
                },
            )
        elif self.verbose:
            ranking = ", ".join(
                f"{c.__name__}={t * 1e6:.0f}us"
                for c, t in zip(candidates, times)
            )
            print(f"autotuner: {ranking} -> {candidates[best].__name__}")

    def _time_one(self, fn: Callable, concrete, static_kwargs) -> float:
        jitted = jax.jit(lambda *xs: fn(*xs, **static_kwargs))
        try:
            for _ in range(self.warmup):
                r = jitted(*concrete)
            jax.tree.map(
                lambda x: np.asarray(jax.tree.leaves(x)[0].ravel()[0:1]), r
            )
            t0 = time.perf_counter()
            for _ in range(self.iters):
                r = jitted(*concrete)
            # device->host sync on one element of one output
            np.asarray(jax.tree.leaves(r)[0].ravel()[0:1])
            return (time.perf_counter() - t0) / self.iters
        except Exception as e:  # candidate doesn't support these shapes
            self._diag_failure(fn, e)
            return float("inf")

    # -- public API --------------------------------------------------------

    def choose(self, candidates: Sequence[Callable], args,
               **static_kwargs) -> Callable:
        """Pick the fastest candidate for these arg shapes (cached)."""
        candidates = list(candidates)
        if len(candidates) == 1:
            return candidates[0]
        key = self._key(candidates, args)
        if key in self.cache:
            return self.cache[key]
        stored = getattr(self, "_stored", None)
        if stored and key in stored:  # ahead-of-time cache hit (see load())
            name = stored[key]
            for c in candidates:
                if c.__module__ + "." + c.__name__ == name:
                    self.cache[key] = c
                    return c
        if self.frozen:
            return candidates[0]
        # `choose` usually runs INSIDE an outer jit trace (op dispatch
        # sites).  Timing cannot happen there: plain calls stage the
        # synthesis into the outer trace (TracerArrayConversionError),
        # ensure_compile_time_eval evaluates candidates op-by-op eagerly
        # (mis-timed by dispatch overhead; Pallas primitives like
        # program_id have no eval rule), and compiling from a helper
        # thread deadlocks against the in-progress outer trace on some
        # backends.  So in-trace requests are RECORDED and candidate[0]
        # returned; `resolve_pending()` times them after the trace
        # completes, and the caller re-traces (e.g. engine.retune()) to
        # bake the winners — same measure-then-freeze lifecycle as the
        # reference's choose_function/final_tune split.
        if any(isinstance(a, jax.core.Tracer)
               for a in args if a is not None):
            self.pending.setdefault(
                key, (list(candidates), self._sig(args), dict(static_kwargs))
            )
            return candidates[0]
        return self._pick(candidates, args, static_kwargs, key)

    def _pick(self, candidates, args_or_sig, static_kwargs, key) -> Callable:
        concrete = self._synthesize(args_or_sig)
        times = [self._time_one(c, concrete, static_kwargs)
                 for c in candidates]
        best = int(np.argmin(times))
        if times[best] == float("inf"):
            best = 0
        self._diag_decision(candidates, times, best)
        self.cache[key] = candidates[best]
        self.version += 1
        return candidates[best]

    def resolve_pending(self) -> int:
        """Time every request recorded during tracing (must be called OUTSIDE
        any trace) and bake the winners into the cache.  Returns the number
        of requests resolved; the caller then re-traces (engine.retune() /
        a fresh jit) so the winners actually enter the compiled program."""
        n = 0
        for key, (candidates, sig, kw) in list(self.pending.items()):
            del self.pending[key]
            if key in self.cache:
                continue
            self._pick(candidates, sig, kw, key)
            n += 1
        return n

    # reference API name (runtime_tuner.py:16)
    choose_function = choose

    def final_tune(self) -> None:
        """Freeze: no further timing; cached winners stay (reference :31-32)."""
        self.frozen = True

    # -- persistence: ahead-of-time autotune cache --------------------------
    #
    # The reference re-times candidates every process (its cache is a dict
    # on the tuner instance, runtime_tuner.py:7-39).  Timing on TPU costs
    # real compiles, so winners can be saved once and reloaded: the cache
    # serializes as {key-json: winner qualified name} and `choose` resolves
    # a stored name against the live candidate list.

    def save(self, path: str) -> int:
        """Write the winner table (and any end-to-end tuned plans) as
        JSON; returns winner entries written.  Loaded entries not re-hit
        this run are preserved (a shared cache file across model configs
        must not lose the other configs' winners on overwrite).

        Format: the v2 envelope {"version": 2, "winners": {...},
        "plans": {...}} — `plans` holds `tune_e2e` results keyed by
        plan_key (model, mesh, backend).  `load` still reads the
        pre-plan flat {key: winner} files."""
        table = {
            json.dumps(key): name
            for key, name in getattr(self, "_stored", {}).items()
        }
        table.update({
            json.dumps(key): fn.__module__ + "." + fn.__name__
            for key, fn in self.cache.items()
        })
        with open(path, "w", encoding="utf-8") as f:
            json.dump({"version": 2, "winners": table,
                       "plans": dict(getattr(self, "_plans", {}))},
                      f, indent=1)
        return len(table)

    def load(self, path: str) -> int:
        """Read a winner table (either format); entries resolve lazily
        at choose() time (a stored name only applies when it matches one
        of the live candidates for that key).  Returns entries read."""
        def tuplify(x):
            return tuple(tuplify(i) for i in x) if isinstance(x, list) else x

        with open(path, encoding="utf-8") as f:
            data = json.load(f)
        if isinstance(data, dict) and data.get("version") == 2:
            table = data.get("winners", {})
            self._plans = dict(data.get("plans", {}))
        else:  # legacy flat winner table
            table = data
        self._stored = {
            tuplify(json.loads(key_s)): name for key_s, name in table.items()
        }
        return len(self._stored)

    # -- end-to-end tuned plans ---------------------------------------------
    #
    # Per-op winners above answer "which kernel for this shape"; a PLAN
    # answers "which knob values for this whole workload": the tune_e2e
    # search's winning assignment of scan_unroll / fp8 mode / kernel
    # block sizes / bucket K / prefetch depth / spec_k, measured against
    # end-to-end objectives (training step time, serving committed
    # tok/s) rather than standalone op timings.  Plans persist in the
    # same AOT cache file, keyed per (model, mesh, backend).

    def store_plan(self, key: str, plan: Dict, record: Optional[Dict]
                   = None, merge: bool = False) -> str:
        """Remember `plan` for `key` (use plan_key()); `record` carries
        the measured A/B evidence.  Returns the plan hash.

        merge=True folds `plan` (and `record`) into an existing entry
        for the key instead of replacing it — how the bench's phased
        tune_e2e (train knobs, then serve knobs, then the comm space)
        accretes ONE plan per workload across phases; the hash is
        recomputed over the merged assignment."""
        plans = getattr(self, "_plans", None)
        if plans is None:
            plans = self._plans = {}
        if merge and key in plans:
            plan = {**plans[key].get("plan", {}), **plan}
            record = {**plans[key].get("record", {}), **(record or {})}
        plans[key] = {"plan": dict(plan), "hash": plan_hash(plan),
                      "record": dict(record or {})}
        return plans[key]["hash"]

    def get_plan(self, key: str) -> Optional[Dict]:
        """The stored plan entry for `key` ({"plan", "hash", "record"}),
        or None."""
        return getattr(self, "_plans", {}).get(key)


# ---------------------------------------------------------------------------
# tune_e2e: one search over the whole knob space, end-to-end objectives
# ---------------------------------------------------------------------------
#
# The per-op tuner above times candidates as STANDALONE jits — a proxy
# that has already been caught lying twice (adamw_pallas: a standalone
# winner losing in-graph; softmax_xent: the ladder capped at 256 because
# standalone timing is blind to live-memory pressure).  tune_e2e closes
# the loop: the caller supplies a `measure(plan) -> float` that runs the
# REAL objective (a training step, a serving trace) with the plan's knob
# assignment applied, and the search walks the joint space.
#
# The search is greedy coordinate descent from the default assignment
# (each knob's first value), `rounds` full sweeps: with K knobs of V
# values it costs O(rounds * K * V) measurements instead of V^K, and for
# the knob spaces here (scan_unroll x fp8 x blocks x bucket K x prefetch
# x spec_k) interactions beyond one sweep are second-order — a second
# round is available where they are not.  Every trial is recorded so
# the bench JSON can show its work.


def plan_key(model: str, mesh: str, backend: str) -> str:
    """Canonical plan-store key: a plan tuned on one (model, mesh,
    backend) must never silently apply to another."""
    return f"{model}|{mesh}|{backend}"


def plan_hash(plan: Dict) -> str:
    """Short stable hash of a knob assignment — stamped into bench
    fingerprints so cached records from different plans never mix."""
    s = json.dumps(plan, sort_keys=True, default=str)
    return hashlib.sha256(s.encode()).hexdigest()[:12]


def tune_e2e(measure: Callable[[Dict], float], space: Dict[str, Sequence],
             *, objective: str = "min", rounds: int = 1,
             start: Optional[Dict] = None, on_trial=None):
    """Greedy coordinate-descent search of `space` ({knob: [values...]},
    first value = the default) against `measure(plan) -> float`.
    `objective` "min" (step seconds) or "max" (tokens/s).  Returns
    (best_plan, best_score, trials) where trials is every measured
    {"plan", "score"} in order (the baseline/default plan is trials[0]).
    `on_trial(plan, score)` observes each measurement (progress logs).
    A measure() that raises marks that assignment infeasible (scored
    worst) rather than aborting the search — a candidate plan that
    fails to compile must not cost the tuning run."""
    if objective not in ("min", "max"):
        raise ValueError(f"objective must be 'min' or 'max': {objective!r}")
    sign = 1.0 if objective == "min" else -1.0
    worst = float("inf")

    def same(a, b):
        # knob values compare by type too: scan_unroll's 1 (scanned)
        # and True (fully unrolled) are DIFFERENT assignments, but
        # Python's True == 1
        return type(a) is type(b) and a == b

    def run(plan):
        try:
            s = float(measure(dict(plan)))
        except Exception:
            return worst
        if on_trial is not None:
            on_trial(dict(plan), s)
        return sign * s

    best = {k: vs[0] for k, vs in space.items()}
    if start:
        best.update({k: v for k, v in start.items() if k in space})
    trials: List[Dict] = []

    def record(plan, signed):
        trials.append({"plan": dict(plan),
                       "score": None if signed == worst else sign * signed})

    best_score = run(best)
    record(best, best_score)
    for _ in range(max(1, rounds)):
        improved = False
        for knob, values in space.items():
            for v in values:
                if same(v, best[knob]):
                    continue
                cand = dict(best, **{knob: v})
                s = run(cand)
                record(cand, s)
                if s < best_score:
                    best, best_score, improved = cand, s, True
        if not improved:
            break
    if best_score == worst:
        raise RuntimeError(
            "tune_e2e: every candidate plan failed to measure — the "
            "objective itself is broken, not the knob space"
        )
    return best, sign * best_score, trials


_default_tuner: Optional[RuntimeAutoTuner] = None


def get_default_tuner() -> Optional[RuntimeAutoTuner]:
    return _default_tuner


def set_default_tuner(tuner: Optional[RuntimeAutoTuner]) -> None:
    """Install a process-wide tuner consulted by op dispatch sites when no
    per-call tuner is passed (the reference threads one through every module
    constructor; a process-global default is the functional equivalent)."""
    global _default_tuner
    _default_tuner = tuner
