"""The one general generator of serving traffic: a mix's data file in, a
seeded schedule of arrivals out.

Grown from `serving/driver.poisson_trace` and `shared_prefix_trace`
(seeded, replayable), with length distributions and three phases.  A mix
is parameters only (see traffic/chat-open.json):

    arrival   {"process": "poisson" | "gamma" | "uniform", "rate_rps": r,
               "cv": c (gamma only)}
    prompt_len, output_len
              {"dist": "log_uniform" | "uniform", "lo": a, "hi": b}
              | {"dist": "fixed", "value": v}
              | {"dist": "choice", "values": [..]}
    shared_prefix (optional)
              {"pool": n, "len": tokens, "zipf_a": a}: each prompt is one of
              n fixed prefixes, Zipf-chosen, followed by prompt_len tokens
    ramp_s, tail_s
              arrivals at the same rate before the window (the engine fills
              to its steady occupancy; counted in set-up) and after it (no
              measured request finishes in a draining engine)
    multiset_seed
              fixes WHICH gaps and lengths a phase holds, and in which order
    shuffle_block (optional)
              the run's seed shuffles gaps and lengths inside consecutive
              blocks of this many arrivals; without it, over the whole phase

Every --seed offers the same multiset of gaps, prompt lengths and output
lengths, in another order, with other token values: a seed that changed the
amount of work would show as spread between runs that is not the
system's.  So a phase of duration D at rate r holds exactly round(r*D)
arrivals, their gaps are drawn once from `multiset_seed` and scaled to sum
to D, and the run's seed only permutes them.  With `shuffle_block` the
permutation is local: every seed replays one trace -- the same bursts and
lulls, the same lengths in the same stretch -- in another local order, so
that a tail, which a few unlucky coincidences of a burst with short requests
decide, is the tail of the same coincidences.
"""

from __future__ import annotations

from typing import List, NamedTuple

import numpy as np


class Arrival(NamedTuple):
    due_s: float          # relative to the start of the measured window
    prompt: List[int]
    max_new_tokens: int
    phase: str            # "ramp" | "window" | "tail"


def _lengths(spec: dict, n: int, rng) -> np.ndarray:
    dist = spec["dist"]
    if dist == "fixed":
        return np.full(n, int(spec["value"]))
    if dist == "choice":
        return rng.choice(np.asarray(spec["values"], int), size=n)
    lo, hi = float(spec["lo"]), float(spec["hi"])
    if dist == "uniform":
        x = rng.uniform(lo, hi, size=n)
    elif dist == "log_uniform":
        x = np.exp(rng.uniform(np.log(lo), np.log(hi), size=n))
    else:
        raise ValueError(f"unknown length distribution {dist!r}")
    return np.clip(np.rint(x), lo, hi).astype(int)


def _gaps(spec: dict, n: int, duration: float, rng) -> np.ndarray:
    process = spec["process"]
    if process == "poisson":
        g = rng.exponential(1.0, size=n)
    elif process == "gamma":  # bursty: coefficient of variation cv > 1
        shape = 1.0 / float(spec["cv"]) ** 2
        g = rng.gamma(shape, 1.0 / shape, size=n)
    elif process == "uniform":
        g = np.ones(n)
    else:
        raise ValueError(f"unknown arrival process {process!r}")
    return g * (duration / g.sum())


def _phase(mix: dict, name: str, start: float, duration: float, vocab: int,
           fixed, rng) -> List[Arrival]:
    n = int(round(mix["arrival"]["rate_rps"] * duration))
    if n < 1:
        return []
    # the multiset, from the mix's own seed ...
    gaps = _gaps(mix["arrival"], n, duration, fixed)
    plens = _lengths(mix["prompt_len"], n, fixed)
    olens = _lengths(mix["output_len"], n, fixed)
    shared = mix.get("shared_prefix")
    if shared:
        w = 1.0 / np.arange(1, shared["pool"] + 1) ** shared.get("zipf_a", 1.2)
        which = fixed.choice(shared["pool"], size=n, p=w / w.sum())
        prefixes = fixed.integers(
            0, vocab, size=(shared["pool"], shared["len"]))
    # ... and its order and token values, from the run's
    block = int(mix.get("shuffle_block", n))

    def shuffled(x):
        """The seed's order: a shuffle inside consecutive blocks of
        `shuffle_block` arrivals (the whole phase if the mix gives none)."""
        return np.concatenate([rng.permutation(x[i:i + block])
                               for i in range(0, n, block)])

    gaps, plens, olens = shuffled(gaps), shuffled(plens), shuffled(olens)
    if shared:
        which = shuffled(which)
    due = start + np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    out = []
    for i in range(n):
        prompt = rng.integers(0, vocab, size=int(plens[i])).tolist()
        if shared:
            prompt = prefixes[int(which[i])].tolist() + prompt
        out.append(Arrival(float(due[i]), prompt, int(olens[i]), name))
    return out


def schedule(mix: dict, seed: int, window_s: float,
             vocab: int) -> List[Arrival]:
    """Ramp, window and tail arrivals in due order; the window's first
    arrival is due at 0.0 exactly."""
    rng = np.random.default_rng([int(seed), 0x5E2E])
    phases = (("ramp", -float(mix["ramp_s"]), float(mix["ramp_s"])),
              ("window", 0.0, float(window_s)),
              ("tail", float(window_s), float(mix["tail_s"])))
    out: List[Arrival] = []
    for k, (name, start, duration) in enumerate(phases):
        if duration > 0:
            fixed = np.random.default_rng([int(mix["multiset_seed"]), k])
            out += _phase(mix, name, start, duration, vocab, fixed, rng)
    return out
