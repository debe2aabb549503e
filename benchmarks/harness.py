"""The harness: one cell, once.  Finds the cell's data files by name, runs
its kind (kinds/<kind>.py), reads its per-layer metrics (metrics/<name>.py)
and builds the one result object the contract fixes.

`run_cell` is the Python entry: `run.py` calls it after requiring the TPU,
tests call it on the CPU with tiny data files of their own under another
`root`.  Nothing here is particular to one configuration, mix or metric.
"""

from __future__ import annotations

import collections
import dataclasses
import importlib.util
import json
import math
import os
import shutil
import time
from typing import Any, Callable, Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)

# model sizes a cell may never override: a cell is a published
# configuration, cut (if at all) only as its config file's `reduced` says
_WIDTHS = ("n_layer", "n_head", "n_embd", "block_size", "vocab_size")
_PUBLISHED = {"n_layer": "n_layer", "n_head": "n_head", "n_embd": "n_embd",
              "n_positions": "block_size"}


def process_start_monotonic() -> float:
    """The instant this process started, on time.monotonic()'s clock, from
    /proc (the interpreter's own start-up is part of set-up)."""
    now = time.monotonic()
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        age = uptime - start_ticks / os.sysconf("SC_CLK_TCK")
        return now - max(0.0, age)
    except (OSError, ValueError, IndexError):
        return now


def _read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    config: dict       # configs/<config>.json
    mix: dict          # traffic/<mix>.json
    chips: int
    sizes: dict        # what belongs to this pair only
    per_layer: List[str]

    @property
    def kind(self) -> str:
        return self.mix["kind"]

    def model_config(self, **overrides):
        """The program's config for this cell: the preset the config file
        maps to, checked against the file's published sizes, with the
        mix's and the cell's non-size settings applied."""
        import jax.numpy as jnp
        from tiny_deepspeed_tpu.models import ALL_PRESETS
        preset = ALL_PRESETS[self.config["preset"]]
        for key, field in _PUBLISHED.items():
            if self.config[key] != getattr(preset, field):
                raise ValueError(
                    f"{self.name}: config says {key}={self.config[key]} but "
                    f"preset {self.config['preset']!r} has "
                    f"{field}={getattr(preset, field)}")
        over = dict(self.sizes.get("model", {}), **overrides)
        bad = [k for k in over if k in _WIDTHS]
        if bad:
            raise ValueError(f"{self.name}: a cell may not set {bad}")
        for k, v in over.items():
            if k.endswith("_dtype") and isinstance(v, str):
                over[k] = jnp.dtype(v)
        return dataclasses.replace(preset, **over)

    def shape_sizes(self) -> dict:
        """Numbers the trace's bucket rules and flops.py are given."""
        from tiny_deepspeed_tpu.models import ALL_PRESETS
        p = ALL_PRESETS[self.config["preset"]]
        sizes = {"n_layer": p.n_layer, "n_head": p.n_head,
                 "n_embd": p.n_embd, "vocab_held": p.vocab_size,
                 "vocab": p.vocab_size, "d": p.n_embd, "d3": 3 * p.n_embd,
                 "d4": 4 * p.n_embd, "dh": p.head_dim}
        if "slots" in self.sizes:  # a paged pool: [blocks + scratch, bt, ..]
            bt = int(self.mix["block_tokens"])
            sizes.update(block_tokens=bt, pool_blocks=int(
                self.sizes["slots"]) * p.block_size // bt + 1)
        return sizes


def load_cell(name: str, root: str = HERE) -> Cell:
    spec = _read_json(os.path.join(root, "cells", name + ".json"))
    return Cell(
        name=name,
        config=_read_json(os.path.join(
            root, "configs", spec["config"] + ".json")),
        mix=_read_json(os.path.join(
            root, "traffic", spec["traffic"] + ".json")),
        chips=int(spec["chips"]), sizes=spec.get("sizes", {}),
        per_layer=list(spec["per_layer"]))


def _load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_kind(root: str, kind: str):
    return _load_module(os.path.join(root, "kinds", kind + ".py"),
                        "bench_kind_" + kind)


def load_metric(root: str, name: str):
    """metrics/<name>.py: UNIT, LAYER, MOVES, SOURCE and read(ctx)."""
    return _load_module(os.path.join(root, "metrics", name + ".py"),
                        "bench_metric_" + name.replace(".", "_"))


class CompileMonitor:
    """Compile requests seen through jax.monitoring, by phase: seconds in
    the backend compile call (a persistent-cache hit included: the
    retrieval is timed by the same event), their number, and persistent-
    cache misses.  `mark("window")` closes set-up: what comes after is the
    window, where there must be none.  jax.monitoring has no public way to
    take a listener back, so `close()` makes this one deaf."""

    def __init__(self):
        import jax
        self.phase: Optional[str] = "setup"
        self.compile_s = collections.Counter()
        self.requests = collections.Counter()
        self.misses = collections.Counter()
        jax.monitoring.register_event_duration_secs_listener(self._on_dur)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_dur(self, event, duration, **kw):
        if (self.phase is not None
                and event == "/jax/core/compile/backend_compile_duration"):
            self.compile_s[self.phase] += duration
            self.requests[self.phase] += 1

    def _on_event(self, event, **kw):
        if (self.phase is not None
                and event == "/jax/compilation_cache/cache_misses"):
            self.misses[self.phase] += 1

    def mark(self, phase: str) -> None:
        self.phase = phase

    def close(self) -> None:
        self.phase = None


class Tracer:
    """The profiler, for the short steady part a --trace 1 run traces.
    Python-level tracing is off (it would dwarf the device events and slow
    the host); TraceAnnotation spans need host level 1 only."""

    def __init__(self, log_dir: str):
        self.log_dir = log_dir
        self.active = False
        self.path: Optional[str] = None

    def start(self) -> None:
        import jax
        shutil.rmtree(self.log_dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 1
        jax.profiler.start_trace(self.log_dir, profiler_options=options)
        self.active = True

    def stop(self) -> str:
        import jax
        from .reduce.xplane import newest_xplane
        jax.profiler.stop_trace()
        self.active = False
        self.path = newest_xplane(self.log_dir)
        return self.path


@dataclasses.dataclass
class Env:
    """What a kind is handed besides its cell."""
    seed: int
    seconds: float
    trace: bool
    t_process: float              # process start, time.monotonic() clock
    monitor: CompileMonitor
    devices: list                 # the chips this cell uses
    tracer: Optional[Tracer]
    say: Callable[[str], None]
    laps: List[Any] = dataclasses.field(default_factory=list)

    def lap(self, label: str) -> None:
        """Close one item of set-up (an itemised `setup_s` is printed)."""
        self.laps.append((label, time.monotonic()))


@dataclasses.dataclass
class Outcome:
    """What a kind hands back."""
    t_window: float               # first measured step / request due
    end_to_end: Dict[str, float]
    correct: bool
    attempted: int
    failed: int
    host: Dict[str, Any]          # what the host-side readers read
    units: int = 0                # steps or ticks inside the traced part


@dataclasses.dataclass
class Ctx:
    """What a per-layer metric's reader is handed."""
    cell: Cell
    env: Env
    host: Dict[str, Any]
    trace: Any                    # reduce.Reduction or None
    peaks: Optional[dict]
    sizes: dict


def annotate(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


def peaks_for(device_kind: str, root: str = HERE) -> dict:
    table = _read_json(os.path.join(root, "peaks.json"))
    if device_kind not in table:
        raise SystemExit(
            f"device_kind {device_kind!r} is not in peaks.json: an unknown "
            "device has no peak (add it with its source)")
    return table[device_kind]


def peak_bytes(stats: Optional[dict]) -> int:
    """One chip's peak from its own allocator.  On this runtime a compiled
    program's temporary memory is counted under `peak_bytes_reserved`, not
    `peak_bytes_in_use` (PERF.md section 5), so the peak is their sum."""
    if not stats:
        return 0
    return int(stats.get("peak_bytes_in_use", 0)
               + stats.get("peak_bytes_reserved", 0))


def memory_peak_bytes(devices) -> int:
    return max(peak_bytes(d.memory_stats()) for d in devices)


def _finite(x: float) -> float:
    """JSON has no infinity: a tail in which a request missed reads as a
    very large number (and the run is not `correct`)."""
    return x if math.isfinite(x) else 1e12


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             *, root: str = HERE, manifest: Optional[str] = None,
             t_process: Optional[float] = None) -> dict:
    """Run one cell once; returns the result object (see run.py)."""
    import jax

    t_process = process_start_monotonic() if t_process is None else t_process
    manifest = _read_json(manifest or os.path.join(REPO, "BENCHMARK.json"))
    entry = next((w for w in manifest["workloads"]
                  if w["name"] == workload), None)
    if entry is None:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json")
    cell = load_cell(workload, root)
    if cell.chips != entry["chips"]:
        raise SystemExit(f"{workload}: cells/ says {cell.chips} chips, "
                         f"BENCHMARK.json {entry['chips']}")
    devices = jax.devices()
    if len(devices) < cell.chips:
        raise SystemExit(f"{workload} needs {cell.chips} chips, JAX finds "
                         f"{len(devices)}")
    dev0 = devices[0]
    tag = f"[{dev0.platform} {dev0.device_kind} x{len(devices)}]"

    def say(msg: str) -> None:
        print(f"{tag} {msg}", flush=True)

    unit_of = {m["name"]: m["unit"] for m in manifest["end_to_end"]}
    env = Env(seed=int(seed), seconds=float(seconds), trace=bool(trace),
              t_process=t_process, monitor=CompileMonitor(),
              devices=devices[:cell.chips],
              tracer=Tracer(os.path.join(root, ".trace", workload))
              if trace else None, say=say)
    say(f"{workload} seed={seed} seconds={seconds} trace={int(trace)} "
        f"kind={cell.kind}")
    try:
        out: Outcome = load_kind(root, cell.kind).run(cell, env)
    finally:
        env.monitor.close()
    setup_s = out.t_window - t_process
    laps = [(label, t) for label, t in env.laps if t <= out.t_window]
    say(f"setup_s {setup_s:.2f} = " + " + ".join(
        f"{label} {t - prev:.2f}" for (label, t), prev in zip(
            laps, [t_process] + [t for _, t in laps])))

    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": len(devices),
              "memory_peak_bytes": memory_peak_bytes(env.devices)}
    result = {"correct": bool(out.correct), "attempted": int(out.attempted),
              "failed": int(out.failed), "metrics": {}, "device": device}
    if not trace:
        for name, value in dict(out.end_to_end, setup_s=setup_s).items():
            result["metrics"][name] = {"value": _finite(float(value)),
                                       "unit": unit_of[name]}
        return result

    sizes = cell.shape_sizes()
    reduction = None
    if env.tracer.path is not None:
        from .reduce.xplane import reduce_trace
        reduction = reduce_trace(env.tracer.path, sizes, out.units)
    peaks = (peaks_for(dev0.device_kind, root)
             if dev0.platform == "tpu" else None)
    ctx = Ctx(cell=cell, env=env, host=out.host, trace=reduction,
              peaks=peaks, sizes=sizes)
    for name in cell.per_layer:
        reader = load_metric(root, name)
        value = reader.read(ctx)
        if value is not None:
            result["metrics"][name] = {"value": _finite(float(value)),
                                       "unit": reader.UNIT}
    if reduction is not None:
        device["busy_s"] = reduction.busy_s
        device["window_s"] = reduction.window_s
        top = sorted(reduction.buckets_s.items(), key=lambda kv: -kv[1])
        result["breakdown"] = {
            "device_ops": [[k, v] for k, v in top[:10]],
            "idle_gaps": [[k, v] for k, v in reduction.gaps[:10]],
        }
    return result
