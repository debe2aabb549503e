"""Read a profiler trace (`.xplane.pb`) and reduce it to the numbers the
per-layer metrics and the breakdown report.

What the v5e's trace looks like (fixture.xplane.pb, recorded by PR 24):
one plane `/device:TPU:<n>` per chip.  Its line `XLA Ops` holds one event
per executed HLO operation, named by its HLO text, with the operations of a
`while` body nested inside the `while`'s own event: what the TensorCore
did, so busy time is the union of these.  Its line `Async XLA Ops` holds
the asynchronous operations (`copy-start`, collectives in flight), which
overlap the first line.  A Pallas kernel is `%closed_call.N = .. custom-call
(..), custom_call_target="tpu_custom_call", .. kernel_metadata={}`: the
trace carries no kernel name, so buckets.json tells kernels apart by the
shapes of their operands.  The host's `jax.profiler.TraceAnnotation` spans
are events on the thread lines of `/host:CPU`, on the same clock.
"""

from __future__ import annotations

import dataclasses
import glob
import json
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

from .intervals import (
    Op, bucket_seconds, exposed, idle_gaps, is_collective, measure,
    self_times, union,
)

OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
ANNOTATION_PREFIX = "bench."
RULES_FILE = os.path.join(os.path.dirname(__file__), "buckets.json")


@dataclasses.dataclass
class Reduction:
    chips: int
    window_s: float          # first device op start to last device op end
    busy_s: float            # union of op intervals, mean over chips
    buckets_s: Dict[str, float]   # self time by bucket, mean over chips
    coll_s: float            # collective op time, mean over chips
    coll_exposed_s: float    # ... of which no compute op ran beside it
    gaps: List[Tuple[str, float]]  # longest idle gaps of the first chip
    units: int               # steps or ticks the traced window held

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def per_unit_ms(self, bucket: str) -> Optional[float]:
        s = self.buckets_s.get(bucket)
        return None if s is None or not self.units else s / self.units * 1e3


def newest_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def _ops(line) -> List[Op]:
    return [Op(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for e in line.events]


def load(path: str):
    """({chip: XLA Ops events}, {chip: Async XLA Ops events}, the host's
    bench.* annotations)."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices: Dict[int, List[Op]] = {}
    in_flight: Dict[int, List[Op]] = {}
    annotations: List[Op] = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name == OPS_LINE:
                devices[int(m.group(1))] = _ops(line)
            elif m and line.name == ASYNC_LINE:
                in_flight[int(m.group(1))] = _ops(line)
            elif plane.name.startswith("/host:"):
                annotations.extend(
                    op for op in _ops(line)
                    if op.name.startswith(ANNOTATION_PREFIX))
    return devices, in_flight, annotations


def load_rules(sizes: dict, path: str = RULES_FILE) -> List[dict]:
    """buckets.json with `{vocab}`-style placeholders filled from the
    cell's sizes; a rule whose placeholder the cell lacks is dropped."""
    with open(path) as f:
        raw = json.load(f)["rules"]
    rules = []
    for rule in raw:
        try:
            rules.append({k: ([s.format(**sizes) for s in v]
                              if isinstance(v, list) else v)
                          for k, v in rule.items()})
        except (KeyError, IndexError):
            continue
    return rules


def reduce_ops(devices: Dict[int, Sequence[Op]], annotations: Sequence[Op],
               rules: Sequence[dict], units: int,
               in_flight: Optional[Dict[int, Sequence[Op]]] = None,
               ) -> Optional[Reduction]:
    devices = {k: v for k, v in devices.items() if v}
    if not devices:
        return None
    lo = min(op.start for ops in devices.values() for op in ops)
    hi = max(op.end for ops in devices.values() for op in ops)
    n = len(devices)
    busy_s = coll_s = exposed_s = 0.0
    buckets: Dict[str, float] = {}
    gaps: List[Tuple[str, float]] = []
    for chip in sorted(devices):
        ops = devices[chip]
        busy = union((op.start, op.end) for op in ops)
        busy_s += measure(busy) * 1e-9 / n
        own = self_times(ops)
        for b, s in bucket_seconds(own, rules).items():
            buckets[b] = buckets.get(b, 0.0) + s / n
        coll = [(op.start, op.end)
                for op in list(ops) + list((in_flight or {}).get(chip, ()))
                if is_collective(op.name)]
        # compute = leaf operations (a `while` is busy, but computes only
        # through its body) that are not collectives
        compute = [(op.start, op.end) for op, t in own
                   if t > 0.0 and not is_collective(op.name)
                   and t >= 0.999 * (op.end - op.start)]
        coll_s += measure(union(coll)) * 1e-9 / n
        exposed_s += exposed(coll, compute) * 1e-9 / n
        if not gaps:
            gaps = idle_gaps(busy, (lo, hi), annotations)
    return Reduction(chips=n, window_s=(hi - lo) * 1e-9, busy_s=busy_s,
                     buckets_s=buckets, coll_s=coll_s,
                     coll_exposed_s=exposed_s, gaps=gaps, units=units)


def reduce_trace(path: str, sizes: dict, units: int) -> Optional[Reduction]:
    devices, in_flight, annotations = load(path)
    return reduce_ops(devices, annotations, load_rules(sizes), units,
                      in_flight)
