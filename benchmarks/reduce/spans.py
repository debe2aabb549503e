"""The program's own names in a profiler trace (`tds.*`, PR 26), reduced.

`xplane.py` times every layer from outside: its host spans are the
benchmark's (`bench.*`) and its device buckets are told apart by operand
shapes.  This module reads what the PROGRAM writes
(tiny_deepspeed_tpu/utils/profiling.TABLE):

  (a) device time by program, from the device plane's `XLA Modules` line:
      one event per executed program, named `jit_tds_decode(<fingerprint>)`;
  (b) device self time by `tds.` scope and by phase (forward / backward /
      recompute / optimizer).  The v5e's trace carries each operation's
      `op_name` -- `jit(tds_train_step)/transpose(jvp())/while/body/
      checkpoint/tds.block/tds.mlp/dot_general` -- as the stat `tf_op` of
      the operation's event METADATA, which `jax.profiler.ProfileData`
      does not show (it gives an event's own stats only).  So the file is
      read as what it is, a protobuf (`XSpace`, tsl/profiler/protobuf/
      xplane.proto), by the small wire-format reader below: no TensorFlow,
      no compiled module text, no registry of programs in the program;
  (c) idle gaps of the device, each put down to the innermost `tds.*` host
      span that covers it.

An operation with no scope of its own (a copy the compiler put in, a
parameter moved between memories) takes the scope of the operation it
feeds, through the operand names in the events' HLO text.

Everything is computed once per trace file (`reduce_spans`) and returns
None where the program wrote no `tds` name: the parent of PR 26, a CPU run.
Times are nanoseconds on the trace's one clock, as in `xplane.py`.
"""

from __future__ import annotations

import bisect
import dataclasses
import functools
import re
import struct
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

from .intervals import (
    head, is_collective, measure, self_times, subtract, union,
)
from .xplane import ASYNC_LINE, DEVICE_PLANE, OPS_LINE

MODULES_LINE = "XLA Modules"
PROGRAM_PREFIX = "jit_tds_"
SPAN_PREFIX = "tds."
PHASES = ("forward", "backward", "recompute", "optimizer")
# A serving program's whole body is written under one scope, so what the
# compiler adds at the program's edge with no op_name of its own (the layout
# copies of a donated argument on the way in and out) is that scope's.
PROGRAM_SCOPE = {"jit_tds_decode": "tds.decode",
                 "jit_tds_prefill": "tds.prefill"}
_SCOPE = re.compile(r"tds\.[a-z_.]+")
_OPERAND = re.compile(r"%([\w.\-]+)")


# -- the file ----------------------------------------------------------------

def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    b = buf[i]
    if b < 0x80:
        return b, i + 1
    out, shift = b & 0x7F, 7
    while True:
        i += 1
        b = buf[i]
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i + 1
        shift += 7


def _fields(buf: bytes, i: int, end: int):
    """(field number, wire type, value) of one message: an int for a
    varint or a fixed field, (start, end) for a length-delimited one."""
    while i < end:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            val = (i, i + n)
            i += n
        elif wire == 1:
            val = int.from_bytes(buf[i:i + 8], "little")
            i += 8
        elif wire == 5:
            val = int.from_bytes(buf[i:i + 4], "little")
            i += 4
        else:
            raise ValueError(f"wire type {wire} in an XSpace")
        yield key >> 3, wire, val


def _signed(v: int) -> int:
    return v - (1 << 64) if v >= 1 << 63 else v


def _stat(buf: bytes, span: Tuple[int, int], stat_names: Dict[int, str]):
    """One XStat -> (name, value); a `ref_value` is looked up."""
    name, value = None, None
    for f, wire, v in _fields(buf, *span):
        if f == 1:
            name = stat_names.get(v, str(v))
        elif f == 2:
            value = struct.unpack("<d", v.to_bytes(8, "little"))[0]
        elif f == 3:
            value = v
        elif f == 4:
            value = _signed(v)
        elif f == 5:
            value = buf[v[0]:v[1]].decode("utf-8", "replace")
        elif f == 6:
            value = buf[v[0]:v[1]]
        elif f == 7:
            value = stat_names.get(v, str(v))
    return name, value


class Event(NamedTuple):
    name: str          # the event metadata's name: an operation's HLO text
    start: float       # ns
    end: float
    mid: int           # metadata id: the key into `Plane.meta`
    stats: tuple       # ((name, value), ..) of the event itself


@dataclasses.dataclass
class Plane:
    name: str
    lines: Dict[str, List[Event]]
    meta: Dict[int, Dict[str, object]]   # metadata id -> its stats


def _entry(buf: bytes, span: Tuple[int, int]) -> Tuple[int, Tuple[int, int]]:
    """A map<int64, message> entry -> (key, the message's span)."""
    key, val = 0, (0, 0)
    for f, _, v in _fields(buf, *span):
        if f == 1:
            key = v
        elif f == 2:
            val = v
    return key, val


def _plane(buf: bytes, span: Tuple[int, int]) -> Plane:
    name = ""
    line_spans, meta_spans, stat_spans = [], [], []
    for f, _, v in _fields(buf, *span):
        if f == 2:
            name = buf[v[0]:v[1]].decode()
        elif f == 3:
            line_spans.append(v)
        elif f == 4:
            meta_spans.append(v)
        elif f == 5:
            stat_spans.append(v)
    stat_names: Dict[int, str] = {}
    for s in stat_spans:
        key, msg = _entry(buf, s)
        for f, _, v in _fields(buf, *msg):
            if f == 2:
                stat_names[key] = buf[v[0]:v[1]].decode()
    names: Dict[int, str] = {}
    meta: Dict[int, Dict[str, object]] = {}
    for s in meta_spans:
        key, msg = _entry(buf, s)
        stats = {}
        for f, _, v in _fields(buf, *msg):
            if f == 2:
                names[key] = buf[v[0]:v[1]].decode("utf-8", "replace")
            elif f == 5:
                k, val = _stat(buf, v, stat_names)
                stats[k] = val
        meta[key] = stats
    lines: Dict[str, List[Event]] = {}
    for s in line_spans:
        lname, t0_ns, ev_spans = "", 0, []
        for f, _, v in _fields(buf, *s):
            if f == 2:
                lname = buf[v[0]:v[1]].decode()
            elif f == 3:
                t0_ns = _signed(v)
            elif f == 4:
                ev_spans.append(v)
        # an event's own stats are kept where they are read: a span's ids
        # on the host's lines, a run's id on the programs' line
        keep = name.startswith("/host:") or lname == MODULES_LINE
        events = []
        for e in ev_spans:
            mid = off = dur = 0
            stats = []
            for f, _, v in _fields(buf, *e):
                if f == 1:
                    mid = v
                elif f == 2:
                    off = v
                elif f == 3:
                    dur = v
                elif f == 4 and keep:
                    stats.append(_stat(buf, v, stat_names))
            start = t0_ns + off / 1e3
            events.append(Event(names.get(mid, str(mid)), start,
                                start + dur / 1e3, mid, tuple(stats)))
        lines.setdefault(lname, []).extend(events)
    return Plane(name, lines, meta)


def read_xspace(path: str) -> List[Plane]:
    """Every plane of an `.xplane.pb`."""
    with open(path, "rb") as f:
        buf = f.read()
    return [_plane(buf, v) for f, _, v in _fields(buf, 0, len(buf))
            if f == 1]


# -- names -------------------------------------------------------------------

def scopes_of(op_name: Optional[str]) -> List[str]:
    """The `tds.` scopes of an op_name, outermost first.  Where the
    compiler merged operations it joins their names with `;`: the first
    one is the fused operation's root."""
    if not op_name:
        return []
    return _SCOPE.findall(op_name.split(";")[0])


def phase_of(op_name: Optional[str]) -> str:
    """forward / backward / recompute / optimizer, from the transforms
    JAX writes into an op_name: `transpose(jvp(..))` is the backward,
    `rematted_computation` the forward run again inside it."""
    first = (op_name or "").split(";")[0]
    if "tds.optim" in first:
        return "optimizer"
    if "rematted_computation" in first:
        return "recompute"
    if "transpose(" in first:
        return "backward"
    return "forward"


def program_of(module_event_name: str) -> str:
    """`jit_tds_decode(6704416550516843479)` -> `jit_tds_decode`."""
    return module_event_name.split("(")[0]


def collective_class(name: str, op_name: Optional[str]) -> str:
    """gather or grad: by the scope the engine wrote (`tds.gather`,
    `tds.grad_sync`); where GSPMD placed the collective itself, by what it
    does: one that reduces (all-reduce, reduce-scatter, a fusion around
    one) carries gradients, one that only moves data brings parameters (an
    all-gather, or the collective-permutes in flight that the v5e's
    compiler makes of ZeRO-3's per-layer gathers)."""
    scopes = scopes_of(op_name)
    if "tds.gather" in scopes:
        return "gather"
    if "tds.grad_sync" in scopes:
        return "grad"
    reduces = any(kind in head(name) or "calls=%" + kind in name
                  for kind in ("all-reduce", "reduce-scatter"))
    return "grad" if reduces else "gather"


# -- the reduction -----------------------------------------------------------

@dataclasses.dataclass
class Gap:
    seconds: float
    span: str            # innermost tds.* span covering it, or `unannotated`
    ids: Dict[str, object]


@dataclasses.dataclass
class SpanReduction:
    chips: int
    units: int
    busy_s: float                      # as xplane.Reduction: mean over chips
    programs_s: Dict[str, float]       # `XLA Modules` time by program
    program_runs: Dict[str, float]     # ... and its runs, mean over chips
    modules_s: float                   # all `XLA Modules` time
    copies_s: Dict[str, float]         # self time of copy* ops, by program
    scopes_s: Dict[str, float]         # self time by innermost tds. scope
    phases_s: Dict[str, float]         # self time by phase, train program
    head_s: float                      # tds.head, forward and backward
    attn_s: Dict[str, float]           # tds.attn.kernel: forward, backward
    unscoped_s: float                  # busy time that reaches no tds. scope
    coll_s: Dict[str, float]           # gather / grad, union of both lines
    idle_in_s: Dict[str, float]        # device idle inside a host span
    gaps: List[Gap]                    # longest idle gaps of the first chip

    def per_unit_ms(self, seconds: Optional[float]) -> Optional[float]:
        if not seconds or not self.units:
            return None
        return seconds / self.units * 1e3

    def per_run_ms(self, table: Dict[str, float], program: str
                   ) -> Optional[float]:
        """A program's seconds in `table` over its runs."""
        runs = self.program_runs.get(program)
        return table.get(program, 0.0) / runs * 1e3 if runs else None

    @property
    def scoped_share(self) -> float:
        return 1.0 - self.unscoped_s / self.busy_s if self.busy_s else 0.0

    @property
    def program_share(self) -> float:
        ours = sum(v for k, v in self.programs_s.items()
                   if k.startswith(PROGRAM_PREFIX))
        return ours / self.modules_s if self.modules_s else 0.0


def _own_scopes(events: Sequence[Event], meta) -> Dict[int, Optional[str]]:
    """metadata id -> op_name, own or borrowed from the operation it feeds.

    Consumers are found through the `%name` operands in each event's HLO
    text; a scope travels backwards along them until nothing changes (a
    copy feeding a copy feeding a matmul)."""
    op_name: Dict[int, Optional[str]] = {}
    by_head: Dict[str, int] = {}
    text: Dict[int, str] = {}
    for e in events:
        if e.mid not in text:
            text[e.mid] = e.name
            by_head[head(e.name).lstrip("%")] = e.mid
            name = meta.get(e.mid, {}).get("tf_op")
            op_name[e.mid] = name if scopes_of(name) else None
    feeds: Dict[int, List[int]] = {}
    for mid, t in text.items():
        body = t.split(" = ", 1)[-1]
        for operand in _OPERAND.findall(body):
            src = by_head.get(operand)
            if src is not None and src != mid:
                feeds.setdefault(src, []).append(mid)
    for _ in range(8):
        changed = False
        for src, users in feeds.items():
            if op_name[src] is None:
                got = next((op_name[u] for u in users if op_name[u]), None)
                if got is not None:
                    op_name[src] = got
                    changed = True
        if not changed:
            break
    return op_name


class _Idle:
    """The idle intervals of one chip, for `how much of [a, b] was idle`
    in logarithmic time (a trace has as many gaps as operations)."""

    def __init__(self, gaps: Sequence[Tuple[float, float]]):
        self.starts = [g[0] for g in gaps]
        self.ends = [g[1] for g in gaps]
        self.cum = [0.0]
        for s, e in gaps:
            self.cum.append(self.cum[-1] + (e - s))

    def within(self, a: float, b: float) -> float:
        i = bisect.bisect_right(self.ends, a)
        j = bisect.bisect_left(self.starts, b)
        if i >= j:
            return 0.0
        return (self.cum[j] - self.cum[i] - max(0.0, a - self.starts[i])
                - max(0.0, self.ends[j - 1] - b))


def _innermost(spans: Sequence[Event], s: float, e: float
               ) -> Tuple[str, Dict[str, object]]:
    """The span an idle gap [s, e] is put down to.  Of the spans that touch
    it, the innermost ones (those with no other inside them) are asked
    first: where together they cover half of the gap, it goes to the one
    that covers most.  Else to the smallest span that covers half of it
    alone; else it is `unannotated`: the host was mostly in no span."""
    touching = [a for a in spans if min(e, a.end) > max(s, a.start)]

    def cover(a: Event) -> float:
        return min(e, a.end) - max(s, a.start)

    leaves = [a for a in touching if not any(
        b is not a and a.start <= b.start and b.end <= a.end
        for b in touching)]
    half = 0.5 * (e - s)
    best = None
    if measure(union((max(s, a.start), min(e, a.end))
                     for a in leaves)) >= half:
        best = max(leaves, key=cover)
    else:
        around = [a for a in touching if cover(a) >= half]
        if around:
            best = min(around, key=lambda a: a.end - a.start)
    if best is None:
        return "unannotated", {}
    return best.name, dict(best.stats)


def reduce_planes(planes: Sequence[Plane], units: int, top: int = 10
                  ) -> Optional[SpanReduction]:
    devices = sorted(
        (int(DEVICE_PLANE.match(p.name).group(1)), p) for p in planes
        if DEVICE_PLANE.match(p.name) and p.lines.get(OPS_LINE))
    if not devices:
        return None
    if not any(program_of(m.name).startswith(PROGRAM_PREFIX)
               for _, p in devices for m in p.lines.get(MODULES_LINE, ())):
        return None   # a program without the names: nothing to read
    spans = sorted((e for p in planes if p.name.startswith("/host:")
                    for evs in p.lines.values() for e in evs
                    if e.name.startswith(SPAN_PREFIX)),
                   key=lambda e: e.start)
    n = len(devices)
    lo = min(e.start for _, p in devices for e in p.lines[OPS_LINE])
    hi = max(e.end for _, p in devices for e in p.lines[OPS_LINE])
    out = SpanReduction(
        chips=n, units=units, busy_s=0.0, programs_s={}, program_runs={},
        modules_s=0.0, copies_s={}, scopes_s={},
        phases_s=dict.fromkeys(PHASES, 0.0), head_s=0.0,
        attn_s={"forward": 0.0, "backward": 0.0}, unscoped_s=0.0,
        coll_s={"gather": 0.0, "grad": 0.0}, idle_in_s={}, gaps=[])

    with_async = sum(bool(p.lines.get(ASYNC_LINE)) for _, p in devices)

    def add(table: Dict[str, float], key: str, ns: float) -> None:
        table[key] = table.get(key, 0.0) + ns * 1e-9 / n

    for chip, plane in devices:
        ops = plane.lines[OPS_LINE]
        modules = sorted(plane.lines.get(MODULES_LINE, ()),
                         key=lambda m: m.start)
        starts = [m.start for m in modules]
        for m in modules:
            prog = program_of(m.name)
            add(out.programs_s, prog, m.end - m.start)
            out.program_runs[prog] = out.program_runs.get(prog, 0) + 1 / n
            out.modules_s += (m.end - m.start) * 1e-9 / n
        op_names = _own_scopes(ops, plane.meta)
        busy = union((e.start, e.end) for e in ops)
        out.busy_s += measure(busy) * 1e-9 / n
        for op, own in self_times(ops):
            if own <= 0.0:
                continue
            k = bisect.bisect_right(starts, op.start) - 1
            prog = (program_of(modules[k].name)
                    if k >= 0 and op.start < modules[k].end else "no program")
            if head(op.name).lstrip("%").startswith("copy"):
                add(out.copies_s, prog, own)
            name = op_names.get(op.mid)
            scopes = scopes_of(name) or scopes_of(PROGRAM_SCOPE.get(prog))
            if not scopes:
                out.unscoped_s += own * 1e-9 / n
                continue
            add(out.scopes_s, scopes[-1], own)
            phase = phase_of(name)
            if prog == PROGRAM_PREFIX + "train_step":
                add(out.phases_s, phase, own)
            if "tds.head" in scopes:
                out.head_s += own * 1e-9 / n
            if scopes[-1] == "tds.attn.kernel":
                # every run of a forward kernel is forward here, the first
                # and the recomputed one: a kernel's time is the kernel's
                add(out.attn_s,
                    "backward" if phase == "backward" else "forward", own)
        # collectives in flight sit on `Async XLA Ops`, a line the profiler
        # writes for the first chip alone: the chips that have it are
        # averaged (another would read the gathers in flight as absent)
        if not with_async or plane.lines.get(ASYNC_LINE):
            coll: Dict[str, List[Tuple[float, float]]] = {"gather": [],
                                                          "grad": []}
            for e in list(ops) + list(plane.lines.get(ASYNC_LINE, ())):
                if is_collective(e.name):
                    coll[collective_class(e.name, plane.meta.get(
                        e.mid, {}).get("tf_op"))].append((e.start, e.end))
            for kind, ivs in coll.items():
                out.coll_s[kind] += measure(union(ivs)) * 1e-9 / (
                    with_async or n)
        if chip == devices[0][0]:
            idle = subtract([(lo, hi)], busy)
            table = _Idle(idle)
            for a in spans:
                out.idle_in_s[a.name] = (
                    out.idle_in_s.get(a.name, 0.0)
                    + table.within(max(a.start, lo), min(a.end, hi)) * 1e-9)
            for s, e in sorted(idle, key=lambda g: g[0] - g[1])[:top]:
                name, ids = _innermost(spans, s, e)
                out.gaps.append(Gap((e - s) * 1e-9, name, ids))
    return out


@functools.lru_cache(maxsize=4)
def reduce_spans(path: str, units: int) -> Optional[SpanReduction]:
    return reduce_planes(read_xspace(path), units)


_said = set()   # traces whose summary lines have been printed


def of(ctx) -> Optional[SpanReduction]:
    """What a per-layer metric's reader asks for: the reduction of this
    run's trace, computed once and said once; None with no trace, no TPU
    plane in it, or a program that wrote no `tds` name."""
    path = getattr(ctx.env.tracer, "path", None)
    if ctx.trace is None or path is None:
        return None
    r = reduce_spans(path, int(ctx.trace.units))
    if r is not None and path not in _said:
        _said.add(path)
        for line in describe(r):
            ctx.env.say(line)
    return r


def describe(r: SpanReduction) -> List[str]:
    """The lines a traced run prints besides its metrics."""
    ms = r.per_unit_ms
    lines = [
        f"tds: {100 * r.scoped_share:.2f} % of device busy time in a tds. "
        f"scope ({100 * (1 - r.scoped_share):.2f} % reaches none), "
        f"{100 * r.program_share:.2f} % of XLA Modules time in a "
        f"{PROGRAM_PREFIX}* program; busy {ms(r.busy_s) or 0:.3f} ms/unit",
        "tds programs (ms/unit, runs/unit): " + ", ".join(
            f"{k} {ms(v) or 0:.3f} x{r.program_runs[k] / max(r.units, 1):.2f}"
            for k, v in sorted(r.programs_s.items(), key=lambda kv: -kv[1])),
        "tds scopes (ms/unit): " + ", ".join(
            f"{k} {ms(v) or 0:.3f}"
            for k, v in sorted(r.scopes_s.items(), key=lambda kv: -kv[1])),
    ]
    if any(r.phases_s.values()):
        lines.append("tds phases (ms/unit): " + ", ".join(
            f"{k} {ms(r.phases_s[k]) or 0:.3f}" for k in PHASES))
    if r.copies_s:
        lines.append("tds copies by program (ms/unit): " + ", ".join(
            f"{k} {ms(v) or 0:.3f}" for k, v in sorted(
                r.copies_s.items(), key=lambda kv: -kv[1])))
    if r.idle_in_s:
        lines.append("tds device idle inside a span (ms/unit): " + ", ".join(
            f"{k} {ms(v) or 0:.3f}" for k, v in sorted(
                r.idle_in_s.items(), key=lambda kv: -kv[1]) if v > 0))
    if r.gaps:
        lines.append("tds idle gaps (ms, innermost span, ids): " + "; ".join(
            f"{g.seconds * 1e3:.3f} {g.span} {g.ids}" for g in r.gaps))
    return lines
