"""Interval arithmetic for the trace reduction.  Times are in one unit
throughout (the trace's nanoseconds); nothing here imports JAX."""

from __future__ import annotations

from typing import Dict, Iterable, List, NamedTuple, Sequence, Tuple

Span = Tuple[float, float]

COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter",
               "collective-permute", "all-to-all")


class Op(NamedTuple):
    name: str
    start: float
    end: float


def union(spans: Iterable[Span]) -> List[Span]:
    """Merged, sorted, disjoint spans: overlapping operations count once."""
    out: List[List[float]] = []
    for s, e in sorted((s, e) for s, e in spans if e > s):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def measure(merged: Sequence[Span]) -> float:
    return sum(e - s for s, e in merged)


def subtract(a: Sequence[Span], b: Sequence[Span]) -> List[Span]:
    """The part of merged spans `a` that no span of merged `b` covers."""
    out: List[Span] = []
    j = 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def head(name: str) -> str:
    """An XLA op event is named by its HLO text, `%fusion.3 = bf16[..]
    fusion(%copy.1, ..)`: the head is what is left of ` = `, so a rule on
    the head is not fooled by an operand's name."""
    return name.split(" = ")[0]


def is_collective(name: str) -> bool:
    """A collective by its own name (`%all-gather.197 = ..`), or a fusion
    around one: the v5e's compiler emits reduce-scatters as `%fusion.291 =
    .. fusion(..), kind=kCustom, calls=%all-reduce-scatter.2..`."""
    h = head(name)
    return (any(c in h for c in COLLECTIVES)
            or any("calls=%" + c in name for c in COLLECTIVES))


def self_times(ops: Sequence[Op]) -> List[Tuple[Op, float]]:
    """Each operation of ONE device line with the time it ran itself: its
    duration less that of the operations nested in it (a `while` spans its
    body's operations on the same line).  Summed over a line this is the
    line's busy time, so buckets built on it never count a moment twice."""
    out: List[Tuple[Op, float]] = []
    stack: List[List] = []  # [op, time covered by direct children]

    def close():
        op, covered = stack.pop()
        out.append((op, max(0.0, (op.end - op.start) - covered)))

    for op in sorted(ops, key=lambda o: (o.start, -o.end)):
        while stack and stack[-1][0].end <= op.start:
            close()
        if stack:
            parent = stack[-1]
            parent[1] += min(op.end, parent[0].end) - op.start
        stack.append([op, 0.0])
    while stack:
        close()
    return out


def bucket_of(name: str, rules: Sequence[dict]) -> str:
    """First rule that matches names the bucket.  A rule matches on any of
    its `head` substrings left of ` = `, on any of its `text` substrings
    anywhere in the name, or on ALL of its `all` substrings together."""
    h = head(name)
    for rule in rules:
        if (any(s in h for s in rule.get("head", ()))
                or any(s in name for s in rule.get("text", ()))
                or ("all" in rule and all(s in name for s in rule["all"]))):
            return rule["bucket"]
    return "other"


def bucket_seconds(own_times: Sequence[Tuple[Op, float]],
                   rules: Sequence[dict],
                   unit: float = 1e-9) -> Dict[str, float]:
    """Self time (the pairs `self_times` gives) summed by bucket."""
    out: Dict[str, float] = {}
    for op, own in own_times:
        if own > 0.0:
            b = bucket_of(op.name, rules)
            out[b] = out.get(b, 0.0) + own * unit
    return out


def exposed(collectives: Iterable[Span], compute: Iterable[Span]) -> float:
    """Collective time during which no compute operation runs."""
    return measure(subtract(union(collectives), union(compute)))


def idle_gaps(busy: Sequence[Span], window: Span,
              annotations: Sequence[Op], top: int = 10,
              unit: float = 1e-9) -> List[Tuple[str, float]]:
    """The longest idle gaps of the window, each named by the host
    annotation that overlaps it most (`unannotated` if none does)."""
    gaps = subtract([window], busy)
    named = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
        best, best_overlap = "unannotated", 0.0
        for a in annotations:
            overlap = min(e, a.end) - max(s, a.start)
            if overlap > best_overlap:
                best, best_overlap = a.name, overlap
        named.append((best, (e - s) * unit))
    return named
