"""Serve arithmetic: from per-request timestamps to the numbers reported.

Every latency is taken from the instant a request was DUE by the schedule,
not from when the generator got round to submitting it, so a late generator
or a stalled engine shows in the latency of the requests it delayed
(`serving/driver.run_trace` times from `submit()`; PR 22's ledger showed
the two a tick apart).  Pure Python on plain records: tested on synthetic
timelines in tests/benchmarks/test_serve_arithmetic.py.
"""

from __future__ import annotations

import dataclasses
import math
from typing import List, Optional, Sequence


@dataclasses.dataclass
class Record:
    """One offered request on the benchmark's monotonic clock (seconds)."""
    due: float                 # when the schedule said it arrives
    submitted: float           # when submit() was called
    want_tokens: int           # output length asked for
    admitted: Optional[float] = None
    first: Optional[float] = None      # first token on the host
    done: Optional[float] = None
    tokens: int = 0
    gaps: Sequence[float] = ()         # between consecutive tokens
    status: Optional[str] = None
    preemptions: int = 0

    @property
    def whole(self) -> bool:
        return self.status == "ok" and self.tokens == self.want_tokens


def percentile(values: Sequence[float], q: float) -> float:
    """q-th percentile (0..100), linear between order statistics -- the
    definition of numpy's default, written out so the yardstick does not
    move with a library."""
    if not values:
        raise ValueError("percentile of nothing")
    xs = sorted(values)
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def measured_set(records: Sequence[Record], t0: float,
                 window_s: float) -> List[Record]:
    """Every request due inside [t0, t0 + window_s)."""
    return [r for r in records if t0 <= r.due < t0 + window_s]


def tpot_ms(r: Record) -> Optional[float]:
    """Time per output token of one request: (done - first) / (tokens - 1),
    the mean of its gaps.  None for a request that is not whole or has a
    single token."""
    if not r.whole or r.tokens < 2:
        return None
    return (r.done - r.first) / (r.tokens - 1) * 1e3


def ttft_ms(r: Record) -> float:
    """First token minus the DUE instant; a request that is not whole
    misses (infinite, so it sorts as the worst)."""
    if not r.whole or r.first is None:
        return math.inf
    return (r.first - r.due) * 1e3


def tpot_p95_ms(measured: Sequence[Record]) -> float:
    vals = [tpot_ms(r) for r in measured]
    return percentile([math.inf if v is None else v for v in vals], 95)


def ttft_p95_ms(measured: Sequence[Record]) -> float:
    return percentile([ttft_ms(r) for r in measured], 95)


def queue_ms(r: Record) -> float:
    """Admission minus due: the wait in the generator and in the queue."""
    return math.inf if r.admitted is None else (r.admitted - r.due) * 1e3


def gen_late_ms(r: Record) -> float:
    """How late the generator ran: submit() minus due."""
    return (r.submitted - r.due) * 1e3


def itl_p95_ms(measured: Sequence[Record]) -> Optional[float]:
    """95th percentile over SINGLE token gaps (first token left out).
    Kept as a layer number only: a gap is one tick, ticks come in stairs
    (0, 1, 2.. admissions), and a percentile of a staircase jumps a stair
    when a handful of ticks change -- what sank PR 22's end-to-end
    metric."""
    gaps = [g * 1e3 for r in measured for g in r.gaps]
    return percentile(gaps, 95) if gaps else None
