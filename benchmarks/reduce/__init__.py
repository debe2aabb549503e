"""Reduction of a profiler trace to numbers: device busy and idle time,
time by bucket of operation, exposed collective time, and the longest idle
gaps named by what the host was doing.

Two halves.  `intervals` is arithmetic on (start, end) pairs and knows
nothing of JAX; `xplane` reads an `.xplane.pb` with
`jax.profiler.ProfileData` into those pairs.  `tests/benchmarks/
test_reduce.py` checks both, the second on `fixture.xplane.pb`, a small
trace recorded on the v5e by `record_fixture.py`.
"""

from .intervals import (  # noqa: F401
    Op, bucket_of, bucket_seconds, exposed, idle_gaps, measure, self_times,
    subtract, union,
)
from .xplane import Reduction, load, reduce_trace  # noqa: F401
