"""95th percentile over measured requests of first token minus the DUE
instant; a request that is not whole misses.  Asked for as an end-to-end
metric and demoted by PR 24: two runs of one seed read 187.8 and 173.3 ms,
too far apart for a bound the contract admits (PERF.md, Open questions).
It moves `tpot_p95_ms` because a prefill in a tick stretches every active
request's gap."""

UNIT = "ms"
BETTER = "lower"
LAYER = "serving scheduler"
MOVES = "tpot_p95_ms"
SOURCE = "program_span"


def read(ctx):
    from benchmarks.serve_arith import ttft_p95_ms
    measured = ctx.host.get("measured")
    return ttft_p95_ms(measured) if measured else None
