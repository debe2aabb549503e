"""Median time of one `engine.tick()` inside the window (the benchmark's own
span, `bench.tick`)."""

UNIT = "ms"
BETTER = "lower"
LAYER = "serving scheduler"
MOVES = "tpot_p95_ms"
SOURCE = "host_clock"


def read(ctx):
    from benchmarks.serve_arith import percentile
    ticks = ctx.host.get("tick_s")
    return percentile(ticks, 50) * 1e3 if ticks else None
