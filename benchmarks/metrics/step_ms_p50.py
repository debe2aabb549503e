"""Median time of one step dispatched and waited for alone (traced run only,
after the window): the step with nothing overlapped."""

UNIT = "ms"
BETTER = "lower"
LAYER = "engine step"
MOVES = "tokens_per_s_chip"
SOURCE = "host_clock"


def read(ctx):
    from benchmarks.serve_arith import percentile
    steps = ctx.host.get("step_sync_s")
    return percentile(steps, 50) * 1e3 if steps else None
