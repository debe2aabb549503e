"""Device self time per step under `tds.optim`: gradient scaling and clipping and
the optimizer's update."""

UNIT = "ms/step"
BETTER = "lower"
LAYER = "engine step"
MOVES = "tokens_per_s_chip"
SOURCE = "device_trace"


def read(ctx):
    from benchmarks.reduce import spans
    r = spans.of(ctx)
    return None if r is None else r.per_unit_ms(r.phases_s["optimizer"])
