"""Collective time per step that carries gradients (under `tds.grad_sync`, or a
reduction GSPMD placed: all-reduce, reduce-scatter), both trace lines, mean over chips."""

UNIT = "ms/step"
BETTER = "lower"
LAYER = "collectives"
MOVES = "tokens_per_s_chip"
SOURCE = "device_trace"


def read(ctx):
    from benchmarks.reduce import spans
    r = spans.of(ctx)
    if r is None or r.chips < 2:
        return None
    return r.per_unit_ms(r.coll_s["grad"])
