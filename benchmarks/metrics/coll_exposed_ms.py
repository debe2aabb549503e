"""Collective-operation time on a chip during which no compute operation runs
there, per step, mean over chips."""

UNIT = "ms/step"
BETTER = "lower"
LAYER = "collectives"
MOVES = "tokens_per_s_chip"
SOURCE = "device_trace"


def read(ctx):
    t = ctx.trace
    if t is None or not t.units or t.chips < 2:
        return None
    return t.coll_exposed_s / t.units * 1e3
