"""Collective time per step that brings parameters (under `tds.gather`, or one
GSPMD placed that moves data and reduces nothing: all-gathers, collective-permutes
in flight), both trace lines as `coll_exposed_ms` takes them, mean over chips."""

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
    return r.per_unit_ms(r.coll_s["gather"])
