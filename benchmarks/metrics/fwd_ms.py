"""Device self time per step of the train program's forward operations (op_names
with no `transpose(`, no `rematted_computation`, no `tds.optim`)."""

UNIT = "ms/step"
BETTER = "lower"
LAYER = "engine step"
MOVES = "tokens_per_s_chip"
SOURCE = "device_trace"


def read(ctx):
    from benchmarks.reduce import spans
    r = spans.of(ctx)
    return None if r is None else r.per_unit_ms(r.phases_s["forward"])
