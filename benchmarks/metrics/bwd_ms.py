"""Device self time per step of the backward (`transpose(jvp(..))`) with the forward
recomputed inside it (`rematted_computation`); the run prints the two apart."""

UNIT = "ms/step"
BETTER = "lower"
LAYER = "engine step"
MOVES = "tokens_per_s_chip"
SOURCE = "device_trace"


def read(ctx):
    from benchmarks.reduce import spans
    r = spans.of(ctx)
    if r is None:
        return None
    return r.per_unit_ms(r.phases_s["backward"] + r.phases_s["recompute"])
