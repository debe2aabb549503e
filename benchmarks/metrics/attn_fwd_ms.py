"""Device self time per step under `tds.attn.kernel` outside the backward: every
run of the forward attention kernel, the recomputed one too."""

UNIT = "ms/step"
BETTER = "lower"
LAYER = "kernels (train)"
MOVES = "tokens_per_s_chip"
SOURCE = "device_trace"


def read(ctx):
    from benchmarks.reduce import spans
    r = spans.of(ctx)
    return None if r is None else r.per_unit_ms(r.attn_s["forward"])
