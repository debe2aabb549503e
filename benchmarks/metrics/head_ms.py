"""Device self time per step under `tds.head`, forward and backward: final norm,
head matmul and loss, fused or not, sharded or not."""

UNIT = "ms/step"
BETTER = "lower"
LAYER = "kernels (train)"
MOVES = "tokens_per_s_chip"
SOURCE = "device_trace"


def read(ctx):
    from benchmarks.reduce import spans
    r = spans.of(ctx)
    return None if r is None else r.per_unit_ms(r.head_s)
