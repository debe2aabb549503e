"""Device time per tick of the paged-attention kernel, by kernel name."""

UNIT = "ms/tick"
BETTER = "lower"
LAYER = "kernels (serve)"
MOVES = "tpot_p95_ms"
SOURCE = "device_trace"


def read(ctx):
    return None if ctx.trace is None else ctx.trace.per_unit_ms('paged_attn')
