"""Device time per tick of copy operations (trace bucket `copies`): PR 22's
trace had them at twice the paged-attention kernel."""

UNIT = "ms/tick"
BETTER = "lower"
LAYER = "kernels (serve)"
MOVES = "tpot_p95_ms"
SOURCE = "device_trace"


def read(ctx):
    return None if ctx.trace is None else ctx.trace.per_unit_ms('copies')
