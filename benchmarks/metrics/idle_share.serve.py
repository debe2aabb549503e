"""Share of the traced window in which no operation ran on the device:
1 - union of operation intervals / window, mean over chips."""

UNIT = "%"
BETTER = "lower"
LAYER = "device"
MOVES = "tpot_p95_ms"
SOURCE = "device_trace"


def read(ctx):
    return None if ctx.trace is None else 100.0 * ctx.trace.idle_share
