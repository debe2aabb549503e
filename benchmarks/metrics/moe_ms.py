"""Device time per tick under `tds.moe` in the decode program: router, dispatch,
the grouped matrix products over the held experts (found by name: XLA
drops their op_name, `mimo_arith.GROUPED`) and the combine, all expert
layers."""

UNIT = "ms/tick"
BETTER = "lower"
LAYER = "kernels (serve)"
MOVES = "tpot_p95_ms"
SOURCE = "device_trace"


def read(ctx):
    from benchmarks import mimo_arith as ma
    path = ma.trace_path(ctx)
    if path is None or ctx.trace is None or not ctx.trace.units:
        return None
    s = ma.moe_seconds(path, "jit_tds_decode")
    return None if s is None else s / ctx.trace.units * 1e3
