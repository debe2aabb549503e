"""Device time per tick of the EVA decode kernel, by kernel name
(`tds_eva_paged_attn`): window rows, summaries and the byte itself under one
softmax, every layer."""

UNIT = "ms/tick"
BETTER = "lower"
LAYER = "kernels (serve)"
MOVES = "tpot_p95_ms"
SOURCE = "device_trace"


def read(ctx):
    from benchmarks import evabyte_arith as ea
    path = ea.trace_path(ctx)
    if path is None or ctx.trace is None or not ctx.trace.units:
        return None
    s = ea.kernel_seconds(path)
    return None if s is None else s / ctx.trace.units * 1e3
