"""Device time per tick of the paged decode attention kernel, found by its
name (`tds_paged_attn`) in whatever model runs it: over a table, or over a
ring with a sink.  (`paged_attn_ms` finds the same kernel by the shape of
GPT-2's pool; one reader by name for every cell is a `benchmark` PR's.)"""

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
    s = ma.kernel_seconds(path, ma.KERNEL)
    return None if s is None else s / ctx.trace.units * 1e3
