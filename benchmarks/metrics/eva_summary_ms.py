"""Device time per tick under `tds.attn.summary` in the decode program:
gathering the chunk that may close, pooling it, writing its summary row."""

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
    s = ea.scope_seconds(path, "jit_tds_decode", "tds.attn.summary")
    return None if s is None else s / ctx.trace.units * 1e3
