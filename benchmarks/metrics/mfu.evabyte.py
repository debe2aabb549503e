"""End-to-end utilization of the traced window, not a roofline share: the
operations EvaByte's mathematics needs for the bytes decoded and the prompts
prefilled in it (evabyte_arith.py; the program counts bytes, attended rows
and prompt lengths on its `tds.tick.*` spans) over the window's length and the
chip's bf16 peak."""

UNIT = "%"
BETTER = "higher"
LAYER = "kernels (serve)"
MOVES = "tpot_p95_ms"
SOURCE = "device_trace"


def read(ctx):
    from benchmarks import evabyte_arith as ea
    path = ea.trace_path(ctx)
    if (path is None or ctx.trace is None or ctx.peaks is None
            or not ctx.trace.window_s):
        return None
    n = ea.tick_counters(path)
    if n is None:
        return None
    cfg = ctx.cell.model_config()
    ops = ea.decode_flops(n["active"], n["rows"], cfg) + sum(
        ea.prefill_flops(p, cfg) for p in ea.prefill_tokens(path))
    return 100.0 * ops / ctx.trace.window_s / ctx.peaks["bf16_flops_per_s"]
