"""End-to-end utilization of the traced window, not a roofline share: the
operations this chip's share of the model needs for the tokens decoded and
the prompts prefilled in it (mimo_arith.py; the program counts tokens,
attended rows, routed pairs and prompt lengths on its `tds.tick.*` spans)
over the window's length and the chip's bf16 peak."""

UNIT = "%"
BETTER = "higher"
LAYER = "kernels (serve)"
MOVES = "tpot_p95_ms"
SOURCE = "device_trace"


def read(ctx):
    from benchmarks import mimo_arith as ma
    path = ma.trace_path(ctx)
    if (path is None or ctx.trace is None or ctx.peaks is None
            or not ctx.trace.window_s):
        return None
    n = ma.tick_counters(path)
    if n is None:
        return None
    cfg = ctx.cell.model_config()
    ops = ma.decode_flops(n["active"], n["pairs"], n["rows_global"],
                          n["rows_window"], cfg) + sum(
        ma.prefill_flops(p, cfg) for p in ma.prefill_tokens(path))
    return 100.0 * ops / ctx.trace.window_s / ctx.peaks["bf16_flops_per_s"]
