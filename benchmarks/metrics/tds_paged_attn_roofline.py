"""The paged decode attention kernel's (`tds_paged_attn`) share of its
roofline, where the slot layout counts the rows a tick attends by kind of
block.  It is bound by bytes: the need is K and V of the rows the traced
ticks attended, by kind (`rows_global`, `rows_window` on `tds.tick.route`,
counted by the program) times that kind's row in all its layers, over the
HBM peak; the operations over the bf16 peak are less and the larger of the
two is taken.  Over the kernel's time in the trace."""

UNIT = "%"
BETTER = "higher"
LAYER = "kernels (serve)"
MOVES = "tpot_p95_ms"
SOURCE = "device_trace"


def read(ctx):
    from benchmarks import mimo_arith as ma
    path = ma.trace_path(ctx)
    if path is None or ctx.trace is None or ctx.peaks is None:
        return None
    spent, n = ma.kernel_seconds(path, ma.KERNEL), ma.tick_counters(path)
    if not spent or n is None:
        return None
    cfg = ctx.cell.model_config()
    rows = n["rows_global"], n["rows_window"]
    need = max(
        ma.attention_bytes(*rows, cfg) / ctx.peaks["hbm_bytes_per_s"],
        ma.attention_flops(*rows, cfg) / ctx.peaks["bf16_flops_per_s"])
    return 100.0 * need / spent
