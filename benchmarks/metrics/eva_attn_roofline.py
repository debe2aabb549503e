"""The EVA decode kernel's share of its roofline.  The kernel is bound by
bytes: its need is the K and V rows of the live window rows and the visible
summaries the traced ticks attended (counted by the program: `rows` on
`tds.tick.roll`), queries in and results out, over the HBM peak; its
operations over the bf16 peak are a hundredth of that and the larger of the
two is taken.  Over the kernel's time in the trace."""

UNIT = "%"
BETTER = "higher"
LAYER = "kernels (serve)"
MOVES = "tpot_p95_ms"
SOURCE = "device_trace"


def read(ctx):
    from benchmarks import evabyte_arith as ea
    path = ea.trace_path(ctx)
    if path is None or ctx.trace is None or ctx.peaks is None:
        return None
    spent, n = ea.kernel_seconds(path), ea.tick_counters(path)
    if not spent or n is None:
        return None
    cfg = ctx.cell.model_config()
    slots = int(ctx.cell.sizes["slots"]) * n["ticks"]
    need = max(
        ea.kernel_bytes(n["rows"], slots, cfg)
        / ctx.peaks["hbm_bytes_per_s"],
        ea.attention_flops(n["rows"] + n["active"], cfg)
        / ctx.peaks["bf16_flops_per_s"])
    return 100.0 * need / spent
