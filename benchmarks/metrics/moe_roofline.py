"""The grouped expert products' share of their roofline at decode.  They are
bound by bytes: the need is the weights of the experts that HAD a token in
the traced ticks (`experts_touched`, counted by the decode program itself,
times an expert's three matrices in bf16) over the HBM peak, so an
implementation that reads all held experts reads lower and none reads over
100; over the time of the grouped products (by name, `mimo_arith.GROUPED`:
XLA drops their op_name) and of what else lies under `tds.moe.experts` in
the decode program."""

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
    spent = ma.moe_seconds(path, "jit_tds_decode", "tds.moe.experts")
    n = ma.tick_counters(path)
    if not spent or n is None:
        return None
    need = ma.experts_bytes(n["experts_touched"], ctx.cell.model_config())
    return 100.0 * need / ctx.peaks["hbm_bytes_per_s"] / spent
