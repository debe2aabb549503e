"""Tokens a held expert's matrix product sees a decode tick: the (token,
expert) pairs the decode program computed over the held experts of all
expert layers and the traced ticks.  In the deployment an expert sees the
tokens of all 16 chips: slots / 2 where this cell's sees slots / 32."""

UNIT = "tokens"
BETTER = "higher"
LAYER = "kernels (serve)"
MOVES = "tpot_p95_ms"
SOURCE = "program_counter"


def read(ctx):
    from benchmarks import mimo_arith as ma
    path = ma.trace_path(ctx)
    n = None if path is None else ma.tick_counters(path)
    if n is None or not n["ticks"]:
        return None
    cfg = ctx.cell.model_config()
    return n["pairs"] / (ma.layers(cfg)["moe"] * cfg.experts_held
                         * n["ticks"])
