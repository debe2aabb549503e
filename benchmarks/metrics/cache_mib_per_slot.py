"""MiB of cache a live slot holds, both kinds of block, mean over the traced
ticks' active slots (counted by the program: `tds.tick.route`).  The kinds
differ in size (a global block is 2 layers x 4 heads, a ring block 5 x 8),
so a count of blocks says nothing: a slot at position n holds ceil((n + 1)
/ 16) global blocks and the ring's 8."""

UNIT = "MiB"
BETTER = "lower"
LAYER = "serving scheduler"
MOVES = "tpot_p95_ms"
SOURCE = "program_counter"


def read(ctx):
    from benchmarks import mimo_arith as ma
    path = ma.trace_path(ctx)
    n = None if path is None else ma.tick_counters(path)
    if n is None or not n["active"]:
        return None
    cfg, bt = ctx.cell.model_config(), int(ctx.cell.mix["block_tokens"])
    return (n["global_blocks"] * ma.block_mib(0, bt, cfg)
            + n["window_blocks"] * ma.block_mib(1, bt, cfg)) / n["active"]
