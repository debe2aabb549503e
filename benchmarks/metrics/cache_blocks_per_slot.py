"""Blocks a live slot holds, window and summary together, mean over the traced
ticks' active slots (counted by the program: `tds.tick.roll`).  A slot of
full K/V would hold its length / 16."""

UNIT = "blocks"
BETTER = "lower"
LAYER = "serving scheduler"
MOVES = "tpot_p95_ms"
SOURCE = "program_counter"


def read(ctx):
    from benchmarks import evabyte_arith as ea
    path = ea.trace_path(ctx)
    n = None if path is None else ea.tick_counters(path)
    if n is None or not n["active"]:
        return None
    return (n["window_blocks"] + n["summary_blocks"]) / n["active"]
