"""Mean over the window's ticks of active slots / slots, read after each tick."""

UNIT = "%"
BETTER = "higher"
LAYER = "serving scheduler"
MOVES = "tpot_p95_ms"
SOURCE = "program_counter"


def read(ctx):
    occ = ctx.host.get("occupancy")
    return 100.0 * sum(occ) / len(occ) if occ else None
