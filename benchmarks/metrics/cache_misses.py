"""Persistent compile-cache misses during set-up (jax.monitoring): 0 once the
checkout's cache is warm.  Compile requests inside the window are not a
metric: one makes the run incorrect."""

UNIT = "count"
BETTER = "lower"
LAYER = "entry / start-up"
MOVES = "setup_s"
SOURCE = "program_counter"


def read(ctx):
    return float(ctx.env.monitor.misses['setup'])
