"""Seconds inside XLA's backend compile call during set-up, as jax.monitoring
times it; with a warm persistent cache this is the time to load programs."""

UNIT = "s"
BETTER = "lower"
LAYER = "entry / start-up"
MOVES = "setup_s"
SOURCE = "program_span"


def read(ctx):
    return ctx.env.monitor.compile_s['setup']
