"""Bytes of the train state (parameters, moments) on the fullest chip, from
the arrays' own shards: what rests between steps."""

UNIT = "GiB"
BETTER = "lower"
LAYER = "engine step"
MOVES = "peak_hbm_gib"
SOURCE = "program_counter"


def read(ctx):
    b = ctx.host.get("resting_bytes")
    return None if b is None else b / 2**30
