"""A metric a later PR might add: found by name, no harness edit."""

UNIT = "count"
BETTER = "higher"
LAYER = "engine step"
MOVES = "tokens_per_s_chip"
SOURCE = "program_counter"


def read(ctx):
    return float(len(ctx.host["input_wait_s"]))
