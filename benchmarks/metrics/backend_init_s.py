"""Seconds inside `select_platform` until `jax.default_backend()` has returned
(`utils/startup.marks`): the TPU backend coming up."""

UNIT = "s"
BETTER = "lower"
LAYER = "entry / start-up"
MOVES = "setup_s"
SOURCE = "program_counter"


def read(ctx):
    try:
        from tiny_deepspeed_tpu.utils import startup
    except ImportError:
        return None
    marks = getattr(startup, "marks", None)
    if not marks or "backend_up" not in marks:
        return None
    return marks["backend_up"] - marks["select_platform"]
