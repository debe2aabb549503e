"""Seconds from the start of the process to the program's package being imported
(`utils/startup.marks["import_done"]`): the interpreter, JAX and the package's
own imports, before any backend exists."""

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
    if not marks or "import_done" not in marks:
        return None
    return marks["import_done"] - ctx.env.t_process
