"""Host time a step spends in `loader.next()` + `jnp.asarray`, mean over the
window's steps (the benchmark's own span, `bench.load`)."""

UNIT = "ms/step"
BETTER = "lower"
LAYER = "input"
MOVES = "tokens_per_s_chip"
SOURCE = "host_clock"


def read(ctx):
    waits = ctx.host.get("input_wait_s")
    return sum(waits) / len(waits) * 1e3 if waits else None
