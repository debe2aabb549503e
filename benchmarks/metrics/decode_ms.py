"""Device time of the decode program per tick: the `jit_tds_decode` events of the
trace's `XLA Modules` line (one per run; a tick runs it once)."""

UNIT = "ms/tick"
BETTER = "lower"
LAYER = "kernels (serve)"
MOVES = "tpot_p95_ms"
SOURCE = "device_trace"


def read(ctx):
    from benchmarks.reduce import spans
    r = spans.of(ctx)
    return None if r is None else r.per_unit_ms(
        r.programs_s.get("jit_tds_decode"))
