"""Device idle time inside the engine's `tds.tick` span, per tick: what the host's
share of a tick costs the chip.  The run prints the longest gaps, each by the
innermost `tds.tick.*` span that covers it."""

UNIT = "ms/tick"
BETTER = "lower"
LAYER = "serving scheduler"
MOVES = "tpot_p95_ms"
SOURCE = "device_trace"


def read(ctx):
    from benchmarks.reduce import spans
    r = spans.of(ctx)
    if r is None or "tds.tick" not in r.idle_in_s:
        return None
    return r.idle_in_s["tds.tick"] / max(r.units, 1) * 1e3
