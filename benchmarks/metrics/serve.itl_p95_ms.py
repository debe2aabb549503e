"""95th percentile over SINGLE token gaps of measured requests
(`Request.token_lat`, first token left out): PR 22's refused end-to-end
metric, kept so that the staircase of tick lengths stays visible."""

UNIT = "ms"
BETTER = "lower"
LAYER = "serving scheduler"
MOVES = "tpot_p95_ms"
SOURCE = "program_span"


def read(ctx):
    from benchmarks.serve_arith import itl_p95_ms
    measured = ctx.host.get("measured")
    return itl_p95_ms(measured) if measured else None
