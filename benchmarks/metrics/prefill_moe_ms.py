"""Device time per admission under `tds.moe` in the prefill program, the
grouped products (found by name) among it."""

UNIT = "ms/admission"
BETTER = "lower"
LAYER = "kernels (serve)"
MOVES = "tpot_p95_ms"
SOURCE = "device_trace"


def read(ctx):
    from benchmarks import mimo_arith as ma
    from benchmarks.reduce import spans
    path, r = ma.trace_path(ctx), spans.of(ctx)
    if path is None or r is None:
        return None
    runs = r.program_runs.get("jit_tds_prefill")
    s = ma.moe_seconds(path, "jit_tds_prefill")
    return None if not runs or s is None else s / runs * 1e3
