"""Device time per admission of EVA attention in the prefill program: what
lies under `tds.attn.kernel` and `tds.attn.summary` there (the FA2 forward
kernel over the windows, the summary term and its merge, the pooling)."""

UNIT = "ms/admission"
BETTER = "lower"
LAYER = "kernels (serve)"
MOVES = "tpot_p95_ms"
SOURCE = "device_trace"


def read(ctx):
    from benchmarks import evabyte_arith as ea
    from benchmarks.reduce import spans
    path, r = ea.trace_path(ctx), spans.of(ctx)
    if path is None or r is None:
        return None
    runs = r.program_runs.get("jit_tds_prefill")
    parts = [ea.scope_seconds(path, "jit_tds_prefill", s)
             for s in ("tds.attn.kernel", "tds.attn.summary")]
    if not runs or all(p is None for p in parts):
        return None
    return sum(p or 0.0 for p in parts) / runs * 1e3
