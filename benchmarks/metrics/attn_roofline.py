"""The FA2 attention kernels' share of their roofline: the least time the chip
could take for causal attention forward + backward (flops.py: the larger of
operations / bf16 peak and bytes / HBM peak) over the kernels' time in the
trace.  A recomputed forward (remat) is in the time and not in the need."""

UNIT = "%"
BETTER = "higher"
LAYER = "kernels (train)"
MOVES = "tokens_per_s_chip"
SOURCE = "device_trace"


def read(ctx):
    from benchmarks import flops
    t = ctx.trace
    if t is None or ctx.peaks is None or not t.units:
        return None
    spent = t.buckets_s.get("attn_kernels")
    if not spent:
        return None
    seqs = ctx.host["batch"] / ctx.cell.chips * t.units
    seq = ctx.host["seq_len"]
    need = max(
        flops.attention_flops_per_seq(ctx.sizes, seq, backward=True)
        / ctx.peaks["bf16_flops_per_s"],
        flops.attention_bytes_per_seq(ctx.sizes, seq)
        / ctx.peaks["hbm_bytes_per_s"]) * seqs
    return 100.0 * need / spent
