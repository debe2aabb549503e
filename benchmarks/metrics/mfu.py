"""End-to-end utilization, not a roofline share: operations the forward and
backward passes need per token (flops.py; recomputation not counted) times
the measured tokens/s/chip over the chip's bf16 peak (peaks.json)."""

UNIT = "%"
BETTER = "higher"
LAYER = "engine step"
MOVES = "tokens_per_s_chip"
SOURCE = "host_clock"


def read(ctx):
    from benchmarks import flops
    rate = ctx.host.get("tokens_per_s_chip")
    if rate is None or ctx.peaks is None:
        return None
    per_token = flops.train_flops_per_token(ctx.sizes, ctx.host["seq_len"])
    return 100.0 * per_token * rate / ctx.peaks["bf16_flops_per_s"]
