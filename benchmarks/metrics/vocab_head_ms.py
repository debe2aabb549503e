"""Device time per step of operations shaped by the vocabulary: the lm_head
matmul, the cross-entropy and the embedding gather (trace bucket
`vocab_head`)."""

UNIT = "ms/step"
BETTER = "lower"
LAYER = "kernels (train)"
MOVES = "tokens_per_s_chip"
SOURCE = "device_trace"


def read(ctx):
    return None if ctx.trace is None else ctx.trace.per_unit_ms('vocab_head')
