"""Operations a GPT-2 training step needs, from shapes alone.

Copied in substance from bench.py's `matmul_mfu` arithmetic (6 x the
parameters that sit in matmuls, plus attention), with one correction:
attention is counted as the CAUSAL half.  The FA2 kernel skips the blocks
above the diagonal, so the forward and backward passes need T*T/2 score
entries per head, not T*T; bench.py's 12*L*T*d per token prices the full
square and reads about 9 % high at T=1024, d=768.  Recomputed operations
(remat, the kernel's own recomputation of the scores in its backward pass)
are not counted: these are the operations the mathematics needs.
"""

from __future__ import annotations


def matmul_params(cfg: dict) -> int:
    """Parameters that are multiplied once per token: the blocks' four
    projections and the lm_head.  Embedding tables (wte, wpe) are gathers;
    layernorm weights and biases are elementwise."""
    d, l, v = cfg["n_embd"], cfg["n_layer"], cfg["vocab_held"]
    per_block = d * 3 * d + d * d + d * 4 * d + 4 * d * d
    return l * per_block + d * v


def attention_flops_per_seq(cfg: dict, t: int, *, backward: bool) -> float:
    """Causal attention over one sequence of t tokens, all layers and
    heads.  Forward: QK^T and PV, 2*t*t*Dh multiply-adds each over half
    the square -> 2*t*t*d flops a layer.  Backward: dV, dP, dQ, dK, twice
    the forward."""
    d, l = cfg["n_embd"], cfg["n_layer"]
    fwd = 2.0 * t * t * d * l
    return fwd * (3.0 if backward else 1.0)


def train_flops_per_token(cfg: dict, t: int) -> float:
    """Forward + backward operations per trained token at sequence
    length t (2 flops per multiply-add; backward is twice forward)."""
    return (6.0 * matmul_params(cfg)
            + attention_flops_per_seq(cfg, t, backward=True) / t)


def attention_bytes_per_seq(cfg: dict, t: int, itemsize: int = 2) -> float:
    """HBM bytes the fused attention kernels must move for one sequence,
    forward + backward, all layers: forward reads q, k, v and writes o;
    backward reads q, k, v, o, do and writes dq, dk, dv (the row
    statistics are t floats a head, left out)."""
    d, l = cfg["n_embd"], cfg["n_layer"]
    return float((4 + 8) * t * d * itemsize * l)
