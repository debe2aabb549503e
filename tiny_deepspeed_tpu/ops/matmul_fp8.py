# Copyright 2026 tiny-deepspeed-tpu authors
# SPDX-License-Identifier: Apache-2.0

"""fp8 (e4m3) matmuls for the big block einsums — QKV/proj/MLP and the
fused-xent head.

Every quantization win so far cut WIRE or CACHE bytes (qwZ fp8 gathers,
int8 grad schedules, int8/fp8 KV blocks) but never FLOPs: the matmuls
themselves still run in compute dtype.  This module quantizes the matmul
OPERANDS so the MXU consumes 1-byte values — the fp8-training design
point — riding the stop-gradient-scale idiom the fp8 weight gather
already proved (models/gpt2.py gather_quant, arXiv:2306.10209): scales
are absmax-derived, `stop_gradient`ed, and the cast edge is
differentiable e4m3, so no straight-through machinery.

Two scaling disciplines:

  * `_fwd_fp8` — the `linear_forward` autotuner CANDIDATE (the new
    entry in ops/linear._CANDIDATES_FWD when the mode enables it):
    per-row (token) scales on x, per-column (output-channel) scales on
    w, computed from the CURRENT tensor ("just-in-time scaling").
    Scales factor exactly out of rows/columns, so the rescale is one
    rank-1 multiply on the f32 accumulator.  Stateless — it drops into
    the existing `linear` custom_vjp (backward stays the exact closed
    form), which is what lets it compose with ZeRO stages, grad accum,
    clipping and loss scaling with no engine changes.
  * `fp8_matmul_delayed` — DELAYED scaling for stateful training loops:
    scales come from a rolling amax HISTORY (`Fp8History`, a pytree the
    caller threads through its step like optimizer state), the
    Transformer-Engine recipe — the current step quantizes against the
    previous steps' maxima (values clipped into e4m3 range when the
    current amax outruns the history), and the history updates with the
    observed amax.  The op-dispatch sites cannot carry state through
    `linear(x, w, b)`, so the candidate path above uses JIT scaling;
    this form exists for loops that want the real delayed recipe and
    for the head (`fused_linear_xent` consumes `fp8_matmul` per chunk).

Mode switch (`set_fp8_matmul`): "off" (default — the trace, and its
HLO, is byte-identical to the pre-fp8 path, pinned in
tests/test_paged_kernel.py), "candidate" (fp8 joins the autotuner
candidate list and wins only if measured faster), "on" (every
`linear_forward` and the fused-xent head's chunk matmuls run fp8).

On non-TPU kernel targets the quantized values upcast to float32 for
the dot (XLA-CPU has no fp8 MXU; the NUMBERS are identical because
quantization already happened at e4m3 — only the multiply width
differs), so parity tests on the CPU mesh exercise the exact arithmetic
the chip sees.
"""

from __future__ import annotations

from contextlib import contextmanager
from typing import NamedTuple

import jax
import jax.numpy as jnp

E4M3_MAX = 448.0
_EPS = 1e-12

FP8_MATMUL_MODES = ("off", "candidate", "on")
_MODE = "off"


def set_fp8_matmul(mode: str) -> None:
    if mode not in FP8_MATMUL_MODES:
        raise ValueError(
            f"fp8_matmul must be one of {FP8_MATMUL_MODES}, got {mode!r}"
        )
    global _MODE
    _MODE = mode


def fp8_matmul_mode() -> str:
    return _MODE


@contextmanager
def fp8_matmul_forced(mode: str):
    prev = _MODE
    set_fp8_matmul(mode)
    try:
        yield
    finally:
        set_fp8_matmul(prev)


def _dot_dtype():
    """Operand dtype for the quantized dot: e4m3 on TPU targets (the
    real 1-byte MXU path), f32 elsewhere — same values either way, the
    e4m3 rounding already happened."""
    from .dispatch import kernel_target
    return jnp.float8_e4m3fn if kernel_target() == "tpu" else jnp.float32


def _quantize(x, amax):
    """Scale x into e4m3 range against `amax` (stop-gradient), cast,
    and return (quantized values in the dot dtype, f32 scale).  The
    clip bounds values that outran a stale (delayed) amax — e4m3 cast
    overflow is backend-defined, saturation is not."""
    scale = jax.lax.stop_gradient(
        amax.astype(jnp.float32) / E4M3_MAX + _EPS
    )
    q = jnp.clip(x.astype(jnp.float32) / scale, -E4M3_MAX, E4M3_MAX)
    return q.astype(jnp.float8_e4m3fn).astype(_dot_dtype()), scale


def fp8_matmul(x, w):
    """y[..., n] = x[..., k] @ w[k, n] with both operands quantized to
    e4m3: per-row (leading-position) scales on x, per-column scales on
    w — JIT scaling.  f32 accumulation and output (callers cast)."""
    qx, sx = _quantize(x, jnp.max(jnp.abs(x), axis=-1, keepdims=True))
    qw, sw = _quantize(w, jnp.max(jnp.abs(w), axis=0, keepdims=True))
    y = jax.lax.dot_general(
        qx, qw,
        dimension_numbers=(((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    return y * sx * sw  # rank-1 rescale on the f32 accumulator


def _fwd_fp8(x, w, b):
    """`linear_forward` candidate: fp8 forward matmul, bias in f32."""
    y = fp8_matmul(x, w).astype(x.dtype)
    if b is not None:
        y = y + b.astype(y.dtype)
    return y


# ---------------------------------------------------------------------------
# delayed scaling
# ---------------------------------------------------------------------------


class Fp8History(NamedTuple):
    """Rolling per-tensor amax histories for one matmul site — the
    delayed-scaling state a training loop threads through its step
    (like optimizer moments).  Row 0 is the most recent step."""

    x_amax: jax.Array  # (H,) f32
    w_amax: jax.Array  # (H,) f32


def fp8_history(length: int = 16) -> Fp8History:
    return Fp8History(jnp.zeros((length,), jnp.float32),
                      jnp.zeros((length,), jnp.float32))


def _delayed_amax(hist, cur):
    """max over the recorded history; a cold (all-zero) history falls
    back to the current amax so step 0 is exact-JIT-scaled rather than
    dividing by epsilon."""
    h = jnp.max(hist)
    return jnp.where(h > 0, h, cur)


def fp8_matmul_delayed(x, w, hist: Fp8History):
    """Delayed-scaling fp8 matmul: quantize against the HISTORY's amax
    (stop-gradient; values clipped into range when the current step
    outruns it), then record this step's observed amax.  Returns
    (y f32, updated Fp8History)."""
    cx = jnp.max(jnp.abs(x)).astype(jnp.float32)
    cw = jnp.max(jnp.abs(w)).astype(jnp.float32)
    qx, sx = _quantize(x, _delayed_amax(hist.x_amax, cx))
    qw, sw = _quantize(w, _delayed_amax(hist.w_amax, cw))
    y = jax.lax.dot_general(
        qx, qw,
        dimension_numbers=(((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * sx * sw
    new = Fp8History(
        jnp.roll(hist.x_amax, 1).at[0].set(cx),
        jnp.roll(hist.w_amax, 1).at[0].set(cw),
    )
    return y, new
