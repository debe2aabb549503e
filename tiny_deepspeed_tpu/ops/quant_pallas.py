# Copyright 2026 tiny-deepspeed-tpu authors
# SPDX-License-Identifier: Apache-2.0

"""Pallas blockwise gradient quantizer (TPU) — the optional kernel behind
the grad_comm quant primitives (parallel/comm.py).

The XLA formulation (reshape -> absmax -> divide -> round -> cast) is
already fusable, but it round-trips the (nb, block) f32 panel through HBM
between the reduce and the elementwise tail on large gradients.  This
kernel does absmax/scale/dither/round/cast in one VMEM pass per row
panel: 8 scale-blocks (8 x block f32 = 8 KB at block=256) per grid step,
emitting the 1-byte codes and the (rows, 1) scales directly.

Stochastic rounding takes the uniform dither as an OPERAND (drawn with
jax.random by the caller) rather than the on-core PRNG, so the parity
tests (tests/test_grad_comm.py), which run the kernel in interpret mode
on the CPU mesh like every other kernel here, round with the same
dither the XLA formulation does.  The extra operand is one f32 read of
the gradient's size — the win this kernel chases is the fused
reduce+quantize pass, not the dither bytes.

Dispatched from `comm.quantize_blockwise` behind the standard trace-time
gate (`ops.dispatch.kernel_target() == "tpu"`); inside the grad_comm
shard_map every mesh axis is manual (the engine enforces a pure
data-parallel mesh), so the Mosaic call is legal where it runs.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_INTERPRET = False  # tests flip this on CPU (no Mosaic backend there)

_QMAX = {"int8": 127.0, "fp8": 448.0}
_QDTYPE = {"int8": jnp.int8, "fp8": jnp.float8_e4m3fn}


def _quant_kernel(*refs, qmax, is_int8, has_dither):
    if has_dither:
        x_ref, d_ref, q_ref, s_ref = refs
    else:
        x_ref, q_ref, s_ref = refs
    x = x_ref[...].astype(jnp.float32)              # (rows, block)
    s = jnp.max(jnp.abs(x), axis=1, keepdims=True) / qmax + 1e-12
    y = x / s
    if has_dither:
        y = y + d_ref[...]
    if is_int8:
        q_ref[...] = jnp.clip(jnp.round(y), -127.0, 127.0).astype(jnp.int8)
    else:
        q_ref[...] = y.astype(jnp.float8_e4m3fn)
    s_ref[...] = s


def pallas_quantize_blockwise(x, mode: str, block: int = 256, dither=None):
    """Flat f32 (len % block == 0) -> (q flat, (nb, 1) f32 scales); same
    contract as the XLA path in comm.quantize_blockwise.  `dither`: flat
    uniform(-1/2, 1/2) f32 of x's length for stochastic rounding (int8),
    or None for round-to-nearest."""
    nb = x.shape[0] // block
    xb = x.reshape(nb, block)
    rows = 8 if nb % 8 == 0 else 1                  # sublane-aligned panel
    args = [xb]
    if dither is not None:
        args.append(dither.reshape(nb, block))
    panel = pl.BlockSpec((rows, block), lambda i: (i, 0))
    q, s = pl.pallas_call(
        functools.partial(
            _quant_kernel, qmax=_QMAX[mode], is_int8=mode == "int8",
            has_dither=dither is not None,
        ),
        grid=(nb // rows,),
        in_specs=[panel] * len(args),
        out_specs=[panel, pl.BlockSpec((rows, 1), lambda i: (i, 0))],
        out_shape=[
            jax.ShapeDtypeStruct((nb, block), _QDTYPE[mode]),
            jax.ShapeDtypeStruct((nb, 1), jnp.float32),
        ],
        interpret=_INTERPRET,
        name="tds_quant",
    )(*args)
    return q.reshape(-1), s
