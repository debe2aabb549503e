# Copyright 2026 tiny-deepspeed-tpu authors
# SPDX-License-Identifier: Apache-2.0

"""Kernel-target resolution for backend-gated dispatch sites.

Pallas kernels (flash attention, fused layernorm, fused AdamW) are chosen
at TRACE time — tracers carry no device, so the gates historically read
`jax.default_backend()`.  That breaks ahead-of-time compilation against a
compile-only TPU topology (scripts/aot_topology.py,
tests/test_aot_topology.py): the process backend is CPU while the program
targets TPU, so every gate silently picked the XLA fallback and the
"TPU-compiled" programs differed from what the chip actually runs —
discovered in round 4 when the AOT memory numbers disagreed with the
measured chip runs (BASELINE.md 124m note).

`force_kernel_target("tpu")` pins the choice for subsequent traces;
`kernel_target()` is what the gates consult.  The default (None) preserves
the old behavior exactly: the process backend decides.
"""

from __future__ import annotations

from contextlib import contextmanager
from typing import Dict, List, Optional

import jax

_FORCED: Optional[str] = None


def force_kernel_target(platform: Optional[str]) -> None:
    """Pin trace-time kernel dispatch to `platform` ("tpu", "cpu", or None
    to restore backend-driven choice).  Affects programs traced AFTER the
    call — already-jitted executables keep their baked choice."""
    global _FORCED
    _FORCED = platform


def kernel_target() -> str:
    """The platform kernel gates should target: the forced override if one
    is set, else the process default backend."""
    return _FORCED or jax.default_backend()


@contextmanager
def kernel_target_forced(platform: Optional[str]):
    """Scoped force_kernel_target — restores the previous override."""
    prev = _FORCED
    force_kernel_target(platform)
    try:
        yield
    finally:
        force_kernel_target(prev)


# --- what the gates chose ---------------------------------------------------
# Each dispatch gate (attention, layernorm, paged attention, loss head)
# notes the implementation it bakes into the program being traced, so a
# run can print what it actually ran instead of what a knob asked for
# (chip_smoke.py asserts "pallas" at every gate on the chip, and prints the
# XLA layernorm under the GSPMD region so it is not read as a fallback).
# Trace-time only: a cached executable re-run notes nothing.

_NOTED: Dict[str, set] = {}


def note_kernel(gate: str, impl: str) -> None:
    _NOTED.setdefault(gate, set()).add(impl)


def impl_label(fn) -> str:
    """"pallas:<name>" for a function from a *_pallas / flash_fa2 kernel
    module, "xla:<name>" otherwise."""
    mod = getattr(fn, "__module__", "") or ""
    kind = "pallas" if ("pallas" in mod or "flash_fa2" in mod) else "xla"
    return f"{kind}:{getattr(fn, '__name__', fn)}"


def kernels_noted(clear: bool = False) -> Dict[str, List[str]]:
    """{gate: sorted implementations traced since the last clear}."""
    out = {g: sorted(v) for g, v in _NOTED.items()}
    if clear:
        _NOTED.clear()
    return out


# --- GSPMD auto-partitioned region -----------------------------------------
# Mosaic (Pallas) custom calls cannot be auto-partitioned by GSPMD: on a
# multi-device mesh they must sit under a fully-manual shard_map or XLA
# refuses to lower ("Mosaic kernels cannot be automatically partitioned").
# Attention handles itself (ops/attention.py wraps its kernel in shard_map
# per parallel mode); the layernorm sites are called naked inside the
# model, so the ENGINE brackets its step/eval traces with this region and
# the layernorm gate falls back to the XLA path whenever it is active.
# Found in round 4: the first-ever multi-device TPU compile (AOT topology)
# hit the lowering error — a bug that would have fired on real multi-chip
# hardware too (single chip and the CPU mesh never exercise the
# combination: one device needs no partitioning, CPU picks XLA anyway).
#
# The bracket is deliberately engine-wide, INCLUDING the pipeline's
# shard_map bodies: those are manual only over {pipe, seq}, and XLA
# rejects a Mosaic call whenever ANY axis stays auto — measured on the
# topology: even a pipe-only mesh (every other axis size 1) fails with
# the same error, because the size-1 "data" axis still counts as auto.
# Refining the gate for a hypothetically fully-manual region can wait
# until such a region exists.

_GSPMD_AUTO = False


def in_gspmd_auto_region() -> bool:
    return _GSPMD_AUTO


@contextmanager
def gspmd_auto_region(active: bool):
    """Mark (at trace time) that the enclosed computation is GSPMD-auto
    partitioned over a multi-device mesh."""
    global _GSPMD_AUTO
    prev = _GSPMD_AUTO
    _GSPMD_AUTO = bool(active)
    try:
        yield
    finally:
        _GSPMD_AUTO = prev
