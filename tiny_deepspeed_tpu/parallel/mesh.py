# Copyright 2026 tiny-deepspeed-tpu authors
# SPDX-License-Identifier: Apache-2.0

"""Device mesh construction + multi-host initialization.

Replaces the reference's process-group bring-up
(`dist.init_process_group('nccl', init_method='env://')`, reference
example/ddp/train.py:19, torchrun rendezvous) with the TPU equivalents:

  * `init_distributed()` — `jax.distributed.initialize()` when running
    multi-host (a no-op on one host).  The reference is single-node only
    (README.md:70 TODO "multi-node"); this framework is multi-host-safe from
    the start: the same mesh code spans ICI within a slice and DCN across
    slices.
  * `make_mesh(axis_names=..., shape=...)` — a `jax.sharding.Mesh` over all
    visible devices.  Axis convention:
        "data"  — batch / ZeRO sharding axis (always present)
        "model" — tensor-parallel axis (optional)
        "seq"   — sequence/context parallel axis (optional, ring attention)
    Collectives ride ICI because mesh axes are laid out over the physical
    device order jax exposes.
"""

from __future__ import annotations

import os
from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def init_distributed(**kwargs) -> None:
    """Multi-host bring-up.  Safe to call unconditionally, BEFORE any other
    JAX backend use (like the reference calls init_process_group first,
    ddp/train.py:19 — torchrun env:// rendezvous becomes
    jax.distributed.initialize auto-configuration on Cloud TPU).

    Single-host runs skip initialization — jax.distributed.initialize
    would otherwise block waiting for a coordinator.  "Single host" is
    decided by COUNT, not spelling: the Cloud TPU runtime sets
    TPU_WORKER_HOSTNAMES on one-host machines too (a four-chip host
    lists its one own address), so only a list of two or more workers,
    an explicit JAX_COORDINATOR_ADDRESS, or explicit kwargs mean
    multi-host.
    """
    if jax.distributed.is_initialized():
        return
    workers = [h for h in os.environ.get(
        "TPU_WORKER_HOSTNAMES", "").split(",") if h.strip()]
    if (kwargs or os.environ.get("JAX_COORDINATOR_ADDRESS")
            or len(workers) > 1):
        jax.distributed.initialize(**kwargs)


def make_mesh(
    shape: Optional[Sequence[int]] = None,
    axis_names: Tuple[str, ...] = ("data",),
    devices=None,
) -> Mesh:
    """Mesh over all devices; default one "data" axis spanning everything.

    On a multi-slice/multi-host topology (devices carrying distinct
    slice_index / process_index), the device grid is laid out hybrid: the
    slow DCN network carries the leading "data" axis (gradient reductions
    amortize over the whole step) while every other axis — "model", "seq",
    "expert", "pipe", whose collectives sit on the critical path — stays
    inside a slice on ICI.  The reference is single-node only (its
    README.md:70 TODO "multi-node"); here the same mesh code spans both.
    """
    devices = list(devices if devices is not None else jax.devices())
    if shape is None:
        shape = (len(devices),) + (1,) * (len(axis_names) - 1)
    shape = tuple(int(s) for s in shape)
    if int(np.prod(shape)) != len(devices):
        raise ValueError(
            f"mesh shape {shape} != device count {len(devices)}"
        )
    grid = _device_grid(shape, axis_names, devices)
    return Mesh(grid, axis_names)


def _n_granules(devices) -> Tuple[int, str]:
    """(number of DCN granules, granule attr name) for these devices.

    Granules must be equal-sized for a hybrid layout (mesh_utils builds one
    ICI mesh per granule); uneven subsets report 1 so callers fall back to
    the flat reshape.  A UNIFORM slice_index means all devices share one ICI
    domain — report 1 granule immediately rather than falling through to
    process_index, which would wrongly treat ICI-connected hosts of a
    single-slice pod as DCN granules (ADVICE r1)."""
    from collections import Counter

    for attr in ("slice_index", "process_index"):
        if hasattr(devices[0], attr):
            counts = Counter(getattr(d, attr) for d in devices)
            if len(counts) > 1 and len(set(counts.values())) == 1:
                return len(counts), attr
            if attr == "slice_index" and len(counts) == 1:
                return 1, ""
    return 1, ""


def _device_grid(shape, axis_names, devices) -> np.ndarray:
    """Device ndarray for Mesh: hybrid ICI x DCN when the devices span
    multiple slices/processes and the data axis can absorb them; plain
    reshape (single-granule, or indivisible data axis) otherwise."""
    n_gran, attr = _n_granules(devices)
    data_ix = axis_names.index("data") if "data" in axis_names else 0
    if n_gran > 1 and shape[data_ix] % n_gran == 0:
        from jax.experimental import mesh_utils

        ici = list(shape)
        dcn = [1] * len(shape)
        ici[data_ix] = shape[data_ix] // n_gran
        dcn[data_ix] = n_gran
        try:
            return mesh_utils.create_hybrid_device_mesh(
                ici, dcn, devices,
                process_is_granule=(attr == "process_index"),
            )
        except Exception:
            # some topologies cannot realize the per-granule ICI shape;
            # a flat reshape still yields a working (if suboptimal) mesh
            # rather than failing mesh construction outright (ADVICE r1)
            pass
    return np.asarray(devices).reshape(shape)


import dataclasses
from typing import Optional as _Optional


@dataclasses.dataclass(frozen=True)
class ParallelContext:
    """How a model forward should lay activations on the mesh.

    The reference has no equivalent — its modes only vary backward-hook
    collectives.  Here the context carries the mesh and axis names so the
    model can (a) run Pallas kernels per-shard under shard_map (XLA cannot
    auto-partition custom calls) and (b) shard the sequence axis for
    ring-attention context parallelism.
    """

    mesh: Mesh
    data_axis: str = "data"
    seq_axis: _Optional[str] = None
    model_axis: _Optional[str] = None
    expert_axis: _Optional[str] = None
    pipe_axis: _Optional[str] = None
    pipe_microbatches: int = 0
    # sequence-parallel attention mechanism: "ring" (ppermute K/V rotation,
    # O(T/n) memory — parallel/ring_attention.py) or "ulysses" (all-to-all
    # head/sequence reshard, DeepSpeed-Ulysses — parallel/ulysses.py)
    seq_impl: str = "ring"
    # {stacked leaf name: in-scan PartitionSpec} — the tensor/expert
    # placements of each per-layer block weight AFTER the leading layer
    # axis is sliced off.  Consumed by the fp8 gather path (_bw): the
    # constraint pins the pre-dequant f8 tensor to its gathered layout so
    # GSPMD moves f8 bytes, not the dequantized f32 (without it the
    # partitioner fuses the dequant multiply shard-side and gathers full
    # precision).  None outside an engine.
    stacked_specs: _Optional[dict] = None
    # ZeRO-3 layer-ahead weight-gather prefetch depth (engine
    # gather_prefetch=).  Informational since the scheduler refactor:
    # the model no longer branches on it — the engine builds the gather
    # slot's executor (parallel/schedule.GatherPrefetchScan or the
    # composed machine) and passes it through model.apply(sched=);
    # kept on the context for introspection/compat.
    gather_prefetch: int = 0
    # hierarchical 2-hop gather: that many consecutive ranks per
    # resting-precision intra-group hop, compute dtype across groups
    # (mirrors grad_comm_groups; needs gather_prefetch >= 2, pure DP)
    gather_groups: _Optional[int] = None
    # {stacked leaf name: in-scan SHARDED PartitionSpec} — each per-layer
    # block weight's resting ZeRO layout after the leading layer axis is
    # sliced off; the prefetched scan's source layout for gathers and the
    # target layout for per-layer dW cotangents (in-loop reduce-scatter)
    stacked_shard_specs: _Optional[dict] = None

    @property
    def is_multi_device(self) -> bool:
        return self.mesh is not None and self.mesh.devices.size > 1

    @property
    def seq_parallel(self) -> bool:
        return self.seq_axis is not None and self.mesh.shape[self.seq_axis] > 1

    @property
    def tensor_parallel(self) -> bool:
        return (
            self.model_axis is not None
            and self.mesh.shape[self.model_axis] > 1
        )

    @property
    def expert_parallel(self) -> bool:
        return (
            self.expert_axis is not None
            and self.mesh.shape[self.expert_axis] > 1
        )

    @property
    def pipe_parallel(self) -> bool:
        return (
            self.pipe_axis is not None and self.mesh.shape[self.pipe_axis] > 1
        )


def granule_map(devices) -> Optional[dict]:
    """{logical device id: DCN granule index} for a device sequence in
    MESH-FLAT order (pass `mesh.devices.flatten()`) — the id space a
    compiled program's replica_groups use, which is what lets
    `utils/hlo_comm.wire_link_split` classify each collective's wire as
    intra-slice (ICI) or cross-slice (DCN).  None when the devices form
    a single granule (one slice / one process — no DCN to cross)."""
    devices = list(devices)
    n_gran, attr = _n_granules(devices)
    if n_gran <= 1:
        return None
    gran_ids = sorted({getattr(d, attr) for d in devices})
    ix = {g: i for i, g in enumerate(gran_ids)}
    return {i: ix[getattr(d, attr)] for i, d in enumerate(devices)}


def granule_geometry(granule_of: Optional[dict], n: int) -> tuple:
    """(n_granules, ici) of a granule map over an n-rank data axis — the
    link hierarchy the DCN-aware "auto" comm sizing keys on
    (parallel/schedule.auto_comm_plan).  A None / empty map is the flat
    single-slice mesh: (1, n).  `ici` is the intra-granule rank count
    when the granules split `n` evenly, else `n` (an uneven map gets no
    2-hop sizing — the schedule-level validators own the loud refusal)."""
    if not granule_of:
        return 1, n
    n_gran = len(set(granule_of.values()))
    if n_gran <= 1 or n % n_gran:
        return max(n_gran, 1), n
    return n_gran, n // n_gran


def mesh_descriptor(mesh: Mesh) -> dict:
    """JSON-safe identity of a mesh's shape: axis names/sizes, device and
    host counts.  Persisted in checkpoint meta sidecars so an elastic
    resume can compare the checkpoint's topology with the current one
    (resilience/elastic.py) and name BOTH in its refusal message."""
    return {
        "axes": {str(k): int(v) for k, v in mesh.shape.items()},
        "n_devices": int(mesh.devices.size),
        "n_processes": int(jax.process_count()),
    }


def describe_mesh(desc: Optional[dict]) -> str:
    """Human-readable one-liner for a mesh_descriptor (or unknown)."""
    if not desc:
        return "<unknown mesh (no checkpoint meta)>"
    axes = "×".join(
        f"{k}={v}" for k, v in desc.get("axes", {}).items()
    ) or "?"
    return f"{axes} ({desc.get('n_devices', '?')} devices)"


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def batch_sharding(mesh: Mesh, axis: str = "data") -> NamedSharding:
    """Shard the leading (batch) dim over the data axis."""
    return NamedSharding(mesh, P(axis))
