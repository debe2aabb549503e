# Copyright 2026 tiny-deepspeed-tpu authors
# SPDX-License-Identifier: Apache-2.0

"""Tiny-DeepSpeed-TPU: a TPU-native re-design of Tiny-DeepSpeed's ZeRO stack.

A brand-new framework (JAX / XLA / pjit / Pallas) providing the capabilities of
the reference liangyuwang/Tiny-DeepSpeed (CUDA/torch, see /root/reference):
single-device, DDP, ZeRO-1, ZeRO-2 and ZeRO-3 training of GPT-2 models, a
custom op layer with swappable kernels and a runtime autotuner, a name-ordered
greedy parameter partitioner ("cache rank map"), and name-keyed SGD/AdamW
optimizers — all re-expressed TPU-first:

  * collectives are XLA collectives over a `jax.sharding.Mesh` (psum /
    reduce_scatter / all_gather over ICI), not NCCL calls in backward hooks
    (reference: tiny_deepspeed/core/zero/ddp/module.py:17-24);
  * compute/communication overlap comes from XLA's latency-hiding scheduler,
    not hand-written async handles (reference: ddp/module.py:36-78);
  * the hot fused kernels are Pallas (reference: Triton layernorm,
    ops/layernorm.py:158-298);
  * meta-device init + cache rank map (reference: zero/utils/partition.py)
    becomes `jax.eval_shape` + NamedSharding placement, so parameters are
    *created* sharded instead of materialized fully then sharded.

Public API shape mirrors the reference's flat surface
(`tiny_deepspeed/core/__init__.py:5-23`):

    from tiny_deepspeed_tpu import (
        DDP, Zero1, Zero2, Zero3, partition_tensors,
        SGD, AdamW, GPTConfig, GPT2Model,
    )
"""

import time as _time

_import_begin = _time.monotonic()

from .parallel.partition import partition_tensors, materialize_owned
from .parallel.engine import SingleDevice, DDP, Zero1, Zero2, Zero3
from .parallel.mesh import make_mesh, init_distributed
from .optim import SGD, AdamW, schedule
from .models import (
    GPTConfig, GPT2Model, MoEConfig, MoEGPT, LlamaConfig, LlamaModel,
)
from .telemetry import Telemetry
from .utils import startup as _startup

_startup.marks["import_begin"] = _import_begin
_startup.marks["import_done"] = _time.monotonic()

# Reference-shaped optimizer names (reference core/__init__.py:5-23 exports
# DDPSGD/DDPAdamW/Zero{1,2,3}SGD/Zero{1,2,3}AdamW — one subclass per mode
# because each mode re-derives the step/broadcast logic).  Here the ZeRO
# stage lives entirely in the ENGINE (sharding strategy), so every "mode
# optimizer" IS the base optimizer; the aliases keep the reference's import
# surface working verbatim:  `Zero2(model, Zero2AdamW(lr=...))`.
DDPSGD = Zero1SGD = Zero2SGD = Zero3SGD = SGD
DDPAdamW = Zero1AdamW = Zero2AdamW = Zero3AdamW = AdamW

__version__ = "0.4.0"

__all__ = [
    "partition_tensors",
    "materialize_owned",
    "SingleDevice",
    "DDP",
    "Zero1",
    "Zero2",
    "Zero3",
    "make_mesh",
    "init_distributed",
    "SGD",
    "AdamW",
    "schedule",
    "DDPSGD", "DDPAdamW",
    "Zero1SGD", "Zero1AdamW",
    "Zero2SGD", "Zero2AdamW",
    "Zero3SGD", "Zero3AdamW",
    "GPTConfig",
    "GPT2Model",
    "MoEConfig",
    "MoEGPT",
    "LlamaConfig",
    "LlamaModel",
    "Telemetry",
]
