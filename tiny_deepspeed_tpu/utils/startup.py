# Copyright 2026 tiny-deepspeed-tpu authors
# SPDX-License-Identifier: Apache-2.0

"""Process start-up rules shared by every entry point and chip_smoke.py.

Two decisions an entry point must not make on its own:

  * WHICH DEVICE.  The kernel gates (ops/dispatch.kernel_target) follow
    `jax.default_backend()`, so a process that lands on the CPU silently
    trains on the XLA-CPU paths and prints tokens/s.  `select_platform`
    makes the choice explicit: with a CPU flag (`--cpu-devices N`, `--cpu`)
    it pins the CPU platform; without one it requires the TPU and exits
    non-zero before anything compiles.
  * WHERE COMPILED PROGRAMS REST.  `compile_cache_dir`: where
    JAX_COMPILATION_CACHE_DIR is set, JAX reads it itself and code sets
    nothing; where it is not, the cache is `<checkout>/.jax_cache` — a
    fixed path, because the path is part of the cache key.
"""

from __future__ import annotations

import os
import time

import jax

# Instants on time.monotonic()'s clock, taken before any profiler session
# can exist: `import_begin` / `import_done` (tiny_deepspeed_tpu/__init__.py,
# around the package's own imports), `select_platform` (entered) and
# `backend_up` (jax.default_backend() has returned).  Read by the
# benchmark's `import_s` and `backend_init_s`.
marks = {}

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def compile_cache_dir() -> str:
    """Apply the compile-cache rule; returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(REPO_ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def select_platform(cpu_devices: int = 0, cpu: bool = False,
                    cpu_flag: str = "") -> str:
    """Pin the platform BEFORE the first backend use and apply the
    compile-cache rule.  The entry point's explicit CPU flag arrives as
    `cpu_devices` > 0 (`--cpu-devices N`: that many virtual CPU devices)
    or `cpu` (`--cpu`: the CPU platform, whatever its device count).
    With neither, the TPU is required: SystemExit otherwise, naming
    `cpu_flag` (the caller's flag spelling, if it has one).  Returns the
    backend name."""
    marks["select_platform"] = time.monotonic()
    if cpu_devices or cpu:
        jax.config.update("jax_platforms", "cpu")
    if cpu_devices:
        jax.config.update("jax_num_cpu_devices", int(cpu_devices))
    compile_cache_dir()
    backend = jax.default_backend()
    marks["backend_up"] = time.monotonic()
    if not (cpu_devices or cpu) and backend != "tpu":
        raise SystemExit(
            f"no TPU: jax.default_backend() is {backend!r} "
            f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS', '')!r}); "
            "this entry point runs on the chip"
            + (f" unless {cpu_flag} asks for the CPU" if cpu_flag else "")
        )
    return backend
