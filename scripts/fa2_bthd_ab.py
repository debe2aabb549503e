# Copyright 2026 tiny-deepspeed-tpu authors
# SPDX-License-Identifier: Apache-2.0

"""A/B the heads-last FA2 entry vs transpose + standard FA2, on the chip.

The round-4 profile priced the per-layer (B,T,H,Dh)->(B,H,T,Dh) copies at
~8.4 ms of the 95 ms gpt2-124m step; `fa2_flash_attention_bthd` deletes
them by addressing the head axis in the BlockSpec index maps.  Whether
Mosaic turns those head-strided panel DMAs into something competitive is
exactly what this measures (not yet timed on a chip).
Run on a live TPU: prints one JSON line per arm; promote the bthd entry
into the dispatch only if it wins f+b at the 124M shape.
"""

import json
import sys
import time

sys.path.insert(0, ".")

import jax
import jax.numpy as jnp

from tiny_deepspeed_tpu.ops.flash_fa2 import (
    fa2_flash_attention, fa2_flash_attention_bthd)

B, H, T, Dh = 12, 12, 1024, 64
x = jax.random.normal(jax.random.PRNGKey(0), (B, T, H, Dh), jnp.bfloat16)
k = jax.random.normal(jax.random.PRNGKey(1), (B, T, H, Dh), jnp.bfloat16)
v = jax.random.normal(jax.random.PRNGKey(2), (B, T, H, Dh), jnp.bfloat16)


def loss_transpose(q, k, v):
    o = fa2_flash_attention(q.swapaxes(1, 2), k.swapaxes(1, 2),
                            v.swapaxes(1, 2), 512, 512)
    # back-transpose o so this arm pays ALL 8 per-layer transposes the
    # real model pays (3 inputs + output, fwd and — via autodiff — bwd);
    # consuming o head-major would hide 2 of them and bias the A/B
    o = o.swapaxes(1, 2)
    return jnp.sum(o.astype(jnp.float32) ** 2)


def loss_bthd(q, k, v):
    o = fa2_flash_attention_bthd(q, k, v, 512, 512)
    return jnp.sum(o.astype(jnp.float32) ** 2)


def timeit(f, n=30):
    g = jax.jit(jax.grad(f, argnums=(0, 1, 2)))
    t0 = time.time()
    r = g(x, k, v)
    float(jnp.sum(r[0].astype(jnp.float32)))
    compile_s = time.time() - t0
    t0 = time.perf_counter()
    for _ in range(n):
        r = g(x, k, v)
    float(jnp.sum(r[0].astype(jnp.float32)))
    return (time.perf_counter() - t0) / n * 1e3, compile_s


for name, fn in [("transpose+fa2", loss_transpose), ("bthd_fa2", loss_bthd)]:
    try:
        ms, compile_s = timeit(fn)
        print(json.dumps({"arm": name, "fb_ms": round(ms, 3),
                          "compile_s": round(compile_s, 1)}), flush=True)
    except Exception as e:  # noqa: BLE001 - report and keep going
        print(json.dumps({"arm": name, "error": repr(e)[:300]}), flush=True)
