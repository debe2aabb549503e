# Copyright 2026 tiny-deepspeed-tpu authors
# SPDX-License-Identifier: Apache-2.0

"""The paged KV pool rests as (blocks, block_tokens, L * KVH * Dh) and
no program ever reshapes it (serving/pool.py's one shape rule).

  * a real `ServingEngine` trace through every program that takes the
    pool view — prefill, decode, shared-prefix suffix prefill, warm
    restart; speculative verify and its span commit; export -> import
    into a second engine — gives the tokens of the contiguous-cache
    reference (`generate`), for GPT-2 and Llama-GQA, resting f32 / int8 /
    fp8, through the Pallas kernel (interpret mode) and the XLA panel;
  * the jaxprs of `tds_decode` and `tds_prefill` hold no `reshape`,
    `transpose` or `convert_element_type` of anything with the pool's
    element count — the part of "no whole-pool copy" that needs no chip
    (tests/test_aot_topology.py compiles both for the v5e, slow tier).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tiny_deepspeed_tpu.ops.paged_attn_pallas as PAP
from tiny_deepspeed_tpu import GPTConfig, GPT2Model
from tiny_deepspeed_tpu.models.llama import LlamaConfig, LlamaModel
from tiny_deepspeed_tpu.serving import ServeConfig, ServingEngine
from tiny_deepspeed_tpu.serving.pool import pool_shape

_SIZES = dict(block_size=64, vocab_size=128, n_layer=2, n_embd=32,
              compute_dtype=jnp.float32)
_FAMILIES = {
    "gpt2": lambda: GPT2Model(GPTConfig(n_head=2, **_SIZES)),
    "llama-gqa": lambda: LlamaModel(LlamaConfig(n_head=4, n_kv_head=2,
                                                **_SIZES)),
}
_NEW = 8  # tokens generated per request
# a quantized cache tracks the full-precision reference, it does not
# repeat it (tests/test_serving*.py hold the same line)
_QUANT_AGREEMENT = 0.6


@pytest.fixture(autouse=True)
def interpret_mode(monkeypatch):
    monkeypatch.setattr(PAP, "INTERPRET", True)


@pytest.fixture(scope="module")
def families():
    out = {}
    for name, make in _FAMILIES.items():
        model = make()
        out[name] = (model, model.init(jax.random.PRNGKey(0)))
    return out


def _prompt(seed, n):
    return np.asarray(jax.random.randint(
        jax.random.PRNGKey(seed), (n,), 0, 128), np.int32).tolist()


def _engine(model, params, kernel, quant, **kw):
    return ServingEngine(model, params, ServeConfig(
        max_active=2, num_blocks=24, block_tokens=8, max_seq_tokens=48,
        paged_kernel=kernel, quant=quant, **kw))


def _trace(model, params, kernel, quant):
    """-> [(prompt, tokens)] of one request per program family."""
    done = []
    # prefill, decode; then a second prompt sharing two full blocks with
    # the first (suffix prefill through the aliased prefix); a warm
    # restart mid-flight rebuilds the pool under it
    eng = _engine(model, params, kernel, quant, prefix_cache=True)
    first = eng.submit(_prompt(1, 21), _NEW)
    eng.drain(max_ticks=50)
    second = eng.submit(first.prompt[:16] + _prompt(2, 5), _NEW)
    for _ in range(3):
        eng.tick()
    assert eng.prefix_stats()["hits"] == 1
    eng._warm_restart("test")
    eng.drain(max_ticks=50)
    assert eng.restarts == 1
    done += [first, second]
    # speculative verify + the span commit
    eng = _engine(model, params, kernel, quant, spec_draft="ngram")
    done.append(eng.submit(_prompt(3, 13), _NEW))
    eng.drain(max_ticks=50)
    # export mid-decode, import into a second engine's pool
    src = _engine(model, params, kernel, quant)
    dst = _engine(model, params, kernel, quant)
    moved = src.submit(_prompt(4, 11), _NEW)
    for _ in range(3):
        src.tick()
    assert dst.import_request(src.export_request(moved.last_slot))
    dst.drain(max_ticks=50)
    done.append(moved)
    assert all(r.status == "ok" and len(r.tokens) == _NEW for r in done)
    return [(r.prompt, list(r.tokens)) for r in done]


@pytest.mark.parametrize("kernel", ["on", "off"],
                         ids=["pallas-interpret", "xla-panel"])
@pytest.mark.parametrize("quant", [None, "int8", "fp8"],
                         ids=["f32", "int8", "fp8"])
@pytest.mark.parametrize("family", sorted(_FAMILIES))
def test_engine_trace_gives_reference_tokens(families, family, quant,
                                             kernel):
    model, params = families[family]
    for prompt, tokens in _trace(model, params, kernel, quant):
        ref = np.asarray(model.generate(
            params, np.asarray(prompt, np.int32)[None, :], _NEW,
            temperature=0.0))[0, len(prompt):]
        if quant is None:
            np.testing.assert_array_equal(np.asarray(tokens), ref)
        else:
            agree = float((np.asarray(tokens) == ref).mean())
            assert agree >= _QUANT_AGREEMENT, (prompt, tokens, ref)


def _eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs its equations carry
    (scan and while bodies, pjit, cond branches, custom calls)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else (v,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _eqns(inner)


@pytest.mark.parametrize("kernel", ["on", "off"],
                         ids=["pallas-interpret", "xla-panel"])
@pytest.mark.parametrize("quant", [None, "int8"], ids=["f32", "int8"])
def test_programs_never_reshape_the_pool(families, quant, kernel):
    """Requirement: `paged_append`, `paged_scatter`, `paged_panel` and the
    kernel's BlockSpecs index the pool array in its resting shape; what is
    reshaped is the sliver or the slab.  A pool block count is chosen so
    that nothing else in the programs has the pool's element count."""
    model, params = families["gpt2"]
    slots, bt, width = 3, 8, 6
    eng = ServingEngine(model, params, ServeConfig(
        max_active=slots, num_blocks=22, block_tokens=bt,
        max_seq_tokens=bt * width, paged_kernel=kernel, quant=quant))
    view = eng.pool.view
    assert view.k.shape == pool_shape(23, bt, 2, 2, 16)
    sizes = {int(np.prod(a.shape)) for a in view if a is not None}
    ints = jnp.zeros((slots,), jnp.int32)
    programs = {
        "tds_decode": (eng._decode_fn, (
            params, eng._stacked, view, ints, ints,
            jnp.zeros((slots, width), jnp.int32), ints, ints,
            jnp.zeros((slots,), jnp.float32))),
        "tds_prefill": (eng._prefill_fn, (
            params, eng._stacked, jnp.zeros((1, 16), jnp.int32),
            jnp.int32(9), jnp.zeros((2,), jnp.int32), view,
            jnp.int32(0), jnp.int32(0))),
    }
    for name, (fn, args) in programs.items():
        jaxpr = jax.make_jaxpr(fn)(*args).jaxpr
        names = [e.primitive.name for e in _eqns(jaxpr)]
        # the walk reaches the layer loop's body, where the pool is used
        assert "scatter" in names, name
        assert ("pallas_call" in names) == (kernel == "on"
                                            and name == "tds_decode"), name
        for eqn in _eqns(jaxpr):
            if eqn.primitive.name not in (
                    "reshape", "transpose", "convert_element_type", "copy",
                    "squeeze", "expand_dims", "broadcast_in_dim"):
                continue
            for v in list(eqn.invars) + list(eqn.outvars):
                shape = getattr(v.aval, "shape", ())
                assert int(np.prod(shape)) not in sizes, (name, str(eqn))
