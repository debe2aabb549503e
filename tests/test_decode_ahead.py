# Copyright 2026 tiny-deepspeed-tpu authors
# SPDX-License-Identifier: Apache-2.0

"""Decode one tick ahead (serving/engine.py `_next_step`): a plain decode
tick dispatches the next tick's step from the tokens still on the chip
before it fetches its own.

Held here, for each family a slot layout serves (GPT-2's dense table,
EvaByte's window ring with summary rows, MiMo's global table beside a
ring): the tokens equal the synchronous engine's, the same programs with
every step dispatched in the tick that commits it, and GPT-2's
`generate()`; after every `tick()` each request holds every token the
engine committed; and `ahead` is 1 on most decode ticks.  Then what makes
the tick synchronous or drops the step in flight, each exact: a poison
armed between ticks, eos, a pool tight enough to preempt, a warm restart,
`export_request`; speculation, the prefix cache and `decode=False` never
dispatch ahead.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tiny_deepspeed_tpu import GPT2Model, GPTConfig
from tiny_deepspeed_tpu.models import ALL_PRESETS, build_model
from tiny_deepspeed_tpu.serving import ServeConfig, ServingEngine

FAMILIES = ["tiny", "evabyte-tiny", "mimo-tiny"]
BT = 8
# (tick it arrives at, prompt length, new tokens): staggered admissions,
# finishes by count at different ticks, a ring wrapped (EvaByte's window
# of 32, MiMo's of 16) and a block boundary crossed by each
SCHEDULE = [(0, 7, 12), (0, 21, 40), (2, 13, 6), (5, 30, 20), (9, 3, 9)]


@pytest.fixture(scope="module", params=FAMILIES)
def served(request):
    cfg = ALL_PRESETS[request.param]
    model = build_model(cfg)
    return request.param, cfg, model, model.init(jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def small():
    """A GPT-2 small enough that each engine's programs compile in a
    second: what the scheduler does is the same at any size."""
    model = GPT2Model(GPTConfig(block_size=64, vocab_size=128, n_layer=2,
                                n_head=2, n_embd=32,
                                compute_dtype=jnp.float32))
    return model, model.init(jax.random.PRNGKey(0))


def _prompt(seed, n, vocab):
    return np.random.default_rng(seed).integers(0, vocab, n).tolist()


def _engine(model, params, sync=False, monkeypatch=None, **kw):
    cfg = dict(max_active=4, num_blocks=200, block_tokens=BT,
               temperature=0.0)
    cfg.update(kw)
    eng = ServingEngine(model, params, ServeConfig(**cfg))
    if sync:
        # the same engine, every step dispatched by the tick that commits it
        monkeypatch.setattr(eng, "_next_step", lambda step: None)
    return eng


def _serve(eng, vocab, schedule=SCHEDULE, between=None):
    """Submit `schedule` at its ticks and tick until every request is
    done; after every tick, each request holds every token the engine
    committed.  `between(eng, tick, reqs)` runs between two ticks.
    -> the requests in schedule order."""
    reqs, tick = [], 0
    pending = list(enumerate(schedule))
    while pending or not all(r.done for r in reqs):
        while pending and pending[0][1][0] <= tick:
            j, (_, n, new) = pending.pop(0)
            reqs.append(eng.submit(_prompt(j, n, vocab), new))
        if between is not None:
            between(eng, tick, reqs)
        eng.tick()
        tick += 1
        # what the engine committed, every tick's `produced`, is what the
        # requests hold, no token of a step in flight among them
        assert sum(len(r.tokens) for r in reqs) == sum(
            rec["produced"] for rec in eng.tick_records)
        assert tick < 500
    return reqs


def _ahead_share(eng):
    flags = [rec["ahead"] for rec in eng.tick_records if "ahead" in rec]
    return sum(flags) / len(flags)


def test_tokens_equal_the_synchronous_engine(served, monkeypatch):
    name, cfg, model, params = served
    eng = _engine(model, params)
    got = _serve(eng, cfg.vocab_size)
    want = _serve(_engine(model, params, sync=True,
                          monkeypatch=monkeypatch), cfg.vocab_size)
    for r, w, (_, _, new) in zip(got, want, SCHEDULE):
        assert r.status == w.status == "ok"
        assert r.tokens == w.tokens and len(r.tokens) == new
    if name == "tiny":
        for r in got:
            out = model.generate(params, np.asarray([r.prompt], np.int32),
                                 len(r.tokens), temperature=0.0)
            assert r.tokens == np.asarray(out)[0, len(r.prompt):].tolist()
    # most decode ticks dispatched the next step ahead: all but the
    # first, the last, and those whose every slot then ended
    assert _ahead_share(eng) >= 0.8
    assert eng.pool.blocks_in_use == 0 and eng._ahead is None
    # one decode executable for both ways of dispatching
    assert eng._decode_fn._cache_size() == 1


def test_one_tick_commits_its_own_step_and_its_logits(served, monkeypatch):
    """What the benchmark's check reads after ONE tick: two tokens a
    request, and `last_logits` of the step that tick committed."""
    name, cfg, model, params = served
    prompts = [_prompt(40 + n, n, cfg.vocab_size) for n in (5, 17, 33)]
    seen = []
    for sync in (False, True):
        eng = _engine(model, params, sync, monkeypatch)
        reqs = [eng.submit(p, 2) for p in prompts]
        eng.tick()
        assert all(r.status == "ok" and len(r.tokens) == 2 for r in reqs)
        assert eng._ahead is None and eng.tick_records[-1]["ahead"] == 0
        logits = np.asarray(eng.last_logits)
        seen.append((reqs, np.stack([logits[r.last_slot] for r in reqs])))
    (got, rows), (want, ref) = seen
    assert [r.tokens for r in got] == [r.tokens for r in want]
    np.testing.assert_array_equal(rows, ref)
    # the committed step's logits sampled the second token
    picked = np.asarray(model.sampling_logits(jnp.asarray(rows))).argmax(-1)
    assert picked.tolist() == [r.tokens[1] for r in got]


def test_a_poison_armed_between_ticks_hits_the_same_step(served,
                                                         monkeypatch):
    """`poison_slot` drops the step in flight: the step the next tick
    commits is the one the arm hits, as in the synchronous engine."""
    name, cfg, model, params = served
    runs = []
    for sync in (False, True):
        def poison(eng, tick, reqs):
            if tick == 4:
                eng.poison_slot(reqs[1].last_slot)
        eng = _engine(model, params, sync, monkeypatch)
        runs.append((_serve(eng, cfg.vocab_size, between=poison), eng))
    (got, eng), (want, _) = runs
    assert [r.status for r in got] == [r.status for r in want]
    assert got[1].status == "failed"
    assert [r.tokens for r in got] == [r.tokens for r in want]
    assert _ahead_share(eng) >= 0.7


def test_a_slot_ending_on_eos_gets_no_extra_token(small, monkeypatch):
    model, params = small
    plain = _serve(_engine(model, params), 128)
    # an eos that ends two requests early, on a token each of them makes
    eos = plain[1].tokens[4]
    ended = [r for r in plain if eos in r.tokens[:-1]]
    assert len(ended) >= 1
    got = _serve(_engine(model, params, eos_id=eos), 128)
    want = _serve(_engine(model, params, True, monkeypatch, eos_id=eos),
                  128)
    assert [r.tokens for r in got] == [r.tokens for r in want]
    for r, p in zip(got, plain):
        cut = p.tokens.index(eos) + 1 if eos in p.tokens else len(p.tokens)
        assert r.tokens == p.tokens[:cut]
        if p in ended:
            assert r.finish_reason == "eos"


def test_a_pool_tight_enough_to_preempt_falls_back_and_stays_exact(
        small, monkeypatch):
    model, params = small
    schedule = [(0, 7, 30), (0, 13, 30), (0, 9, 30), (1, 5, 20)]
    got_eng = _engine(model, params, max_active=4, num_blocks=14)
    got = _serve(got_eng, 128, schedule)
    want = _serve(_engine(model, params, True, monkeypatch, max_active=4,
                          num_blocks=14), 128, schedule)
    assert sum(r.preemptions for r in got) >= 1
    assert [r.tokens for r in got] == [r.tokens for r in want]
    for r in got:
        out = model.generate(params, np.asarray([r.prompt], np.int32),
                             len(r.tokens), temperature=0.0)
        assert r.tokens == np.asarray(out)[0, len(r.prompt):].tolist()
    flags = [rec["ahead"] for rec in got_eng.tick_records if "ahead" in rec]
    assert 0 < sum(flags) < len(flags)   # some ticks fell back
    assert got_eng.pool.blocks_in_use == 0


def test_a_warm_restart_with_a_step_in_flight_loses_nothing(served,
                                                            monkeypatch):
    name, cfg, model, params = served

    def fail(eng, tick, reqs):
        if tick == 6:
            assert eng._ahead is not None
            eng.arm_prefill_exception(RuntimeError("prefill lost"))

    eng = _engine(model, params)
    got = _serve(eng, cfg.vocab_size, between=fail)
    want = _serve(_engine(model, params, True, monkeypatch), cfg.vocab_size)
    assert eng.restarts == 1
    assert [r.tokens for r in got] == [r.tokens for r in want]
    assert all(r.status == "ok" for r in got)


def test_export_with_a_step_in_flight_loses_nothing(small):
    model, params = small
    a = _engine(model, params)
    b = _engine(model, params)
    reqs = [a.submit(_prompt(j, n, 128), 16) for j, n in enumerate((7, 19))]
    for _ in range(4):
        a.tick()
    assert a._ahead is not None
    moved = reqs[0]
    b.import_request(a.export_request(moved.last_slot))
    assert a._ahead is None
    while not all(r.done for r in reqs):
        a.tick()
        b.tick()
    for r in reqs:
        out = model.generate(params, np.asarray([r.prompt], np.int32),
                             16, temperature=0.0)
        assert r.tokens == np.asarray(out)[0, len(r.prompt):].tolist()


@pytest.mark.parametrize("kw", [dict(spec_draft="ngram", spec_k=2),
                                dict(prefix_cache=True)],
                         ids=["spec", "prefix"])
def test_speculation_and_the_prefix_cache_never_dispatch_ahead(small, kw):
    model, params = small
    eng = _engine(model, params, **kw)
    reqs = _serve(eng, 128, SCHEDULE[:3])
    assert all(r.status == "ok" for r in reqs)
    flags = [rec["ahead"] for rec in eng.tick_records if "ahead" in rec]
    assert flags and not any(flags)


def test_a_tick_that_decodes_nothing_drops_the_step_in_flight(small):
    model, params = small
    eng = _engine(model, params)
    r = eng.submit(_prompt(0, 9, 128), 8)
    eng.tick()
    eng.tick()
    assert eng._ahead is not None and len(r.tokens) == 3
    eng.tick(decode=False)
    assert eng._ahead is None and len(r.tokens) == 3
    assert "ahead" not in eng.tick_records[-1]
    eng.drain()
    out = model.generate(params, np.asarray([r.prompt], np.int32), 8,
                         temperature=0.0)
    assert r.tokens == np.asarray(out)[0, len(r.prompt):].tolist()
