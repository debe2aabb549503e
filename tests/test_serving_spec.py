# Copyright 2026 tiny-deepspeed-tpu authors
# SPDX-License-Identifier: Apache-2.0

"""Speculative decoding over the serving scheduler (ISSUE 10).

Acceptance pins:
  * greedy spec-on output is TOKEN-EXACT vs `generate` per request —
    through staggered admission (quick), preemption, warm restart, and
    journal `recover()` (slow tier: each pays fresh engine compiles);
  * temperature>0 acceptance sampling is deterministic under
    preemption/restart/recovery: the one accept-or-residual rule keyed
    by (request seed, output position) commits the same tokens no
    matter how the scheduler's spans realign (slow tier);
  * only VERIFIED tokens reach the request/journal/pool — pool
    accounting stays exact at every tick and rejected-draft K/V routes
    to scratch inside the verify program;
  * ngram-drafter acceptance sanity: exact pattern continuation on a
    repetitive context (unit), and on a briefly-trained echoing model
    a repetitive prompt out-accepts a random one (slow — an UNTRAINED
    model's greedy output is aperiodic, so nothing accepts on it; the
    quick ceiling/floor contrast uses model:self vs ngram-on-random);
  * schema v7 surface: spec_proposed/spec_accepted request fields,
    draft_s tick field, serve_spec_* gauges, all validating.

Budget note: this module keeps the quick tier LEAN (tier-1 headroom on
the 2-vCPU box is under a minute — scripts/tier1_times.py warns below
60 s); every multi-engine composition run is slow-marked from the
start.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tiny_deepspeed_tpu import GPTConfig, GPT2Model

CFG = dict(block_size=64, vocab_size=128, n_layer=2, n_head=2,
           n_embd=32, compute_dtype=jnp.float32)


@pytest.fixture(scope="module")
def model():
    return GPT2Model(GPTConfig(**CFG))


@pytest.fixture(scope="module")
def params(model):
    return model.init(jax.random.PRNGKey(0))


def _prompt(seed, n, vocab=128):
    return np.asarray(
        jax.random.randint(jax.random.PRNGKey(seed), (n,), 0, vocab),
        np.int32,
    ).tolist()


def _ref_tokens(model, params, prompt, new):
    out = model.generate(
        params, np.asarray(prompt, np.int32)[None, :], new,
        temperature=0.0,
    )
    return np.asarray(out)[0, len(prompt):]


def _spec_config(**kw):
    from tiny_deepspeed_tpu.serving import ServeConfig
    kw.setdefault("max_active", 3)
    kw.setdefault("num_blocks", 24)
    kw.setdefault("block_tokens", 8)
    kw.setdefault("spec_draft", "ngram")
    kw.setdefault("spec_k", 3)
    return ServeConfig(**kw)


def _assert_accounting(eng):
    used = sum(len(t) for t in eng.active_block_tables().values())
    assert used == eng.pool.blocks_in_use, (
        f"pool accounting drift: tables hold {used}, pool reports "
        f"{eng.pool.blocks_in_use}"
    )


def _accept_rate(eng) -> float:
    return eng._spec_accepted / max(1, eng._spec_proposed)


class TestNgramDrafterUnit:
    """Host-side drafter behavior — no device work, no compiles."""

    def test_repetitive_context_proposes_pattern_continuation(self):
        from tiny_deepspeed_tpu.serving.drafter import NgramDrafter
        d = NgramDrafter(k=4)
        # period-3 context ending mid-pattern: the lookup must continue
        # the pattern exactly, k+1 tokens out (the autoregressive
        # feedback keeps extending it)
        ctx = [5, 9, 2] * 4 + [5, 9]
        assert d.propose_one(ctx) == [2, 5, 9, 2, 5]

    def test_matchless_context_pads_with_tail(self):
        from tiny_deepspeed_tpu.serving.drafter import NgramDrafter
        d = NgramDrafter(k=3)
        # all-distinct tokens: no earlier n-gram occurrence at any n —
        # proposals fall back to tail padding (verify rejects for free)
        out = d.propose_one([1, 2, 3, 4, 5])
        assert out == [5, 5, 5, 5]

    def test_feedback_is_autoregressively_consistent(self):
        """Proposal j equals what a fresh lookup on ctx + proposals
        1..j-1 would return — the determinism guarantee's premise."""
        from tiny_deepspeed_tpu.serving.drafter import NgramDrafter
        d = NgramDrafter(k=4)
        ctx = [3, 1, 4, 1, 5, 9, 2, 6, 5, 3, 5, 1, 4]
        out = d.propose_one(ctx)
        ext = list(ctx)
        for t in out:
            assert t == d.propose_one(ext)[0]
            ext.append(t)


class TestSpecRefusals:
    def test_bad_drafter_and_k(self, model, params):
        from tiny_deepspeed_tpu.serving import ServingEngine
        with pytest.raises(ValueError, match="spec_draft"):
            ServingEngine(model, params,
                          _spec_config(spec_draft="oracle"))
        with pytest.raises(ValueError, match="spec_k"):
            ServingEngine(model, params, _spec_config(spec_k=0))
        with pytest.raises(ValueError, match="spec_k"):
            ServingEngine(model, params, _spec_config(spec_k=99))

    def test_vocab_mismatch_draft_preset_refused(self, model, params):
        from tiny_deepspeed_tpu.serving import ServingEngine
        # llama-tiny's vocab is 512, the test model serves 128 — drafts
        # are token ids, so the mismatch must be refused up front
        with pytest.raises(ValueError, match="vocab"):
            ServingEngine(model, params,
                          _spec_config(spec_draft="model:llama-tiny"))
        with pytest.raises(ValueError, match="unknown draft preset"):
            ServingEngine(model, params,
                          _spec_config(spec_draft="model:nope"))

    def test_short_context_draft_model_refused(self, model, params):
        """A draft model whose context cannot hold the engine's longest
        committed prefix is refused at CONSTRUCTION — admitting it
        would crash the serving loop at the first (re)admission whose
        prefix outgrows the drafter's block_size."""
        from tiny_deepspeed_tpu.serving.drafter import ModelDrafter
        with pytest.raises(ValueError, match="block_size"):
            ModelDrafter(model, params, 2, max_active=2,
                         max_seq=model.config.block_size * 2,
                         block_tokens=8)


class TestSpecGreedyParity:
    def test_ngram_staggered_parity_accounting_and_records(
            self, model, params, tmp_path):
        """The core contract in one engine: requests admitted and
        evicted at different ticks under the ngram drafter each
        reproduce their `generate` tokens exactly (speculation changes
        throughput, never tokens), pool accounting is exact at every
        tick (rejected-draft K/V never allocates), and the schema-v7
        surface lands: spec_proposed/spec_accepted on every request
        record, draft_s on tick records, serve_spec_* gauges
        registered and documented."""
        from tiny_deepspeed_tpu.serving import ServingEngine
        from tiny_deepspeed_tpu.telemetry import Telemetry, schema
        from tiny_deepspeed_tpu.utils.profiling import MetricsLogger
        tel = Telemetry()
        path = str(tmp_path / "spec.jsonl")
        with MetricsLogger(path, stdout=False) as ml:
            ml.log_meta(schema_version=schema.SCHEMA_VERSION,
                        engine="serve:test")
            eng = ServingEngine(model, params, _spec_config(),
                                telemetry=tel, logger=ml)
            specs = [(1, 7, 10), (2, 13, 6)]
            reqs = [eng.submit(_prompt(s, n), new)
                    for s, n, new in specs]
            for _ in range(2):
                eng.tick()
                _assert_accounting(eng)
            late = [(3, 7, 10), (4, 13, 6)]  # same prefill buckets
            reqs += [eng.submit(_prompt(s, n), new)
                     for s, n, new in late]
            ticks = 0
            while eng.queue_depth or eng.n_active:
                eng.tick()
                _assert_accounting(eng)
                ticks += 1
                assert ticks < 100
            tel.flush(ml)
        assert eng.pool.blocks_in_use == 0
        for r, (s, n, new) in zip(reqs, specs + late):
            assert len(r.tokens) == new
            np.testing.assert_array_equal(
                np.asarray(r.tokens),
                _ref_tokens(model, params, r.prompt, new),
                err_msg=f"request {r.id} diverged from generate()",
            )
            assert r.spec_proposed > 0
        # the engine commits MORE than one token per request per tick
        # whenever anything accepts; at minimum every tick commits one
        assert eng._spec_tokens >= eng._spec_ticks
        g = tel.gauges
        assert "serve_spec_accept_rate" in g
        assert "serve_spec_tokens_per_tick" in g
        assert g["serve_spec_tokens_per_tick"] >= 1.0
        for name in g:
            assert name in schema.GAUGES
        counts, errs = schema.validate_file(path)
        assert not errs, errs
        with open(path) as f:
            recs = [json.loads(ln) for ln in f]
        req_recs = [r for r in recs if r.get("kind") == "request"]
        assert len(req_recs) == 4
        assert all("spec_proposed" in r and "spec_accepted" in r
                   for r in req_recs)
        tick_recs = [r for r in recs if r.get("kind") == "tick"]
        assert any("draft_s" in r for r in tick_recs)

    @pytest.mark.slow
    def test_model_self_parity_and_acceptance_ceiling(
            self, model, params):
        """Slow-marked from the start: the model-drafter machinery
        (rollout + drafter-prefill jits) is this module's priciest
        compile and tier-1 headroom on this box is under a minute;
        the slow llama/eos/int8 cases compile the same machinery.

        `model:self` — the target drafting for itself — is the
        acceptance CEILING (proposals are the target's own greedy
        continuations) and the model-drafter machinery's exactness
        pin: token parity must hold while most drafts accept.  The
        floor is the ngram drafter on uniform-random prompts, whose
        proposals an aperiodic untrained model essentially never
        matches — the two bracket the acceptance gauge."""
        from tiny_deepspeed_tpu.serving import ServingEngine
        eng = ServingEngine(model, params,
                            _spec_config(spec_draft="model:self"))
        specs = [(1, 7, 10), (2, 13, 8)]
        reqs = [eng.submit(_prompt(s, n), new) for s, n, new in specs]
        eng.drain(max_ticks=100)
        for r, (s, n, new) in zip(reqs, specs):
            np.testing.assert_array_equal(
                np.asarray(r.tokens),
                _ref_tokens(model, params, r.prompt, new),
                err_msg=f"request {r.id} diverged under model:self",
            )
        ceiling = _accept_rate(eng)
        assert ceiling >= 0.5, (
            f"model:self acceptance {ceiling:.2f} — the target "
            "rejecting its own greedy continuations means the verify "
            "path's logits diverged from the decode path's"
        )
        floor = ServingEngine(model, params, _spec_config())
        fr = [floor.submit(_prompt(s, 9), 8) for s in (7, 8)]
        floor.drain(max_ticks=100)
        assert all(r.status == "ok" for r in fr)
        assert _accept_rate(floor) <= 0.2
        assert ceiling > _accept_rate(floor)


@pytest.mark.slow
class TestSpecComposition:
    """Spec x scheduler fault machinery — every case pays fresh engine
    compiles, so the whole class is slow-marked from the start (the
    tier-1 box has <60s of headroom)."""

    def test_preemption_parity(self, model, params):
        """Tight pool forces preemption mid-span; resumed requests
        (re-prefill prompt+produced, spec prefill commit rule) finish
        token-exact."""
        from tiny_deepspeed_tpu.serving import ServingEngine
        eng = ServingEngine(
            model, params, _spec_config(num_blocks=6))
        reqs = [eng.submit(_prompt(s, 10), 14) for s in (1, 2, 3)]
        eng.drain(max_ticks=2000)
        assert sum(r.preemptions for r in reqs) >= 1
        for r in reqs:
            np.testing.assert_array_equal(
                np.asarray(r.tokens),
                _ref_tokens(model, params, r.prompt, 14),
                err_msg=f"request {r.id} diverged after preemption",
            )

    @pytest.mark.parametrize("draft", ["ngram", "model:self"])
    def test_temp_determinism_tight_vs_roomy(self, model, params,
                                             draft):
        """temperature>0: a preempted-and-resumed spec run commits the
        SAME tokens as an undisturbed one — the one accept-or-residual
        rule keyed by (seed, output position) holds regardless of how
        the spans realign (the ServingEngine docstring guarantee,
        extended to speculation)."""
        from tiny_deepspeed_tpu.serving import ServingEngine
        outs = []
        preempts = []
        for blocks in (5, 24):
            eng = ServingEngine(model, params, _spec_config(
                num_blocks=blocks, temperature=1.0, top_k=16,
                spec_draft=draft))
            reqs = [eng.submit(_prompt(s, 10), 14, seed=100 + s)
                    for s in (1, 2, 3)]
            eng.drain(max_ticks=2000)
            outs.append([list(r.tokens) for r in reqs])
            preempts.append(sum(r.preemptions for r in reqs))
        assert preempts[0] >= 1 and preempts[1] == 0
        assert outs[0] == outs[1], (
            f"{draft}: temp>0 spec resume diverged from the "
            "undisturbed run"
        )

    def test_warm_restart_parity(self, model, params):
        """Consecutive poisoned verify ticks trip the watchdog; the
        re-queued survivors continue token-exact on the rebuilt pool
        (drafter state rebuilt through the one admission path)."""
        from tiny_deepspeed_tpu.resilience import (
            Chaos, ChaosServingEngine,
        )
        from tiny_deepspeed_tpu.serving import ServingEngine
        eng = ServingEngine(model, params, _spec_config(
            max_active=2, guard_k_restart=2))
        ce = ChaosServingEngine(eng, Chaos(seed=3,
                                           tick_nan_steps=(1, 2)))
        reqs = [ce.submit(_prompt(s, 7), 12) for s in (1, 2, 3)]
        ce.drain(max_ticks=300)
        assert eng.restarts == 1
        assert sorted(r.status for r in reqs).count("failed") == 2
        ok = [r for r in reqs if r.status == "ok"]
        assert ok, "someone must survive the restart"
        for r in ok:
            np.testing.assert_array_equal(
                np.asarray(r.tokens),
                _ref_tokens(model, params, r.prompt, 12),
                err_msg=f"request {r.id} diverged across warm restart",
            )
        _assert_accounting(eng)

    def test_journal_recover_parity(self, model, params, tmp_path):
        """Abandon a spec engine mid-flight; a fresh spec engine
        recovers from the journal (which holds only VERIFIED tokens)
        and finishes every request token-exact."""
        from tiny_deepspeed_tpu.serving import ServingEngine
        jp = str(tmp_path / "journal.jsonl")
        cfg = _spec_config(max_active=2)
        engA = ServingEngine(model, params, cfg, journal=jp)
        specs = [(6, 7, 10), (7, 13, 10), (8, 7, 10)]
        ra = [engA.submit(_prompt(s, n), new) for s, n, new in specs]
        for _ in range(3):
            engA.tick()
        assert any(r.tokens for r in ra) and not all(r.done for r in ra)
        engB = ServingEngine(model, params, cfg, journal=jp)
        rec = engB.recover()
        assert [r.id for r in rec] == [r.id for r in ra]
        engB.drain(max_ticks=200)
        for r, (s, n, new) in zip(rec, specs):
            assert r.status == "ok"
            np.testing.assert_array_equal(
                np.asarray(r.tokens),
                _ref_tokens(model, params, r.prompt, new),
                err_msg=f"recovered request {r.id} diverged",
            )

    def test_temp_recover_determinism(self, model, params, tmp_path):
        """temperature>0 journal recovery commits the same tokens the
        uninterrupted spec run would have."""
        from tiny_deepspeed_tpu.serving import ServingEngine
        cfg = _spec_config(max_active=2, temperature=1.0, top_k=16)
        eu = ServingEngine(model, params, cfg)
        ru = [eu.submit(_prompt(s, 9), 12, seed=50 + s)
              for s in (1, 2)]
        eu.drain(max_ticks=200)
        jp = str(tmp_path / "j.jsonl")
        ea = ServingEngine(model, params, cfg, journal=jp)
        for s in (1, 2):
            ea.submit(_prompt(s, 9), 12, seed=50 + s)
        for _ in range(2):
            ea.tick()
        eb = ServingEngine(model, params, cfg, journal=jp)
        rb = eb.recover()
        eb.drain(max_ticks=200)
        assert [list(r.tokens) for r in rb] == \
            [list(r.tokens) for r in ru]

    def test_eos_truncates_mid_span(self, model, params):
        """An eos landing inside an accepted span truncates the commit
        at the eos (kept, like the plain path) — tokens after it are
        discarded even though the verify accepted them."""
        from tiny_deepspeed_tpu.serving import ServingEngine
        g = _ref_tokens(model, params, _prompt(1, 7), 12)
        eos = int(g[5])
        eng = ServingEngine(model, params, _spec_config(
            max_active=2, eos_id=eos, spec_draft="model:self",
            spec_k=4))
        r = eng.submit(_prompt(1, 7), 12)
        eng.drain(max_ticks=100)
        assert r.finish_reason == "eos"
        np.testing.assert_array_equal(
            np.asarray(r.tokens), g[:list(g).index(eos) + 1])

    @pytest.mark.parametrize("draft", ["ngram", "model:self"])
    def test_llama_family_parity(self, draft):
        """The verify path generalizes across model families: Llama's
        GQA + per-position RoPE spans reproduce its `generate` tokens
        exactly under both drafters."""
        from tiny_deepspeed_tpu.models.llama import (
            LlamaConfig, LlamaModel,
        )
        from tiny_deepspeed_tpu.serving import ServingEngine
        lm = LlamaModel(LlamaConfig(
            block_size=64, vocab_size=128, n_layer=2, n_head=4,
            n_kv_head=2, n_embd=32, compute_dtype=jnp.float32))
        lp = lm.init(jax.random.PRNGKey(0))
        eng = ServingEngine(lm, lp, _spec_config(
            max_active=2, spec_draft=draft))
        reqs = [eng.submit(_prompt(s, 9), 10) for s in (1, 2)]
        eng.drain(max_ticks=100)
        for r in reqs:
            out = lm.generate(lp, np.asarray(r.prompt,
                                             np.int32)[None, :], 10,
                              temperature=0.0)
            np.testing.assert_array_equal(
                np.asarray(r.tokens),
                np.asarray(out)[0, len(r.prompt):],
                err_msg=f"llama {draft} request {r.id} diverged",
            )

    def test_quantized_pool_spec_tolerance(self, model, params):
        """int8 cache blocks under speculation: the span commits
        through the same blockwise-absmax codec, so greedy agreement
        stays at the quantized-cache tolerance, not exactness."""
        from tiny_deepspeed_tpu.serving import ServingEngine
        eng = ServingEngine(model, params, _spec_config(
            max_active=2, quant="int8", spec_draft="model:self"))
        reqs = [eng.submit(_prompt(s, 7), 8) for s in (1, 2)]
        eng.drain(max_ticks=100)
        for r in reqs:
            ref = _ref_tokens(model, params, r.prompt, 8)
            agree = float((np.asarray(r.tokens) == ref).mean())
            assert agree >= 0.6, f"int8 spec diverged: {agree:.2f}"

    def test_trained_model_repetitive_prompt_out_accepts_random(self):
        """The ISSUE's acceptance-rate sanity, in the regime where it
        means something: an UNTRAINED model's greedy output is
        aperiodic (measured — nothing accepts on it, repetitive prompt
        or not), so train a small-vocab model briefly on periodic
        sequences.  The contrast is measured
        over a SHORT horizon (6 new tokens, 5 prompts each way):
        prompt lookup has material from the first span on a repetitive
        prompt, while a random prompt offers nothing to mine until the
        model's own (periodic) output accumulates — over long horizons
        the output's self-repetition dominates the context and the
        prompt distinction honestly washes out."""
        from tiny_deepspeed_tpu import AdamW, SingleDevice
        from tiny_deepspeed_tpu.serving import ServingEngine
        vocab = 32  # induction over a small vocab trains in seconds
        model = GPT2Model(GPTConfig(
            block_size=64, vocab_size=vocab, n_layer=2, n_head=2,
            n_embd=32, compute_dtype=jnp.float32))
        eng_t = SingleDevice(model, AdamW(lr=1e-3))
        state = eng_t.init(jax.random.PRNGKey(0))
        rng = np.random.default_rng(1)

        def batch():
            xs = []
            for _ in range(8):
                m = rng.integers(2, 5)
                motif = rng.integers(0, vocab, m)
                xs.append(np.tile(motif, -(-49 // m))[:49])
            a = np.asarray(xs, np.int32)
            return a[:, :-1], a[:, 1:]

        for _ in range(500):
            state, _ = eng_t.step(state, batch())
        params = state.params

        def rate(prompt):
            eng = ServingEngine(model, params, _spec_config(
                max_active=1, spec_k=4))
            r = eng.submit(prompt, 6)
            eng.drain(max_ticks=200)
            assert r.status == "ok"
            return _accept_rate(eng)

        reps, rnds = [], []
        for s in range(5):
            r2 = np.random.default_rng(100 + s)
            motif = r2.integers(0, vocab, 3)
            reps.append(rate(np.tile(motif, 6)[:16].tolist()))
            rnds.append(rate(r2.integers(0, vocab, 16).tolist()))
        rep, rnd = float(np.mean(reps)), float(np.mean(rnds))
        assert rep >= 0.4, f"repetitive-prompt acceptance {rep:.2f}"
        assert rep > rnd + 0.15, (
            f"repetitive {rep:.2f} vs random {rnd:.2f}: the echoing "
            "regime must out-accept the no-material floor"
        )
