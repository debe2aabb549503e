# Copyright 2026 tiny-deepspeed-tpu authors
# SPDX-License-Identifier: Apache-2.0

"""Shared-prefix KV reuse + multi-tenant serving (ISSUE 13).

Acceptance pins:
  * greedy token-identity with the prefix cache ON vs `generate()`
    across staggered admission, pool-pressure tree eviction,
    preemption/resume, and journal recovery — aliasing changes where
    K/V is READ from, never the committed tokens;
  * exact per-tick block accounting extended to refcounts: every
    allocated block's refcount equals its holder count (active-table
    occurrences + one per radix-tree node), and
    free + distinct-allocated == usable — including under eviction and
    preemption;
  * the radix tree holds weak ownership: finished requests' prompt
    blocks stay warm, and under pool pressure unreferenced leaves drop
    LRU BEFORE any running request is preempted;
  * weighted-fair tenancy: stride scheduling admits token cost
    proportional to weight under contention, token budgets throttle a
    flooding tenant, and the per-tenant door watermark sheds its
    overflow — the headline isolation pin: an abusive tenant
    (chaos `tenant_flood`) must not move a well-behaved tenant's p99
    TTFT beyond the stated bound, and absorbs every shed itself.
"""

import json
from collections import Counter

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tiny_deepspeed_tpu import GPTConfig, GPT2Model

# same small-and-fast shape family as test_serving.py — XLA-CPU
# compiles of the serving programs dominate this module's budget
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


def _serve_config(**kw):
    from tiny_deepspeed_tpu.serving import ServeConfig
    kw.setdefault("max_active", 2)
    kw.setdefault("num_blocks", 24)
    kw.setdefault("block_tokens", 8)
    return ServeConfig(**kw)


def _assert_ref_accounting(eng):
    """The refcount-extended exact-accounting pin: per-block refcount
    == enumerable holders, free + distinct-allocated == usable."""
    holders = Counter(
        b for t in eng.active_block_tables().values() for b in t)
    if eng._prefix is not None:
        holders.update(eng._prefix.blocks())
    assert dict(holders) == eng.pool.ref_counts(), (
        f"refcount drift: holders {dict(holders)} vs pool "
        f"{eng.pool.ref_counts()}"
    )
    assert (eng.pool.blocks_in_use + eng.pool.blocks_free
            == eng.pool.num_usable)


class TestRefcountedPool:
    """pool.py's refcounted free list — host-side, no compiled code."""

    def _pool(self, n=6):
        from tiny_deepspeed_tpu.serving import PagedKVPool
        return PagedKVPool.dense(n_layer=1, kv_heads=1, head_dim=4,
                                 num_blocks=n, block_tokens=4,
                                 dtype=jnp.float32)

    def test_share_free_and_exact_counts(self):
        pool = self._pool()
        ids = pool.alloc(2)
        assert [pool.refcount(b) for b in ids] == [1, 1]
        pool.share(ids)
        assert [pool.refcount(b) for b in ids] == [2, 2]
        assert pool.blocks_in_use == 2  # distinct, not refcount-weighted
        pool.free_blocks(ids)  # one holder down: still allocated
        assert pool.blocks_in_use == 2
        pool.free_blocks(ids)  # last holder: back on the free list
        assert pool.blocks_in_use == 0 and pool.blocks_free == 6
        assert pool.ref_counts() == {}

    def test_double_free_and_share_free_refused(self):
        pool = self._pool()
        ids = pool.alloc(1)
        pool.free_blocks(ids)
        with pytest.raises(ValueError, match="double free"):
            pool.free_blocks(ids)
        with pytest.raises(ValueError, match="not allocated"):
            pool.share(ids)
        # over-release within ONE call is caught before any mutation
        ids2 = pool.alloc(1)
        with pytest.raises(ValueError, match="double free"):
            pool.free_blocks(ids2 + ids2)
        assert pool.refcount(ids2[0]) == 1  # untouched by the refusal

    def test_lifo_realloc_unchanged_without_sharing(self):
        """Refcounts at 1 everywhere == the pre-refcount pool: frees
        push back LIFO and realloc returns the most recent."""
        pool = self._pool()
        a = pool.alloc(3)
        pool.free_blocks(a)
        b = pool.alloc(3)
        assert b == list(reversed(a)) or b == a[::-1]


class TestPrefixTree:
    """prefix.py radix semantics over a real (tiny) pool."""

    def _pool(self, n=8):
        from tiny_deepspeed_tpu.serving import PagedKVPool
        return PagedKVPool.dense(n_layer=1, kv_heads=1, head_dim=4,
                                 num_blocks=n, block_tokens=4,
                                 dtype=jnp.float32)

    def test_match_insert_and_weak_ownership(self):
        from tiny_deepspeed_tpu.serving import PrefixCache
        pool, tree = self._pool(), PrefixCache(block_tokens=4)
        toks = list(range(12))  # 3 full blocks
        table = pool.alloc(3)
        tree.insert(toks, table, pool, tick=0)
        assert len(tree) == 3
        assert [pool.refcount(b) for b in table] == [2, 2, 2]
        # full match capped by limit; partial-prefix prompt matches
        # only its full blocks; divergent prompt matches nothing
        assert tree.match(toks, limit=3, tick=1) == table
        assert tree.match(toks, limit=2, tick=1) == table[:2]
        assert tree.match(toks[:6] + [99] * 6, limit=3,
                          tick=1) == table[:1]
        assert tree.match([99] + toks[1:], limit=3, tick=1) == []
        # the request frees its table: blocks stay warm via the tree
        pool.free_blocks(table)
        assert pool.blocks_in_use == 3
        assert sorted(tree.blocks()) == sorted(table)

    def test_evict_lru_leaves_only_and_never_referenced(self):
        from tiny_deepspeed_tpu.serving import PrefixCache
        pool, tree = self._pool(), PrefixCache(block_tokens=4)
        # two chains: A (2 blocks, older), B (1 block, newer)
        ta = pool.alloc(2)
        tree.insert(list(range(8)), ta, pool, tick=1)
        tb = pool.alloc(1)
        tree.insert(list(range(100, 104)), tb, pool, tick=5)
        pool.free_blocks(ta + tb)  # tree is now the only holder
        # a block some live table still references is never freed
        pool.share([tb[0]])
        freed = tree.evict(pool, need=2)
        # A's LEAF (older chain) drops first, then A's root — B's
        # block is referenced (refcount 2) and survives as a node
        assert freed == 2
        assert set(tree.blocks()) == {tb[0]}
        assert pool.refcount(tb[0]) == 2
        assert pool.refcount(ta[0]) == 0 and pool.refcount(ta[1]) == 0

    def test_interior_nodes_outlive_leaves(self):
        from tiny_deepspeed_tpu.serving import PrefixCache
        pool, tree = self._pool(), PrefixCache(block_tokens=4)
        t = pool.alloc(3)
        tree.insert(list(range(12)), t, pool, tick=0)
        pool.free_blocks(t)
        assert tree.evict(pool, need=1) == 1
        # only the deepest node dropped; the chain prefix still matches
        assert tree.match(list(range(12)), limit=3, tick=1) == t[:2]


class TestTenantQueue:
    """tenancy.py stride scheduling + budgets — pure host logic."""

    def _req(self, tenant, cost=10):
        from tiny_deepspeed_tpu.serving.engine import Request
        return Request([0] * (cost - 1), 1, tenant=tenant)

    def test_stride_shares_follow_weights(self):
        from tiny_deepspeed_tpu.serving import TenantPolicy, TenantQueue
        q = TenantQueue({"pro": TenantPolicy(weight=3.0),
                         "free": TenantPolicy(weight=1.0)})
        for i in range(20):
            q.append(self._req("pro"))
            q.append(self._req("free"))
        order = []
        for _ in range(16):
            r = q.peek()
            q.pop(r)
            order.append(r.tenant)
        # 3:1 admission mix under contention (stride guarantees it
        # over any window once both passes initialize)
        assert order.count("pro") == 12 and order.count("free") == 4

    def test_budget_throttles_and_refills(self):
        from tiny_deepspeed_tpu.serving import TenantPolicy, TenantQueue
        q = TenantQueue({"cap": TenantPolicy(
            tokens_per_tick=10.0, burst_tokens=20.0)})
        for _ in range(6):
            q.append(self._req("cap", cost=10))
        # initial budget = burst (20): two admissions, then dry
        for _ in range(2):
            q.pop(q.peek())
        assert q.peek() is None  # over budget: queued but ineligible
        q.on_tick()  # +10
        assert q.peek() is not None
        q.pop(q.peek())
        assert q.peek() is None
        # utilization accounting reaches the stats surface
        st = q.stats()["cap"]
        assert st["admitted_tokens"] == 30
        assert 0 < st["budget_utilization"] <= 1.0

    def test_refund_restores_charge_on_aborted_admission(self):
        """An aborted admission (prefill exception re-queues the
        request) must refund the pop's charge — otherwise one
        transient fault bills the tenant twice and a budget-capped
        tenant starves behind a flaky prefill."""
        from tiny_deepspeed_tpu.serving import TenantPolicy, TenantQueue
        q = TenantQueue({"cap": TenantPolicy(
            weight=2.0, tokens_per_tick=10.0, burst_tokens=20.0)})
        r = self._req("cap", cost=20)
        q.append(r)
        q.pop(r)
        assert q.stats()["cap"]["admitted_tokens"] == 20
        q.refund(r)
        q.appendleft(r)  # what the engine's abort path does
        st = q.stats()["cap"]
        assert st["admitted_tokens"] == 0
        assert q._t["cap"].pass_v == 0.0  # stride charge rolled back
        assert q._t["cap"].budget == 20.0  # full burst restored
        assert q.peek() is r  # immediately admissible again

    def test_parse_tenant_spec(self):
        from tiny_deepspeed_tpu.serving import parse_tenant_spec
        pol = parse_tenant_spec("pro:4,free:1:64:8")
        assert pol["pro"].weight == 4.0
        assert pol["free"].tokens_per_tick == 64.0
        assert pol["free"].max_queue == 8
        with pytest.raises(ValueError, match="empty"):
            parse_tenant_spec(",")


class TestPrefixServing:
    def test_parity_accounting_eviction_and_preemption(
            self, model, params, tmp_path):
        """The tentpole pin in one choreography: a cold boundary-length
        prompt (plain full-prefill path), Zipf-ish shared-prefix hits
        (suffix prefill over aliased blocks), a tight pool forcing
        LRU tree eviction and youngest-first preemption with shared
        blocks in flight — every request token-identical to
        `generate()`, refcount accounting exact at every tick, and the
        emitted records carry the v9 tenant/prefix fields."""
        from tiny_deepspeed_tpu.serving import (
            ServingEngine, TenantPolicy,
        )
        from tiny_deepspeed_tpu.telemetry import Telemetry
        from tiny_deepspeed_tpu.telemetry.schema import validate_file
        from tiny_deepspeed_tpu.utils.profiling import MetricsLogger

        path = str(tmp_path / "run.jsonl")
        logger = MetricsLogger(path, stdout=False)
        tel = Telemetry()
        eng = ServingEngine(
            model, params,
            _serve_config(max_active=2, num_blocks=8, prefix_cache=True,
                          tenants={"a": TenantPolicy(weight=2.0),
                                   "b": TenantPolicy(weight=1.0)}),
            telemetry=tel, logger=logger)
        sp = _prompt(100, 16)  # 2-block shared prefix, boundary length
        specs = [
            (sp, 6, "a"),                    # cold, p % bt == 0 (plain
            (sp + _prompt(1, 4), 10, "a"),   # boundary path) then hits
            (sp + _prompt(2, 4), 10, "b"),
            (sp + _prompt(3, 9), 12, "b"),   # long: grows under pressure
            (sp[:8] + _prompt(4, 4), 8, "a"),  # partial-prefix hit
        ]
        reqs = [eng.submit(p, n, tenant=t) for p, n, t in specs]
        ticks = 0
        while eng.queue_depth or eng.n_active:
            eng.tick()
            _assert_ref_accounting(eng)
            ticks += 1
            assert ticks < 400
        for r, (p, n, _t) in zip(reqs, specs):
            assert r.status == "ok"
            np.testing.assert_array_equal(
                np.asarray(r.tokens), _ref_tokens(model, params, p, n),
                err_msg=f"request {r.id} diverged with the cache on",
            )
        st = eng.prefix_stats()
        assert st["prefill_tokens_avoided"] > 0
        assert st["blocks_aliased"] >= 3
        assert sum(r.prefix_blocks for r in reqs) == st["blocks_aliased"]
        # phase 2 — weak ownership under pressure: every request done,
        # the tree is the sole holder of the warm blocks; a long
        # DIVERGENT request (no hit, 6-block demand vs 8-block pool)
        # must grow by evicting LRU tree leaves, not by stalling or
        # preempting itself
        assert st["cached_blocks"] >= 2
        big_p = _prompt(200, 24)
        big = eng.submit(big_p, 24, tenant="b")
        ticks = 0
        while eng.queue_depth or eng.n_active:
            eng.tick()
            _assert_ref_accounting(eng)
            ticks += 1
            assert ticks < 400
        assert big.status == "ok" and big.preemptions == 0
        np.testing.assert_array_equal(
            np.asarray(big.tokens),
            _ref_tokens(model, params, big_p, 24),
            err_msg="post-eviction request diverged",
        )
        st = eng.prefix_stats()
        assert st["tree_evictions"] >= 1, st
        logger.close()
        # v9 surface: records validate, tenant + prefix fields present
        _counts, errs = validate_file(path)
        assert not errs, errs[:5]
        recs = [json.loads(ln) for ln in open(path)]
        req_recs = [r for r in recs if r.get("kind") == "request"]
        assert {r["tenant"] for r in req_recs} == {"a", "b"}
        assert any(r["prefix_blocks"] > 0 for r in req_recs)
        assert tel.gauge("serve_prefix_tokens_avoided") > 0

    def test_recovery_with_aliased_blocks_token_exact(
            self, model, params, tmp_path):
        """Journal replay when the dead engine's requests held ALIASED
        blocks: recovery rebuilds pool and radix tree from empty
        (stated warm-from-empty contract) and the re-decoded sequences
        are token-identical."""
        from tiny_deepspeed_tpu.serving import ServingEngine
        jp = str(tmp_path / "j.jsonl")
        cfg = _serve_config(prefix_cache=True)
        eng = ServingEngine(model, params, cfg, journal=jp)
        sp = _prompt(50, 16)
        specs = [(sp + _prompt(5, 4), 8), (sp + _prompt(6, 4), 8)]
        reqs = [eng.submit(p, n) for p, n in specs]
        for _ in range(3):
            eng.tick()
        assert any(r.prefix_blocks > 0 for r in reqs)  # aliases in flight
        eng.abandon()  # on-disk image of a mid-trace death
        fresh = ServingEngine(model, params, cfg,
                              journal=str(tmp_path / "j2.jsonl"))
        recovered = fresh.recover(jp)
        assert len(recovered) == 2
        assert len(fresh._prefix) == 0  # warm-from-empty
        fresh.drain(max_ticks=200)
        for r, (p, n) in zip(recovered, specs):
            np.testing.assert_array_equal(
                np.asarray(r.tokens), _ref_tokens(model, params, p, n),
                err_msg=f"recovered request {r.id} diverged",
            )

    def test_spec_composition_refused(self, model, params):
        from tiny_deepspeed_tpu.serving import ServingEngine
        with pytest.raises(ValueError, match="prefix_cache"):
            ServingEngine(model, params, _serve_config(
                prefix_cache=True, spec_draft="ngram"))


@pytest.mark.slow
class TestPrefixCompositionsSlow:
    """Family/dtype compositions of the suffix-prefill program — slow
    tier: the mechanism is the same compiled span path the quick
    choreography pins; these pin the GQA+RoPE override and the
    quantized-pool codec riding it."""

    def test_llama_prefix_parity(self):
        from tiny_deepspeed_tpu import LlamaConfig, LlamaModel
        from tiny_deepspeed_tpu.serving import ServingEngine
        m = LlamaModel(LlamaConfig(
            block_size=64, vocab_size=128, n_layer=2, n_head=4,
            n_kv_head=2, n_embd=32, compute_dtype=jnp.float32))
        p = m.init(jax.random.PRNGKey(0))
        eng = ServingEngine(m, p, _serve_config(prefix_cache=True))
        sp = _prompt(77, 16)
        specs = [(sp + _prompt(1, 4), 8), (sp + _prompt(2, 4), 8),
                 (sp + _prompt(3, 7), 8)]
        reqs = [eng.submit(pr, n) for pr, n in specs]
        ticks = 0
        while eng.queue_depth or eng.n_active:
            eng.tick()
            _assert_ref_accounting(eng)
            ticks += 1
            assert ticks < 200
        assert eng.prefix_stats()["blocks_aliased"] > 0
        for r, (pr, n) in zip(reqs, specs):
            np.testing.assert_array_equal(
                np.asarray(r.tokens), _ref_tokens(m, p, pr, n),
                err_msg=f"llama request {r.id} diverged (rope_span / "
                        "GQA suffix path)",
            )

    def test_int8_pool_prefix_tolerance(self, model, params):
        """Aliased int8 blocks read back through the SAME dequant path
        a fresh prefill's would — agreement with the f32 reference
        stays at the quantized-cache tolerance, and the first token of
        a HIT admission is exact (the suffix forward is full
        precision; only the committed prefix K/V is quantized)."""
        from tiny_deepspeed_tpu.serving import ServingEngine
        eng = ServingEngine(model, params, _serve_config(
            quant="int8", prefix_cache=True))
        sp = _prompt(88, 16)
        specs = [(sp + _prompt(4, 4), 8), (sp + _prompt(5, 4), 8)]
        reqs = [eng.submit(pr, n) for pr, n in specs]
        eng.drain(max_ticks=200)
        assert reqs[1].prefix_blocks > 0  # the second admission hit
        for r, (pr, n) in zip(reqs, specs):
            ref = _ref_tokens(model, params, pr, n)
            agree = float((np.asarray(r.tokens) == ref).mean())
            assert agree >= 0.75, (
                f"int8 aliased decode diverged: {agree:.2f}"
            )


class TestTenantIsolation:
    def test_flood_does_not_move_well_behaved_p99(self, model, params):
        """THE isolation pin (ROADMAP scenario item b): one abusive
        tenant floods at many times its budget (chaos `tenant_flood`);
        the well-behaved tenant must finish every request ok with its
        p99 TTFT inside the stated bound — within 5x its flood-free
        p99 (or an absolute 0.5 s floor, whichever is larger: the
        2-vCPU box's scheduler noise must not decide the pin) — while
        the abuser absorbs every shed at its own watermark/budget."""
        from tiny_deepspeed_tpu.resilience import ChaosServingEngine
        from tiny_deepspeed_tpu.resilience.chaos import Chaos
        from tiny_deepspeed_tpu.serving import (
            ServingEngine, TenantPolicy,
        )
        from tiny_deepspeed_tpu.serving.driver import Arrival, run_trace

        cfg = _serve_config(
            max_active=2, num_blocks=24,
            tenants={"good": TenantPolicy(weight=1.0),
                     "abuser": TenantPolicy(
                         weight=1.0, tokens_per_tick=16.0,
                         max_queue=2)})
        good_trace = [Arrival(0.0, _prompt(20 + i, 8), 8, None, "good")
                      for i in range(6)]

        def run(chaos=None):
            eng = ServingEngine(model, params, cfg)
            target = (ChaosServingEngine(eng, chaos)
                      if chaos is not None else eng)
            res = run_trace(target, list(good_trace), realtime=False)
            return res["tenants"]["good"]

        baseline = run()
        chaos = Chaos(seed=7, tenant_flood_steps=(0, 1, 2),
                      flood_requests=8, flood_prompt_len=8,
                      flood_new_tokens=8)
        flooded = run(chaos)
        # structural isolation: the good tenant loses nothing
        assert flooded["status_counts"]["ok"] == 6, flooded
        assert flooded["status_counts"]["shed"] == 0
        # the abuser absorbed the overflow at its own door
        assert len(chaos.injected) == 3
        assert all("shed" in f["action"] for f in chaos.injected)
        # the stated p99 bound
        bound = max(5.0 * baseline["ttft"]["p99_ms"], 500.0)
        assert flooded["ttft"]["p99_ms"] <= bound, (
            f"good tenant p99 TTFT {flooded['ttft']['p99_ms']}ms "
            f"blew the bound {bound}ms (flood-free "
            f"{baseline['ttft']['p99_ms']}ms)"
        )
