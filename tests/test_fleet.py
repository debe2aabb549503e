# Copyright 2026 tiny-deepspeed-tpu authors
# SPDX-License-Identifier: Apache-2.0

"""Fleet serving tier: SLO-aware router over N replicas, journal-replay
failover across engine loss, disaggregated prefill/decode with priced
paged-KV migration.

Acceptance pins (ISSUE 12):
  * chaos-killing one of N engines mid-trace loses ZERO requests: the
    dead replica's journal replays onto a sibling and greedy outputs
    are token-identical to an uninterrupted run — with the callers'
    `submit()`-returned handles surviving the failover (quick
    in-process variant here; the real-SIGKILL variant in the slow tier
    recovers BOTH dead replicas' journals in a fresh process);
  * dispatch is least-loaded (an even fleet splits an even load) and
    deadline-aware AT THE DOOR: a deadline no warm replica prices as
    meetable sheds before touching any queue;
  * disaggregated requests decode token-identically to a single engine,
    and EVERY one carries measured kv_migration_bytes + a link class
    from the wire_link_split granule logic on its request record;
  * `recover()` validates journal-vs-engine geometry up front, naming
    both sides (failover made the mismatched-sibling path load-bearing:
    without it the failure is a deep pool-scatter shape error).
"""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tiny_deepspeed_tpu import GPT2Model, GPTConfig

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
    return np.asarray(out)[0, len(prompt):].tolist()


def _serve_config(**kw):
    from tiny_deepspeed_tpu.serving import ServeConfig
    kw.setdefault("max_active", 2)
    kw.setdefault("num_blocks", 16)
    kw.setdefault("block_tokens", 8)
    kw.setdefault("max_seq_tokens", 40)
    return ServeConfig(**kw)


def _fleet(model, params, tmp_path, n=2, kill_at=None, tel=None,
           logger=None, tag=""):
    """n-replica router with per-replica journals; `kill_at` wraps
    replica 0 in a chaos engine_kill at that wrapper tick."""
    from tiny_deepspeed_tpu.fleet import FleetRouter
    from tiny_deepspeed_tpu.resilience import Chaos, ChaosServingEngine
    from tiny_deepspeed_tpu.serving import ServingEngine
    engines = []
    for i in range(n):
        e = ServingEngine(
            model, params, _serve_config(),
            journal=str(tmp_path / f"fleet{tag}.r{i}.jsonl"),
            replica_id=i, telemetry=tel, logger=logger,
        )
        if i == 0 and kill_at is not None:
            e = ChaosServingEngine(e, Chaos(seed=3,
                                            engine_kill_step=kill_at))
        engines.append(e)
    return FleetRouter(engines, telemetry=tel, logger=logger)


class TestRouterDispatch:
    def test_least_loaded_spread_and_door_shed(self, model, params,
                                               tmp_path):
        """Cold even fleet: 4 submissions split 2/2 (queue depth is the
        load signal before any decode runs).  After warming both
        replicas' measured decode price, a deadline NO replica can meet
        sheds at the door — terminal immediately, no queue touched."""
        router = _fleet(model, params, tmp_path)
        reqs = [router.submit(_prompt(s, 7), 12) for s in (1, 2, 3, 4)]
        counts = router.dispatch_counts()
        assert counts == {0: 2, 1: 2}, counts
        router.drain(max_ticks=300)
        assert all(r.status == "ok" for r in reqs)
        for r in reqs:
            assert r.tokens == _ref_tokens(model, params, r.prompt, 12)
        # both replicas now have a measured per-token price
        for rep in router.replicas:
            assert rep.raw._gap_p50() is not None
        shed = router.submit(_prompt(9, 7), 12, deadline_s=1e-6)
        assert shed.status == "shed"
        assert shed.finish_reason == "shed:fleet_unmeetable"
        assert router.queue_depth == 0 and router.n_active == 0
        # a generous deadline still dispatches normally
        ok = router.submit(_prompt(10, 7), 6, deadline_s=60.0)
        router.drain(max_ticks=100)
        assert ok.status == "ok"


class TestFailover:
    def test_engine_kill_failover_token_identical(self, model, params,
                                                  tmp_path):
        """THE fleet acceptance, in-process: chaos engine_kill takes
        replica 0 whole at tick 3; the router replays its journal onto
        replica 1; zero requests are lost, the callers' handles finish
        through the sibling, and every greedy output is token-identical
        to the uninterrupted reference.  The shared metrics stream
        carries replica_id on the request records and the router's
        fleet_failover fault record, all schema-valid."""
        from tiny_deepspeed_tpu.telemetry import Telemetry
        from tiny_deepspeed_tpu.telemetry import schema
        from tiny_deepspeed_tpu.utils.profiling import MetricsLogger
        jsonl = str(tmp_path / "fleet_run.jsonl")
        tel = Telemetry()
        with MetricsLogger(jsonl, stdout=False) as logger:
            router = _fleet(model, params, tmp_path, kill_at=3,
                            tel=tel, logger=logger, tag="kill")
            specs = [(1, 7, 10), (2, 13, 10), (3, 9, 10), (4, 11, 10)]
            reqs = [router.submit(_prompt(s, n), new)
                    for s, n, new in specs]
            assert router.dispatch_counts() == {0: 2, 1: 2}
            router.drain(max_ticks=500)
        assert router.failovers == 1
        assert [r.alive for r in router.replicas] == [False, True]
        # zero requests lost: every ORIGINAL handle reached "ok"
        for r, (s, n, new) in zip(reqs, specs):
            assert r.status == "ok", (r.id, r.status)
            assert r.tokens == _ref_tokens(model, params, r.prompt,
                                           new), f"request {r.id}"
        assert tel.gauge("fleet_failover") == 1.0
        assert tel.gauge("fleet_replicas_live") == 1.0
        # the stream: schema-valid, replica-stamped, failover on record
        counts, errs = schema.validate_file(jsonl)
        assert not errs, errs[:5]
        metas = [json.loads(ln) for ln in open(jsonl)]
        recs = [m for m in metas if m.get("kind") == "request"]
        assert len(recs) == 4
        assert all(isinstance(m.get("replica_id"), int) for m in recs)
        # the killed replica's requests terminate on the sibling
        assert {m["replica_id"] for m in recs} == {1} | (
            {0} if any(m["replica_id"] == 0 for m in recs) else set())
        fo = [m for m in metas if m.get("kind") == "fault"
              and m.get("fault") == "fleet_failover"]
        assert len(fo) == 1 and fo[0]["replica_id"] == 0
        assert "replica 1" in fo[0]["action"]

    def test_failover_without_sibling_raises(self, model, params,
                                             tmp_path):
        """A 1-replica fleet has nowhere to fail over to: the replica's
        death must surface, not vanish into a half-alive router."""
        from tiny_deepspeed_tpu.fleet import EngineKilled
        router = _fleet(model, params, tmp_path, n=1, kill_at=1,
                        tag="solo")
        router.submit(_prompt(1, 7), 8)
        with pytest.raises(EngineKilled):
            router.drain(max_ticks=100)

    def test_recover_geometry_mismatch_named(self, model, params,
                                             tmp_path):
        """Satellite: a journal replayed onto a sibling with different
        serving geometry is refused UP FRONT with both sides named —
        the old failure was a shape error deep inside pool scatter."""
        from tiny_deepspeed_tpu.serving import ServingEngine
        jp = str(tmp_path / "geom.jsonl")
        a = ServingEngine(model, params, _serve_config(), journal=jp)
        a.submit(_prompt(1, 7), 8)
        b = ServingEngine(
            model, params,
            _serve_config(block_tokens=16, max_seq_tokens=64))
        with pytest.raises(ValueError) as ei:
            b.recover(journal=jp)
        msg = str(ei.value)
        assert "geometry mismatch" in msg
        assert "block_tokens: journal=8 vs engine=16" in msg
        assert "max_seq_tokens: journal=40 vs engine=64" in msg
        # same geometry replays fine (and adopts nothing by default)
        c = ServingEngine(model, params, _serve_config())
        assert len(c.recover(journal=jp)) == 1

    def test_journal_repair_on_open_seals_torn_tail(self, tmp_path):
        """Re-opening a journal whose last line was torn by a crash
        must TRUNCATE the fragment before appending: otherwise the
        next line (e.g. the attaching engine's geometry stamp) glues
        onto it — one merged unparseable line that is no longer the
        tail, which a second replay rightly refuses as corruption."""
        from tiny_deepspeed_tpu.serving.journal import RequestJournal
        p = str(tmp_path / "torn.jsonl")
        with open(p, "w") as f:
            f.write(json.dumps({"ev": "submit", "id": 0,
                                "prompt": [1, 2], "max_new": 4,
                                "deadline_s": None, "seed": 0}) + "\n")
            f.write(json.dumps({"ev": "tok", "id": 0,
                                "toks": [5]}) + "\n")
            f.write('{"ev": "tok", "id": 0, "to')  # the torn write
        j = RequestJournal(p)
        j.geometry({"block_size": 64, "max_seq_tokens": 40,
                    "vocab": 128, "block_tokens": 8})
        j.tokens(0, [9])
        j.close()
        # the fragment is gone, the committed prefix + new lines parse
        pending, done = RequestJournal.replay(p)
        assert done == [] and len(pending) == 1
        assert pending[0]["tokens"] == [5, 9]
        assert RequestJournal.read_geometry(p)["block_tokens"] == 8


class TestDisaggregation:
    def test_disagg_parity_and_priced_migration(self, model, params,
                                                tmp_path):
        """Disaggregated prefill/decode serves token-identically to a
        single engine, and EVERY request record carries its measured
        migration bytes + link class (the fleet acceptance's
        attribution half)."""
        from tiny_deepspeed_tpu.fleet import DisaggEngine
        from tiny_deepspeed_tpu.telemetry import schema
        from tiny_deepspeed_tpu.utils.profiling import MetricsLogger
        jsonl = str(tmp_path / "disagg.jsonl")
        with MetricsLogger(jsonl, stdout=False) as logger:
            dis = DisaggEngine(model, params, _serve_config(),
                               logger=logger,
                               journal=str(tmp_path / "dj.jsonl"))
            reqs = [dis.submit(_prompt(s, n), 10)
                    for s, n in ((1, 7), (2, 13), (3, 9))]
            dis.drain(max_ticks=300)
        for r in reqs:
            assert r.status == "ok", (r.id, r.status)
            assert r.tokens == _ref_tokens(model, params, r.prompt, 10)
            assert r.kv_migration_bytes > 0
            assert r.kv_migration_link == "ici"  # one CPU device
        assert dis.prefill.n_active == 0 and dis.decode.n_active == 0
        # exact accounting across BOTH pools after the handoffs
        assert dis.prefill.pool.blocks_in_use == 0
        assert dis.decode.pool.blocks_in_use == 0
        summ = dis.migration_summary()
        assert summ["migrations"] == 3
        assert summ["migrated_bytes"] == sum(r.kv_migration_bytes
                                             for r in reqs)
        counts, errs = schema.validate_file(jsonl)
        assert not errs, errs[:5]
        recs = [json.loads(ln) for ln in open(jsonl)]
        recs = [m for m in recs if m.get("kind") == "request"]
        assert all(m.get("kv_migration_bytes", 0) > 0
                   and m.get("kv_migration_link") == "ici"
                   for m in recs), recs

    def test_migration_link_granule_logic(self):
        """wire_link_split's granule rule applied to one handoff: same
        granule -> ici, spanning granules -> dcn; granule_of override
        and the dst_granule CPU-emulation knob behave like the ledger
        split's emulated 2-slice idiom."""
        from types import SimpleNamespace as NS

        from tiny_deepspeed_tpu.fleet import migration_link
        a0 = NS(id=0, slice_index=0)
        a1 = NS(id=1, slice_index=0)
        b0 = NS(id=2, slice_index=1)
        assert migration_link([a0], [a1]) == "ici"
        assert migration_link([a0], [b0]) == "dcn"
        assert migration_link([a0], [a1],
                              granule_of={0: 0, 1: 7}) == "dcn"
        # one physical device can still EMULATE a cross-slice decode
        assert migration_link([a0], [a0], dst_granule=1) == "dcn"
        assert migration_link([a0], [a0]) == "ici"
        # attribute-less devices (bare CPU) are one granule
        c = NS(id=0)
        assert migration_link([c], [c]) == "ici"

    def test_quantized_payload_compression_and_refusals(self):
        """A quantized pool's migration payload rests at the same ~4x
        compression as the pool (1-byte blocks + f32 head-vector
        scales), and cross-pool mismatches are refused naming both
        sides — all from array dtypes, no engine needed."""
        from tiny_deepspeed_tpu.serving.pool import (
            PagedKVPool, export_blocks, import_blocks, payload_bytes,
        )
        kw = dict(n_layer=2, kv_heads=2, head_dim=16, num_blocks=8,
                  block_tokens=8, dtype=jnp.float32)
        pf = PagedKVPool.dense(**kw)
        pq = PagedKVPool.dense(**kw, quant="int8")
        bf = payload_bytes(export_blocks(pf.view, [1, 2]))
        bq = payload_bytes(export_blocks(pq.view, [1, 2]))
        # f32 block = 4 B/elem; int8 block = 1 B/elem + f32 scale per
        # 16-elem head vector = 1.25 B/elem -> 3.2x here, and the block
        # bytes alone are exactly 4x
        assert bf / bq == pytest.approx(3.2)
        with pytest.raises(ValueError, match="dtype mismatch"):
            import_blocks(pq.view, [1, 2], export_blocks(pf.view, [1, 2]))
        small = PagedKVPool.dense(**{**kw, "block_tokens": 4})
        with pytest.raises(ValueError, match="geometry mismatch"):
            import_blocks(small.view, [1, 2],
                          export_blocks(pf.view, [1, 2]))
        with pytest.raises(ValueError, match="destination blocks"):
            import_blocks(pf.view, [1], export_blocks(pf.view, [1, 2]))

    def test_disagg_refuses_spec_and_mismatched_pools(self, model,
                                                      params):
        from tiny_deepspeed_tpu.fleet import DisaggEngine
        with pytest.raises(ValueError, match="speculative"):
            DisaggEngine(model, params,
                         _serve_config(spec_draft="ngram"))
        with pytest.raises(ValueError, match="geometry must match"):
            DisaggEngine(model, params, _serve_config(),
                         prefill_config=_serve_config(quant="int8"))


@pytest.mark.slow
class TestFleetSoak:
    def test_sigkill_fleet_recovery_token_exact(self, tmp_path):
        """Real-SIGKILL variant of the failover acceptance: the whole
        2-replica fleet process dies between a journal append and its
        fsync; a fresh process replays BOTH dead replicas' journals
        onto one new engine (the cross-journal recover path, for real)
        and every interrupted request's final sequence equals the
        uninterrupted run's."""
        here = os.path.dirname(os.path.abspath(__file__))
        base = str(tmp_path / "fleet_journal")
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        env.pop("XLA_FLAGS", None)

        def run(mode, check=True):
            out = subprocess.run(
                [sys.executable, os.path.join(here, "fleet_worker.py"),
                 mode, base],
                capture_output=True, text=True, timeout=600, env=env,
            )
            if check:
                assert out.returncode == 0, out.stderr[-2000:]
                return json.loads(out.stdout.strip().splitlines()[-1])
            return out

        straight = run("straight")["outputs"]
        killed = run("serve", check=False)
        assert killed.returncode == -9, (
            f"worker was supposed to die by SIGKILL, got rc="
            f"{killed.returncode}: {killed.stderr[-1000:]}"
        )
        assert os.path.exists(base + ".r0")
        assert os.path.exists(base + ".r1")
        rec = run("recover")
        assert rec["recovered"], "the kill left no in-flight requests?"
        assert all(s == "ok" for s in rec["statuses"].values())
        for rid, toks in rec["outputs"].items():
            assert toks == straight[rid], (
                f"request {rid} diverged across fleet SIGKILL+recover:"
                f"\n  recovered: {toks}\n  straight:  {straight[rid]}"
            )
