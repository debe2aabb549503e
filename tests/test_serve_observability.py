# Copyright 2026 tiny-deepspeed-tpu authors
# SPDX-License-Identifier: Apache-2.0

"""Serving observability (ISSUE 9): request-lifecycle tracing, per-tick
time series, the serving flight recorder, tail-latency attribution, and
the ICI-vs-DCN ledger split.

Acceptance pins:
  * every terminal request's latency components PARTITION its terminal
    latency (sum(comp_*_s) == lat_s within rounding) — the attribution
    dashboard's numbers are exact, not estimates;
  * a chaos run's Perfetto export is STRICT-parseable JSON with one
    track per decode slot plus a queue track, the poisoned slot's
    quarantine and the watchdog restart visible as markers, and
    tick-segment span walls summing to within each tick's measured wall;
  * the `flight` record flushed on a watchdog restart covers the ticks
    LEADING UP to it (ring semantics, at_step = the restart tick);
  * `tick` records pass the schema gate (report_run.py --check) and the
    event-triggered + sampled emission bounds quiet-traffic volume;
  * `wire_link_split` pins cross-slice (DCN) bytes from the compiled
    replica_groups on a CPU-emulated 2-slice mesh: intra-slice
    collectives bill to ICI, slice-spanning ones to DCN.
"""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tiny_deepspeed_tpu import GPTConfig, GPT2Model

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CFG = dict(block_size=64, vocab_size=128, n_layer=2, n_head=2,
           n_embd=32, compute_dtype=jnp.float32)


@pytest.fixture(scope="module")
def model():
    return GPT2Model(GPTConfig(**CFG))


@pytest.fixture(scope="module")
def params(model):
    return model.init(jax.random.PRNGKey(0))


def _logger(path, serve_cfg=None):
    from tiny_deepspeed_tpu.telemetry.schema import SCHEMA_VERSION
    from tiny_deepspeed_tpu.utils.profiling import MetricsLogger
    lg = MetricsLogger(str(path), stdout=False)
    meta = dict(schema_version=SCHEMA_VERSION, engine="serve:test",
                model="tiny")
    if serve_cfg is not None:
        meta["serve"] = dict(max_active=serve_cfg.max_active,
                             num_blocks=serve_cfg.num_blocks,
                             block_tokens=serve_cfg.block_tokens)
    lg.log_meta(**meta)
    return lg


@pytest.fixture(scope="module")
def preempt_run(model, params, tmp_path_factory):
    """A tight-pool run that exercises queue wait, preemption, and
    natural completion — the clean-path attribution fixture.  One
    engine, reused by several tests (XLA compiles dominate)."""
    from tiny_deepspeed_tpu.serving import ServeConfig, ServingEngine
    path = tmp_path_factory.mktemp("serveobs") / "preempt.jsonl"
    cfg = ServeConfig(max_active=3, num_blocks=8, block_tokens=8,
                      max_seq_tokens=40, tick_record_every=4)
    lg = _logger(path, cfg)
    eng = ServingEngine(model, params, cfg, logger=lg)
    reqs = [eng.submit([1 + i, 2, 3, 4 + i], 20) for i in range(4)]
    eng.drain()
    lg.close()
    return str(path), reqs, eng


@pytest.fixture(scope="module")
def chaos_run(model, params, tmp_path_factory):
    """A poisoned run: one quarantine, then a watchdog warm restart
    (guard_k_restart=1 — the first poisoned tick trips it), then clean
    completion.  Drives the flight-flush, restart-overhead, and
    trace-marker pins."""
    from tiny_deepspeed_tpu.serving import ServeConfig, ServingEngine
    path = tmp_path_factory.mktemp("serveobs") / "chaos.jsonl"
    cfg = ServeConfig(max_active=2, num_blocks=16, block_tokens=8,
                      max_seq_tokens=32, guard_k_restart=1,
                      tick_record_every=1)
    lg = _logger(path, cfg)
    eng = ServingEngine(model, params, cfg, logger=lg)
    reqs = [eng.submit([1, 2, 3, 4], 12), eng.submit([5, 6, 7, 8], 12)]
    eng.tick()            # admit both
    eng.poison_slot(0)
    eng.tick()            # quarantine slot 0 AND trip the watchdog
    eng.drain()
    lg.close()
    return str(path), reqs, eng


def _records(path, kind=None):
    out = []
    with open(path) as f:
        for line in f:
            rec = json.loads(line)
            if kind is None or rec.get("kind") == kind:
                out.append(rec)
    return out


class TestLatencyAttribution:
    COMPONENTS = ("comp_queue_s", "comp_prefill_s", "comp_decode_s",
                  "comp_preempt_s", "comp_restart_s")

    def test_components_partition_latency(self, preempt_run):
        """The headline pin: per-request component sums equal terminal
        latency within measurement noise (here: 6-decimal rounding of
        the shared-timestamp partition — sub-millisecond)."""
        path, reqs, _ = preempt_run
        recs = _records(path, "request")
        assert len(recs) == 4
        for rec in recs:
            total = sum(rec[k] for k in self.COMPONENTS)
            assert total == pytest.approx(rec["lat_s"], abs=1e-3), rec

    def test_preempted_request_pays_preempt_wait(self, preempt_run):
        path, reqs, _ = preempt_run
        assert any(r.preemptions > 0 for r in reqs), \
            "fixture rotted: the tight pool no longer preempts"
        recs = {r["request_id"]: r for r in _records(path, "request")}
        for r in reqs:
            if r.preemptions:
                assert recs[r.id]["comp_preempt_s"] > 0.0

    def test_restart_overhead_attributed(self, chaos_run):
        """The surviving neighbor of the watchdog restart pays
        restart-overhead (restart re-queue -> re-admission), NOT
        preempted-wait — the dashboard must bill the watchdog."""
        path, reqs, eng = chaos_run
        assert eng.restarts == 1
        recs = {r["request_id"]: r for r in _records(path, "request")}
        survivor = [r for r in reqs if r.status == "ok"]
        assert survivor, "fixture rotted: nobody survived the restart"
        assert any(recs[r.id]["comp_restart_s"] > 0.0 for r in survivor)
        for rec in recs.values():
            total = sum(rec[k] for k in self.COMPONENTS)
            assert total == pytest.approx(rec["lat_s"], abs=1e-3), rec

    def test_lifecycle_events_on_record(self, chaos_run):
        path, reqs, _ = chaos_run
        recs = {r["request_id"]: r for r in _records(path, "request")}
        failed = [r for r in reqs if r.status == "failed"][0]
        names = [e[0] for e in recs[failed.id]["events"]]
        assert names[0] == "submitted"
        assert "admitted" in names and "quarantined" in names
        assert names[-1] == "terminal:failed"
        ok = [r for r in reqs if r.status == "ok"][0]
        names = [e[0] for e in recs[ok.id]["events"]]
        assert "restart_requeued" in names
        assert names[-1] == "terminal:ok"
        # events share one monotonic clock: non-decreasing stamps
        for rec in recs.values():
            ts = [e[1] for e in rec["events"]]
            assert ts == sorted(ts)


class TestPrefillFailureRequeue:
    def test_real_prefill_exception_requeues_and_terminates(
            self, model, params, tmp_path):
        """A REAL exception out of the compiled prefill (not the chaos
        hook, which re-queues by hand) must not strand the request in a
        non-terminal limbo: the admission path puts it back at the
        front, the watchdog warm-restarts, and the request still ends
        in exactly one terminal status with an exact component
        partition."""
        from tiny_deepspeed_tpu.serving import ServeConfig, ServingEngine
        path = tmp_path / "prefill_fail.jsonl"
        cfg = ServeConfig(max_active=2, num_blocks=16, block_tokens=8,
                          max_seq_tokens=32, tick_record_every=1)
        lg = _logger(path, cfg)
        eng = ServingEngine(model, params, cfg, logger=lg)
        real_prefill = eng._prefill_fn
        boom = {"armed": True}

        def flaky_prefill(*a, **kw):
            if boom.pop("armed", False):
                raise RuntimeError("transient XLA prefill failure")
            return real_prefill(*a, **kw)

        eng._prefill_fn = flaky_prefill
        req = eng.submit([1, 2, 3, 4], 8)
        eng.drain()
        lg.close()
        assert req.status == "ok"
        assert eng.restarts == 1
        assert eng.pool.blocks_in_use == 0
        rec = _records(str(path), "request")[0]
        names = [e[0] for e in rec["events"]]
        assert "admission_aborted" in names
        comps = sum(rec[k] for k in TestLatencyAttribution.COMPONENTS)
        assert comps == pytest.approx(rec["lat_s"], abs=1e-3)


class TestTickRecords:
    def test_schema_gate(self, preempt_run, chaos_run):
        from tiny_deepspeed_tpu.telemetry import schema
        for path in (preempt_run[0], chaos_run[0]):
            counts, errs = schema.validate_file(path)
            assert errs == [], errs[:5]
            assert counts["meta"] > 0

    def test_wall_split_bounded_by_tick_wall(self, preempt_run):
        ticks = _records(preempt_run[0], "tick")
        assert ticks
        for t in ticks:
            parts = (t["sched_s"] + t["prefill_s"] + t["decode_s"]
                     + t["fetch_s"])
            # sched_s is the clamped remainder, so the sum can only
            # undershoot the wall by clock granularity, never overshoot
            assert parts <= t["wall_s"] + 2e-3, t
            assert parts >= 0.9 * t["wall_s"] - 2e-3, t

    def test_eventful_ticks_always_emit_quiet_ticks_sampled(
            self, model, params, tmp_path):
        """Emission policy: with tick_record_every=0 ONLY eventful ticks
        (admission/eviction here) write records — a long quiet decode
        stretch adds nothing to the file."""
        from tiny_deepspeed_tpu.serving import ServeConfig, ServingEngine
        path = tmp_path / "quiet.jsonl"
        cfg = ServeConfig(max_active=2, num_blocks=16, block_tokens=8,
                          max_seq_tokens=32, tick_record_every=0)
        lg = _logger(path, cfg)
        eng = ServingEngine(model, params, cfg, logger=lg)
        eng.submit([1, 2, 3, 4], 16)
        n_ticks = 0
        while eng.n_active or eng.queue_depth:
            eng.tick()
            n_ticks += 1
        lg.close()
        ticks = _records(path, "tick")
        # admission tick + eviction tick are eventful; the ~14 decode
        # ticks in between stay silent
        assert n_ticks > 4
        assert 1 <= len(ticks) <= 3, (n_ticks, len(ticks))
        assert all(t["emit"] == "event" for t in ticks)

    def test_counts_match_engine(self, chaos_run):
        """tick_record_every=1 records EVERY tick, so the per-tick
        counters must total the engine's cumulative story exactly."""
        path, reqs, eng = chaos_run
        ticks = _records(path, "tick")
        assert sum(t["quarantined"] for t in ticks) == 1
        assert sum(t["restarted"] for t in ticks) == 1
        assert sum(t["produced"] for t in ticks) == sum(
            len(r.tokens) for r in reqs)
        occ = [t["occupancy"] for t in ticks]
        assert all(0.0 <= o <= 1.0 for o in occ)


class TestServingFlightRecorder:
    def test_flush_on_restart_covers_leadup(self, chaos_run):
        """The restart pin: the flight record's ring ends AT the restart
        tick and carries the ticks leading up to it."""
        path, _, _ = chaos_run
        flights = _records(path, "flight")
        restarts = [f for f in flights if f["reason"] == "serve_restart"]
        assert len(restarts) == 1
        fl = restarts[0]
        steps = fl["steps"]
        assert steps, "empty flight ring on a restart"
        assert steps[-1]["step"] == fl["at_step"]
        # the lead-up: the admission tick BEFORE the poisoned tick is in
        # the ring too (capacity 64 >> run length, nothing evicted)
        assert steps[0]["step"] < fl["at_step"]
        # ring entries carry the tick state + wall split
        assert "health" in steps[-1] and "segments" in steps[-1]
        assert steps[-1]["health"]["quarantined"] >= 1

    def test_quarantine_outranked_by_restart_same_tick(self, chaos_run):
        """One tick, two triggers (quarantine + watchdog restart): ONE
        flush, named after the graver trigger."""
        path, _, _ = chaos_run
        flights = _records(path, "flight")
        reasons = [f["reason"] for f in flights]
        assert "serve_restart" in reasons
        assert "serve_quarantine" not in reasons


class TestServingTraceExport:
    @pytest.fixture(scope="class")
    def trace_doc(self, chaos_run, tmp_path_factory):
        out = str(tmp_path_factory.mktemp("serveobs") / "chaos.trace.json")
        r = subprocess.run(
            [sys.executable,
             os.path.join(REPO, "scripts", "trace_view.py"),
             chaos_run[0], "-o", out],
            capture_output=True, text=True, timeout=120,
        )
        assert r.returncode == 0, r.stderr[-2000:]
        # STRICT parse (json.load raises on NaN-bearing output Perfetto
        # would reject)
        with open(out) as f:
            return json.load(f)

    def test_slot_and_queue_tracks_present(self, trace_doc):
        names = {e["args"]["name"] for e in trace_doc["traceEvents"]
                 if e.get("name") == "thread_name"}
        assert {"queue", "slot 0", "slot 1",
                "scheduler ticks", "tick wall split"} <= names

    def test_quarantine_and_restart_visible(self, trace_doc):
        insts = [e["name"] for e in trace_doc["traceEvents"]
                 if e.get("ph") == "i"]
        assert any("quarantine" in n for n in insts), insts
        assert any("restart" in n for n in insts), insts
        # the quarantined request's active window closes with the reason
        closed = [e["args"].get("window")
                  for e in trace_doc["traceEvents"]
                  if e.get("ph") == "X" and "args" in e]
        assert "quarantined" in closed

    def test_tick_parts_sit_at_their_measured_starts(self, trace_doc):
        """Per tick: its parts (the tick record's `spans`, the engine's
        tick_records segments) lie inside the measured tick wall, one
        after the other, and nothing is schematic any more."""
        ev = trace_doc["traceEvents"]
        ticks = [e for e in ev if e.get("ph") == "X"
                 and str(e.get("name", "")).startswith("tick ")]
        segs = sorted((e for e in ev if e.get("ph") == "X"
                       and e.get("tid") == 1 and "seconds" in e["args"]),
                      key=lambda e: e["ts"])
        assert ticks and segs
        assert not any("schematic" in k for e in ev
                       for k in e.get("args", {}))
        # (`observe` is still open when the record is written)
        assert {"sched", "decode.operands", "decode.dispatch",
                "decode.fetch", "commit"} <= {s["name"] for s in segs}
        for t in ticks:
            inside = [s for s in segs
                      if t["ts"] - 1 <= s["ts"] <= t["ts"] + t["dur"] + 1]
            assert inside, t
            for s in inside:
                assert s["ts"] + s["dur"] <= t["ts"] + t["dur"] + 2
            for a, b in zip(inside, inside[1:]):
                assert a["ts"] + a["dur"] <= b["ts"] + 2   # disjoint
            assert sum(s["dur"] for s in inside) <= t["dur"] + 2

    def test_queue_and_slot_walls_positive(self, trace_doc):
        spans = [e for e in trace_doc["traceEvents"]
                 if e.get("ph") == "X"
                 and str(e.get("name", "")).startswith("req ")]
        assert spans
        assert all(s["dur"] >= 0 for s in spans)


class TestDashboards:
    def test_serve_report_names_tail_component(self, chaos_run):
        r = subprocess.run(
            [sys.executable,
             os.path.join(REPO, "scripts", "serve_report.py"),
             chaos_run[0]],
            capture_output=True, text=True, timeout=120,
        )
        assert r.returncode == 0, r.stderr[-2000:]
        md = r.stdout
        assert "Tail attribution" in md
        assert "p99 verdict" in md
        for label in ("queue-wait", "prefill", "decode-active",
                      "preempted-wait", "restart-overhead"):
            assert label in md
        assert "Flight records" in md and "serve_restart" in md

    def test_report_run_serving_section_and_check(self, preempt_run):
        path = preempt_run[0]
        for args, want_rc in ((["--check", path], 0), ([path], 0)):
            r = subprocess.run(
                [sys.executable,
                 os.path.join(REPO, "scripts", "report_run.py")] + args,
                capture_output=True, text=True, timeout=120,
            )
            assert r.returncode == want_rc, (args, r.stderr[-1500:])
        assert "## Serving" in r.stdout
        assert "serve_report.py" in r.stdout

    def test_serve_report_rejects_training_only_file(self, tmp_path):
        path = tmp_path / "train.jsonl"
        path.write_text(json.dumps(
            {"kind": "run_meta", "ts": 0.0, "engine": "DDP"}) + "\n"
            + json.dumps({"step": 0, "ts": 1.0, "loss": 2.0}) + "\n")
        r = subprocess.run(
            [sys.executable,
             os.path.join(REPO, "scripts", "serve_report.py"),
             str(path)],
            capture_output=True, text=True, timeout=120,
        )
        assert r.returncode == 2
        assert "no serving records" in r.stderr


class TestWireLinkSplit:
    """ICI-vs-DCN ledger split (ROADMAP satellite): cross-slice bytes
    measured from the compiled replica_groups on a CPU-emulated 2-slice
    mesh — a pinned number, not a model."""

    def test_group_membership_parser(self):
        from tiny_deepspeed_tpu.utils.hlo_comm import _group_members
        assert _group_members(
            "x replica_groups={{0,1},{2,3}} y") == ((0, 1), (2, 3))
        assert _group_members(
            "x replica_groups=[2,4]<=[8] y") == ((0, 1, 2, 3),
                                                 (4, 5, 6, 7))
        # transposed iota: groups stride across the leading dim
        assert _group_members(
            "x replica_groups=[4,2]<=[2,4]T(1,0) y") == (
            (0, 4), (1, 5), (2, 6), (3, 7))
        # 1-D iota = one group of everybody
        assert _group_members(
            "x replica_groups=[8]<=[8] y") == (
            (0, 1, 2, 3, 4, 5, 6, 7),)
        assert _group_members("x no groups here y") is None

    def test_two_slice_mesh_split_pins_dcn_bytes(self):
        """On an emulated 2-slice (4+4) mesh: a model-axis psum (groups
        {0..3},{4..7}) stays intra-slice -> ICI; a data-axis psum
        (groups {0,4},{1,5},...) spans slices -> ALL its wire bills to
        DCN.  The split is read off the compiled HLO's replica_groups,
        so the numbers equal the ledger's per-op wire exactly."""
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        from tiny_deepspeed_tpu.parallel.mesh import make_mesh
        from tiny_deepspeed_tpu.utils.hlo_comm import (
            collective_ledger, ledger_summary, wire_link_split,
        )
        if jax.device_count() < 8:
            pytest.skip("needs 8 emulated CPU devices")
        mesh = make_mesh((2, 4), ("data", "model"))
        gmap = {i: i // 4 for i in range(8)}  # two slices of four
        x = jnp.ones((8, 8), jnp.float32)

        intra = jax.jit(shard_map(
            lambda a: jax.lax.psum(a, "model"), mesh=mesh,
            in_specs=P("data", "model"), out_specs=P("data")))
        led = collective_ledger(intra.lower(x).compile().as_text())
        split = wire_link_split(led, gmap)
        assert split["dcn_wire_bytes"] == 0.0
        assert split["ici_wire_bytes"] == pytest.approx(
            led["wire_bytes"]["all-reduce"])
        assert split["unresolved_wire_bytes"] == 0.0

        cross = jax.jit(shard_map(
            lambda a: jax.lax.psum(a, "data"), mesh=mesh,
            in_specs=P("data", "model"), out_specs=P(None, "model")))
        led = collective_ledger(cross.lower(x).compile().as_text())
        split = wire_link_split(led, gmap)
        assert split["ici_wire_bytes"] == 0.0
        assert split["dcn_wire_bytes"] == pytest.approx(
            led["wire_bytes"]["all-reduce"])
        assert split["dcn_frac"] == 1.0
        # the run_meta form carries the same split
        summ = ledger_summary(led, granule_of=gmap)
        assert summ["wire_bytes_by_link"]["dcn_wire_bytes"] \
            == split["dcn_wire_bytes"]

    # tier-1 budget: the DDP engine compile (~5s) re-checks WIRING only —
    # the split math + 2-slice classification stay quick above, and the
    # gauge NAME stays pinned by the hygiene grep (test_repo_hygiene)
    @pytest.mark.slow
    def test_capture_compiled_gauges_dcn(self, tmp_path):
        """Telemetry wiring: capture_compiled with an (emulated) granule
        map documents cross-slice bytes as the dcn_wire_bytes gauge and
        embeds the split in comm_measured — the DDP grad all-reduce
        spans the whole data axis, so under a 2-slice emulation ALL its
        wire is DCN-crossing."""
        from tiny_deepspeed_tpu import AdamW, DDP, Telemetry
        if jax.device_count() < 8:
            pytest.skip("needs 8 emulated CPU devices")
        model = GPT2Model(GPTConfig(
            block_size=32, vocab_size=128, n_layer=2, n_head=2,
            n_embd=32, compute_dtype=jnp.float32))
        telem = Telemetry()
        eng = DDP(model, AdamW(lr=1e-3), telemetry=telem)
        state = eng.init(jax.random.PRNGKey(0))
        k1, k2 = jax.random.split(jax.random.PRNGKey(1))
        batch = (jax.random.randint(k1, (8, 32), 0, 128),
                 jax.random.randint(k2, (8, 32), 0, 128))
        gmap = {i: i // 4 for i in range(8)}
        out = telem.capture_compiled(state, batch, granule_of=gmap)
        split = out["comm_measured"]["wire_bytes_by_link"]
        assert split["dcn_wire_bytes"] > 0.0
        assert telem.gauge("dcn_wire_bytes") == pytest.approx(
            split["dcn_wire_bytes"])
        # the data-axis gradient reduction is what crosses
        assert split["dcn_wire_bytes"] == pytest.approx(
            out["comm_measured"]["wire_bytes"]["all-reduce"]
            + out["comm_measured"]["wire_bytes"].get(
                "reduce-scatter", 0.0), rel=0.01)
