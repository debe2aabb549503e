# Copyright 2026 tiny-deepspeed-tpu authors
# SPDX-License-Identifier: Apache-2.0

"""Step-trace timeline, per-layer health, flight recorder, and straggler
attribution (ISSUE 5) on the CPU mesh: layers-off HLO identity, per-layer
norms vs an independent recompute, one-step first-NaN localization into
the flight record, ring wraparound / anomaly flush / no-sync hot path,
straggler gauges with an injected all-gather, and the Chrome-trace export
whose loop-resident collective spans carry the exact HLO-ledger wire
bytes."""

import importlib.util
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tiny_deepspeed_tpu import (
    AdamW, DDP, GPTConfig, GPT2Model, Telemetry, Zero3,
)
from tiny_deepspeed_tpu.models.moe import MoEConfig, MoEGPT
from tiny_deepspeed_tpu.telemetry import (
    LAYER_FIELDS, FlightRecorder, first_nonfinite_layer, schema, trace,
)
from tiny_deepspeed_tpu.utils import MetricsLogger

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TINY = GPTConfig(
    block_size=32, vocab_size=128, n_layer=2, n_head=2, n_embd=32,
    compute_dtype=jnp.float32,
)


def make_batch(seed=1, b=8, t=32, vocab=128):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    return (jax.random.randint(k1, (b, t), 0, vocab),
            jax.random.randint(k2, (b, t), 0, vocab))


@pytest.fixture(scope="module")
def model():
    return GPT2Model(TINY)


@pytest.fixture(scope="module")
def layers_engine(model):
    telem = Telemetry(layers=True)
    return DDP(model, AdamW(lr=1e-3), telemetry=telem), telem


class TestLayersOffIsFree:
    def test_layers_off_program_identical(self, model):
        """Acceptance: the layers knob OFF lowers the byte-identical step
        program as plain telemetry — the per-layer machinery costs
        nothing unless asked for."""
        e_plain = DDP(model, AdamW(lr=1e-3), telemetry=Telemetry())
        e_off = DDP(model, AdamW(lr=1e-3),
                    telemetry=Telemetry(layers=False))
        batch = make_batch(1)
        s1 = e_plain.init(jax.random.PRNGKey(0))
        s2 = e_off.init(jax.random.PRNGKey(0))
        assert e_plain._step.lower(s1, batch).as_text() \
            == e_off._step.lower(s2, batch).as_text()

class TestLayerHealth:
    # DDP (replicated grads) reuses the module-scoped layers_engine; the
    # one fresh compile is Zero3 WITH accum_steps=2 — the far end of the
    # sharding spectrum and the microbatch-accumulation path in a single
    # program (Zero2 alone would add a third CPU-mesh compile for no new
    # code path; test_telemetry already pins the whole-run health vector
    # across all three stages).  The accumulated microbatches are the
    # SAME batch twice, so the mean gradient equals the single-batch
    # gradient and ONE host-side recompute references both engines.
    @pytest.mark.parametrize("mode", ["ddp", "zero3_accum"])
    def test_per_layer_grad_norms_match_recompute(self, model, mode,
                                                  layers_engine):
        """Per-layer grad norms in the layer-health matrix match an
        independent host-side recompute from plain autodiff, across
        sharding stages and microbatch accumulation (the sums are
        logical, so neither may change them; probe sq-sums accumulate
        across microbatches and take the norm once)."""
        if mode == "ddp":
            eng, telem = layers_engine
        else:
            telem = Telemetry(layers=True)
            eng = Zero3(model, AdamW(lr=1e-3), accum_steps=2,
                        telemetry=telem)
        state = eng.init(jax.random.PRNGKey(0))
        idx, tgt = make_batch(7)
        before = {n: np.asarray(p, dtype=np.float64)
                  for n, p in state.params.items()}

        batch = ((idx, tgt) if mode == "ddp"
                 else (jnp.stack([idx, idx]), jnp.stack([tgt, tgt])))
        state, _ = eng.step(state, batch)
        mat = telem.layer_health()
        assert mat is not None and mat.shape == (TINY.n_layer,
                                                 len(LAYER_FIELDS))

        ref_params = {n: jnp.asarray(v, jnp.float32)
                      for n, v in before.items()}
        _, grads_ref = jax.value_and_grad(
            lambda p: model.apply(p, idx, tgt, pctx=None)
        )(ref_params)
        per_layer = np.zeros(TINY.n_layer)
        for n, g in grads_ref.items():
            if n.startswith("h."):
                g = np.asarray(g, dtype=np.float64)
                per_layer += np.square(g).reshape(g.shape[0], -1).sum(1)
        np.testing.assert_allclose(
            mat[:, LAYER_FIELDS.index("grad_norm")],
            np.sqrt(per_layer), rtol=2e-3,
        )
        # healthy step: every non-finite column is exactly zero, and the
        # forward/backward activation norms are positive (under accum the
        # act/dact sq-sums cover BOTH microbatches — positivity, not
        # equality, is the check there)
        for col in ("act_nonfinite", "dact_nonfinite", "grad_nonfinite"):
            assert np.all(mat[:, LAYER_FIELDS.index(col)] == 0.0)
        assert np.all(mat[:, LAYER_FIELDS.index("act_norm")] > 0)
        assert np.all(mat[:, LAYER_FIELDS.index("dact_norm")] > 0)
        assert np.all(np.isfinite(mat))

    def test_nan_localized_to_injected_layer_in_one_step(self,
                                                         layers_engine,
                                                         tmp_path):
        """Acceptance: a forced overflow in layer k is localized to layer
        k in the flight record after ONE step — no bisection.  The
        backward poisons EVERY layer's grads (the cotangent of a NaN loss
        is NaN everywhere), so only the in-scan forward activation stats
        can name the layer."""
        k = 1
        eng, telem = layers_engine  # shared compile; pollution reset below
        state = eng.init(jax.random.PRNGKey(0))
        batch = make_batch(3)
        bad = dict(state.params)
        for name in ("h.mlp.fc.w", "h.mlp.proj.w"):
            w = np.asarray(bad[name]).copy()
            w[k] *= 1e30  # f32 overflow in layer k's MLP product
            bad[name] = jnp.asarray(w)
        state = state.replace(params=bad)

        with telem.step() as t:
            state, loss = eng.step(state, batch)
        assert not np.isfinite(float(loss))
        mat = telem.layer_health()
        # grads alone CANNOT localize: every layer's grads are poisoned
        assert np.all(mat[:, LAYER_FIELDS.index("grad_nonfinite")] > 0)
        src = first_nonfinite_layer(mat)
        assert src == (k, "act_nonfinite")

        # the non-finite health arms the flight flush in the same step
        assert telem.flight_pending == "nonfinite"
        path = str(tmp_path / "nan.jsonl")
        with MetricsLogger(path, stdout=False) as ml:
            assert telem.maybe_flush_flight(ml) == "nonfinite"
            assert telem.maybe_flush_flight(ml) is None  # one-shot
        rec = json.loads(open(path).read().strip())
        assert rec["kind"] == "flight" and rec["reason"] == "nonfinite"
        assert rec["first_nonfinite_layer"] == k
        entry = rec["steps"][-1]
        assert entry["first_nonfinite_layer"] == k
        assert entry["nonfinite_field"] == "act_nonfinite"
        assert len(entry["layers"]) == TINY.n_layer
        counts, errs = schema.validate_file(path)
        assert errs == [] and counts["meta"] == 1
        # un-pollute the shared telemetry for later fixture users
        telem._recent.clear()

    def test_rejected_for_incapable_model(self):
        moe = MoEGPT(MoEConfig(
            block_size=32, vocab_size=128, n_layer=2, n_head=2, n_embd=32,
            n_expert=2, compute_dtype=jnp.float32,
        ))
        with pytest.raises(ValueError, match="layer_health_capable"):
            DDP(moe, AdamW(lr=1e-3), telemetry=Telemetry(layers=True))

    def test_layers_composes_with_grad_buckets(self, model):
        """Layer health x bucketed grads used to refuse; the scheduler
        composes them now (probe + grad slots -> the composed lowering)
        and the per-layer matrix still rides the step.  The deep parity
        pins live in tests/test_schedule.py."""
        telem = Telemetry(layers=True)
        eng = DDP(model, AdamW(lr=1e-3), grad_buckets=2, telemetry=telem)
        assert eng._lowering == "composed"
        state = eng.init(jax.random.PRNGKey(0))
        state, loss = eng.step(state, make_batch(3))
        assert np.isfinite(float(loss))
        mat = telem.layer_health()
        assert mat is not None and mat.shape[0] == TINY.n_layer
        assert np.all(np.isfinite(mat))

    def test_first_nonfinite_layer_resolution_order(self):
        mat = np.zeros((4, 6))
        assert first_nonfinite_layer(mat) is None
        m = mat.copy()
        m[2, 1] = 1  # forward act at layer 2 -> first forward layer wins
        m[3, 1] = 5
        m[0, 3] = 1
        assert first_nonfinite_layer(m) == (2, "act_nonfinite")
        m = mat.copy()
        m[0, 3] = m[1, 3] = 1  # backward-only: LAST layer with bad dact
        assert first_nonfinite_layer(m) == (1, "dact_nonfinite")
        m = mat.copy()
        m[3, 5] = 2.0  # dW-only overflow names itself
        assert first_nonfinite_layer(m) == (3, "grad_nonfinite")


class _Unsyncable:
    """Stand-in for a device array that must NOT be materialized on the
    flight recorder's hot path."""

    def __array__(self, *a, **k):
        raise AssertionError(
            "flight recorder synced a device array on the hot path"
        )


class TestFlightRecorder:
    def test_ring_wraparound(self):
        fr = FlightRecorder(capacity=4)
        for i in range(10):
            fr.record(i, step_s=0.1 * i, health={"loss": float(i)})
        assert len(fr) == 4
        snap = fr.snapshot()
        assert [e["step"] for e in snap] == [6, 7, 8, 9]  # oldest->newest
        assert snap[-1]["health"]["loss"] == 9.0

    def test_record_never_syncs_devices(self):
        fr = FlightRecorder(capacity=8)
        for i in range(20):  # wraparound included: still no sync
            fr.record(i, step_s=0.1, health={"loss": 1.0},
                      layers=_Unsyncable())
        # flush IS allowed to sync — swap in real matrices first
        for e in fr._buf:
            e["layers"] = np.zeros((2, 6))
        lines = []

        class _Log:
            def log_meta(self, **kw):
                lines.append(kw)

        fr.flush(_Log(), "slow_step")
        assert lines and lines[0]["kind"] == "flight"
        assert len(lines[0]["steps"]) == 8

    def test_anomaly_triggered_flush(self, tmp_path):
        """The slow-step anomaly arms a flight flush alongside the xprof
        trace; maybe_flush_flight writes ONE schema-valid record holding
        the recorded history."""
        # anomaly_min_steps above the instrumented-step count: the real
        # (jittery) CPU wall times can never self-arm the detector, so
        # the injected slow sample below is deterministic
        telem = Telemetry(anomaly_factor=2.0, anomaly_min_steps=5,
                          flight_steps=8,
                          tracer=(lambda p: None, lambda: None))
        for _ in range(4):
            with telem.step() as t:
                t.observe(jnp.zeros((5,)))
        assert telem.flight_pending is None
        telem.note_step_time(0.1)             # 5th sample: detector live
        assert telem.note_step_time(1.0)      # injected slow step
        assert telem.flight_pending == "slow_step"
        path = str(tmp_path / "flight.jsonl")
        with MetricsLogger(path, stdout=False) as ml:
            assert telem.maybe_flush_flight(ml) == "slow_step"
        rec = json.loads(open(path).read().strip())
        assert rec["kind"] == "flight" and rec["reason"] == "slow_step"
        assert len(rec["steps"]) == 4         # the instrumented history
        counts, errs = schema.validate_file(path)
        assert errs == []
        assert telem.counters["flight_flushes"].value == 1

    def test_capacity_validation(self):
        with pytest.raises(ValueError):
            FlightRecorder(capacity=0)


class TestStragglers:
    def test_injected_allgather(self):
        telem = Telemetry()
        rec = telem.sample_stragglers(
            step_s=0.1, allgather=lambda mine: [mine, mine, 3 * mine,
                                                mine]
        )
        assert rec["hosts"] == 4
        assert rec["slowest_host"] == 2
        # slowest 0.3 vs median 0.1: 2/3 of the slowest host's time the
        # median host would not have spent — a [0, 1) FRACTION, not an
        # unbounded slowdown ratio
        assert rec["straggler_frac"] == pytest.approx(2.0 / 3.0)
        assert telem.gauges["straggler_frac"] \
            == pytest.approx(2.0 / 3.0)
        assert telem.gauges["straggler_slowest_host"] == 2
        assert telem.gauges["straggler_slowest_step_s"] \
            == pytest.approx(0.3)

    def test_single_host_degenerate(self):
        telem = Telemetry()
        rec = telem.sample_stragglers(step_s=0.25)
        assert rec == {
            "hosts": 1, "quantity": "step_s",
            "step_s_by_host": [0.25], "slowest_host": 0,
            "straggler_frac": 0.0,
        }

    def test_record_is_schema_valid(self, tmp_path):
        telem = Telemetry()
        path = str(tmp_path / "s.jsonl")
        with MetricsLogger(path, stdout=False) as ml:
            ml.log_meta(kind="straggler", **telem.sample_stragglers(
                step_s=0.1, quantity="host_prep_s",
            ))
        counts, errs = schema.validate_file(path)
        assert errs == [] and counts["meta"] == 1


@pytest.fixture(scope="module")
def traced_run_jsonl(tmp_path_factory, layers_engine):
    """An instrumented mini-run's JSONL with run_meta + straggler
    records — what examples/common.py writes with --telemetry."""
    eng, telem = layers_engine
    path = str(tmp_path_factory.mktemp("trace") / "run.jsonl")
    state = eng.init(jax.random.PRNGKey(0))
    batch = make_batch(3)
    with MetricsLogger(path, stdout=False) as ml:
        ml.log_meta(**telem.run_meta(
            state, batch, model="tiny", n_params=eng.model.num_params(),
            batch=8, seq_len=32, tokens_per_step=8 * 32,
        ))
        for i in range(3):
            with telem.step() as t:
                t.mark("data")
                t.mark("h2d")
                state, loss = eng.step(state, batch)
            ml.log(i, loss=telem.last_health["loss"],
                   step_s=telem.timer.times[-1],
                   tokens_per_s=8 * 32 / max(telem.timer.times[-1], 1e-9),
                   **telem.step_record())
        ml.log_meta(kind="straggler", **telem.sample_stragglers())
        telem.flush(ml)
    return path


def _load_script(name):
    spec = importlib.util.spec_from_file_location(
        f"{name}_under_test", os.path.join(REPO, "scripts", f"{name}.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class TestTraceTimeline:
    def test_schema_validates_traced_run(self, traced_run_jsonl):
        counts, errs = schema.validate_file(traced_run_jsonl)
        assert errs == []
        assert counts["step"] == 3 and counts["meta"] == 3

    def test_trace_view_cli_draws_no_training_timeline(
            self, traced_run_jsonl, tmp_path, capsys):
        """A training run's JSONL has no serving records: the viewer
        says where a step's timeline comes from now (a profiler trace)
        and writes nothing, instead of drawing a schematic one."""
        tv = _load_script("trace_view")
        out = str(tmp_path / "t.trace.json")
        assert tv.main([traced_run_jsonl, "-o", out]) == 2
        assert "--profile" in capsys.readouterr().err
        assert not os.path.exists(out)
        assert not hasattr(trace, "chrome_trace")

    def test_trace_view_cli_missing_and_empty(self, tmp_path):
        tv = _load_script("trace_view")
        assert tv.main(["/nonexistent.jsonl"]) == 2
        empty = str(tmp_path / "empty.jsonl")
        open(empty, "w").close()
        assert tv.main([empty]) == 2


class TestReportRunHardening:
    def test_empty_file_exits_nonzero(self, tmp_path, capsys):
        rr = _load_script("report_run")
        empty = str(tmp_path / "empty.jsonl")
        open(empty, "w").close()
        assert rr.main([empty]) == 2
        assert "no records" in capsys.readouterr().err
        assert rr.main(["--check", empty]) == 2

    def test_truncated_line_exits_nonzero(self, tmp_path, capsys):
        rr = _load_script("report_run")
        path = str(tmp_path / "trunc.jsonl")
        with open(path, "w") as f:
            f.write(json.dumps({"step": 0, "ts": 1.0, "loss": 2.0}) + "\n")
            f.write('{"step": 1, "ts": 2.0, "los')  # crashed writer
        assert rr.main([path]) == 1
        err = capsys.readouterr().err
        assert "invalid JSON" in err and "valid records" in err
        assert rr.main(["--check", path]) == 1

    def test_check_rejects_unknown_kind(self, tmp_path, capsys):
        rr = _load_script("report_run")
        path = str(tmp_path / "kind.jsonl")
        with open(path, "w") as f:
            f.write(json.dumps({"kind": "mystery_kind", "ts": 1.0}) + "\n")
        assert rr.main(["--check", path]) == 1
        assert "mystery_kind" in capsys.readouterr().err

    def test_check_warns_on_version_mismatch(self, tmp_path, capsys):
        rr = _load_script("report_run")
        path = str(tmp_path / "ver.jsonl")
        with open(path, "w") as f:
            f.write(json.dumps({
                "kind": "run_meta", "ts": 1.0,
                "schema_version": schema.SCHEMA_VERSION + 1,
            }) + "\n")
        assert rr.main(["--check", path]) == 0  # advisory, not an error
        assert "schema v" in capsys.readouterr().err

    def test_report_renders_tail_and_straggler(self, traced_run_jsonl):
        rr = _load_script("report_run")
        metas, steps, _ = rr.load_run(traced_run_jsonl)
        report = rr.render_report(metas, steps, source=traced_run_jsonl)
        assert "p99" in report and "max" in report


class TestStepTimerTail:
    def test_p99_and_max(self):
        from tiny_deepspeed_tpu.utils import StepTimer
        timer = StepTimer()
        timer.times = [10.0] + [0.1] * 99 + [0.5]  # first sample dropped
        assert timer.max_s == 0.5
        assert timer.p99_s > timer.p95_s
        assert timer.p99_s <= 0.5
        empty = StepTimer()
        assert empty.max_s == 0.0 and empty.p99_s == 0.0
