# Copyright 2026 tiny-deepspeed-tpu authors
# SPDX-License-Identifier: Apache-2.0

"""Telemetry subsystem on the CPU mesh: on-device health metrics vs host
recomputation across ZeRO stages, telemetry-off HLO identity (the knob is
free when off), step-timer upgrades (p50/p95, segments, recompile
attribution, exception safety), anomaly one-shot firing, the JSONL schema
round-trip through scripts/report_run.py.
"""

import importlib.util
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tiny_deepspeed_tpu import (
    AdamW, DDP, GPTConfig, GPT2Model, SingleDevice, Telemetry, Zero2, Zero3,
)
from tiny_deepspeed_tpu.telemetry import HEALTH_FIELDS, health_dict, schema
from tiny_deepspeed_tpu.utils import MetricsLogger, StepTimer

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
def ddp_off(model):
    return DDP(model, AdamW(lr=1e-3))


@pytest.fixture(scope="module")
def ddp_on(model):
    telem = Telemetry()
    return DDP(model, AdamW(lr=1e-3), telemetry=telem), telem


def _tree_sq_sum(tree):
    return sum(
        float(np.sum(np.square(np.asarray(x, dtype=np.float64))))
        for x in jax.tree.leaves(tree)
    )


class TestHealthMetrics:
    """Health-vector values match an independent host-side recompute for a
    tiny GPT-2, across ZeRO stages 0/2/3 (the norms are GLOBAL: XLA psums
    the sharded partial sums, so every stage must report the same
    numbers)."""

    # tier-1 budget (scripts/tier1_times.py): DDP's replicated grads are
    # the degenerate case of the cross-shard psum the Zero2/Zero3 rows
    # pin — it runs in the full tier
    @pytest.mark.parametrize("eng_cls", [
        pytest.param(DDP, marks=pytest.mark.slow), Zero2, Zero3,
    ])
    def test_matches_host_recompute(self, model, eng_cls):
        telem = Telemetry()
        eng = eng_cls(model, AdamW(lr=1e-3), telemetry=telem)
        state = eng.init(jax.random.PRNGKey(0))
        idx, tgt = make_batch(7)

        # host-side copies BEFORE the step (the step donates its input)
        before = {
            n: np.asarray(p, dtype=np.float64)
            for n, p in state.params.items()
        }
        # independent grad recompute: plain autodiff of the model's loss on
        # replicated params (single-device pctx)
        sd = SingleDevice(model, AdamW(lr=1e-3))
        ref_params = {n: jnp.asarray(v, jnp.float32) for n, v in
                      before.items()}
        loss_ref, grads_ref = jax.value_and_grad(
            lambda p: model.apply(p, idx, tgt, pctx=sd.pctx)
        )(ref_params)

        state, loss = eng.step(state, (idx, tgt))
        h = telem.poll()
        assert h is not None and set(h) == set(HEALTH_FIELDS)

        assert h["nonfinite_grads"] == 0
        np.testing.assert_allclose(h["loss"], float(loss_ref), rtol=1e-4)
        np.testing.assert_allclose(
            h["grad_norm"], np.sqrt(_tree_sq_sum(grads_ref)), rtol=2e-3,
        )
        after = {
            n: np.asarray(p, dtype=np.float64)
            for n, p in state.params.items()
        }
        np.testing.assert_allclose(
            h["param_norm"], np.sqrt(_tree_sq_sum(after)), rtol=2e-3,
        )
        upd_sq = sum(
            float(np.sum(np.square(after[n] - before[n]))) for n in after
        )
        np.testing.assert_allclose(
            h["update_norm"], np.sqrt(upd_sq), rtol=5e-3,
        )

    def test_health_dict_field_order(self):
        vec = np.array([1.5, 2.0, 3.0, 4.0, 0.0])
        h = health_dict(vec)
        assert h["loss"] == 1.5  # loss MUST be element 0 (the sync barrier)
        assert h["nonfinite_grads"] == 0
        assert isinstance(h["nonfinite_grads"], int)


class TestTelemetryOffIsFree:
    """Acceptance: telemetry is opt-in and free when off."""

    def test_off_program_identical_to_default(self, model, ddp_off):
        """telemetry=None lowers the byte-identical step program as an
        engine constructed without the knob at all."""
        eng_none = DDP(model, AdamW(lr=1e-3), telemetry=None)
        state = ddp_off.init(jax.random.PRNGKey(0))
        batch = make_batch(1)
        text_default = ddp_off._step.lower(state, batch).as_text()
        state2 = eng_none.init(jax.random.PRNGKey(0))
        text_none = eng_none._step.lower(state2, batch).as_text()
        assert text_default == text_none

    @pytest.mark.slow  # tier-1 budget: telemetry-off byte-identity is
    # the quick primary pin; this ledger corollary runs in the full tier
    def test_off_vs_on_collective_ledger(self, model, ddp_off, ddp_on):
        """The health norms may add only scalar-sized reductions: the
        telemetry-on step's collective ledger stays within 1 KB of the
        off step's."""
        from tiny_deepspeed_tpu.utils.hlo_comm import hlo_comm_report
        batch = make_batch(1)
        eng_on, _ = ddp_on
        led_off = hlo_comm_report(
            ddp_off, ddp_off.init(jax.random.PRNGKey(0)), batch
        )
        led_on = hlo_comm_report(
            eng_on, eng_on.init(jax.random.PRNGKey(0)), batch
        )
        assert abs(led_on["total_wire_bytes"]
                   - led_off["total_wire_bytes"]) <= 1024

    @pytest.mark.slow  # tier-1 budget: subsumed by the byte-identity
    # pin (identical programs have identical signatures) — full tier
    def test_step_returns_same_signature(self, model, ddp_off, ddp_on):
        eng_on, telem = ddp_on
        batch = make_batch(1)
        s_off, l_off = ddp_off.step(
            ddp_off.init(jax.random.PRNGKey(0)), batch
        )
        s_on, l_on = eng_on.step(eng_on.init(jax.random.PRNGKey(0)), batch)
        assert float(l_off) == float(l_on)
        assert telem.poll()["loss"] == float(l_on)

    def test_overhead_under_two_percent(self, model, ddp_off, ddp_on):
        """<2% step-time overhead on the CPU-mesh tiny config, measured by
        StepTimer p50.  XLA-CPU step times drift +-40% with machine load,
        so the two engines are sampled INTERLEAVED (drift hits both
        distributions equally) with a small absolute guard for timer
        granularity on top of the 2% relative bound."""
        eng_on, _ = ddp_on
        batch = make_batch(1)
        timers = {False: StepTimer(), True: StepTimer()}
        states = {False: ddp_off.init(jax.random.PRNGKey(0)),
                  True: eng_on.init(jax.random.PRNGKey(0))}
        engines = {False: ddp_off, True: eng_on}
        for eng, state in engines.items():  # warm both compiles
            states[eng], _ = engines[eng].step(states[eng], batch)
        # 32 interleaved rounds: the minimum needs one quiet sample of each
        # engine, and under the tier-1 run's six workers 16 rounds did not
        # always hold one (PR 26: 15.7 ms against 10.3 ms, once)
        for _ in range(32):
            for on in (False, True):
                timer = timers[on]
                with timer.step() as t:
                    states[on], loss = engines[on].step(states[on], batch)
                    t.observe(loss)
        # compare best-case samples: scheduler noise on the 8-thread CPU
        # mesh is one-sided (a step is only ever SLOWED by load), so the
        # minimum over interleaved samples is the stable estimate of each
        # program's true cost; a small absolute guard covers CPU fusion-
        # dispatch granularity that a real accelerator doesn't see
        off = min(timers[False].times)
        on = min(timers[True].times)
        assert on <= off * 1.02 + 0.003, (on, off)


class TestStepTimerUpgrades:
    def test_percentiles(self):
        timer = StepTimer()
        timer.times = [10.0] + [0.1] * 10 + [0.2]  # first sample dropped
        assert timer.p50_s == pytest.approx(0.1)
        assert timer.p95_s <= 0.2
        assert timer.p95_s >= 0.1

    def test_failed_step_clears_observed_output(self):
        timer = StepTimer()
        with pytest.raises(RuntimeError):
            with timer.step() as t:
                t.observe(jnp.ones((4,)))
                raise RuntimeError("boom")
        assert timer._last_out is None
        assert timer.times == []  # no sample recorded for the failed step
        # and the next step does not sync the stale output
        with timer.step() as t:
            pass
        assert len(timer.times) == 1

    def test_marks_split_segments(self):
        timer = StepTimer()
        with timer.step() as t:
            t.mark("data")
            t.mark("h2d")
        seg = timer.segments[-1]
        assert set(seg) == {"data_s", "h2d_s", "compute_s"}
        assert abs(sum(seg.values()) - timer.times[-1]) < 0.05

    def test_compile_watch_counts_lowerings(self):
        f = jax.jit(lambda x: x * 2)
        timer = StepTimer()
        timer.watch(f)
        with timer.step() as t:
            t.observe(f(jnp.ones((4,))))
        with timer.step() as t:
            t.observe(f(jnp.ones((4,))))
        with timer.step() as t:  # new shape -> recompile
            t.observe(f(jnp.ones((8,))))
        assert timer.compiled_steps == [1, 0, 1]
        assert timer.compile_count == 2

    def test_fetch_full_delivers_whole_vector(self):
        timer = StepTimer(fetch_full=True)
        with timer.step() as t:
            t.observe(jnp.arange(5.0))
        assert timer.last_value == 0.0
        np.testing.assert_array_equal(timer.last_host,
                                      np.arange(5.0, dtype=np.float32))


class TestMetricsLoggerContextManager:
    def test_closes_on_exception(self, tmp_path):
        path = str(tmp_path / "m.jsonl")
        with pytest.raises(ValueError):
            with MetricsLogger(path, stdout=False) as ml:
                ml.log(0, loss=1.0)
                fh = ml._fh
                raise ValueError("boom")
        assert ml._fh is None and fh.closed
        # close() still works standalone (and is idempotent)
        ml2 = MetricsLogger(path, stdout=False)
        ml2.close()
        ml2.close()

    def test_log_meta_writes_kind_record(self, tmp_path, capsys):
        path = str(tmp_path / "m.jsonl")
        with MetricsLogger(path, stdout=True) as ml:
            ml.log_meta(kind="run_meta", engine="DDP(...)", devices=8)
        assert capsys.readouterr().out == ""  # meta is JSONL-only
        rec = json.loads(open(path).read().strip())
        assert rec["kind"] == "run_meta" and rec["devices"] == 8


class TestAnomalyTrigger:
    def _telem(self, tmp_path, calls):
        return Telemetry(
            trace_dir=str(tmp_path),
            anomaly_factor=2.0,
            anomaly_min_steps=3,
            tracer=(lambda p: calls.append(("start", p)),
                    lambda: calls.append(("stop",))),
        )

    def test_fires_exactly_once(self, tmp_path):
        calls = []
        telem = self._telem(tmp_path, calls)
        for _ in range(5):
            assert not telem.note_step_time(0.1)
        assert telem.note_step_time(0.5)         # injected slow step
        assert not telem.note_step_time(0.5)     # armed: no re-fire
        # the NEXT instrumented step runs under the tracer, once
        for _ in range(3):
            with telem.step() as t:
                t.observe(jnp.float32(1.0))
        assert calls == [("start", os.path.join(str(tmp_path), "anomaly")),
                         ("stop",)]
        assert telem.counters["anomaly_traces"].value == 1
        assert telem.counters["anomalies"].value == 1
        # later slow steps never re-arm
        assert not telem.note_step_time(10.0)

    def test_no_trace_dir_still_fires_once(self, tmp_path):
        telem = Telemetry(anomaly_factor=2.0, anomaly_min_steps=3,
                          tracer=(lambda p: None, lambda: None))
        for _ in range(4):
            telem.note_step_time(0.1)
        assert telem.note_step_time(1.0)
        assert not telem.note_step_time(1.0)
        assert telem.counters["anomalies"].value == 1


def _load_report_run():
    spec = importlib.util.spec_from_file_location(
        "report_run_under_test", os.path.join(REPO, "scripts",
                                              "report_run.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def run_jsonl(tmp_path_factory, ddp_on):
    """A real instrumented mini-run's JSONL: run_meta (measured HLO
    ledger), per-step records with health + segments, and the final
    telemetry_summary."""
    eng, telem = ddp_on
    path = str(tmp_path_factory.mktemp("telem") / "run.jsonl")
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
        telem.flush(ml)
    return path


class TestSchemaAndReport:
    def test_schema_validates_clean_run(self, run_jsonl):
        counts, errs = schema.validate_file(run_jsonl)
        assert errs == []
        assert counts["step"] == 3 and counts["meta"] == 2

    def test_schema_rejects_drift(self):
        assert schema.validate_record({"step": 0}) != []          # no ts
        assert schema.validate_record(
            {"step": 0, "ts": 1.0, "mystery_field": 1}
        ) != []
        assert schema.validate_record(
            {"step": 0, "ts": 1.0, "loss": "high"}
        ) != []
        assert schema.validate_record(
            {"kind": "nope", "ts": 1.0}
        ) != []
        assert schema.validate_record(
            {"step": 0, "ts": 1.0, "loss": 2.5, "grad_norm": 0.1}
        ) == []

    def test_report_renders_markdown(self, run_jsonl):
        rr = _load_report_run()
        metas, steps, errs = rr.load_run(run_jsonl)
        assert errs == []
        report = rr.render_report(metas, steps, source=run_jsonl)
        assert "# Run report" in report
        assert "## Throughput" in report
        assert "steps recorded: 3" in report
        # measured HLO-ledger bytes render next to the ring model
        assert "HLO ledger" in report
        assert "ring-model prediction" in report
        assert "all-reduce" in report
        assert "## Health" in report
        assert "grad norm" in report

    def test_check_cli_smoke(self, run_jsonl, tmp_path):
        """tier-1 smoke of `report_run.py --check`: rc 0 on a clean file,
        non-zero on schema drift."""
        script = os.path.join(REPO, "scripts", "report_run.py")
        r = subprocess.run(
            [sys.executable, script, "--check", run_jsonl],
            capture_output=True, text=True, timeout=120,
        )
        assert r.returncode == 0, r.stderr
        assert "ok" in r.stdout
        # drifted copy: one record with an unknown field
        bad = str(tmp_path / "bad.jsonl")
        with open(run_jsonl) as f, open(bad, "w") as g:
            g.write(f.read())
            g.write(json.dumps(
                {"step": 99, "ts": 1.0, "not_a_metric": 1}
            ) + "\n")
        r = subprocess.run(
            [sys.executable, script, "--check", bad],
            capture_output=True, text=True, timeout=120,
        )
        assert r.returncode == 1
        assert "not_a_metric" in r.stderr

    def test_check_cli_missing_file(self):
        script = os.path.join(REPO, "scripts", "report_run.py")
        r = subprocess.run(
            [sys.executable, script, "--check", "/nonexistent.jsonl"],
            capture_output=True, text=True, timeout=120,
        )
        assert r.returncode == 2


class TestExampleEndToEnd:
    @pytest.mark.slow  # tier-1 budget: an example SUBPROCESS e2e like
    # the (slow) test_examples suite; report_run schema/render pins
    # stay quick above
    def test_ddp_example_renders_report(self, tmp_path):
        """Acceptance: scripts/report_run.py renders a markdown run report
        from a REAL examples/ddp run's JSONL, including measured
        (HLO-ledger) collective bytes alongside the comm_report model."""
        jsonl = str(tmp_path / "ddp_run.jsonl")
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        env.pop("XLA_FLAGS", None)  # the entry point sets its own device count
        r = subprocess.run(
            [sys.executable, os.path.join(REPO, "examples", "ddp",
                                          "train.py"),
             "--cpu-devices", "2", "--iters", "4", "--telemetry",
             "--metrics", jsonl],
            capture_output=True, text=True, timeout=420, env=env,
        )
        assert r.returncode == 0, r.stderr[-2000:]
        assert "telemetry=on" in r.stdout
        counts, errs = schema.validate_file(jsonl)
        assert errs == []
        # run_meta + straggler + telemetry_summary
        assert counts["step"] == 4 and counts["meta"] == 3
        rr = _load_report_run()
        metas, steps, _ = rr.load_run(jsonl)
        report = rr.render_report(metas, steps, source=jsonl)
        assert "HLO ledger" in report and "all-reduce" in report
        assert "ring-model prediction" in report
        assert "grad_allreduce_bytes" in report
        assert "steps recorded: 4" in report
        # measured bytes appear as a real magnitude, not zero
        meta = [m for m in metas if m.get("kind") == "run_meta"][0]
        assert meta["comm_measured"]["total_wire_bytes"] > 0
        assert meta["comm_model"]["grad_allreduce_bytes"] > 0
        assert meta["schema_version"] == schema.SCHEMA_VERSION


class TestReportPricesMfu:
    """A run_meta that names the device's peak gets an MFU line, labeled
    with the accounting it rests on: the HLO-counted FLOPs where the file
    has them, else the matmul formula, else 6N."""

    STEPS = [{"step": i, "step_s": 0.1, "tokens_per_s": 2.56e6}
             for i in range(3)]
    RUN = {"kind": "run_meta", "schema_version": schema.SCHEMA_VERSION,
           "model": "tiny", "devices": 1, "n_params": 1_000_000_000,
           "tokens_per_step": 256, "peak_flops_per_chip": 197e12}

    @pytest.mark.parametrize("extra,label,flops_per_token", [
        ({"hlo_cost": {"total_flops": 256 * 7e9},
          "flops_per_token_matmul": 5e9}, "HLO-counted", 7e9),
        ({"flops_per_token_matmul": 5e9}, "matmul accounting", 5e9),
        ({}, "6N naive", 6e9),
    ])
    def test_labels_the_accounting(self, extra, label, flops_per_token):
        rr = _load_report_run()
        report = rr.render_report([dict(self.RUN, **extra)], self.STEPS,
                                  source="x.jsonl")
        line = [ln for ln in report.splitlines() if "MFU" in ln]
        assert len(line) == 1 and label in line[0], report
        mfu = flops_per_token * 2.56e6 / 197e12
        assert line[0].endswith(f"{mfu:.3f}")
