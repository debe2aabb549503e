# Copyright 2026 tiny-deepspeed-tpu authors
# SPDX-License-Identifier: Apache-2.0

"""Start-up rules (utils/startup.py) and the entry points that ride them.

  * the compile-cache rule: JAX_COMPILATION_CACHE_DIR set -> code sets
    nothing; unset -> <checkout>/.jax_cache;
  * every entry point, and chip_smoke.py, exits non-zero with no result on
    stdout when no TPU is visible and its CPU flag was not given — before
    anything compiles (a silent CPU run prints tokens/s for programs nobody
    deploys);
  * chip_smoke.py's labeled CPU rehearsal runs end to end (slow tier).
"""

import json
import os
import subprocess
import sys

import pytest

from tiny_deepspeed_tpu.utils import startup

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class TestCompileCacheRule:
    def _recorded(self, monkeypatch):
        calls = []
        monkeypatch.setattr(startup.jax.config, "update",
                            lambda k, v: calls.append((k, v)))
        return calls

    def test_env_set_code_sets_nothing(self, monkeypatch, tmp_path):
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        calls = self._recorded(monkeypatch)
        assert startup.compile_cache_dir() == str(tmp_path)
        assert calls == []

    def test_env_unset_uses_checkout_dot_jax_cache(self, monkeypatch):
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        calls = self._recorded(monkeypatch)
        want = os.path.join(REPO, ".jax_cache")
        assert startup.compile_cache_dir() == want
        assert calls == [("jax_compilation_cache_dir", want)]

    def test_no_private_cache_variables_remain(self):
        """One rule in one place: the old private spellings are gone
        from every entry point, script and test helper."""
        old = ("JAX_" "CACHE_DIR", "TINY_DS_NO_" "COMPILE_CACHE",
               "TINY_DS_TEST_" "CACHE")
        hits = []
        for root in ("tiny_deepspeed_tpu", "examples", "scripts", "tests"):
            for dp, _, fs in os.walk(os.path.join(REPO, root)):
                for f in fs:
                    if f.endswith((".py", ".sh")):
                        with open(os.path.join(dp, f)) as fh:
                            text = fh.read()
                        hits += [(f, o) for o in old if o in text]
        for f in ("__graft_entry__.py", "chip_smoke.py"):
            with open(os.path.join(REPO, f)) as fh:
                text = fh.read()
            hits += [(f, o) for o in old if o in text]
        assert not hits, hits


class TestSelectPlatform:
    """In-process halves of the refusal (the subprocess tests below prove
    the entry points are wired to it)."""

    def test_no_tpu_and_no_flag_exits_naming_the_flag(self, monkeypatch):
        monkeypatch.setattr(startup, "compile_cache_dir", lambda: "")
        with pytest.raises(SystemExit) as e:
            startup.select_platform(cpu_flag="--cpu-devices N")
        assert "no TPU" in str(e.value) and "--cpu-devices N" in str(e.value)

    def test_tpu_backend_is_accepted(self, monkeypatch):
        monkeypatch.setattr(startup, "compile_cache_dir", lambda: "")
        monkeypatch.setattr(startup.jax, "default_backend", lambda: "tpu")
        assert startup.select_platform() == "tpu"

    def test_cpu_flag_is_an_explicit_request(self, monkeypatch):
        monkeypatch.setattr(startup, "compile_cache_dir", lambda: "")
        assert startup.select_platform(cpu=True) == "cpu"
        # the count already in force may be restated after backend init
        assert startup.select_platform(cpu_devices=8) == "cpu"


class TestInitDistributed:
    """Multi-host is decided by worker COUNT: the Cloud TPU runtime sets
    TPU_WORKER_HOSTNAMES on one-host machines too (the four-chip host
    says `localhost`), and a wrong guess blocks on a coordinator."""

    @pytest.mark.parametrize("env,called", [
        ({}, False),
        ({"TPU_WORKER_HOSTNAMES": "localhost"}, False),
        ({"TPU_WORKER_HOSTNAMES": "10.0.0.7"}, False),
        ({"TPU_WORKER_HOSTNAMES": "10.0.0.7,10.0.0.8"}, True),
        ({"JAX_COORDINATOR_ADDRESS": "10.0.0.7:1234"}, True),
    ])
    def test_decided_by_worker_count(self, monkeypatch, env, called):
        from tiny_deepspeed_tpu.parallel import mesh
        for k in ("TPU_WORKER_HOSTNAMES", "JAX_COORDINATOR_ADDRESS"):
            monkeypatch.delenv(k, raising=False)
        for k, v in env.items():
            monkeypatch.setenv(k, v)
        calls = []
        monkeypatch.setattr(mesh.jax.distributed, "initialize",
                            lambda **kw: calls.append(kw))
        mesh.init_distributed()
        assert bool(calls) == called


class TestGeneratedFilesDoNotSteer:
    def test_native_loader_is_keyed_by_source_content(self):
        """A copied tree keeps no meaningful mtimes: the built library's
        name carries the hash of dataloader.cpp, so a stale binary from
        another source is never picked up."""
        import hashlib

        from tiny_deepspeed_tpu.data import loader
        with open(loader._SRC, "rb") as f:
            digest = hashlib.sha256(f.read()).hexdigest()[:16]
        assert os.path.basename(loader._so_path()) == (
            f"libtds_dataloader-{digest}.so")
        if loader.native_available():
            assert loader.native_build_error() is None
            assert os.path.exists(loader._so_path())
        else:
            assert loader.native_build_error()


class TestKernelNotes:
    def test_gates_note_what_they_traced(self):
        """ops/dispatch.kernels_noted is what chip_smoke.py prints: the
        layernorm gate notes XLA on the CPU, and XLA again inside a GSPMD
        auto-partitioned region even with the TPU kernels targeted — by
        design, so the smoke prints it rather than calling it a fallback."""
        import jax.numpy as jnp

        from tiny_deepspeed_tpu.ops import dispatch
        from tiny_deepspeed_tpu.ops.layernorm import layernorm

        import jax
        x = jnp.ones((16, 128))
        w = b = jnp.ones((128,))
        dispatch.kernels_noted(clear=True)
        # gates note at TRACE time: eval_shape traces without compiling
        jax.eval_shape(layernorm, x, w, b)
        assert dispatch.kernels_noted() == {"layernorm": ["xla:_ln_fwd_xla"]}
        with dispatch.kernel_target_forced("tpu"), \
                dispatch.gspmd_auto_region(True):
            jax.eval_shape(layernorm, x, w, b)
        assert dispatch.kernels_noted(clear=True) == {
            "layernorm": ["xla:_ln_fwd_xla"]}
        assert dispatch.kernels_noted() == {}

    def test_report_run_says_not_measured_without_a_peak(self):
        """A CPU run's hlo_cost carries counts only; the report must not
        invent a roofline for it."""
        import importlib.util
        spec = importlib.util.spec_from_file_location(
            "report_run_startup_test",
            os.path.join(REPO, "scripts", "report_run.py"))
        rr = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(rr)
        meta = {"kind": "run_meta", "schema_version": 15,
                "hlo_cost": {"total_flops": 1e9, "flops_in_loops": 0.0,
                             "hbm_bytes": 1e6,
                             "arithmetic_intensity": 1000.0}}
        rep = rr.render_report([meta], [], source="x.jsonl")
        assert "bound verdict: not measured" in rep
        assert "-bound**" not in rep


TRAINERS = [os.path.join("examples", d, "train.py")
            for d in ("single_device", "ddp", "zero1", "zero2", "zero3",
                      "pipeline")]
OTHERS = ["chip_smoke.py",
          os.path.join("scripts", "serve_bench.py"),
          os.path.join("examples", "generate.py")]


def _refused_without_chip(paths):
    """Launch every entry point at once under JAX_PLATFORMS=cpu with no
    CPU flag; each must exit non-zero, say why, and print no result."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    procs = [(p, subprocess.Popen(
        [sys.executable, os.path.join(REPO, p)], cwd=REPO, env=env,
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True))
        for p in paths]
    for path, proc in procs:
        out, err = proc.communicate(timeout=300)
        assert proc.returncode != 0, (path, out[-500:])
        assert "no TPU" in err, (path, err[-500:])
        # refused before any work: no loss line, no JSON record
        assert out.strip() == "", (path, out[-500:])


def test_chip_smoke_and_serve_bench_refuse_the_cpu():
    _refused_without_chip(OTHERS[:2])


def test_every_entry_point_is_wired_to_the_refusal():
    """Static half of the slow subprocess test below: each entry point
    calls select_platform (the trainers through examples/common.run)."""
    for path in OTHERS + [os.path.join("examples", "common.py")]:
        with open(os.path.join(REPO, path)) as f:
            assert "select_platform(" in f.read(), path
    for path in TRAINERS:
        with open(os.path.join(REPO, path)) as f:
            assert "from common import parse_args, run" in f.read(), path


@pytest.mark.slow  # nine interpreters at once
def test_every_entry_point_refuses_the_cpu():
    _refused_without_chip(TRAINERS + OTHERS)


@pytest.mark.slow  # ~1 min: tiny model, interpreted kernels, 4 devices
def test_chip_smoke_rehearsal_runs_end_to_end(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"),
         "--rehearse-cpu", "4", "--out", str(tmp_path)],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    assert "REHEARSAL" in r.stdout
    last = json.loads(r.stdout.strip().splitlines()[-1])
    assert last == {"ok": True, "rehearsal": True,
                    "device": {"platform": "cpu", "kind": "cpu", "count": 4}}
    with open(tmp_path / "summary.json") as f:
        summary = json.load(f)
    assert set(summary["phases"]) == {
        "train", "parity", "serve", "stages", "zero3_1p5b", "zero2_1p5b"}
    assert all(p["verdict"] == "pass" for p in summary["phases"].values())
