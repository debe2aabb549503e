# Copyright 2026 tiny-deepspeed-tpu authors
# SPDX-License-Identifier: Apache-2.0

"""Test bootstrap: 8 virtual CPU devices before JAX initializes.

The reference has NO test suite at all (SURVEY §4) — its de-facto tests are
the runnable train scripts under torchrun.  Here multi-device behavior is
unit-testable without a pod: JAX's host-platform trick exposes N CPU devices,
so every ZeRO mode runs on a real 8-way mesh in CI.
"""

import os
import sys
import time

# Tests run on the 8-device virtual CPU mesh whatever the session's
# platform is; the settings must land before the backend initializes.
# The environment copies are what subprocess-launched workers inherit.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


# ---------------------------------------------------------------------------
# test tiers (round-4 verdict #5): `pytest -m quick` = <2 min warm signal
# covering ops/optim/engine/partition parity; the multi-minute composition
# suites are marked slow.  Everything not slow is auto-marked quick, so
# `-m quick` and `-m "not slow"` select the same set.
# ---------------------------------------------------------------------------

import pytest  # noqa: E402


def pytest_collection_modifyitems(config, items):
    # slow modules declare `pytestmark = pytest.mark.slow` themselves (one
    # source of truth, no central list to forget); everything else is
    # auto-marked quick so `-m quick` == `-m "not slow"`
    for item in items:
        if not any(m.name == "slow" for m in item.iter_markers()):
            item.add_marker(pytest.mark.quick)


# ---------------------------------------------------------------------------
# tier-1 runtime budget gate: the CI box kills the suite at a hard wall
# timeout, which TRUNCATES the run and silently sheds whatever coverage
# sorts last.  This gate makes creep fail LOUDLY first: a full
# `-m "not slow"` run whose summed test durations exceed the
# scripts/tier1_times.py budget exits non-zero with the trim-guidance
# message, and every tier-1 run leaves artifacts/tier1_durations.log for
# `python scripts/tier1_times.py --from-log` spend analysis.
# ---------------------------------------------------------------------------

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_DURATIONS = []
# wall-clock origin for the budget gate: conftest import time, so the
# measure includes the JAX import and collection that per-test durations
# never see (the box timeout is a WALL timeout — summed durations alone
# leave a blind band where the gate passes but the box still truncates)
_WALL_T0 = time.time()


def _tier1_times():
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "tier1_times", os.path.join(_REPO, "scripts", "tier1_times.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def pytest_runtest_logreport(report):
    if report.duration:
        _DURATIONS.append((report.duration, report.when, report.nodeid))


def pytest_sessionfinish(session, exitstatus):
    config = session.config
    # the gate means "the tier-1 suite outgrew its box": it applies only
    # to the canonical tier-1 selection, unnarrowed by -k or by
    # positional paths (partial runs can only undershoot, so they pass
    # vacuously — and must not clobber the full run's durations log)
    if getattr(config.option, "markexpr", "") != "not slow" \
            or getattr(config.option, "keyword", ""):
        return
    canon = {os.path.realpath(_REPO),
             os.path.realpath(os.path.join(_REPO, "tests"))}
    if any(os.path.realpath(str(a).split("::")[0]) not in canon
           for a in config.args):
        return
    total = sum(d for d, _, _ in _DURATIONS)
    try:
        os.makedirs(os.path.join(_REPO, "artifacts"), exist_ok=True)
        with open(os.path.join(_REPO, "artifacts",
                               "tier1_durations.log"), "w") as f:
            for d, phase, nodeid in _DURATIONS:
                f.write(f"{d:.2f}s {phase:<8} {nodeid}\n")
    except OSError:
        pass
    wall = time.time() - _WALL_T0
    try:
        mod = _tier1_times()
        # gate on WALL (what the box timeout actually kills), tripped a
        # margin early: per-test sums exclude import/collection/gap
        # overhead, so a sum-only gate has a blind band where it passes
        # while the box still truncates the tail
        ok, msg = mod.budget_check(
            wall, mod.TIER1_BUDGET_S - mod.TIER1_WALL_MARGIN_S)
    except Exception as e:  # noqa: BLE001 - the gate must not eat the run
        print(f"\n[tier1-budget] gate unavailable: {e!r}")
        return
    print(f"\n[tier1-budget] wall {wall:.1f}s "
          f"(test time {total:.1f}s + overhead): {msg}")
    if not ok and session.exitstatus == 0:
        session.exitstatus = 1
