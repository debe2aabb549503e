# Copyright 2026 tiny-deepspeed-tpu authors
# SPDX-License-Identifier: Apache-2.0

"""Repo hygiene gates: build artifacts must never be tracked.

A committed `__pycache__` .pyc once rode along with a PR; these tests make
that class of regression fail CI instead of relying on reviewer eyes.
Skipped (not failed) when the checkout has no git metadata (sdist/tarball
installs)."""

import os
import subprocess

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# tracked-path fragments that are always build artifacts, never source
_ARTIFACT_MARKERS = ("__pycache__",)
_ARTIFACT_SUFFIXES = (".pyc", ".pyo", ".pyd")


def _tracked_files():
    if not os.path.isdir(os.path.join(REPO, ".git")):
        pytest.skip("not a git checkout (no .git directory)")
    try:
        out = subprocess.run(
            ["git", "ls-files"], cwd=REPO, capture_output=True,
            text=True, timeout=30,
        )
    except FileNotFoundError:
        pytest.skip("git unavailable")
    if out.returncode != 0:
        pytest.skip(f"git ls-files failed: {out.stderr[:200]}")
    return out.stdout.splitlines()


def test_no_tracked_bytecode_artifacts():
    bad = [
        p for p in _tracked_files()
        if any(m in p for m in _ARTIFACT_MARKERS)
        or p.endswith(_ARTIFACT_SUFFIXES)
    ]
    assert not bad, (
        f"tracked build artifacts: {bad} — `git rm --cached` them; "
        ".gitignore already excludes __pycache__/ and *.pyc"
    )


def test_gitignore_covers_bytecode():
    """The .gitignore entries the tracked-artifact gate relies on must
    stay present (removing them re-opens the accidental-add path)."""
    with open(os.path.join(REPO, ".gitignore")) as f:
        lines = {ln.strip() for ln in f}
    assert "__pycache__/" in lines
    assert "*.pyc" in lines
    assert "*.so" in lines


# the ONE shared object this repo may ever carry: the native dataloader
# builds libtds_dataloader.so next to its source on first use
# (data/loader.py), and some checkouts have shipped the prebuilt binary.
# Nothing else compiled belongs in the tree.
_ALLOWED_SO = {"tiny_deepspeed_tpu/native/libtds_dataloader.so"}


def test_no_new_tracked_shared_objects():
    """Pin that no build artifact beyond the allowlisted native-loader
    binary ever gets tracked: .so files are machine-specific build
    outputs (g++ rebuilds the loader from dataloader.cpp on first use),
    and a second one appearing in `git ls-files` means someone committed
    their local build."""
    bad = [
        p for p in _tracked_files()
        if p.endswith((".so", ".dylib", ".a", ".o"))
        and p not in _ALLOWED_SO
    ]
    assert not bad, (
        f"tracked compiled artifacts beyond the allowlist: {bad} — "
        f"`git rm --cached` them (.gitignore already excludes *.so; "
        f"only {sorted(_ALLOWED_SO)} is tolerated for historical "
        f"checkouts)"
    )


def _load_tier1_times():
    # the session gate's loader is the one under test — share it rather
    # than keeping a second copy of the importlib boilerplate in sync
    from conftest import _tier1_times
    return _tier1_times()


def test_tier1_budget_check_predicate():
    """The shared budget predicate (scripts/tier1_times.budget_check):
    CLI --budget exit codes and the conftest session gate both ride it,
    so its pass/fail boundary is pinned here — including the headroom
    report and the thin-headroom WARNING (a pass with <60s to spare on
    this 2-vCPU box is one noisy neighbor away from truncation)."""
    m = _load_tier1_times()
    ok, msg = m.budget_check(100.0, 870.0)
    assert ok and "within budget" in msg
    assert "headroom 770.0s" in msg and "WARNING" not in msg
    ok, msg = m.budget_check(820.0, 870.0)  # passes, but thin
    assert ok and "WARNING" in msg and "headroom 50.0s" in msg
    assert "slow" in msg  # the warning names the remedy
    ok, msg = m.budget_check(871.0, 870.0)
    assert not ok and "EXCEEDED" in msg and "slow" in msg
    # the CLI surfaces it as exit code 1 on a parsed log
    durations = [(500.0, "call", "tests/test_a.py::t"),
                 (400.0, "call", "tests/test_b.py::t")]
    assert m.report(durations, budget=870.0) == 1
    assert m.report(durations, budget=1000.0) == 0


def test_tier1_budget_gate_is_wired_into_conftest():
    """The session gate must stay wired: tests/conftest.py imports the
    budget predicate from scripts/tier1_times.py and applies it at
    sessionfinish — removing the hook would silently re-open the
    truncation failure mode the budget exists to catch."""
    with open(os.path.join(REPO, "tests", "conftest.py")) as f:
        text = f.read()
    assert "def pytest_sessionfinish" in text
    assert "budget_check" in text
    assert "tier1_times" in text


def test_gauge_names_documented_in_schema():
    """Name-drift guard: every telemetry gauge registered by a literal
    `.gauge("name", ...)` call anywhere in the package/scripts/bench must
    be documented in telemetry/schema.GAUGES — dashboards key on these
    names, so an undocumented (or renamed-in-code-only) gauge silently
    desynchronizes them from the code."""
    import re

    from tiny_deepspeed_tpu.telemetry import schema

    pat = re.compile(r"""\.gauge\(\s*['"]([A-Za-z0-9_]+)['"]""")
    used = {}
    roots = [
        os.path.join(REPO, "tiny_deepspeed_tpu"),
        os.path.join(REPO, "scripts"),
        os.path.join(REPO, "examples"),
    ]
    for root in roots:
        files = [root] if root.endswith(".py") else [
            os.path.join(dp, f)
            for dp, _, fs in os.walk(root) for f in fs if f.endswith(".py")
        ]
        for path in files:
            with open(path) as f:
                for name in pat.findall(f.read()):
                    used.setdefault(name, os.path.relpath(path, REPO))
    assert used, "no gauge call sites found — the grep pattern rotted"
    undocumented = {n: p for n, p in used.items() if n not in schema.GAUGES}
    assert not undocumented, (
        f"gauge names registered in code but not documented in "
        f"telemetry/schema.GAUGES: {undocumented} — add them there "
        "(one line each) so the metrics surface stays self-describing"
    )


def test_serving_robustness_schema_v5_names():
    """The serving fault surface is part of the schema contract: the
    v5 gauges must stay documented AND registered by the engine (a
    rename on either side desynchronizes dashboards), and the
    terminal-status request-record fields must stay validatable —
    `report_run.py --check` hard-fails on records carrying them
    otherwise."""
    from tiny_deepspeed_tpu.telemetry import schema

    assert schema.SCHEMA_VERSION >= 5
    v5_gauges = {"serve_shed", "serve_expired", "serve_quarantined",
                 "serve_restarts"}
    assert v5_gauges <= set(schema.GAUGES), (
        v5_gauges - set(schema.GAUGES))
    with open(os.path.join(
            REPO, "tiny_deepspeed_tpu", "serving", "engine.py")) as f:
        engine_src = f.read()
    for g in sorted(v5_gauges):
        assert f'"{g}"' in engine_src, (
            f"gauge {g} documented in schema but no longer registered "
            "by serving/engine.py"
        )
    for field, ty in (("status", str), ("finish", str),
                      ("deadline_s", (int, float)), ("slot", int)):
        assert field in schema.META_FIELDS
    # a representative terminal record of each status validates
    for status, finish in (("ok", "length"), ("shed", "shed:queue"),
                           ("expired", "deadline"),
                           ("failed", "nonfinite_logits")):
        errs = schema.validate_record({
            "kind": "request", "ts": 0.0, "request_id": 1,
            "prompt_tokens": 4, "new_tokens": 2, "preemptions": 0,
            "status": status, "finish": finish,
        })
        assert not errs, (status, errs)


def test_serving_observability_schema_v6_names():
    """Schema-v6 drift guard (serving observability): the `tick` record
    kind with its full field set, the request lifecycle/attribution
    fields, and the ICI-vs-DCN gauge must stay documented AND wired —
    `report_run.py --check` hard-fails any sidecar carrying them
    otherwise, and the dashboards key on these names."""
    from tiny_deepspeed_tpu.telemetry import schema

    assert schema.SCHEMA_VERSION >= 6
    assert "tick" in schema.META_KINDS
    assert "dcn_wire_bytes" in schema.GAUGES
    # a representative tick record of each emission class validates
    for emit in ("event", "sample"):
        errs = schema.validate_record({
            "kind": "tick", "ts": 0.0, "tick": 3, "t_s": 1.25,
            "wall_s": 0.01, "sched_s": 0.001, "prefill_s": 0.004,
            "decode_s": 0.004, "fetch_s": 0.001, "occupancy": 0.5,
            "pool_util": 0.25, "queue_depth": 1, "admitted": 1,
            "evicted": 0, "preempted": 0, "shed": 0, "expired": 0,
            "quarantined": 0, "restarted": 0, "produced": 2,
            "emit": emit,
        })
        assert not errs, (emit, errs)
    # a v6 request record (events + latency-component partition)
    errs = schema.validate_record({
        "kind": "request", "ts": 0.0, "request_id": 1,
        "prompt_tokens": 4, "new_tokens": 2, "preemptions": 1,
        "status": "ok", "finish": "length", "slot": 0,
        "lat_s": 0.1, "comp_queue_s": 0.02, "comp_prefill_s": 0.01,
        "comp_decode_s": 0.05, "comp_preempt_s": 0.02,
        "comp_restart_s": 0.0,
        "events": [["submitted", 0.0], ["admitted", 0.02, 0],
                   ["terminal:ok", 0.1, 0]],
    })
    assert not errs, errs
    # the engine still registers the tick-record emission and the
    # attribution fields it promises
    with open(os.path.join(
            REPO, "tiny_deepspeed_tpu", "serving", "engine.py")) as f:
        engine_src = f.read()
    for name in ('kind="tick"', "comp_queue_s", "comp_restart_s",
                 "serve_restart", "serve_quarantine",
                 "serve_shed_burst", "serve_recover"):
        assert name in engine_src, f"{name} gone from serving/engine.py"


def test_serving_spec_schema_v7_names():
    """Schema-v7 drift guard (speculative decoding): the spec gauges
    must stay documented AND registered by the engine, the draft_s
    tick field and the per-request spec_proposed/spec_accepted fields
    must stay validatable, and the ServeConfig knobs the docs/bench
    name must still exist — `report_run.py --check` hard-fails any
    spec sidecar otherwise."""
    from tiny_deepspeed_tpu.telemetry import schema

    assert schema.SCHEMA_VERSION >= 7
    v7_gauges = {"serve_spec_accept_rate", "serve_spec_tokens_per_tick"}
    assert v7_gauges <= set(schema.GAUGES), (
        v7_gauges - set(schema.GAUGES))
    with open(os.path.join(
            REPO, "tiny_deepspeed_tpu", "serving", "engine.py")) as f:
        engine_src = f.read()
    for g in sorted(v7_gauges):
        assert f'"{g}"' in engine_src, (
            f"gauge {g} documented in schema but no longer registered "
            "by serving/engine.py"
        )
    # the spec knobs the bench fingerprint and docs name
    for knob in ("spec_draft", "spec_k"):
        assert knob in engine_src, f"ServeConfig.{knob} gone"
    # a spec-enabled tick record (draft_s) and request record validate
    errs = schema.validate_record({
        "kind": "tick", "ts": 0.0, "tick": 3, "t_s": 1.25,
        "wall_s": 0.01, "sched_s": 0.001, "draft_s": 0.002,
        "prefill_s": 0.0, "decode_s": 0.004, "fetch_s": 0.001,
        "occupancy": 0.5, "pool_util": 0.25, "queue_depth": 0,
        "admitted": 0, "evicted": 0, "preempted": 0, "shed": 0,
        "expired": 0, "quarantined": 0, "restarted": 0, "produced": 7,
        "emit": "sample",
    })
    assert not errs, errs
    errs = schema.validate_record({
        "kind": "request", "ts": 0.0, "request_id": 1,
        "prompt_tokens": 4, "new_tokens": 8, "preemptions": 0,
        "status": "ok", "finish": "length",
        "spec_proposed": 12, "spec_accepted": 9,
    })
    assert not errs, errs


def test_fleet_schema_v8_names():
    """Schema-v8 drift guard (fleet serving): the router gauges must
    stay documented AND registered by fleet/router.py, the engine must
    stamp replica_id / kv_migration_* on its records, the chaos
    harness must keep the engine_kill kind the failover tests key on —
    and v8 records must validate, else `report_run.py --check`
    hard-fails every fleet sidecar."""
    from tiny_deepspeed_tpu.telemetry import schema

    assert schema.SCHEMA_VERSION >= 8
    v8_gauges = {"fleet_dispatch", "fleet_failover",
                 "fleet_replicas_live"}
    assert v8_gauges <= set(schema.GAUGES), (
        v8_gauges - set(schema.GAUGES))
    with open(os.path.join(
            REPO, "tiny_deepspeed_tpu", "fleet", "router.py")) as f:
        router_src = f.read()
    for g in sorted(v8_gauges):
        assert f'"{g}"' in router_src, (
            f"gauge {g} documented in schema but no longer registered "
            "by fleet/router.py"
        )
    with open(os.path.join(
            REPO, "tiny_deepspeed_tpu", "serving", "engine.py")) as f:
        engine_src = f.read()
    for name in ("replica_id", "kv_migration_bytes",
                 "kv_migration_link"):
        assert name in schema.META_FIELDS, name
        assert name in engine_src, (
            f"{name} gone from serving/engine.py record stamping"
        )
    with open(os.path.join(
            REPO, "tiny_deepspeed_tpu", "resilience", "chaos.py")) as f:
        chaos_src = f.read()
    assert "engine_kill" in chaos_src, (
        "chaos engine_kill kind gone — the fleet failover A/B and "
        "tests key on it"
    )
    # a fleet request record (replica + migration attribution) and a
    # replica-stamped tick record validate
    errs = schema.validate_record({
        "kind": "request", "ts": 0.0, "request_id": 1,
        "prompt_tokens": 4, "new_tokens": 8, "preemptions": 0,
        "status": "ok", "finish": "length", "replica_id": 1,
        "kv_migration_bytes": 7168, "kv_migration_link": "dcn",
    })
    assert not errs, errs
    errs = schema.validate_record({
        "kind": "tick", "ts": 0.0, "tick": 3, "t_s": 1.25,
        "wall_s": 0.01, "sched_s": 0.001, "prefill_s": 0.004,
        "decode_s": 0.004, "fetch_s": 0.001, "occupancy": 0.5,
        "pool_util": 0.25, "queue_depth": 1, "admitted": 1,
        "evicted": 0, "preempted": 0, "shed": 0, "expired": 0,
        "quarantined": 0, "restarted": 0, "produced": 2,
        "replica_id": 0, "emit": "event",
    })
    assert not errs, errs
    # the failover fault record the router writes
    errs = schema.validate_record({
        "kind": "fault", "ts": 0.0, "fault": "fleet_failover",
        "at_step": 4, "replica_id": 0,
        "action": "replica 0 died; journal replayed onto replica 1",
    })
    assert not errs, errs


def test_prefix_tenancy_schema_v9_names():
    """Schema-v9 drift guard (shared-prefix KV reuse + multi-tenant
    serving): the serve_prefix_* / serve_tenants_active gauges must
    stay documented AND registered by the engine, the request-record
    tenant/prefix fields must stay validatable, the ServeConfig knobs
    the bench/docs name must exist, and the chaos tenant_flood kind the
    isolation pin keys on must survive — `report_run.py --check`
    hard-fails any v9 sidecar otherwise."""
    from tiny_deepspeed_tpu.telemetry import schema

    assert schema.SCHEMA_VERSION >= 9
    v9_gauges = {"serve_prefix_hit_rate", "serve_prefix_blocks_aliased",
                 "serve_prefix_tokens_avoided",
                 "serve_prefix_cached_blocks",
                 "serve_prefix_pool_saved_bytes", "serve_tenants_active"}
    assert v9_gauges <= set(schema.GAUGES), (
        v9_gauges - set(schema.GAUGES))
    with open(os.path.join(
            REPO, "tiny_deepspeed_tpu", "serving", "engine.py")) as f:
        engine_src = f.read()
    for g in sorted(v9_gauges):
        assert f'"{g}"' in engine_src, (
            f"gauge {g} documented in schema but no longer registered "
            "by serving/engine.py"
        )
    # the knobs serve_bench and the docs name
    for knob in ("prefix_cache", "tenants"):
        assert knob in engine_src, f"ServeConfig.{knob} gone"
    for field in ("tenant", "prefix_blocks", "prefix_tokens"):
        assert field in schema.META_FIELDS, field
        assert field in engine_src, (
            f"{field} gone from serving/engine.py record stamping"
        )
    with open(os.path.join(
            REPO, "tiny_deepspeed_tpu", "resilience", "chaos.py")) as f:
        chaos_src = f.read()
    assert "tenant_flood" in chaos_src, (
        "chaos tenant_flood kind gone — the multi-tenant isolation "
        "pin and serve_bench flood A/B key on it"
    )
    # a v9 request record (tenant + prefix attribution) validates
    errs = schema.validate_record({
        "kind": "request", "ts": 0.0, "request_id": 1,
        "prompt_tokens": 72, "new_tokens": 8, "preemptions": 0,
        "status": "ok", "finish": "length", "tenant": "pro",
        "prefix_blocks": 4, "prefix_tokens": 64,
    })
    assert not errs, errs
    # tenant_queue_watermark shed reason reaches records unchanged
    errs = schema.validate_record({
        "kind": "request", "ts": 0.0, "request_id": 2,
        "prompt_tokens": 8, "new_tokens": 0, "preemptions": 0,
        "status": "shed", "finish": "shed:tenant_queue_watermark",
        "tenant": "abuser",
    })
    assert not errs, errs


def test_no_scan_tap_custom_vjp_outside_schedule():
    """Scheduler-consolidation guard (the PR-15 tentpole): the four-way
    custom_vjp scan-tap drift this repo once carried (bucket taps,
    prefetch scan, health probe, quantized schedule — each with its own
    pairwise refusals) was unified into parallel/schedule.py.  No NEW
    `jax.custom_vjp` scan-tap may appear under parallel/ or models/
    outside schedule.py — per-layer in-scan work must be declared as a
    scheduler SLOT instead, so the drift cannot regrow."""
    import ast

    # ring_attention's custom_vjp is an ATTENTION-KERNEL vjp (per-chunk
    # softmax merge), not a scan tap riding the block scan — it predates
    # the scheduler and schedules nothing
    allow = {"parallel/ring_attention.py"}
    offenders = {}
    for sub in ("parallel", "models"):
        root = os.path.join(REPO, "tiny_deepspeed_tpu", sub)
        for fn in sorted(os.listdir(root)):
            if not fn.endswith(".py") or fn == "schedule.py":
                continue
            rel = f"{sub}/{fn}"
            if rel in allow:
                continue
            with open(os.path.join(root, fn)) as f:
                tree = ast.parse(f.read())
            hits = [
                node.lineno for node in ast.walk(tree)
                if isinstance(node, ast.Attribute)
                and node.attr == "custom_vjp"
            ]
            if hits:
                offenders[rel] = hits
    assert not offenders, (
        f"jax.custom_vjp scan-tap outside parallel/schedule.py: "
        f"{offenders} — declare the per-layer work as a scheduler slot "
        "(GatherSlot/GradSlot/ProbeSlot) in parallel/schedule.py instead "
        "of growing a fifth bespoke tap"
    )


def test_scheduler_schema_v11_names():
    """Schema-v11 drift guard (the in-scan collective scheduler): the
    per-slot overlap gauges + the hpZ acceptance gauge must stay
    documented AND registered by telemetry/registry.capture_compiled,
    and the ledger must keep the loop-resident per-op group split the
    hpZ pin reads."""
    from tiny_deepspeed_tpu.telemetry import schema

    assert schema.SCHEMA_VERSION >= 11
    v11_gauges = {"sched_gather_overlap_frac", "sched_grad_overlap_frac",
                  "hpz_dcn_wire_bytes"}
    assert v11_gauges <= set(schema.GAUGES), (
        v11_gauges - set(schema.GAUGES))
    with open(os.path.join(
            REPO, "tiny_deepspeed_tpu", "telemetry", "registry.py")) as f:
        reg_src = f.read()
    for g in sorted(v11_gauges):
        assert f'"{g}"' in reg_src, (
            f"gauge {g} documented in schema but no longer registered "
            "by telemetry/registry.py capture_compiled"
        )
    with open(os.path.join(
            REPO, "tiny_deepspeed_tpu", "utils", "hlo_comm.py")) as f:
        hlo_src = f.read()
    for name in ("wire_bytes_by_op_groups_in_loops",
                 "gather_link_split_in_loops"):
        assert name in hlo_src, (
            f"{name} gone from utils/hlo_comm.py — the hpZ in-scan DCN "
            "pin reads it"
        )


def test_hlo_cost_schema_v12_names():
    """Schema-v12 drift guard (the HLO cost ledger): the roofline gauges
    must stay documented AND registered by telemetry/registry
    capture_compiled, and utils/hlo_cost.py must keep the entry points
    the reports and bench read."""
    from tiny_deepspeed_tpu.telemetry import schema

    assert schema.SCHEMA_VERSION >= 12
    v12_gauges = {"hlo_flops", "hlo_hbm_bytes", "step_mfu_hlo",
                  "arithmetic_intensity"}
    assert v12_gauges <= set(schema.GAUGES), (
        v12_gauges - set(schema.GAUGES))
    assert schema.META_FIELDS.get("hlo_cost") is dict
    # the schematic timeline's template fields went with it (schema v16)
    assert "compute_spans" not in schema.META_FIELDS
    assert "trace" not in schema.META_KINDS
    with open(os.path.join(
            REPO, "tiny_deepspeed_tpu", "telemetry", "registry.py")) as f:
        reg_src = f.read()
    for g in sorted(v12_gauges):
        assert f'"{g}"' in reg_src, (
            f"gauge {g} documented in schema but no longer registered "
            "by telemetry/registry.py capture_compiled"
        )
    with open(os.path.join(
            REPO, "tiny_deepspeed_tpu", "utils", "hlo_cost.py")) as f:
        cost_src = f.read()
    for name in ("cost_ledger", "cost_summary", "roofline_verdict",
                 "peak_flops_per_chip"):
        assert name in cost_src, (
            f"{name} gone from utils/hlo_cost.py — reports, bench and "
            "the registry read it"
        )


def test_wire_agenda_schema_v13_names():
    """Schema-v13 drift guard (the wire-agenda close-out): the quantized
    tail / hpZ rebuild gauges must stay documented AND registered by
    telemetry/registry.capture_compiled, utils/hlo_comm.py must keep
    the exact-group isolation helper the rebuild pin reads, and the
    scheduler must keep the "auto" sizing + plan round-trip entry
    points bench and the tuner consume."""
    from tiny_deepspeed_tpu.telemetry import schema

    assert schema.SCHEMA_VERSION >= 13
    v13_gauges = {"zero3_tail_wire_bytes", "hpz_rebuild_dcn_bytes"}
    assert v13_gauges <= set(schema.GAUGES), (
        v13_gauges - set(schema.GAUGES))
    with open(os.path.join(
            REPO, "tiny_deepspeed_tpu", "telemetry", "registry.py")) as f:
        reg_src = f.read()
    for g in sorted(v13_gauges):
        assert f'"{g}"' in reg_src, (
            f"gauge {g} documented in schema but no longer registered "
            "by telemetry/registry.py capture_compiled"
        )
    with open(os.path.join(
            REPO, "tiny_deepspeed_tpu", "utils", "hlo_comm.py")) as f:
        hlo_src = f.read()
    assert "group_wire_outside_loops" in hlo_src, (
        "group_wire_outside_loops gone from utils/hlo_comm.py — the "
        "hpZ rebuild pin and the registry gauge read it"
    )
    with open(os.path.join(
            REPO, "tiny_deepspeed_tpu", "parallel", "schedule.py")) as f:
        sched_src = f.read()
    for name in ("auto_comm_plan", "comm_plan_engine_kwargs",
                 "COMM_PLAN_KEYS"):
        assert name in sched_src, (
            f"{name} gone from parallel/schedule.py — bench's comm "
            "phase and the AOT plan round-trip consume it"
        )


def test_live_slo_schema_v15_names():
    """Schema-v15 drift guard (live observability plane): the `slo`
    record kind and the cross-engine tracing fields must stay
    documented, the engine must keep stamping trace_id / comp_migrate_s
    and arming the slo_fast_burn flight, the registry must keep
    label-qualifying gauge keys through telemetry/live.gauge_key, and
    serve_bench must keep the --slo / --live-port surfaces the docs
    name — `report_run.py --check` hard-fails any v15 sidecar
    otherwise."""
    from tiny_deepspeed_tpu.telemetry import schema

    assert schema.SCHEMA_VERSION >= 15
    assert "slo" in schema.META_KINDS
    for field in ("trace_id", "comp_migrate_s", "windows", "tenants",
                  "attainment", "alerts"):
        assert field in schema.META_FIELDS, field
    with open(os.path.join(
            REPO, "tiny_deepspeed_tpu", "serving", "engine.py")) as f:
        engine_src = f.read()
    for name in ("trace_id", "slo_fast_burn", "attach_slo",
                 "attach_live"):
        assert name in engine_src, (
            f"{name} gone from serving/engine.py — the live plane and "
            "cross-engine tracing key on it"
        )
    assert "comp_migrate_s" in engine_src, (
        "comp_migrate_s gone from serving/engine.py record stamping — "
        "the disagg tail attribution keys on it"
    )
    with open(os.path.join(
            REPO, "tiny_deepspeed_tpu", "telemetry", "registry.py")) as f:
        reg_src = f.read()
    assert "gauge_key" in reg_src, (
        "registry gauges no longer label-qualified via "
        "telemetry/live.gauge_key — fleet replicas would regress to "
        "last-writer-wins shared gauges"
    )
    with open(os.path.join(REPO, "scripts", "serve_bench.py")) as f:
        bench_src = f.read()
    for flag in ("--slo", "--live-port"):
        assert flag in bench_src, (
            f"serve_bench {flag} gone — README's observability recipe "
            "and the live smoke test drive it"
        )
    # a v15 slo record (the SLOTracker.record shape) validates
    errs = schema.validate_record({
        "kind": "slo", "ts": 0.0, "windows": {"s": [30.0, 300.0]},
        "tenants": {"_default": {
            "objective": {"target": 0.99, "ttft_s": None,
                          "latency_s": None},
            "requests": 10, "good": 9, "attainment": 0.9,
            "budget_spent_frac": 1.0,
            "burn": {"30s": 10.0, "300s": 2.0}}},
        "attainment": 0.9, "at_step": 12,
        "alerts": [{"tenant": "_default", "kind": "fast_burn",
                    "burn": 10.0, "window_s": 30.0, "threshold": 14.0,
                    "t": 1.5}],
    })
    assert not errs, errs
    # a v15 request record: trace_id correlation + the migrate
    # component joining the latency partition
    errs = schema.validate_record({
        "kind": "request", "ts": 0.0, "request_id": 1,
        "prompt_tokens": 8, "new_tokens": 4, "preemptions": 0,
        "status": "ok", "finish": "length", "lat_s": 0.5,
        "comp_queue_s": 0.1, "comp_prefill_s": 0.1,
        "comp_decode_s": 0.1, "comp_preempt_s": 0.0,
        "comp_restart_s": 0.0, "comp_migrate_s": 0.2,
        "trace_id": "t000001", "replica_id": 1,
        "events": [["submitted", 0.0], ["exported", 0.1, 0, 0],
                   ["imported", 0.2, 1, 1], ["terminal:ok", 0.5, 1]],
    })
    assert not errs, errs
    # labeled gauge keys in a telemetry_summary validate as plain dict
    # entries (the key carries the label, the schema names the base)
    errs = schema.validate_record({
        "kind": "telemetry_summary", "ts": 0.0,
        "gauges": {"serve_queue_depth{replica=0}": 1.0,
                   "serve_queue_depth{replica=1}": 0.0},
        "counters": {}, "histograms": {},
    })
    assert not errs, errs


def test_decode_ahead_schema_v20_names():
    """Schema-v20 drift guard: a `tick` record carries `ahead`, whether
    the tick dispatched the next tick's decode step before it fetched its
    own; the engine still writes it into the record and onto the
    dispatch span, and the profiler's table still names it."""
    from tiny_deepspeed_tpu.telemetry import schema
    from tiny_deepspeed_tpu.utils.profiling import TABLE

    assert schema.SCHEMA_VERSION >= 20
    assert schema.META_FIELDS["ahead"] is int
    assert TABLE["ahead"][0] == "counter"
    with open(os.path.join(
            REPO, "tiny_deepspeed_tpu", "serving", "engine.py")) as f:
        engine_src = f.read()
    for name in ('self._tick["ahead"]', '"decode.dispatch"', "ahead=int(",
                 '"ahead") if k in rec'):
        assert name in engine_src, (
            f"{name} gone from serving/engine.py: the tick record and the "
            "dispatch span carry `ahead` from there")
    errs = schema.validate_record({
        "kind": "tick", "ts": 0.0, "tick": 3, "t_s": 1.0, "wall_s": 0.002,
        "sched_s": 0.001, "prefill_s": 0.0, "decode_s": 0.0005,
        "fetch_s": 0.0005, "occupancy": 0.5, "pool_util": 0.1,
        "queue_depth": 0, "admitted": 0, "evicted": 0, "preempted": 0,
        "shed": 0, "expired": 0, "quarantined": 0, "restarted": 0,
        "produced": 2, "kv_steps_live": 2, "kv_steps": 8, "ahead": 1,
        "emit": "sample"})
    assert not errs, errs


def test_serving_engine_asks_the_layout_not_whether_there_is_one():
    """One cache seam (PR 32): every servable model states a slot layout
    (serving/pool.DenseLayout), so `serving/engine.py` holds no test of
    whether it has one, and the paged kernel's arithmetic is the layout's:
    of `ops/paged_attn_pallas` the engine imports the kernel mode only."""
    import ast

    with open(os.path.join(
            REPO, "tiny_deepspeed_tpu", "serving", "engine.py")) as f:
        src = f.read()
    assert "_layout is" not in src
    assert "self._layout." in src  # the seam is there, under this name
    imported = set()
    for node in ast.walk(ast.parse(src)):
        if isinstance(node, ast.ImportFrom) and (
                node.module or "").endswith("paged_attn_pallas"):
            imported |= {a.name for a in node.names}
        elif isinstance(node, ast.Import):
            assert not any("paged_attn_pallas" in a.name
                           for a in node.names)
    assert imported == {"PAGED_KERNEL_MODES", "paged_kernel_forced"}


def test_the_old_benchmark_is_named_by_no_code():
    """One benchmark (PR 32): `BENCHMARK.json` + `benchmarks/`.  The
    script it replaced, that script's configuration table and its
    environment arms are named by no Python or shell file outside
    `benchmarks/` (whose prose is the benchmark's own to mend), nor by
    README.md.  The records (PERF.md, CHANGES.md, ROADMAP.md, PROFILE.md,
    BASELINE.md) may name them: they say what was measured by what."""
    import re

    gone = re.compile("|".join((
        r"\bben" r"ch\.py\b", r"\b(?:import|from) ben" r"ch\b",
        r"_ben" r"ch_config", r"\bBEN" r"CH_[A-Z]")))
    paths = [os.path.join(REPO, "README.md")]
    for dp, dns, fs in os.walk(REPO):
        # a checkout's own leavings and the chip tool's copies aside
        dns[:] = [d for d in dns if not d.startswith((".", "chiprun_"))
                  and d != "__pycache__"
                  and os.path.join(dp, d) != os.path.join(REPO,
                                                          "benchmarks")]
        paths += [os.path.join(dp, f) for f in fs
                  if f.endswith((".py", ".sh"))]
    assert len(paths) > 100, "the walk found no tree to read"
    hits = []
    for path in paths:
        with open(path) as f:
            for n, line in enumerate(f, 1):
                if gone.search(line):
                    hits.append(f"{os.path.relpath(path, REPO)}:{n}: "
                                f"{line.strip()[:80]}")
    assert not hits, hits
    assert not os.path.exists(os.path.join(REPO, "ben" "ch.py"))
