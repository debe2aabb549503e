# Copyright 2026 tiny-deepspeed-tpu authors
# SPDX-License-Identifier: Apache-2.0

"""The table-driven pipeline schedules (parallel/pipe_schedule.py) and
their PipeSlot client in the composable scheduler (ISSUE 19).

The builder is pure numpy, so the whole schedule contract pins WITHOUT a
mesh or a compile:

  * the V=1 regression anchor — the greedy list scheduler reproduces the
    textbook 1F1B table exactly: T = 2(M+S-1) ticks and the analytic
    bubble (S-1)/(M+S-1), warmup/steady/cooldown shapes included.
  * the acceptance ordering, exact values pinned —
    bubble(zbub) <= bubble(interleaved V>=2) < bubble(1f1b) at fixed
    (S, M), e.g. S=2 V=2 M=4: 0.04 <= 0.1579 < 0.20.
  * a pure-python EMULATOR replays every (tick, stage) program with the
    executor's exact semantics (park arrivals before the op, chunk-0
    self-stash, head-seeded final chunk, one-tick ring hops): every stash
    read must return the value the dependency graph requires, so slot
    collisions, lost arrivals, and order violations all surface as token
    mismatches — no jax, no device.
  * geometry refusals (ValueError from the builder, ScheduleConflictError
    from build_schedule) and the pipe x {gather, grad, probe, MoE, busy
    axes} named refusals.

Engine-level parity across 1f1b / interleaved / zbub and the legacy HLO
determinism pin are slow-marked (zero-sum tier-1 budget): they compile.
The parity pin runs on a data=1 mesh (pipeline_parallel = all 8 CPU
devices) — this jaxlib's CPU backend cannot partition a partial-manual
program with a >1 GSPMD data axis (the same env limitation the
test_profiling xfails document).
"""

import hashlib
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tiny_deepspeed_tpu import AdamW, DDP, GPTConfig, GPT2Model
from tiny_deepspeed_tpu.parallel import schedule as S
from tiny_deepspeed_tpu.parallel import pipe_schedule as PS

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# n_layer=4 divides every stages*virtual geometry used below
CFG4 = GPTConfig(
    block_size=32, vocab_size=128, n_layer=4, n_head=2, n_embd=32,
    compute_dtype=jnp.float32,
)


@pytest.fixture(scope="module")
def model4():
    return GPT2Model(CFG4)


def make_batch(seed=1, b=8, t=32, vocab=128):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    return (jax.random.randint(k1, (b, t), 0, vocab),
            jax.random.randint(k2, (b, t), 0, vocab))


def _build(model, **kw):
    args = dict(model=model, stage=0, n_shard=8,
                busy_axes=(None, None, None, None), accum_steps=1,
                scan_unroll=1)
    args.update(kw)
    return S.build_schedule(**args)


def _pipe_build(model, kind="interleaved", stages=2, virtual=2, mb=4,
                **kw):
    return _build(model, pipe_schedule=kind, pipe_stages=stages,
                  pipe_virtual=virtual, pipe_microbatches=mb, **kw)


# ---------------------------------------------------------------------------
# builder: analytic anchors and the acceptance ordering (quick, no jax use)
# ---------------------------------------------------------------------------

class TestBuilderAnalytic:
    @pytest.mark.parametrize("s,m", [(2, 2), (2, 4), (2, 8), (4, 4),
                                     (4, 8), (8, 8)])
    def test_v1_reproduces_textbook_1f1b(self, s, m):
        """The regression anchor: V=1 without the split IS 1F1B —
        T = 2(M+S-1) ticks and bubble (S-1)/(M+S-1) exactly."""
        prog = PS.build_pipe_program(s, 1, m)
        assert prog.n_ticks == 2 * (m + s - 1)
        assert prog.bubble_frac == pytest.approx(
            PS.analytic_1f1b_bubble(s, m), abs=1e-12)
        # every stage runs exactly 2M ops (one F + one B per microbatch)
        assert list(prog.busy) == [2 * m] * s

    def test_acceptance_ordering_pinned_exact(self):
        """ISSUE 19 acceptance at S=2 V=2 M=4: interleaved beats the
        1F1B bubble, zbub beats interleaved — exact values pinned."""
        f1 = PS.analytic_1f1b_bubble(2, 4)
        il = PS.build_pipe_program(2, 2, 4)
        zb = PS.build_pipe_program(2, 2, 4, split_w=True)
        assert f1 == pytest.approx(0.2)
        assert il.n_ticks == 19
        assert il.bubble_frac == pytest.approx(0.1579, abs=5e-5)
        assert zb.n_ticks == 25
        assert zb.bubble_frac == pytest.approx(0.04, abs=1e-12)
        assert zb.bubble_frac <= il.bubble_frac < f1

    @pytest.mark.parametrize("s,v,m", [(2, 2, 4), (2, 2, 8), (4, 2, 8),
                                       (8, 2, 8), (2, 4, 8), (3, 2, 6)])
    def test_acceptance_ordering_general(self, s, v, m):
        il = PS.build_pipe_program(s, v, m)
        zb = PS.build_pipe_program(s, v, m, split_w=True)
        assert il.bubble_frac < PS.analytic_1f1b_bubble(s, m)
        assert zb.bubble_frac <= il.bubble_frac

    @pytest.mark.parametrize("s,v,m,split", [(2, 1, 4, False),
                                             (2, 2, 4, True),
                                             (4, 2, 8, False),
                                             (4, 2, 8, True)])
    def test_op_counts(self, s, v, m, split):
        prog = PS.build_pipe_program(s, v, m, split_w=split)
        counts = {op: int((prog.op == op).sum())
                  for op in (PS.OP_F, PS.OP_B, PS.OP_W)}
        assert counts[PS.OP_F] == s * v * m
        assert counts[PS.OP_B] == s * v * m
        assert counts[PS.OP_W] == (s * v * m if split else 0)
        assert int(prog.busy.sum()) == sum(counts.values())
        assert prog.bubble_frac == pytest.approx(
            1.0 - prog.busy.sum() / (prog.n_ticks * s))


class TestBuilderStructure:
    def test_1f1b_warmup_steady_cooldown(self):
        """V=1 shape: stage st idles st warmup ticks then opens with F,
        and drains with its last B st ticks before the table ends."""
        s, m = 4, 8
        prog = PS.build_pipe_program(s, 1, m)
        for st in range(s):
            col = prog.op[:, st]
            busy_ticks = np.nonzero(col)[0]
            assert busy_ticks[0] == st and col[busy_ticks[0]] == PS.OP_F
            assert busy_ticks[-1] == prog.n_ticks - 1 - st
            assert col[busy_ticks[-1]] == PS.OP_B
        # steady state on the last stage: strict F/B alternation
        last = prog.op[:, s - 1]
        ops = [int(o) for o in last if o != PS.OP_IDLE]
        assert ops == [PS.OP_F, PS.OP_B] * m

    def test_w_is_filler_after_its_b(self):
        """zbub: every W runs strictly after its own (chunk, mb) B on
        the same stage — wgrad is deferred off the critical path."""
        prog = PS.build_pipe_program(4, 2, 8, split_w=True)
        ticks = {}  # (op, stage, vchunk, mb) -> tick
        for t in range(prog.n_ticks):
            for st in range(prog.stages):
                o = int(prog.op[t, st])
                if o != PS.OP_IDLE:
                    key = (o, st, int(prog.vchunk[t, st]),
                           int(prog.mb[t, st]))
                    assert key not in ticks, f"duplicate op {key}"
                    ticks[key] = t
        n_w = 0
        for (o, st, vv, j), t in ticks.items():
            if o == PS.OP_W:
                n_w += 1
                assert t > ticks[(PS.OP_B, st, vv, j)]
        assert n_w == prog.chunks * prog.microbatches

    def test_describe_and_render(self):
        il = PS.build_pipe_program(2, 2, 4)
        zb = PS.build_pipe_program(2, 2, 4, split_w=True)
        f1 = PS.build_pipe_program(2, 1, 4)
        assert il.describe().startswith("pipe=interleaved:2[s=2 m=4")
        assert "bubble=0.158" in il.describe()
        assert zb.describe().startswith("pipe=zbub:2")
        assert f1.describe().startswith("pipe=1f1b:1")
        rows = il.render().splitlines()
        assert len(rows) == 2
        assert all(len(r.split()) == 1 + il.n_ticks for r in rows)
        assert "F0.0" in rows[0] and "...." in rows[0]

    def test_geometry_refusals(self):
        with pytest.raises(ValueError, match=">= 2 stages"):
            PS.build_pipe_program(1, 1, 4)
        with pytest.raises(ValueError, match="virtual stages"):
            PS.build_pipe_program(2, 0, 4)
        with pytest.raises(ValueError, match="microbatches"):
            PS.build_pipe_program(2, 1, 0)
        with pytest.raises(ValueError, match="not divisible"):
            PS.build_pipe_program(2, 2, 4, n_layer=6)


# ---------------------------------------------------------------------------
# the emulator: replay every program with the executor's semantics
# ---------------------------------------------------------------------------

def _emulate(prog):
    """Pure-python interpreter of a PipeProgram with spmd_pipeline_table's
    exact semantics.  Tokens name dataflow values symbolically:

      ("a", c, j) — the INPUT activation of global chunk c, microbatch j
                    (chunk c-1's output; the raw microbatch for c == 0)
      ("g", c, j) — the cotangent w.r.t. chunk c's OUTPUT

    Per tick: park ring arrivals into stash slots BEFORE the op (one-tick
    hop latency), then run the op, reading its stash slots and asserting
    the token is exactly what the dependency graph requires.  Any stash
    slot collision, lost/phantom arrival, or ordering bug makes some read
    see the wrong token.  Returns the per-op execution counts."""
    s, m, c_total = prog.stages, prog.microbatches, prog.chunks
    astash = [dict() for _ in range(s)]   # slot -> token
    cstash = [dict() for _ in range(s)]
    sent_f = [None] * s                   # payload sent last tick
    sent_b = [None] * s
    done = {PS.OP_F: set(), PS.OP_B: set(), PS.OP_W: set()}

    for t in range(prog.n_ticks):
        arr_f = [sent_f[(st - 1) % s] for st in range(s)]
        arr_b = [sent_b[(st + 1) % s] for st in range(s)]
        now_f = [None] * s
        now_b = [None] * s
        for st in range(s):   # park arrivals before any op runs
            sl = int(prog.recv_f[t, st])
            assert (sl >= 0) == (arr_f[st] is not None), \
                f"t={t} s{st}: fwd arrival/parking mismatch"
            if sl >= 0:
                assert sl < prog.ka
                astash[st][sl] = arr_f[st]
            sl = int(prog.recv_b[t, st])
            assert (sl >= 0) == (arr_b[st] is not None), \
                f"t={t} s{st}: bwd arrival/parking mismatch"
            if sl >= 0:
                assert sl < prog.kc
                cstash[st][sl] = arr_b[st]
        for st in range(s):
            o = int(prog.op[t, st])
            if o == PS.OP_IDLE:
                continue
            c = int(prog.vchunk[t, st]) * s + st
            j = int(prog.mb[t, st])
            asl = int(prog.aslot[t, st])
            csl = int(prog.cslot[t, st])
            assert 0 <= asl < prog.ka
            key = (c, j)
            assert key not in done[o], f"t={t} s{st}: {key} re-executed"
            done[o].add(key)
            if o == PS.OP_F:
                if c == 0:   # chunk 0 self-stashes the injected batch
                    astash[st][asl] = ("a", 0, j)
                else:
                    assert astash[st].get(asl) == ("a", c, j), \
                        f"t={t} s{st} F{key}: stale activation slot"
                if c < c_total - 1:
                    now_f[st] = ("a", c + 1, j)
            else:            # B and W both re-linearize from the stash
                assert (c, j) in done[PS.OP_F]
                assert astash[st].get(asl) == ("a", c, j), \
                    f"t={t} s{st} {PS.OP_NAMES[o]}{key}: activation lost"
                if c == c_total - 1:
                    assert csl == -1   # head-seeded, no cotangent stash
                else:
                    assert 0 <= csl < prog.kc
                    assert cstash[st].get(csl) == ("g", c, j), \
                        f"t={t} s{st} {PS.OP_NAMES[o]}{key}: cot lost"
                if o == PS.OP_W:
                    assert prog.split_w and (c, j) in done[PS.OP_B]
                elif c > 0:
                    now_b[st] = ("g", c - 1, j)
        sent_f, sent_b = now_f, now_b

    every = {(c, j) for c in range(c_total) for j in range(m)}
    assert done[PS.OP_F] == every and done[PS.OP_B] == every
    assert done[PS.OP_W] == (every if prog.split_w else set())
    assert sent_f == [None] * s and sent_b == [None] * s
    return {k: len(v) for k, v in done.items()}


class TestTableEmulator:
    @pytest.mark.parametrize("s,v,m,split", [
        (2, 1, 2, False), (2, 1, 8, False), (4, 1, 8, False),
        (8, 1, 8, False), (2, 2, 4, False), (2, 2, 4, True),
        (4, 2, 8, False), (4, 2, 8, True), (8, 2, 8, True),
        (2, 4, 8, True), (3, 2, 6, False), (3, 2, 6, True),
    ])
    def test_program_replays_clean(self, s, v, m, split):
        prog = PS.build_pipe_program(s, v, m, split_w=split)
        counts = _emulate(prog)
        assert counts[PS.OP_F] == counts[PS.OP_B] == s * v * m


class TestChunkPermutation:
    def test_identity_at_v1(self):
        perm, inv = PS.chunk_permutation(8, 4, 1)
        assert list(perm) == list(range(8)) == list(inv)

    def test_round_trip(self):
        for (L, s, v) in [(8, 2, 2), (16, 4, 2), (16, 2, 4), (24, 4, 3)]:
            perm, inv = PS.chunk_permutation(L, s, v)
            assert sorted(perm) == list(range(L))
            assert list(perm[inv]) == list(range(L))
            assert list(inv[perm]) == list(range(L))

    def test_stage_gets_its_chunks_contiguously(self):
        # L=8 S=2 V=2: global chunks (0,2) on stage 0 -> layers 0,1,4,5
        perm, _ = PS.chunk_permutation(8, 2, 2)
        assert list(perm[:4]) == [0, 1, 4, 5]   # stage 0: v0 then v1
        assert list(perm[4:]) == [2, 3, 6, 7]   # stage 1


# ---------------------------------------------------------------------------
# the PipeSlot client of build_schedule (quick, no compiles)
# ---------------------------------------------------------------------------

class TestScheduleClient:
    def test_pipe_lowering_builds(self, model4):
        sched = _pipe_build(model4)
        assert sched.lowering == "pipe"
        assert sched.pipe.kind == "interleaved"
        prog = sched.pipe_program
        assert (prog.stages, prog.virtual, prog.microbatches) == (2, 2, 4)
        assert prog.split_w is False
        zb = _pipe_build(model4, kind="zbub")
        assert zb.pipe_program.split_w is True
        assert zb.pipe_program.bubble_frac <= prog.bubble_frac

    def test_pipe_axis_not_busy(self, model4):
        # the engine lists its own pipe axis among busy_axes; the slot
        # must not refuse ITSELF over it
        sched = _pipe_build(model4,
                            busy_axes=(None, None, None, "pipe"))
        assert sched.lowering == "pipe"

    def test_named_refusals_per_slot(self, model4):
        with pytest.raises(S.ScheduleConflictError,
                           match="pipe slot.*grad.*int8"):
            _pipe_build(model4, grad_comm="int8")
        with pytest.raises(S.ScheduleConflictError,
                           match="pipe slot.*gather"):
            _pipe_build(model4, stage=3, gather_prefetch=2)
        with pytest.raises(S.ScheduleConflictError,
                           match="pipe slot.*health"):
            _pipe_build(model4, telemetry_layers=True)
        with pytest.raises(S.ScheduleConflictError,
                           match="active axes.*seq"):
            _pipe_build(model4, busy_axes=("seq", None, None, "pipe"))

    def test_moe_refused_by_capability_flag(self):
        from tiny_deepspeed_tpu.models.moe import MoEConfig, MoEGPT
        moe = MoEGPT(MoEConfig(
            block_size=32, vocab_size=128, n_layer=4, n_head=2,
            n_embd=32, n_expert=2, compute_dtype=jnp.float32,
        ))
        with pytest.raises(S.ScheduleConflictError,
                           match="supports_pipe_table"):
            _pipe_build(moe)

    def test_divisibility_refused_with_slot_name(self, model4):
        # n_layer=4, stages*virtual=2*4=8: refuses by name
        with pytest.raises(S.ScheduleConflictError,
                           match="pipe slot.*not.*divisible"):
            _pipe_build(model4, virtual=4)

    def test_builder_valueerror_becomes_conflict(self, model4):
        # geometry the builder itself refuses surfaces as the ONE
        # scheduler error type, wrapped with the slot name
        with pytest.raises(S.ScheduleConflictError,
                           match="pipe slot.*2 stages"):
            _pipe_build(model4, stages=1, virtual=1)

    def test_sched_spec_pipe(self):
        assert S.parse_sched_spec("pipe=interleaved:2") == {
            "pipeline_schedule": "interleaved", "pipeline_virtual": 2}
        # interleaved without :V defaults to 2 (V=1 would be plain 1f1b)
        assert S.parse_sched_spec("pipe=interleaved") == {
            "pipeline_schedule": "interleaved", "pipeline_virtual": 2}
        assert S.parse_sched_spec("pipe=zbub") == {
            "pipeline_schedule": "zbub"}
        assert S.parse_sched_spec("pipe=1f1b") == {
            "pipeline_schedule": "1f1b"}
        with pytest.raises(ValueError, match="pipe must be one of"):
            S.parse_sched_spec("pipe=wavefront")


class TestEngineValidation:
    """Ctor-time validation + eager schedule build — no compiles."""

    def test_bad_schedule_name(self, model4):
        with pytest.raises(ValueError, match="pipeline_schedule must be"):
            DDP(model4, AdamW(lr=1e-3), pipeline_parallel=2,
                pipeline_schedule="wavefront")

    def test_bad_virtual_suffix(self, model4):
        with pytest.raises(ValueError, match="':V' suffix must be an"):
            DDP(model4, AdamW(lr=1e-3), pipeline_parallel=2,
                pipeline_schedule="interleaved:x")

    def test_table_schedule_needs_pipe_axis(self, model4):
        with pytest.raises(ValueError, match="requires pipeline_parallel"):
            DDP(model4, AdamW(lr=1e-3), pipeline_schedule="zbub")

    def test_ctor_builds_pipe_program(self, model4):
        eng = DDP(model4, AdamW(lr=1e-3), pipeline_parallel=2,
                  pipeline_microbatches=4,
                  pipeline_schedule="interleaved:2")
        assert eng._lowering == "pipe"
        prog = eng._schedule.pipe_program
        assert (prog.stages, prog.virtual, prog.microbatches) == (2, 2, 4)
        assert prog.bubble_frac < PS.analytic_1f1b_bubble(2, 4)
        # the ":V" suffix and the explicit kwarg are the same knob
        eng2 = DDP(model4, AdamW(lr=1e-3), pipeline_parallel=2,
                   pipeline_microbatches=4, pipeline_schedule="zbub",
                   pipeline_virtual=2)
        assert eng2._schedule.pipe_program.split_w is True
        assert eng2._schedule.pipe_program.virtual == 2

    def test_engine_surfaces_conflict(self, model4):
        with pytest.raises(S.ScheduleConflictError, match="pipe slot"):
            DDP(model4, AdamW(lr=1e-3), pipeline_parallel=2,
                pipeline_microbatches=4,
                pipeline_schedule="interleaved:2", grad_comm="int8")


# ---------------------------------------------------------------------------
# heavies (slow from the start — zero-sum tier-1 budget): compiles
# ---------------------------------------------------------------------------

_CFG16 = dict(block_size=32, vocab_size=128, n_layer=16, n_head=2,
              n_embd=32, compute_dtype=jnp.float32)


@pytest.mark.slow
class TestEnginePipeParity:
    """ISSUE 19 acceptance: loss parity across the three schedules at
    fixed (S, M) on the CPU mesh.  pipeline_parallel=8 puts ALL devices
    on the pipe axis (data=1) — the only geometry this jaxlib's CPU
    partitioner accepts for a partial-manual program."""

    def _run(self, sched, steps=20):
        model = GPT2Model(GPTConfig(**_CFG16))
        eng = DDP(model, AdamW(lr=1e-3), pipeline_parallel=8,
                  pipeline_microbatches=8, pipeline_schedule=sched)
        state = eng.init(jax.random.PRNGKey(0))
        batch = make_batch(1)
        losses = []
        for _ in range(steps):
            state, loss = eng.step(state, batch)
            losses.append(float(loss))
        return losses, eng

    def test_three_schedules_agree(self):
        base, eng1 = self._run("1f1b")
        assert eng1._schedule.pipe_program is None
        for sched in ("interleaved:2", "zbub:2"):
            losses, eng = self._run(sched)
            prog = eng._schedule.pipe_program
            assert prog is not None and prog.virtual == 2
            # the compiled program's bubble beats the 1F1B analytic
            assert prog.bubble_frac < PS.analytic_1f1b_bubble(8, 8)
            err = max(abs(a - b) for a, b in zip(base, losses))
            assert err < 1e-4, f"{sched}: max |dloss| = {err}"
        assert base[-1] < base[0]   # and training actually trains


_SUBPROC_LEGACY = r"""
import hashlib, json, sys
sys.path.insert(0, {repo!r})
import jax, jax.numpy as jnp
from tiny_deepspeed_tpu import AdamW, DDP, GPTConfig, GPT2Model
cfg = GPTConfig(block_size=32, vocab_size=128, n_layer=4, n_head=2,
                n_embd=32, compute_dtype=jnp.float32)
model = GPT2Model(cfg)
k1, k2 = jax.random.split(jax.random.PRNGKey(1))
batch = (jax.random.randint(k1, (8, 32), 0, 128),
         jax.random.randint(k2, (8, 32), 0, 128))
out = {{}}
for name in ("gpipe", "1f1b"):
    eng = DDP(model, AdamW(lr=1e-3), pipeline_parallel=4,
              pipeline_microbatches=4, pipeline_schedule=name)
    state = eng.init(jax.random.PRNGKey(0))
    txt = eng._step.lower(state, batch).as_text()
    out[name] = hashlib.sha256(txt.encode()).hexdigest()
print(json.dumps(out))
"""


@pytest.mark.slow
class TestLegacyPathsUntouched:
    def test_gpipe_1f1b_hlo_deterministic_fresh_subprocess(self, model4):
        """The legacy executors with the new knobs at their defaults
        lower to the SAME HLO bytes in a fresh interpreter — the table
        machinery adds nothing to the gpipe/1f1b programs."""
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        flags = env.get("XLA_FLAGS", "")
        if "host_platform_device_count" not in flags:
            env["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8"
            ).strip()
        proc = subprocess.run(
            [sys.executable, "-c", _SUBPROC_LEGACY.format(repo=REPO)],
            capture_output=True, text=True, timeout=900, env=env,
        )
        assert proc.returncode == 0, proc.stderr[-2000:]
        remote = json.loads(proc.stdout.strip().splitlines()[-1])
        batch = make_batch(1)
        for name in ("gpipe", "1f1b"):
            eng = DDP(model4, AdamW(lr=1e-3), pipeline_parallel=4,
                      pipeline_microbatches=4, pipeline_schedule=name)
            state = eng.init(jax.random.PRNGKey(0))
            txt = eng._step.lower(state, batch).as_text()
            assert hashlib.sha256(txt.encode()).hexdigest() \
                == remote[name], name

    def test_virtual_knob_inert_on_legacy_schedules(self, model4):
        """pipeline_virtual only exists for the table schedules: on
        gpipe it must not perturb the traced program AT ALL."""
        def hlo(**kw):
            eng = DDP(model4, AdamW(lr=1e-3), pipeline_parallel=4,
                      pipeline_microbatches=4,
                      pipeline_schedule="gpipe", **kw)
            state = eng.init(jax.random.PRNGKey(0))
            return eng._step.lower(state, make_batch()).as_text()
        assert hlo() == hlo(pipeline_virtual=3)
