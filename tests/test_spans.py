# Copyright 2026 tiny-deepspeed-tpu authors
# SPDX-License-Identifier: Apache-2.0

"""The program's own span layer (PR 26): `tds.*` host spans in the tick
and the step, named scopes and kernel names in the compiled programs,
`ServingEngine.tick_records`, the start-up marks.

`utils/profiling.TABLE` is the one list of names; these tests hold the
code to it in both directions: every name of the table is written by a
tiny run under `jax.profiler`, and no `tds` name is written that the table
lacks.  All on the CPU: a host span lands in the profiler's file here as
on the chip, and an op_name is in the compiled module's text.
"""

import dataclasses
import glob
import os
import re
import subprocess
import sys
import timeit

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tiny_deepspeed_tpu as tds
from benchmarks.reduce import spans
from tiny_deepspeed_tpu.data import TokenLoader
from tiny_deepspeed_tpu.models import ALL_PRESETS, build_model
from tiny_deepspeed_tpu.serving import ServeConfig, ServingEngine
from tiny_deepspeed_tpu.utils import hlo_cost, profiling, startup
from tiny_deepspeed_tpu.utils.profiling import TABLE, StepTimer, span

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TINY = ALL_PRESETS["tiny"]


def _names(kind):
    return {n for n, (k, _, _) in TABLE.items() if k == kind}


def _traced(tmp, body):
    """Run body() under the profiler as the benchmark's Tracer sets it
    (no Python tracer, host level 1); -> the program's `tds.*` spans."""
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp), profiler_options=options)
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    return _host_spans(tmp)


def _host_spans(log_dir):
    """The `tds.*` host spans of the newest trace under log_dir, in order."""
    path = sorted(glob.glob(os.path.join(
        str(log_dir), "plugins", "profile", "*", "*.xplane.pb")))[-1]
    return sorted((e for p in spans.read_xspace(path)
                   if p.name.startswith("/host:")
                   for evs in p.lines.values() for e in evs
                   if e.name.startswith("tds.")), key=lambda e: e.start)


def _inside(child, parent):
    return parent.start <= child.start and child.end <= parent.end


@pytest.fixture(scope="module")
def model_params():
    model = build_model(TINY)
    return model, model.init(jax.random.PRNGKey(0))


def _engine(model_params, **kw):
    model, params = model_params
    cfg = dict(max_active=4, num_blocks=32, block_tokens=16,
               temperature=0.0)
    cfg.update(kw)
    return ServingEngine(model, params, ServeConfig(**cfg))


@pytest.fixture(scope="module")
def tick_spans(model_params, tmp_path_factory):
    """One traced serving run: three requests through a warm engine, then
    a speculative engine's ticks (the only writer of tds.tick.draft)."""
    eng = _engine(model_params)
    warm = eng.submit(list(range(1, 20)), 3)
    eng.drain()
    spec = _engine(model_params, max_active=3, num_blocks=24,
                   block_tokens=8, spec_draft="ngram", spec_k=3)
    spec.submit([5, 6, 7] * 6, 4)
    spec.drain()
    reqs = []

    def body():
        for i in range(3):
            reqs.append(eng.submit(list(range(1, 20 + 7 * i)), 6))
        eng.drain()

    def spec_body():
        spec.submit([5, 6, 7] * 6, 6)
        spec.drain()

    # a model with two kinds of cache (the only writer of tds.tick.roll)
    eva_model = build_model(ALL_PRESETS["evabyte-tiny"])
    eva = ServingEngine(eva_model, eva_model.init(jax.random.PRNGKey(0)),
                        ServeConfig(max_active=2, num_blocks=64,
                                    block_tokens=8, temperature=0.0))
    eva.submit(list(range(1, 30)), 3)
    eva.drain()

    def eva_body():
        eva.submit(list(range(1, 30)), 8)
        eva.drain()

    # a model whose decode program counts its own step (the only writer
    # of tds.tick.route)
    mimo_model = build_model(ALL_PRESETS["mimo-tiny"])
    mimo = ServingEngine(mimo_model, mimo_model.init(jax.random.PRNGKey(0)),
                         ServeConfig(max_active=2, num_blocks=64,
                                     block_tokens=8, temperature=0.0))
    mimo.submit(list(range(1, 30)), 3)
    mimo.drain()

    def mimo_body():
        mimo.submit(list(range(1, 30)), 5)
        mimo.drain()

    found = _traced(tmp_path_factory.mktemp("tick"), body)
    drafted = _traced(tmp_path_factory.mktemp("spec"), spec_body)
    drafted += _traced(tmp_path_factory.mktemp("eva"), eva_body)
    drafted += _traced(tmp_path_factory.mktemp("mimo"), mimo_body)
    assert warm.done and all(r.done for r in reqs)
    return eng, reqs, found, drafted


@pytest.fixture(scope="module")
def step_spans(tmp_path_factory):
    """A traced training loop as examples/common.run writes it: loader,
    staging, engine.step, the StepTimer's closing fetch."""
    model = build_model(TINY)
    eng = tds.SingleDevice(model, tds.AdamW(lr=1e-3))
    state = eng.init(jax.random.PRNGKey(0))
    loader = TokenLoader(None, batch=2, seq=32, vocab_size=TINY.vocab_size,
                         seed=1)
    timer = StepTimer()

    def one(state):
        with timer.step() as t:
            idx, tgt = loader.next()
            with span("tds.h2d"):
                batch = (jnp.asarray(idx), jnp.asarray(tgt))
            state, loss = eng.step(state, batch)
            t.observe(loss)
        return state

    state = one(state)                      # compile outside the trace
    found = _traced(tmp_path_factory.mktemp("step"),
                    lambda: [one(state) for _ in range(1)])
    loader.close()
    return found


# -- host spans --------------------------------------------------------------

def test_every_span_of_the_table_is_written_and_none_besides(
        tick_spans, step_spans):
    _, _, ticks, drafted = tick_spans
    written = {e.name for e in ticks + drafted + step_spans}
    assert written == _names("span")
    assert "tds.tick.draft" in {e.name for e in drafted}
    # a window that starts over is counted on the span, and in the record
    rolls = [dict(e.stats) for e in drafted if e.name == "tds.tick.roll"]
    assert len(rolls) == 7 and sum(r["windows_rolled"] for r in rolls) == 1
    assert all(r["active"] == 1 and r["window_blocks"] == 4 for r in rolls)
    assert [r["rows"] for r in rolls] == [
        n % 32 + n // 32 * 8 for n in range(29, 36)]
    # and in the EVA kernel's chunks, 256 rows of a range: one of the
    # window at 29-31, one of the 8 summaries at 32 (a window just
    # begun), both from 33; a table row holds one of each, two slots four
    assert [r["kv_steps_live"] for r in rolls] == [1, 1, 1, 1, 2, 2, 2]
    assert {r["kv_steps"] for r in rolls} == {2 * (1 + 1)}
    # what a decode program counted of its own step rides the span that
    # follows its fetch: one slot's 4 choices in each of 6 expert layers,
    # every expert held (mimo-tiny), beside what the layout counted
    routes = [dict(e.stats) for e in drafted if e.name == "tds.tick.route"]
    assert len(routes) == 4
    assert all(r["pairs"] == 6 * 4 and 6 <= r["experts_touched"] <= 24
               for r in routes)
    assert [r["rows_global"] for r in routes] == [29, 30, 31, 32]
    assert all(r["active"] == 1 and r["rows_window"] == 15
               and r["window_blocks"] == 2 for r in routes)
    assert [r["global_blocks"] for r in routes] == [4, 4, 4, 5]


def test_tick_spans_nest_as_the_table_says_and_carry_the_tick_number(
        tick_spans):
    eng, _, found, _ = tick_spans
    ticks = [e for e in found if e.name == "tds.tick"]
    numbers = [dict(e.stats)["tick"] for e in ticks]
    assert len(ticks) >= 4 and numbers == sorted(numbers)
    parts = [e for e in found if e.name.startswith("tds.tick.")]
    for e in parts:
        homes = [t for t in ticks if _inside(e, t)]
        assert len(homes) == 1, e.name
        ids = dict(e.stats)
        # a request's span carries its id, a tick's the tick number
        assert ("request" in ids) != ("tick" in ids), (e.name, ids)
        if "tick" in ids:
            assert ids["tick"] == dict(homes[0].stats)["tick"]
    # tds.submit lies outside every tick
    submits = [e for e in found if e.name == "tds.submit"]
    assert len(submits) == 3
    assert not any(_inside(s, t) for s in submits for t in ticks)


def test_an_admission_is_admit_dispatch_fetch_commit_with_its_request_id(
        tick_spans):
    _, reqs, found, _ = tick_spans
    for r in reqs:
        mine = [e for e in found if dict(e.stats).get("request") == r.id]
        assert [e.name for e in mine] == [
            "tds.tick.admit", "tds.tick.prefill.dispatch",
            "tds.tick.prefill.fetch", "tds.tick.commit"]
        for a, b in zip(mine, mine[1:]):
            assert a.end <= b.start
        bucket = dict(mine[1].stats)["bucket"]
        assert bucket >= len(r.prompt) and bucket % 16 == 0
    # the decode half of a tick, in order
    tick = next(t for t in found if t.name == "tds.tick"
                and dict(t.stats)["tick"] == 3)
    order = [e.name for e in found
             if e.name.startswith("tds.tick.") and _inside(e, tick)]
    assert order == ["tds.tick.sched", "tds.tick.decode.operands",
                     "tds.tick.decode.dispatch", "tds.tick.decode.fetch",
                     "tds.tick.commit", "tds.tick.observe"]


def test_step_spans_follow_the_loop(step_spans):
    assert [e.name for e in step_spans] == [
        "tds.load", "tds.h2d", "tds.step", "tds.sync"]
    for a, b in zip(step_spans, step_spans[1:]):
        assert a.end <= b.start


# -- tick records ------------------------------------------------------------

def test_tick_records_are_kept_without_a_logger(tick_spans):
    eng, reqs, _, _ = tick_spans
    assert eng.logger is None and eng.telemetry is None
    recs = list(eng.tick_records)
    assert [r["tick"] for r in recs] == list(range(len(recs)))
    assert eng.tick_records.maxlen == 512
    first = next(r for r in recs if r["admitted"] == 3)
    assert first["buckets"] == [32, 32, 64] and first["active"] == 3
    assert first["produced"] == 6     # three first tokens, three decoded
    assert [n for n, _, _ in first["segments"]].count("admit") == 3
    assert sum(r["produced"] for r in recs) == 3 + sum(
        len(r.tokens) for r in reqs)
    assert not hasattr(eng, "_seg")


def test_tick_record_segments_are_disjoint_inside_the_tick_and_sum_to_it(
        model_params):
    # wider than `tiny`, so that a tick is milliseconds of device work and
    # the Python between two spans is small beside it
    cfg = dataclasses.replace(TINY, n_embd=256, n_head=4, n_layer=4)
    model = build_model(cfg)
    eng = ServingEngine(model, model.init(jax.random.PRNGKey(0)),
                        ServeConfig(max_active=8, num_blocks=128,
                                    block_tokens=16, temperature=0.0))
    for i in range(8):
        eng.submit(list(range(1, 100 + i)), 12)
    eng.drain()
    recs = list(eng.tick_records)[2:]       # the first ticks compile
    assert len(recs) >= 8
    for r in recs:
        segs = r["segments"]
        assert r["t0"] <= segs[0][1] and segs[-1][2] <= r["t1"]
        for (_, _, end), (_, start, _) in zip(segs, segs[1:]):
            assert end <= start
        assert all(end >= start for _, start, end in segs)
    covered = sum(end - start for r in recs for _, start, end in r["segments"])
    wall = sum(r["t1"] - r["t0"] for r in recs)
    assert 0.95 * wall <= covered <= wall


def test_tick_jsonl_record_carries_the_segments_at_their_starts(
        model_params):
    class Sink:
        def __init__(self):
            self.records = []

        def log_meta(self, **rec):
            self.records.append(rec)

    model, params = model_params
    sink = Sink()
    eng = ServingEngine(model, params, ServeConfig(
        max_active=4, num_blocks=32, block_tokens=16, temperature=0.0,
        tick_record_every=1), logger=sink)
    eng.submit(list(range(1, 20)), 3)
    eng.drain()
    ticks = [r for r in sink.records if r["kind"] == "tick"]
    assert len(ticks) == len(eng.tick_records)
    for rec, kept in zip(ticks, eng.tick_records):
        names = [n for n, _, _ in rec["spans"]]
        assert names == [n for n, _, _ in kept["segments"]][:len(names)]
        assert all(0 <= rel and rel + dur <= rec["wall_s"] + 1e-6
                   for _, rel, dur in rec["spans"])
        split = sum(rec[k] for k in ("sched_s", "prefill_s", "decode_s",
                                     "fetch_s"))
        assert split == pytest.approx(rec["wall_s"], abs=5e-6)


def test_a_decode_tick_counts_the_kernels_live_steps(
        model_params, monkeypatch, tmp_path):
    """`kv_steps_live` of `kv_steps`: the paged kernel's pool steps a
    layer that begin below their slot's length, counted from the slots'
    `pos` into `tick_records`, the `tick` JSONL record and the ids of
    `tds.tick.decode.operands`.  A step is cut to one 16-token block so
    that three short requests reach several."""
    import tiny_deepspeed_tpu.ops.paged_attn_pallas as PAP
    from tiny_deepspeed_tpu.telemetry import schema

    class Sink:
        def __init__(self):
            self.records = []

        def log_meta(self, **rec):
            self.records.append(rec)

    monkeypatch.setattr(PAP, "_STEP_TOKENS", 16)
    model, params = model_params
    sink = Sink()
    eng = ServingEngine(model, params, ServeConfig(
        max_active=4, num_blocks=32, block_tokens=16, temperature=0.0,
        tick_record_every=1), logger=sink)
    eng.submit(list(range(1, 20)), 2)
    eng.drain()                                 # compile outside the trace
    first = len(eng.tick_records)
    asked = [(14, 6), (30, 4), (47, 6)]         # prompt tokens, new tokens

    def body():
        for n, new in asked:
            eng.submit(list(range(1, n + 1)), new)
        eng.drain()

    found = _traced(tmp_path, body)
    # all three are admitted in one tick, which decodes too: at decode
    # step t a request of n prompt tokens holds n + t, and it leaves
    # after new - 1 steps (the prefill gave its first token)
    by_hand = [sum(-(-(n + t) // 16) for n, new in asked if t < new - 1)
               for t in range(5)]
    assert by_hand == [6, 6, 7, 6, 6]
    # the dense layout's two, and the two a decode program that counts
    # its own step hands back (tds.tick.route, above)
    counters = {"kv_steps_live", "kv_steps"}
    assert counters | {"pairs", "experts_touched"} == {
        n for n in _names("counter") if TABLE[n][1] == "kernels (serve)"}
    kept = list(eng.tick_records)[first:]
    assert [r["kv_steps_live"] for r in kept] == by_hand
    assert {r["kv_steps"] for r in kept} == {4 * eng.max_blocks_per_req}
    ticks = [r for r in sink.records if r["kind"] == "tick"][first:]
    ids = [dict(e.stats) for e in found
           if e.name == "tds.tick.decode.operands"]
    for rec, written, span_ids in zip(kept, ticks, ids, strict=True):
        assert {k: written[k] for k in counters} == {
            k: rec[k] for k in counters} == {
            k: span_ids[k] for k in counters}
        assert span_ids["tick"] == rec["tick"]
        assert schema.validate_record(dict(written, ts=0.0)) == []
    # a tick that decodes nothing counts nothing
    idle = ServingEngine(model, params, ServeConfig(max_active=2,
                                                    num_blocks=8))
    idle.tick()
    assert not counters & set(idle.tick_records[-1])


# -- a span costs nothing with no session ------------------------------------

def test_a_dead_span_costs_under_two_microseconds_and_keeps_nothing():
    def one():
        with span("tds.tick.admit", request=7):
            pass

    # the best of many short batches: on a CPU that six test workers
    # share, one batch can be slow throughout; a span that allocates or
    # records is slow in every batch
    n = 2000
    best = min(timeit.repeat(one, number=n, repeat=40)) / n
    assert best < 2e-6, f"{best * 1e6:.2f} us"
    import gc
    gc.collect()
    before = len(gc.get_objects())
    for _ in range(1000):
        one()
    gc.collect()
    assert len(gc.get_objects()) - before < 50


# -- start-up marks ----------------------------------------------------------

def test_startup_marks_order_imports_before_the_backend():
    code = (
        "import json, time; t = time.monotonic();"
        "from tiny_deepspeed_tpu.utils import startup;"
        "startup.select_platform(cpu=True);"
        "print(json.dumps(dict(startup.marks, t=t, now=time.monotonic())))")
    out = subprocess.run([sys.executable, "-c", code], cwd=REPO, text=True,
                         capture_output=True, timeout=300,
                         env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 0, out.stderr[-2000:]
    import json
    m = json.loads(out.stdout.strip().splitlines()[-1])
    assert set(m) - {"t", "now"} == {
        n for n in _names("counter") if TABLE[n][1] == "entry / start-up"}
    assert (m["t"] <= m["import_begin"] <= m["import_done"]
            <= m["select_platform"] <= m["backend_up"] <= m["now"])
    # the package import is seconds of work: the mark is not a constant
    assert m["import_done"] - m["import_begin"] > 0.05
    assert set(startup.marks) >= {"import_begin", "import_done"}


# -- names inside the compiled programs --------------------------------------

_DOT = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = (\S+) dot\(([^)]*)\)(.*)$")
_DEF = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = (\([^=]*?\)|\S+) ")


def _dot_flops_by_instruction(text):
    """{instruction: 2 x result elements x contracted size} of every dot."""
    shape = {}
    for line in text.splitlines():
        m = _DEF.match(line)
        if m:
            dims = re.search(r"\[([\d,]*)\]", m.group(2))
            shape[m.group(1)] = [int(d) for d in dims.group(1).split(",")
                                 if d] if dims else []
    out = {}
    for line in text.splitlines():
        m = _DOT.match(line)
        if not m:
            continue
        lhs = re.findall(r"%([\w.\-]+)", m.group(3))[0]
        contract = re.search(r"lhs_contracting_dims=\{([\d,]*)\}", line)
        k = int(np.prod([shape[lhs][int(d)] for d in
                         contract.group(1).split(",") if d]))
        out[m.group(1)] = 2.0 * int(np.prod(shape[m.group(1)] or [1])) * k
    return out


@pytest.fixture(scope="module")
def compiled_step():
    """The tiny GPT-2 step with remat, compiled; (text, scope map)."""
    cfg = dataclasses.replace(TINY, remat=True, remat_policy="nothing")
    eng = tds.SingleDevice(build_model(cfg), tds.AdamW(lr=1e-3),
                           grad_clip=1.0)
    state = jax.eval_shape(eng.init, jax.random.PRNGKey(0))
    idx = jax.ShapeDtypeStruct((2, 64), jnp.int32)
    text = eng._step.lower(state, (idx, idx)).compile().as_text()
    return text, hlo_cost.scope_map(text)


def test_scope_map_gives_the_matmuls_a_tds_scope(compiled_step):
    text, scope_map = compiled_step
    assert text.startswith("HloModule jit_tds_train_step")
    flops = _dot_flops_by_instruction(text)
    assert len(flops) >= 12
    scoped = sum(f for name, f in flops.items()
                 if spans.scopes_of(scope_map.get(name)))
    assert scoped >= 0.95 * sum(flops.values())
    # every matmul sits in the scope its layer's name says
    inner = {spans.scopes_of(scope_map[name])[-1] for name in flops}
    assert inner == {"tds.attn.qkv", "tds.attn.kernel", "tds.attn.proj",
                     "tds.mlp", "tds.head"}


def test_scope_map_tells_forward_backward_recompute_and_optimizer(
        compiled_step):
    _, scope_map = compiled_step
    seen = {}
    for op_name in scope_map.values():
        scopes = spans.scopes_of(op_name)
        if scopes:
            seen.setdefault(spans.phase_of(op_name), set()).update(scopes)
    assert set(seen) == set(spans.PHASES)
    block = {"tds.blocks", "tds.block", "tds.ln", "tds.attn.qkv",
             "tds.attn.kernel", "tds.attn.proj", "tds.mlp"}
    assert block <= seen["forward"] and block <= seen["backward"]
    assert block <= seen["recompute"]          # remat "nothing"
    assert {"tds.embed", "tds.head"} <= seen["forward"] & seen["backward"]
    assert seen["optimizer"] == {"tds.optim"}
    written = set().union(*seen.values())
    assert written <= _names("scope")


def test_serving_programs_are_named_and_scoped(model_params):
    eng = _engine(model_params)
    eng.submit(list(range(1, 20)), 2)
    eng.drain()
    model, params = model_params
    S = eng.config.max_active
    view = eng.pool.view
    ints = jax.ShapeDtypeStruct((S,), jnp.int32)
    text = eng._decode_fn.lower(
        params, eng._stacked, view, ints, ints,
        jax.ShapeDtypeStruct((S, eng.max_blocks_per_req), jnp.int32),
        ints, ints, jax.ShapeDtypeStruct((S,), jnp.float32),
    ).compile().as_text()
    assert text.startswith("HloModule jit_tds_decode")
    scopes = {s for n in hlo_cost.scope_map(text).values()
              for s in spans.scopes_of(n)}
    assert {"tds.decode", "tds.embed", "tds.blocks", "tds.block",
            "tds.kv_write", "tds.attn.kernel", "tds.mlp", "tds.head",
            "tds.sample"} <= scopes <= _names("scope")
    # everything the decode program does is under tds.decode (an inner
    # computation's instructions carry their name from its own root on)
    firsts = {spans.scopes_of(n)[0] for n in hlo_cost.scope_map(
        text).values() if n.startswith("jit(tds_decode)/")}
    assert firsts == {"tds.decode"}
    pre = eng._prefill_fn.lower(
        params, eng._stacked, jax.ShapeDtypeStruct((1, 32), jnp.int32), 3,
        jax.ShapeDtypeStruct((2,), jnp.int32), view, np.int32(0),
        np.int32(0)).compile().as_text()
    assert pre.startswith("HloModule jit_tds_prefill")
    assert {"tds.prefill", "tds.kv_write", "tds.sample"} <= {
        s for n in hlo_cost.scope_map(pre).values()
        for s in spans.scopes_of(n)}


def test_scopes_change_nothing_but_metadata(monkeypatch):
    """The optimized HLO with metadata= stripped is the same text with and
    without the scopes.  The program has no switch for them: the test
    patches jax.named_scope away for the comparison."""
    import contextlib

    def text():
        eng = tds.SingleDevice(build_model(TINY), tds.AdamW(lr=1e-3))
        state = jax.eval_shape(eng.init, jax.random.PRNGKey(0))
        idx = jax.ShapeDtypeStruct((2, 32), jnp.int32)
        raw = eng._step.lower(state, (idx, idx)).compile().as_text()
        # metadata= on each instruction, and the module's table of the
        # source locations that metadata points into
        bare = re.sub(r", metadata=\{[^}]*\}", "", raw)
        bare = re.sub(r"\n(?:FileNames|FunctionNames|FileLocations|"
                      r"StackFrames)\n(?:\d+ .*\n)+", "\n", bare)
        return bare, raw

    with_scopes, raw = text()
    assert "tds.block" in raw

    @contextlib.contextmanager
    def no_scope(name):
        yield

    monkeypatch.setattr(jax, "named_scope", no_scope)
    without, raw = text()
    assert "tds.block" not in raw and "tds.optim" not in raw
    assert with_scopes == without


def test_every_pallas_kernel_has_its_table_name():
    found = set()
    for path in glob.glob(os.path.join(
            REPO, "tiny_deepspeed_tpu", "ops", "*.py")):
        with open(path) as f:
            src = f.read()
        calls = src.count("pl.pallas_call(")
        names = re.findall(r'\bname="(tds_\w+)"', src)
        assert len(names) == calls, path
        found.update(names)
    assert found == _names("kernel") and len(found) == 15


def test_the_table_names_layers_and_metrics_that_exist():
    import json
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    layers = {m["layer"] for m in manifest["per_layer"]}
    metrics = {m["name"] for m in manifest["per_layer"]} | {
        os.path.basename(p)[:-3] for p in glob.glob(os.path.join(
            REPO, "benchmarks", "metrics", "*.py"))}
    for name, (kind, layer, metric) in TABLE.items():
        assert kind in ("span", "scope", "program", "kernel", "counter")
        assert layer in layers, (name, layer)
        assert metric is None or metric in metrics, (name, metric)
    assert profiling.span("tds.tick").__class__ is \
        jax.profiler.TraceAnnotation


def test_the_trainer_writes_its_spans_into_its_own_profile(tmp_path):
    """examples/common.run under --profile: staging and the loss fetch are
    the same helper's spans (`tds.h2d`, `tds.sync`), beside the loader's
    and the engine's."""
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "examples", "single_device",
                                      "train.py"),
         "--cpu-devices", "1", "--iters", "6", "--seq-len", "32",
         "--profile", str(tmp_path)],
        cwd=REPO, text=True, capture_output=True, timeout=600,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 0, out.stderr[-2000:]
    names = [e.name for e in _host_spans(tmp_path)]
    assert {"tds.load", "tds.h2d", "tds.step", "tds.sync"} == set(names)
    assert names.count("tds.step") >= 2
