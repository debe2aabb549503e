"""reduce/spans.py: the reduction of the program's own names (`tds.*`) in a
profiler trace.  On hand-made planes, on the first fixture (a trace of the
parent program: no `tds` name, so nothing to read), and on
`fixture_spans_{train,serve}.xplane.pb`, recorded on the v5e by
`record_fixture_spans.py` from the tiny train cell and the tiny serve cell.
Reading a fixture needs no TPU library and compiles nothing."""

import importlib.util
import json
import os
import re

import pytest

import tinyroot
from benchmarks import harness
from benchmarks.reduce import spans, xplane
from benchmarks.reduce.intervals import measure, self_times, union
from benchmarks.spans_run import append_per_layer, cells_readers

REDUCE = os.path.dirname(xplane.__file__)
REPO = tinyroot.REPO
FIRST = os.path.join(REDUCE, "fixture.xplane.pb")
TRAIN = os.path.join(REDUCE, "fixture_spans_train.xplane.pb")
SERVE = os.path.join(REDUCE, "fixture_spans_serve.xplane.pb")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


# -- names -------------------------------------------------------------------

def test_scopes_and_phases_are_read_from_an_op_name():
    fwd = "jit(tds_train_step)/jvp(tds.blocks)/while/body/tds.block/tds.mlp/dot"
    bwd = ("jit(tds_train_step)/transpose(jvp(tds.blocks))/while/body/"
           "checkpoint/tds.block/tds.attn.kernel/pallas_call")
    again = ("jit(tds_train_step)/transpose(jvp(tds.blocks))/while/body/"
             "checkpoint/rematted_computation/tds.block/tds.ln/mul")
    assert spans.scopes_of(fwd) == ["tds.blocks", "tds.block", "tds.mlp"]
    assert spans.scopes_of(bwd)[-1] == "tds.attn.kernel"
    assert [spans.phase_of(n) for n in (fwd, bwd, again)] == [
        "forward", "backward", "recompute"]
    assert spans.phase_of("jit(tds_train_step)/tds.optim/mul") == "optimizer"
    # merged operations: the first name is the fused root's
    assert spans.scopes_of("a/tds.block/tds.ln/x;b/tds.block/tds.mlp/y") == [
        "tds.block", "tds.ln"]
    assert spans.scopes_of(None) == [] and spans.scopes_of("jit(f)/mul") == []
    assert spans.phase_of(None) == "forward"
    assert spans.program_of("jit_tds_decode(6704416550516843479)") == \
        "jit_tds_decode"


def test_a_collective_is_a_gather_or_a_gradients_by_scope_then_by_what_it_does():
    rs = ("%fusion.291 = f32[1792,1600] fusion(%gte.1), kind=kCustom, "
          "calls=%all-reduce-scatter.2.clone")
    ag = "%all-gather.197 = bf16[1,1600,4800] all-gather(%x)"
    ar = "%all-reduce.75 = f32[1600] all-reduce(%y)"
    cp = ("%collective-permute-start.3 = (bf16[576,1600], bf16[576,1600], "
          "u32[], u32[]) collective-permute-start(bf16[576,1600] %slice.71)")
    assert spans.collective_class(ag, None) == "gather"
    assert spans.collective_class(cp, None) == "gather"   # moves, reduces nothing
    assert spans.collective_class(rs, None) == "grad"
    assert spans.collective_class(ar, "jit(f)/jvp(tds.head)/psum") == "grad"
    # the engine's own scope wins over the operation's kind
    assert spans.collective_class(ar, "jit(f)/tds.gather/x") == "gather"
    assert spans.collective_class(ag, "jit(f)/tds.grad_sync/x") == "grad"


# -- hand-made planes --------------------------------------------------------

def _ev(name, start, end, mid=0, **ids):
    return spans.Event(name, start, end, mid, tuple(ids.items()))


def _planes():
    """One chip, two ticks: a prefill and a decode, then a decode; the host
    inside tds.tick.* spans; a copy with no op_name that feeds the kernel."""
    meta = {
        1: {"tf_op": "jit(tds_prefill)/tds.prefill/tds.blocks/tds.block/"
                     "tds.mlp/dot_general:"},
        2: {"tf_op": "jit(tds_prefill)/tds.prefill/tds.kv_write/scatter:"},
        3: {},                                            # the bare copy
        4: {"tf_op": "jit(tds_decode)/tds.decode/tds.blocks/while/body/"
                     "tds.block/tds.attn.kernel/pallas_call:"},
        5: {"tf_op": "jit(tds_decode)/tds.decode/tds.sample/argmax:"},
        6: {"tf_op": "jit(tds_decode)/while:"},           # no tds scope
    }
    ops = [
        _ev("%fusion.1 = bf16[8] fusion(%p)", 100, 140, 1),
        _ev("%copy.9 = bf16[64,16] copy(%pool)", 140, 170, 2),
        _ev("%while.1 = (s32[]) while(%t)", 200, 300, 6),
        _ev("%copy.3 = bf16[4097,16] copy(%view)", 200, 250, 3),
        _ev("%tds_paged_attn.2 = bf16[8] custom-call(%copy.3)", 250, 290, 4),
        _ev("%fusion.5 = s32[8] fusion(%x)", 300, 310, 5),
        _ev("%while.1 = (s32[]) while(%t)", 500, 600, 6),
        _ev("%copy.3 = bf16[4097,16] copy(%view)", 500, 550, 3),
        _ev("%tds_paged_attn.2 = bf16[8] custom-call(%copy.3)", 550, 590, 4),
        _ev("%fusion.5 = s32[8] fusion(%x)", 600, 610, 5),
    ]
    modules = [_ev("jit_tds_prefill(11)", 100, 170),
               _ev("jit_tds_decode(22)", 200, 310),
               _ev("jit_tds_decode(22)", 500, 610)]
    host = [
        _ev("tds.tick", 90, 330, tick=7),
        _ev("tds.tick.admit", 92, 98, request=3),
        _ev("tds.tick.prefill.dispatch", 98, 105, request=3, bucket=64),
        _ev("tds.tick.prefill.fetch", 105, 172, request=3),
        _ev("tds.tick.decode.operands", 172, 198, tick=7),
        _ev("tds.tick.decode.fetch", 199, 312, tick=7),
        _ev("tds.tick.commit", 312, 329, tick=7),
        _ev("bench.tick", 85, 335),
        _ev("tds.tick", 340, 620, tick=8),
        _ev("tds.tick.sched", 341, 480, tick=8),
        _ev("tds.tick.decode.operands", 480, 498, tick=8),
    ]
    return [
        spans.Plane("/device:TPU:0", {"XLA Ops": ops, "XLA Modules": modules},
                    meta),
        spans.Plane("/host:CPU", {"python": host}, {}),
    ]


def test_programs_copies_scopes_and_the_unscoped_share():
    r = spans.reduce_planes(_planes(), units=2)
    ns = 1e-9
    assert r.chips == 1 and r.units == 2
    assert r.programs_s == {"jit_tds_prefill": pytest.approx(70 * ns),
                            "jit_tds_decode": pytest.approx(220 * ns)}
    assert r.program_runs == {"jit_tds_prefill": 1, "jit_tds_decode": 2}
    assert r.program_share == pytest.approx(1.0)
    assert r.per_unit_ms(r.programs_s["jit_tds_decode"]) == \
        pytest.approx(110e-6)
    assert r.per_run_ms(r.programs_s, "jit_tds_prefill") == \
        pytest.approx(70e-6)
    assert r.per_run_ms(r.copies_s, "jit_tds_eval") is None
    # copies by the program they ran in: by the module event around them
    assert r.copies_s == {"jit_tds_prefill": pytest.approx(30 * ns),
                          "jit_tds_decode": pytest.approx(100 * ns)}
    # the bare copy takes the scope of the kernel it feeds; the while's own
    # 10 + 10 ns have no op_name and feed nothing that has: they are the
    # program's, whose whole body is written under tds.decode
    assert r.scopes_s == {
        "tds.mlp": pytest.approx(40 * ns),
        "tds.kv_write": pytest.approx(30 * ns),
        "tds.attn.kernel": pytest.approx(180 * ns),
        "tds.sample": pytest.approx(20 * ns),
        "tds.decode": pytest.approx(20 * ns)}
    assert r.unscoped_s == 0.0 and r.scoped_share == 1.0
    assert r.busy_s == pytest.approx(290 * ns)
    assert sum(r.scopes_s.values()) == pytest.approx(r.busy_s)
    # a train step has no scope around the whole of it: there such
    # operations reach none, and the share says so
    planes = _planes()
    planes[0].lines["XLA Modules"] = [_ev("jit_tds_train_step(5)", 100, 610)]
    t = spans.reduce_planes(planes, units=2)
    assert t.unscoped_s == pytest.approx(20 * ns)
    assert t.scoped_share == pytest.approx(1 - 20 / 290)


def test_idle_inside_a_span_and_gaps_by_innermost_span():
    r = spans.reduce_planes(_planes(), units=2)
    ns = 1e-9
    # idle inside the two ticks: 100-90 is outside the traced window
    # (which begins with the first operation), 170-200, 310-330; 340-500
    assert r.idle_in_s["tds.tick"] == pytest.approx((30 + 20 + 160) * ns)
    assert r.idle_in_s["tds.tick.sched"] == pytest.approx(139 * ns)
    assert "bench.tick" not in r.idle_in_s          # the program's own only
    gaps = [(g.span, round(g.seconds / ns), g.ids) for g in r.gaps]
    # the longest gap runs through commit, sched and decode.operands, the
    # innermost spans, which cover most of it: it goes to the one that
    # covers most, not to tds.tick
    assert gaps[0] == ("tds.tick.sched", 190, {"tick": 8})
    assert gaps[1] == ("tds.tick.decode.operands", 30, {"tick": 7})
    assert len(gaps) == 2
    assert not any(g.span in ("tds.tick", "unannotated") for g in r.gaps)
    host = [e for e in _planes()[1].lines["python"]
            if e.name.startswith("tds.")]
    # mostly between two ticks: in no span; inside a tick but in none of
    # its parts: the tick's
    assert spans._innermost(host, 325, 345) == ("unannotated", {})
    assert spans._innermost(host, 329.2, 330)[0] == "tds.tick"


def test_a_program_without_the_names_gives_nothing_to_read():
    planes = _planes()
    planes[0].lines["XLA Modules"] = [_ev("jit__step_impl(5)", 100, 610)]
    assert spans.reduce_planes(planes, units=2) is None
    assert spans.reduce_planes([planes[1]], units=2) is None   # no device
    # the first fixture is a trace of the parent program
    assert spans.reduce_spans(FIRST, 2) is None


def test_train_phases_head_attention_and_collectives_over_two_chips():
    def chip(shift):
        meta = {
            1: {"tf_op": "jit(tds_train_step)/jvp(tds.blocks)/tds.block/"
                         "tds.attn.kernel/pallas_call:"},
            2: {"tf_op": "jit(tds_train_step)/jvp(tds.head)/dot_general:"},
            3: {"tf_op": "jit(tds_train_step)/transpose(jvp(tds.head))/dot:"},
            4: {"tf_op": "jit(tds_train_step)/transpose(jvp(tds.blocks))/"
                         "checkpoint/rematted_computation/tds.block/"
                         "tds.attn.kernel/pallas_call:"},
            5: {"tf_op": "jit(tds_train_step)/transpose(jvp(tds.blocks))/"
                         "checkpoint/tds.block/tds.attn.kernel/pallas_call:"},
            6: {"tf_op": "jit(tds_train_step)/tds.optim/mul:"},
            7: {}, 8: {},
        }
        ops = [
            _ev("%all-gather.1 = bf16[8] all-gather(%w)", 0, 10 + shift, 7),
            _ev("%tds_fa2_fwd.1 = bf16[8] custom-call(%q)", 20, 30, 1),
            _ev("%fusion.2 = f32[8] fusion(%x)", 30, 50, 2),
            _ev("%fusion.3 = f32[8] fusion(%y)", 50, 80, 3),
            _ev("%tds_fa2_fwd.1 = bf16[8] custom-call(%q)", 80, 90, 4),
            _ev("%tds_fa2_dkv.1 = bf16[8] custom-call(%q)", 90, 115, 5),
            _ev("%fusion.9 = f32[8] fusion(%g), kind=kCustom, "
                "calls=%all-reduce-scatter.2", 115, 135, 8),
            _ev("%fusion.6 = f32[8] fusion(%p)", 135, 150, 6),
        ]
        flying = [_ev("%all-gather-start.4 = bf16[8] all-gather-start(%w)",
                      5, 25, 7)]
        return spans.Plane(
            f"/device:TPU:{shift}",
            {"XLA Ops": ops, "Async XLA Ops": flying,
             "XLA Modules": [_ev("jit_tds_train_step(1)", 0, 150)]}, meta)

    r = spans.reduce_planes([chip(0), chip(1)], units=1)
    ns = 1e-9
    assert r.chips == 2
    # the collectives' own time has no phase: they take their consumers'
    # scope where one is written, and are read through coll_s
    assert r.phases_s["forward"] == pytest.approx(30 * ns)
    assert r.phases_s["recompute"] == pytest.approx(10 * ns)
    assert r.phases_s["backward"] == pytest.approx(55 * ns)
    assert r.phases_s["optimizer"] == pytest.approx(15 * ns)
    assert r.head_s == pytest.approx(50 * ns)
    # every run of the forward kernel is forward, the recomputed one too
    assert r.attn_s == {"forward": pytest.approx(20 * ns),
                        "backward": pytest.approx(25 * ns)}
    # gathers: both lines, as a union (0-10 | 5-25, one ns more on chip 1)
    assert r.coll_s["gather"] == pytest.approx(25 * ns)
    assert r.coll_s["grad"] == pytest.approx(20 * ns)
    assert r.program_share == pytest.approx(1.0)
    # the profiler writes the line of operations in flight for the first
    # chip alone: a chip without it does not halve the gathers
    bare = chip(1)
    bare.lines["Async XLA Ops"] = []
    r = spans.reduce_planes([chip(0), bare], units=1)
    assert r.coll_s["gather"] == pytest.approx(25 * ns)
    assert r.coll_s["grad"] == pytest.approx(20 * ns)


# -- the reader of the file ---------------------------------------------------

def test_the_wire_reader_agrees_with_profiledata_on_the_first_fixture():
    devices, in_flight, annotations = xplane.load(FIRST)
    planes = {p.name: p for p in spans.read_xspace(FIRST)}
    dev = planes["/device:TPU:0"]
    for line, want in ((xplane.OPS_LINE, devices[0]),
                       (xplane.ASYNC_LINE, in_flight[0])):
        got = dev.lines[line]
        assert len(got) == len(want)
        for e, o in zip(got, want):
            assert e.name == o.name
            # ProfileData cuts start and duration to whole ns each; the
            # file has ps
            assert abs(e.start - o.start) < 1 and abs(e.end - o.end) < 2
    own = self_times(dev.lines[xplane.OPS_LINE])
    busy = measure(union((e.start, e.end) for e in dev.lines[xplane.OPS_LINE]))
    assert sum(t for _, t in own) == pytest.approx(busy, rel=1e-9)
    host = [e for evs in planes["/host:CPU"].lines.values() for e in evs
            if e.name.startswith("bench.")]
    assert sorted(e.name for e in host) == sorted(a.name for a in annotations)
    # what ProfileData hides: the stats of the event METADATA
    ops_with_name = [m for m in dev.meta.values() if "tf_op" in m]
    assert len(ops_with_name) == 162
    assert any(str(m["tf_op"]).startswith("jit(_step_impl)/transpose(jvp())")
               for m in ops_with_name)
    assert {"hlo_category", "program_id", "flops", "bytes_accessed"} <= set(
        dev.meta[dev.lines[xplane.OPS_LINE][0].mid])
    modules = dev.lines[spans.MODULES_LINE]
    assert [spans.program_of(m.name) for m in modules] == [
        "jit__step_impl"] * 2
    assert [dict(m.stats)["run_id"] for m in modules] == [10, 11]


# -- the readers through the harness ------------------------------------------

def _reader(name):
    spec = importlib.util.spec_from_file_location(
        "reader_" + name.replace(".", "_"),
        os.path.join(REPO, "benchmarks", "metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ALL_NEW = sorted({n for names in cells_readers().values() for n in names})


@pytest.mark.parametrize("name", ALL_NEW)
def test_each_new_reader_has_the_contracts_form(name):
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    reader = _reader(name)
    assert NAME.match(name) and UNIT.match(reader.UNIT)
    assert reader.BETTER == "lower"
    assert reader.SOURCE in ("device_trace", "program_counter")
    assert reader.LAYER in {m["layer"] for m in manifest["per_layer"]}
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    assert reader.MOVES in e2e
    # every cell it is listed for reports the end-to-end metric it moves
    for cell, names in cells_readers().items():
        if name in names:
            assert cell in e2e[reader.MOVES].get(
                "workloads", [w["name"] for w in manifest["workloads"]])
    # not in BENCHMARK.json yet: cells/*.json cannot name it in this PR
    assert name not in {m["name"] for m in manifest["per_layer"]}
    assert len(ALL_NEW) == 15


def test_readers_find_nothing_in_a_cpu_run_and_do_not_raise(tmp_path):
    """Attached to the tiny cells in a temporary copy (tinyroot.py itself
    unchanged): no TPU plane on the CPU, so every device_trace reader
    returns nothing and the line leaves it out; the counters are read."""
    root, manifest = tinyroot.build(str(tmp_path))
    train = cells_readers()["gpt2-xl.zero3-4chip"]
    append_per_layer(root, "tiny.tiny-train", train)
    assert harness.load_cell("tiny.tiny-train", root).per_layer[-len(
        train):] == train
    result = harness.run_cell("tiny.tiny-train", seed=2**31 + 26,
                              seconds=0.5, trace=True, root=root,
                              manifest=manifest)
    assert result["correct"] is True
    got = set(result["metrics"])
    assert not got & {"fwd_ms", "bwd_ms", "optim_ms", "head_ms",
                      "attn_fwd_ms", "attn_bwd_ms", "coll_gather_ms",
                      "coll_grad_ms"}
    # the package was imported long before this test began
    assert result["metrics"]["import_s"]["unit"] == "s"
    append_per_layer(root, "tiny.tiny-train", train)      # idempotent
    assert harness.load_cell("tiny.tiny-train", root).per_layer.count(
        "fwd_ms") == 1


# -- the recorded traces (TPU v5e, record_fixture_spans.py) --------------------

@pytest.fixture(scope="module")
def train():
    return spans.reduce_spans(TRAIN, 2)


@pytest.fixture(scope="module")
def serve():
    return spans.reduce_spans(SERVE, 5)


def test_train_fixture_module_times_and_kernel_names(train):
    planes = {p.name: p for p in spans.read_xspace(TRAIN)}
    dev = planes["/device:TPU:0"]
    modules = dev.lines[spans.MODULES_LINE]
    assert [spans.program_of(m.name) for m in modules] == [
        "jit_tds_train_step"] * 2
    assert [dict(m.stats)["run_id"] for m in modules] == [10, 11]
    assert train.programs_s == {
        "jit_tds_train_step": pytest.approx(1.33888e-4, rel=1e-4)}
    assert train.program_runs == {"jit_tds_train_step": 2.0}
    assert train.program_share == 1.0
    # a program's event spans its operations and the moments between them
    assert train.busy_s == pytest.approx(1.28306e-4, rel=1e-4)
    assert train.busy_s < train.modules_s
    # the Pallas kernels are named by their pallas_call's name=
    kernels = {e.name.split(" = ")[0].split(".")[0]
               for e in dev.lines[xplane.OPS_LINE]
               if "tpu_custom_call" in e.name}
    assert kernels == {"%tds_ln_fwd", "%tds_ln_dx", "%tds_ln_dwdb",
                       "%tds_fa2_fwd", "%tds_fa2_dq", "%tds_fa2_dkv"}
    fwd = next(e for e in dev.lines[xplane.OPS_LINE]
               if e.name.startswith("%tds_fa2_fwd"))
    assert "tds.block/tds.attn.kernel" in dev.meta[fwd.mid]["tf_op"]


def test_train_fixture_scope_times_sum_to_busy_time(train):
    assert sum(train.scopes_s.values()) + train.unscoped_s == \
        pytest.approx(train.busy_s, rel=1e-9)
    assert set(train.scopes_s) == {
        "tds.embed", "tds.blocks", "tds.ln", "tds.attn.qkv",
        "tds.attn.kernel", "tds.attn.proj", "tds.mlp", "tds.head",
        "tds.optim"}
    assert train.scoped_share == pytest.approx(0.9473, abs=1e-4)
    assert train.scopes_s["tds.attn.kernel"] == pytest.approx(
        3.00249e-5, rel=1e-4)
    # forward, backward, the forward recomputed (remat "nothing"), optimizer
    assert train.phases_s == {
        "forward": pytest.approx(3.58140e-5, rel=1e-4),
        "backward": pytest.approx(4.95033e-5, rel=1e-4),
        "recompute": pytest.approx(1.35682e-5, rel=1e-4),
        "optimizer": pytest.approx(2.26623e-5, rel=1e-4)}
    assert sum(train.phases_s.values()) + train.unscoped_s == \
        pytest.approx(train.busy_s, rel=1e-9)
    assert train.phases_s["optimizer"] == train.scopes_s["tds.optim"]
    assert train.head_s == train.scopes_s["tds.head"]
    # the forward kernel runs twice (remat), the two backward kernels once
    assert train.attn_s == {"forward": pytest.approx(1.52439e-5, rel=1e-4),
                            "backward": pytest.approx(1.47810e-5, rel=1e-4)}
    assert sum(train.attn_s.values()) == pytest.approx(
        train.scopes_s["tds.attn.kernel"], rel=1e-9)
    assert train.coll_s == {"gather": 0.0, "grad": 0.0}      # one chip


def test_serve_fixture_programs_and_copies_by_program(serve):
    assert serve.program_runs == {"jit_tds_decode": 5.0,
                                  "jit_tds_prefill": 4.0}
    assert serve.programs_s == {
        "jit_tds_decode": pytest.approx(7.03960e-4, rel=1e-4),
        "jit_tds_prefill": pytest.approx(1.66089e-4, rel=1e-4)}
    assert serve.program_share == pytest.approx(1.0)
    assert serve.per_unit_ms(serve.programs_s["jit_tds_decode"]) == \
        pytest.approx(0.140792, rel=1e-4)
    assert serve.per_run_ms(serve.programs_s, "jit_tds_prefill") == \
        pytest.approx(0.041522, rel=1e-4)
    assert serve.copies_s == {
        "jit_tds_decode": pytest.approx(1.28127e-4, rel=1e-4),
        "jit_tds_prefill": pytest.approx(7.79902e-5, rel=1e-4)}
    assert sum(serve.scopes_s.values()) == pytest.approx(serve.busy_s,
                                                         rel=1e-9)
    assert serve.unscoped_s == 0.0
    assert {"tds.decode", "tds.prefill", "tds.kv_write", "tds.attn.kernel",
            "tds.sample"} <= set(serve.scopes_s)
    assert not any(serve.phases_s.values())       # no train program ran


def test_serve_fixture_gaps_are_named_by_the_innermost_tick_span(serve):
    # 47 ms in which the engine had nothing to do: the host was in no span
    assert (serve.gaps[0].span, serve.gaps[0].ids) == ("unannotated", {})
    assert serve.gaps[0].seconds == pytest.approx(0.0474371, rel=1e-5)
    # then the device waits while the host is still fetching the last tick's
    # tokens, or dispatching the next program
    named = serve.gaps[1:8]
    assert [g.span for g in named[:3]] == ["tds.tick.decode.fetch"] * 3
    assert named[0].ids == {"tick": 51}
    assert named[0].seconds == pytest.approx(2.679927e-3, rel=1e-5)
    assert {g.span for g in named} == {
        "tds.tick.decode.fetch", "tds.tick.decode.dispatch",
        "tds.tick.prefill.fetch"}
    assert any("request" in g.ids for g in named)
    # idle inside a tick, by its parts
    idle = serve.idle_in_s
    assert idle["tds.tick"] == pytest.approx(0.0174541, rel=1e-4)
    parts = sum(v for k, v in idle.items() if k.startswith("tds.tick."))
    assert 0.95 * idle["tds.tick"] <= parts <= idle["tds.tick"]
    assert max(idle, key=idle.get) == "tds.tick"
    assert sorted(idle, key=idle.get)[-2] == "tds.tick.decode.fetch"


def test_serve_fixture_spans_carry_request_ids_and_buckets():
    host = [e for p in spans.read_xspace(SERVE)
            if p.name.startswith("/host:")
            for evs in p.lines.values() for e in evs
            if e.name.startswith("tds.")]
    dispatch = [dict(e.stats) for e in host
                if e.name == "tds.tick.prefill.dispatch"]
    assert len(dispatch) == 4
    assert {d["bucket"] for d in dispatch} <= {16, 32, 64}
    admitted = {dict(e.stats)["request"] for e in host
                if e.name == "tds.tick.admit"}
    assert {d["request"] for d in dispatch} == admitted
    ticks = [dict(e.stats)["tick"] for e in host if e.name == "tds.tick"]
    assert ticks == sorted(ticks) and len(ticks) == len(set(ticks)) >= 5
    assert any(e.name == "tds.submit" for e in host)


def test_describe_says_what_a_traced_run_prints(train, serve):
    text = "\n".join(spans.describe(serve))
    assert "100.00 % of device busy time in a tds. scope" in text
    assert "jit_tds_decode 0.141 x1.00" in text
    assert "tds copies by program" in text
    assert "tds.tick.decode.fetch {'tick': 51}" in text
    text = "\n".join(spans.describe(train))
    assert "5.27 % reaches none" in text
    assert "recompute 0.007" in text
