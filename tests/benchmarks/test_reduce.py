"""The trace reduction: on hand-made intervals, and on `fixture.xplane.pb`,
a small trace recorded on the v5e (benchmarks/reduce/record_fixture.py).
Reading the fixture needs only jax.profiler.ProfileData: no TPU library is
loaded, nothing compiles."""

import os

import pytest

from benchmarks.reduce import (
    Op, bucket_of, bucket_seconds, exposed, idle_gaps, measure, self_times,
    subtract, union,
)
from benchmarks.reduce import xplane

FIXTURE = os.path.join(os.path.dirname(xplane.__file__), "fixture.xplane.pb")
RULES = [
    {"bucket": "collectives", "head": ["all-gather", "reduce-scatter"]},
    {"bucket": "attn_kernels", "text": ["_fwd_kernel"]},
    {"bucket": "vocab_head", "text": ["50304"]},
    {"bucket": "copies", "head": ["copy"]},
]


def test_busy_is_a_union_overlaps_count_once():
    spans = [(0, 10), (5, 15), (20, 30), (30, 31), (40, 40)]
    assert union(spans) == [(0, 15), (20, 31)]
    assert measure(union(spans)) == 26
    assert measure(union([(0, 10), (2, 3), (4, 5)])) == 10  # nested


def test_subtract_and_exposed_collective_time():
    assert subtract([(0, 10)], [(2, 4), (6, 12)]) == [(0, 2), (4, 6)]
    assert subtract([(0, 10), (20, 30)], []) == [(0, 10), (20, 30)]
    assert subtract([(0, 10)], [(0, 10)]) == []
    # a collective of 10 with compute beside it for 6: 4 exposed
    assert exposed([(100, 110)], [(90, 103), (107, 110)]) == 4
    assert exposed([(0, 5), (3, 8)], []) == 8   # two overlapping: union
    assert exposed([], [(0, 5)]) == 0


def test_self_time_takes_nested_operations_out_of_their_parent():
    ops = [Op("%while.1 = while(...)", 0, 100),
           Op("%fusion.1 = fusion(...)", 10, 40),
           Op("%copy.2 = copy(...)", 50, 90),
           Op("%fusion.9 = fusion(...)", 120, 130)]
    own = {op.name.split(" = ")[0]: t for op, t in self_times(ops)}
    assert own == {"%while.1": 30, "%fusion.1": 30, "%copy.2": 40,
                   "%fusion.9": 10}
    assert sum(own.values()) == measure(union((o.start, o.end) for o in ops))


def test_buckets_by_name_first_rule_wins_head_versus_text():
    assert bucket_of("%all-gather.3 = f32[8] all-gather(%p)", RULES) == \
        "collectives"
    assert bucket_of("%custom-call.2 = bf16[4] custom-call(), "
                     "kernel_name=\"_fwd_kernel\"", RULES) == "attn_kernels"
    assert bucket_of("%copy.1 = bf16[12,1024] copy(%x)", RULES) == "copies"
    # an operand named copy does not make a fusion a copy (head rule) ...
    assert bucket_of("%fusion.7 = bf16[8] fusion(%copy.1)", RULES) == "other"
    # ... a shape anywhere in the text does name the vocabulary head
    assert bucket_of("%fusion.8 = bf16[12,1024,50304] fusion(%copy.1)",
                     RULES) == "vocab_head"
    ops = [Op("%while.1 = while()", 0, 100e9),
           Op("%copy.1 = copy()", 0, 25e9),
           Op("%fusion.8 = bf16[1,50304] fusion()", 50e9, 60e9)]
    assert bucket_seconds(self_times(ops), RULES) == {
        "copies": 25.0, "vocab_head": 10.0, "other": 65.0}


def test_collective_fusions_of_the_four_chip_trace_are_collectives():
    """Names as the v5e's ZeRO-3 trace has them (PR 24): all-gathers and
    all-reduces by their own name, reduce-scatters as custom fusions."""
    from benchmarks.reduce.intervals import is_collective
    rs = ("%fusion.291 = f32[1792,1600]{1,0:T(8,128)S(1)} fusion(f32[6400,"
          "1600]{1,0:T(8,128)} %get-tuple-element.2231), kind=kCustom, "
          "calls=%all-reduce-scatter.2.clone")
    ag = ("%all-gather.197 = bf16[1,1600,4800]{1,2,0} all-gather(bf16[1,"
          "1600,1200]{1,2,0} %constant_dynamic-slice_fusion.21)")
    mm = "%fusion.7 = bf16[4096,6400]{1,0} fusion(%all-gather.197), kind=kOutput"
    rules = xplane.load_rules({"vocab": 50304, "d": 1600, "d3": 4800,
                               "d4": 6400, "dh": 64})
    assert is_collective(rs) and is_collective(ag) and not is_collective(mm)
    assert bucket_of(rs, rules) == bucket_of(ag, rules) == "collectives"
    assert bucket_of(mm, rules) == "mlp"      # an operand's name is no match


def test_idle_gaps_are_named_by_the_annotation_that_covers_them():
    busy = [(0, 10), (14, 20), (50, 60)]
    annotations = [Op("bench.step", 0, 12), Op("bench.load", 12, 15),
                   Op("bench.sync", 18, 49)]
    gaps = idle_gaps(busy, (0, 70), annotations, unit=1.0)
    assert gaps == [("bench.sync", 30.0), ("unannotated", 10.0),
                    ("bench.step", 4.0)]
    assert idle_gaps(busy, (0, 70), annotations, top=1, unit=1.0) == [
        ("bench.sync", 30.0)]


def test_reduce_ops_busy_idle_buckets_and_collectives_over_two_chips():
    chip0 = [Op("%fusion.1 = fusion()", 0, 40e9),
             Op("%all-gather.1 = all-gather()", 40e9, 50e9),
             Op("%fusion.2 = fusion()", 60e9, 100e9)]
    chip1 = [Op("%fusion.1 = fusion()", 0, 50e9),
             Op("%all-gather.1 = all-gather()", 50e9, 60e9),
             Op("%fusion.2 = fusion()", 60e9, 100e9)]
    r = xplane.reduce_ops({0: chip0, 1: chip1},
                          [Op("bench.step", 45e9, 65e9)], RULES, units=2)
    assert r.chips == 2 and r.window_s == 100.0
    assert r.busy_s == pytest.approx((90 + 100) / 2)
    assert r.idle_share == pytest.approx(0.05)
    assert r.buckets_s == {"other": pytest.approx(85.0),
                           "collectives": pytest.approx(10.0)}
    assert r.coll_s == pytest.approx(10.0)
    assert r.coll_exposed_s == pytest.approx(10.0)  # nothing ran beside them
    assert r.gaps == [("bench.step", 10.0)]
    assert r.per_unit_ms("collectives") == pytest.approx(5000.0)
    assert r.per_unit_ms("paged_attn") is None
    assert xplane.reduce_ops({}, [], RULES, units=1) is None


def test_bucket_rules_fill_their_placeholders_from_the_cells_sizes():
    rules = xplane.load_rules({"vocab": 50304, "d3": 2304, "d4": 3072})
    by = {r["bucket"]: r for r in rules}
    assert by["vocab_head"]["text"] == ["50304"]
    assert by["mlp"]["text"] == ["3072"] and by["qkv"]["text"] == ["2304"]
    # a rule whose placeholder the cell lacks is dropped, not guessed
    assert "mlp" not in {r["bucket"] for r in xplane.load_rules(
        {"vocab": 512, "d3": 192})}


def test_async_operations_in_flight_count_as_collective_time():
    """Collectives in flight sit on the trace's `Async XLA Ops` line and
    overlap the compute line: exposed is what no compute op covers."""
    ops = [Op("%fusion.1 = fusion()", 0, 40e9),
           Op("%fusion.2 = fusion()", 55e9, 100e9)]
    flying = [Op("%all-gather-start.1 = all-gather-start()", 30e9, 55e9),
              Op("%copy-start.1 = copy-start()", 0, 90e9)]
    r = xplane.reduce_ops({0: ops}, [], RULES, units=1, in_flight={0: flying})
    assert r.coll_s == pytest.approx(25.0)
    assert r.coll_exposed_s == pytest.approx(15.0)
    assert r.busy_s == pytest.approx(85.0)   # the compute line alone


# -- the recorded trace ------------------------------------------------------

TINY = {"vocab": 512, "d": 64, "d3": 192, "d4": 256, "dh": 32}


@pytest.fixture(scope="module")
def recorded():
    return xplane.load(FIXTURE)


def test_fixture_has_the_lines_the_reduction_reads(recorded):
    devices, in_flight, annotations = recorded
    assert sorted(devices) == [0] and len(devices[0]) == 998
    assert len(in_flight[0]) == 210
    assert all("-start" in op.name.split(" = ")[0] for op in in_flight[0])
    names = {a.name for a in annotations}
    assert {"bench.step", "bench.load"} <= names
    heads = [op.name.split(" = ")[0] for op in devices[0]]
    assert any(h.startswith("%while") for h in heads)       # scanned layers
    kernels = [op for op in devices[0] if "tpu_custom_call" in op.name]
    assert kernels and all("kernel_metadata={}" in k.name for k in kernels)


def test_fixture_self_times_sum_to_busy_and_whiles_hold_children(recorded):
    ops = recorded[0][0]
    busy = measure(union((o.start, o.end) for o in ops))
    own = self_times(ops)
    assert sum(t for _, t in own) == pytest.approx(busy, rel=1e-9)
    assert sum(o.end - o.start for o in ops) > 1.2 * busy   # nesting
    whiles = [(o, t) for o, t in own if o.name.startswith("%while")]
    assert whiles and all(t < 0.5 * (o.end - o.start) for o, t in whiles)


def test_fixture_reduction(recorded):
    devices, in_flight, annotations = recorded
    r = xplane.reduce_ops(devices, annotations, xplane.load_rules(TINY),
                          units=2, in_flight=in_flight)
    assert r.chips == 1 and r.units == 2
    assert r.window_s == pytest.approx(1.797833e-3, rel=1e-6)
    assert r.busy_s == pytest.approx(1.13539e-4, rel=1e-6)
    # a two-layer model of width 64 leaves the chip idle: the host's gap
    assert r.idle_share == pytest.approx(0.93685, abs=1e-4)
    assert sum(r.buckets_s.values()) == pytest.approx(r.busy_s, rel=1e-9)
    assert {"attn_kernels", "layernorm_kernels", "vocab_head", "copies",
            "mlp"} <= set(r.buckets_s)
    assert r.buckets_s["attn_kernels"] == pytest.approx(2.0856e-5, rel=1e-3)
    assert r.coll_s == 0.0 and r.coll_exposed_s == 0.0      # one chip
    # the one long gap lies between the two traced steps' programs, while
    # the host was inside the harness's `bench.step` span
    assert r.gaps[0][0] == "bench.step"
    assert r.gaps[0][1] == pytest.approx(1.682e-3, rel=1e-3)
    assert len(r.gaps) == 10
    assert xplane.reduce_trace(FIXTURE, TINY, 2) == r
