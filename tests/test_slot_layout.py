# Copyright 2026 tiny-deepspeed-tpu authors
# SPDX-License-Identifier: Apache-2.0

"""The slot layout: the one thing `serving/engine.py` asks about a model's
cache (serving/pool.DenseLayout states the members).  Held here for both
layouts the package has, the dense one every GPT-2-family model states and
EvaByte's window ring with summary rows: what the engine relies on without
checking, and the operands and counts it builds from them, as fixed arrays.
"""

import types

import jax
import numpy as np
import pytest

from tiny_deepspeed_tpu.models import ALL_PRESETS, build_model
from tiny_deepspeed_tpu.serving import ServeConfig, ServingEngine
from tiny_deepspeed_tpu.serving.engine import Request, _Slot

BT = 8
FAMILIES = ["tiny", "evabyte-tiny", "mimo-tiny"]


@pytest.fixture(scope="module", params=FAMILIES)
def served(request):
    cfg = ALL_PRESETS[request.param]
    model = build_model(cfg)
    return request.param, cfg, model, model.init(jax.random.PRNGKey(0))


def _layout(served):
    _, cfg, model, _ = served
    return cfg, model.paged_layout(cfg.block_size, BT)


def _slot(pos, table, summary=(), seed=5, produced=2, last=9):
    req = Request([1, 2, 3], 50, seed=seed)
    req.tokens = [0] * produced
    return _Slot(req, list(table), pos, last, admitted_at=0.0,
                 summary=list(summary))


def test_need_never_shrinks_and_never_exceeds_the_table(served):
    cfg, lay = _layout(served)
    before = (0, 0)
    for pos in range(cfg.block_size):
        n_table, n_summary = lay.need(pos)
        assert n_table >= before[0] and n_summary >= before[1], pos
        assert n_table >= 1
        before = (n_table, n_summary)
    # the last position's blocks are what the row was made for
    assert sum(before) == lay.width
    row = np.zeros((lay.width,), np.int32)
    lay.fill_row(row, range(1, before[0] + 1),
                 range(100, 100 + before[1]))
    assert (row != 0).all()


def test_every_prefill_bucket_fits_the_table_row(served):
    cfg, lay = _layout(served)
    eng_bucket = ServingEngine._bucket
    me = types.SimpleNamespace(config=ServeConfig(block_tokens=BT),
                               model=types.SimpleNamespace(config=cfg))
    buckets = {eng_bucket(me, p) for p in range(1, cfg.block_size + 1)}
    assert max(buckets) == cfg.block_size and min(buckets) == BT
    for bucket in sorted(buckets):
        nw, ns = lay.prefill_panel(bucket)
        assert nw >= 1 and ns >= 0
        # what a prefill scatters, the slot's row can name: the table's
        # part within the table's, the summaries' within theirs
        full = lay.need(cfg.block_size - 1)
        assert nw <= full[0] and ns <= full[1], bucket
        assert nw + ns <= lay.width


# three seeded slots (slot 2 stays empty): lengths, block lists, and what
# `_slot_arrays` and `tick_counts` must make of them
_SLOTS = {0: dict(pos=19, table=[7, 3, 11], summary=[21]),
          1: dict(pos=64, table=[5, 6, 8, 9], summary=[30, 31, 32],
                  seed=77, produced=4, last=123),
          3: dict(pos=1, table=[2], summary=[40])}
_S = 0  # scratch
_ROWS = {
    # ceil(256 / 8) = 32 entries, the table's blocks from entry 0
    "tiny": {0: [7, 3, 11] + [_S] * 29,
             1: [5, 6, 8, 9] + [_S] * 28,
             3: [2] + [_S] * 31},
    # a window of 32 rows = 4 entries, then 512 / 4 / 8 = 16 summary entries
    "evabyte-tiny": {0: [7, 3, 11, _S, 21] + [_S] * 15,
                     1: [5, 6, 8, 9, 30, 31, 32] + [_S] * 13,
                     3: [2, _S, _S, _S, 40] + [_S] * 15},
    # the global table's 32 entries, then a ring of 16 rows = 2 entries
    # (a slot's second list is as long as the ring at most)
    "mimo-tiny": {0: [7, 3, 11] + [_S] * 29 + [21, _S],
                  1: [5, 6, 8, 9] + [_S] * 28 + [30, 31],
                  3: [2] + [_S] * 31 + [40, _S]},
}
_COUNTS = {
    # a chunk of the row is 256 tokens = the whole row: one chunk a slot
    "tiny": ("decode.operands",
             dict(kv_steps_live=3, kv_steps=4),
             dict(kv_steps_live=3, kv_steps=4)),
    # rows attended: 19 of the window and none of the summaries (the first
    # window is not past); 0 of a window just begun and 2 x 8 summaries; 1
    # and none.  Slot 1 starts a new window.  In the EVA kernel's chunks
    # of 256 rows a range: the window's 32 rows are one chunk and the
    # summaries' 128 one, so 2 a table row, 8 for four slots; live are
    # slot 0's window, slot 1's summaries, slot 3's window
    "evabyte-tiny": ("roll",
                     dict(window_blocks=8, summary_blocks=5,
                          windows_rolled=1, kv_steps_live=1 + 1 + 1,
                          kv_steps=4 * (1 + 1)),
                     dict(active=3, rows=19 + 16 + 1, window_blocks=8,
                          summary_blocks=5, windows_rolled=1,
                          kv_steps_live=3, kv_steps=8)),
    # blocks by kind; on the span the rows a global layer attends (every
    # position before the slot's own) and a window layer (at most the
    # window's other 15)
    "mimo-tiny": ("route",
                  dict(global_blocks=8, window_blocks=4),
                  dict(active=3, rows_global=19 + 64 + 1,
                       rows_window=15 + 15 + 1, global_blocks=8,
                       window_blocks=4)),
}


def test_seeded_slots_fill_fixed_operands_and_counts(served):
    name, cfg, model, params = served
    eng = ServingEngine(model, params, ServeConfig(
        max_active=4, num_blocks=200, block_tokens=BT))
    dense = name == "tiny"
    # a ring holds no more blocks than it has entries
    second = getattr(eng._layout, "ring", None)
    for i, kw in _SLOTS.items():
        kw = dict(kw, summary=() if dense else kw["summary"][:second])
        eng._slots[i] = _slot(**kw)
    active = [(i, s) for i, s in enumerate(eng._slots) if s is not None]
    eng._poison_pending.add(3)
    tokens, pos, seeds, nprod, poison, tables = eng._slot_arrays(active)
    assert tokens.tolist() == [9, 123, 0, 9]
    assert pos.tolist() == [19, 64, 0, 1]
    assert seeds.tolist() == [5, 77, 0, 5]
    assert nprod.tolist() == [2, 4, 0, 2]
    assert np.isnan(poison[3]) and poison[:3].tolist() == [0.0, 0.0, 0.0]
    assert not eng._poison_pending
    assert tables.dtype == np.int32
    assert tables.shape == (4, eng.max_blocks_per_req)
    want = {**_ROWS[name], 2: [_S] * eng.max_blocks_per_req}
    for i in range(4):
        assert tables[i].tolist() == want[i], i
    span, counts, ids = _COUNTS[name]
    lay = eng._layout
    assert lay.span == span
    got = lay.tick_counts([s for _, s in active], 4)
    assert got == (counts, ids)
    assert list(got[1]) == list(ids)  # the span's ids keep their order
    # the operands span hands them on for the step: its commit writes
    # them into the record and opens the layout's own span, where it has
    # one, after the fetch (tests/test_spans.py)
    with eng._operands_span(active) as made:
        pass
    assert made == (counts, ids)
    assert [s[0] for s in eng._tick["segments"]] == ["decode.operands"]
    # a slot the step in flight carries on is one position further on
    # (its token comes from that step's output on the chip)
    tokens, pos, seeds, nprod, poison, _ = eng._slot_arrays(active, {1})
    assert pos.tolist() == [19, 65, 0, 1]
    assert nprod.tolist() == [2, 5, 0, 2]
    assert not np.isnan(poison).any()
    with eng._operands_span(active, {1}) as (_, on):
        pass
    row = {"tiny": None, "evabyte-tiny": "rows",
           "mimo-tiny": "rows_global"}[name]
    if row is not None:
        assert on[row] == ids[row] + 1


@pytest.mark.parametrize("step, live, per_row", [
    # rows a chunk -> the seeded slots' live chunks (19 window rows and no
    # summary; none and 16; 1 and none), and the chunks of a table row of
    # 32 window rows and 128 summary rows
    (8, 3 + 2 + 1, 4 + 16),
    (16, 2 + 1 + 1, 2 + 8),
    (256, 1 + 1 + 1, 1 + 1),
])
def test_the_eva_layout_counts_chunks_as_its_kernel_cuts_them(
        monkeypatch, step, live, per_row):
    """One constant cuts both: `kv_steps_live` is the trip count of the
    kernel's loop summed over the slots, whatever a chunk holds."""
    from tiny_deepspeed_tpu.ops import eva_attn_pallas
    monkeypatch.setattr(eva_attn_pallas, "_STEP_TOKENS", step)
    cfg = ALL_PRESETS["evabyte-tiny"]
    lay = build_model(cfg).paged_layout(cfg.block_size, BT)
    nb, npw, nps = eva_attn_pallas.eva_steps(lay.window, lay.summary, BT)
    assert nb * BT == step and npw + nps == per_row
    slots = [_slot(**kw) for kw in _SLOTS.values()]
    counts, ids = lay.tick_counts(slots, 4)
    assert counts["kv_steps_live"] == ids["kv_steps_live"] == live
    assert counts["kv_steps"] == ids["kv_steps"] == 4 * per_row
    # an empty slot list, a tick that decodes nothing
    assert lay.tick_counts([], 4)[0]["kv_steps_live"] == 0


_MECHANISM = {"prefix_cache": "radix tree", "spec_draft": "verify program",
              "quant": "per-vector scales",
              "export_request": "export_blocks / import_blocks",
              "import_request": "export_blocks / import_blocks"}


def test_each_refusal_names_its_mechanism(served):
    name, cfg, model, params = served
    _, lay = _layout(served)
    if name == "tiny":
        # nothing refused, and the pool is the caller's: a prefix tree
        # keeps blocks that no slot owns
        assert lay.refuses == {} and not lay.bounds_pool
        eng = ServingEngine(model, params, ServeConfig(
            max_active=2, num_blocks=500, block_tokens=BT,
            prefix_cache=True, quant="int8"))
        assert eng.pool.num_usable == 500
        return
    assert sorted(lay.refuses) == sorted(_MECHANISM) and lay.bounds_pool
    eng = ServingEngine(model, params, ServeConfig(
        max_active=2, num_blocks=500, block_tokens=BT))
    assert eng.pool.num_usable == 2 * lay.width
    for feature, mechanism in _MECHANISM.items():
        assert mechanism in lay.refuses[feature]
        with pytest.raises(ValueError, match=type(model).__name__
                           + " cannot .*" + mechanism):
            eng._refuse(feature)
    # the first stated one is raised, none for what the layout can follow
    eng._refuse("tenants")
    with pytest.raises(ValueError, match="prefix_cache"):
        eng._refuse("tenants", "prefix_cache", "quant")
