# Copyright 2026 tiny-deepspeed-tpu authors
# SPDX-License-Identifier: Apache-2.0

"""MiMo-V2-Flash (models/mimo.py) at `mimo-tiny`, seeded random weights,
float32 on both sides so that routing agrees exactly, against the plain
reference the benchmark's cell is decided by (benchmarks/reference/mimo.py):
the full forward and the loss; prefill and decode through the paged pool's
two kinds of block across two wraps of the window ring, kernel on
(interpreted) and off; every planted fault failing; the expert layer's
shares adding up to the uncut layer; the configuration file held to the
preset; the pool's accounting by kind.

Each compiled shape is used for many comparisons: the reference runs ONE
program a test (tests/test_evabyte.py says why).
"""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import harness
from tiny_deepspeed_tpu.models import ALL_PRESETS, build_model
from tiny_deepspeed_tpu.models import mimo
from tiny_deepspeed_tpu.ops import paged_attn_pallas
from tiny_deepspeed_tpu.serving import ServeConfig, ServingEngine

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CFG = ALL_PRESETS["mimo-tiny"]
BT = 8
T = 64            # positions a reference call holds
REF = harness.load_reference(harness.HERE, "mimo")


@pytest.fixture(scope="module")
def served():
    """The model and its seeded weights, the selection bias ten times its
    initial size so that what it chooses, and a gate that wrongly holds
    it, show at 16 experts."""
    model = build_model(CFG)
    params = model.init(jax.random.PRNGKey(0))
    return model, dict(params, **{
        "moe.router.bias": params["moe.router.bias"] * 10})


@pytest.fixture(scope="module")
def sequences():
    return np.random.default_rng(3).integers(
        0, CFG.vocab_size, (2, T)).astype(np.int32)


@pytest.fixture(scope="module")
def program_logits(served, sequences):
    model, params = served
    return np.asarray(jax.jit(model.logits)(params, sequences))


def _reference_rows(params, seq, fault=""):
    """The reference's logits at every position of one sequence."""
    return np.asarray(jax.jit(lambda p, ix, pos: REF.logits_at(
        p, ix, pos, CFG, fault=fault))(
            params, np.repeat(seq[None], T, 0), np.arange(T, dtype=np.int32)))


# -- the full forward ---------------------------------------------------------

def test_full_forward_and_loss_agree_with_the_reference(
        served, sequences, program_logits):
    model, params = served
    for b in range(2):
        want = _reference_rows(params, sequences[b])
        assert want.std() > 0.1
        assert np.abs(program_logits[b] - want).max() < 1e-5
    targets = np.roll(sequences, -1, axis=1)
    got = float(jax.jit(model.apply)(params, sequences, targets))
    want = float(REF.loss(params, sequences, targets, CFG))
    assert abs(got - want) < 1e-5 and 5.0 < got < 7.0
    # and `apply` without targets is the last position's row
    last = np.asarray(jax.jit(model.apply)(params, sequences))
    assert np.abs(last[:, 0] - program_logits[:, -1]).max() < 1e-6


@pytest.mark.parametrize("fault", REF.FAULTS)
def test_a_planted_fault_is_told_from_the_program(
        served, sequences, program_logits, fault):
    """Each wrong reading of the layer, computed by the reference in the
    reference's place, lies far from what the program computes: thousands
    of times the sound gap of 2e-7."""
    _, params = served
    gap = np.abs(program_logits[0]
                 - _reference_rows(params, sequences[0], fault)).max()
    assert gap > 1e-3, (fault, gap)


def test_the_faults_are_the_issues_list():
    assert set(REF.FAULTS) == {
        "expert_dropped", "gates_unnormalised", "bias_in_gate",
        "sink_left_out", "window_127", "window_129", "theta_swapped"}


# -- the layers' order --------------------------------------------------------

def _unfold(plan):
    return [(a, m) for reps, group in plan for _ in range(reps)
            for a, m, n in group for _ in range(n)]


def test_the_plan_keeps_the_published_order_and_folds_what_repeats():
    full = ALL_PRESETS["mimo-v2-flash"]
    plan = mimo.layer_plan(full.layer_kinds, full.moe_layers)
    assert _unfold(plan) == list(zip(full.layer_kinds, full.moe_layers))
    # global+dense, 4 window, then 7 x (1 global + 5 window), 1 global:
    # five block bodies for 48 layers
    assert plan == [(1, [(0, 0, 1)]), (1, [(1, 1, 4)]),
                    (7, [(0, 1, 1), (1, 1, 5)]), (1, [(0, 1, 1)])]
    assert sum(len(group) for _, group in plan) == 5
    cut = ALL_PRESETS["mimo-v2-flash-7l"]
    assert mimo.layer_plan(cut.layer_kinds, cut.moe_layers) == [
        (1, [(0, 0, 1)]), (1, [(1, 1, 4)]), (1, [(0, 1, 1)]),
        (1, [(1, 1, 1)])]


def test_a_folded_stack_computes_what_its_layers_in_order_compute():
    """17 layers in the published pattern fold into a scan over two
    repetitions; the reference walks them one by one."""
    kinds, moe = mimo._PATTERN[:17], mimo._MOE[:17]
    cfg = dataclasses.replace(CFG, n_layer=17, layer_kinds=kinds,
                              moe_layers=moe)
    model = build_model(cfg)
    assert [reps for reps, _ in model.plan] == [1, 1, 2]
    params = model.init(jax.random.PRNGKey(1))
    seq = np.random.default_rng(4).integers(0, cfg.vocab_size, (1, 40))
    got = np.asarray(jax.jit(model.apply)(params, seq.astype(np.int32)))
    want = np.asarray(REF.logits_at(params, seq.astype(np.int32),
                                    np.asarray([39], np.int32), cfg))
    assert np.abs(got[0, 0] - want[0]).max() < 1e-5


# -- the expert layer ----------------------------------------------------------

def _layer_inputs(params, n=24):
    rows = jax.random.normal(jax.random.PRNGKey(7), (n, CFG.n_embd))
    lm = 2  # the third expert layer
    stacks = {k: params["moe.experts." + k] for k in
              ("gate.w", "up.w", "down.w")}
    return rows, lm, stacks


@pytest.mark.parametrize("by_product", [4096, 0])
def test_the_shares_of_the_expert_layer_add_up_to_the_uncut_layer(
        served, by_product, monkeypatch):
    """All four shares `held = (4 r, 4)` of mimo-tiny's 16 experts, each
    told which experts it holds and given only their weights, add up to
    the reference's layer over all 16; with the combine a product with
    the gates (a decode step's few pairs) and a gather (a prefill's)."""
    monkeypatch.setattr(mimo, "_COMBINE_BY_PRODUCT", by_product)
    _, params = served
    rows, lm, stacks = _layer_inputs(params)
    e = CFG.experts_held
    router = params["moe.router.w"][lm], params["moe.router.bias"][lm]
    want = np.asarray(REF._experts(
        rows, {"router.w": router[0], "router.bias": router[1]},
        (stacks, lm * e), CFG, jnp.float32, ""))
    total, pairs = 0.0, 0
    for r in range(4):
        share = [stacks[k].reshape(6, e, *stacks[k].shape[1:])[
            :, 4 * r:4 * r + 4].reshape(24, *stacks[k].shape[1:])
            for k in ("gate.w", "up.w", "down.w")]
        y, counts = mimo.moe_layer(
            rows, *router, *share, lm, top_k=CFG.n_experts_per_tok,
            held=(4 * r, 4))
        total = total + np.asarray(y)
        pairs += int(counts[0])
        assert 1 <= int(counts[1]) <= 4
    assert np.abs(total - want).max() < 1e-7 < 1e-3 < np.abs(want).max()
    # nothing dropped: every (token, choice) pair was some share's
    assert pairs == 24 * CFG.n_experts_per_tok


def test_rows_that_are_no_tokens_are_routed_nowhere(served):
    _, params = served
    rows, lm, stacks = _layer_inputs(params)
    args = (rows, params["moe.router.w"][lm], params["moe.router.bias"][lm],
            stacks["gate.w"], stacks["up.w"], stacks["down.w"], lm)
    kw = dict(top_k=CFG.n_experts_per_tok, held=(0, CFG.experts_held))
    valid = jnp.arange(24) % 3 != 0
    y, counts = mimo.moe_layer(*args, valid=valid, **kw)
    full, _ = mimo.moe_layer(*args, **kw)
    assert int(counts[0]) == 16 * CFG.n_experts_per_tok
    assert np.abs(np.asarray(y)[::3]).max() == 0.0
    assert np.allclose(np.asarray(y)[1::3], np.asarray(full)[1::3],
                       atol=1e-6)
    choice, gate = mimo.moe_route(rows, *args[1:3], CFG.n_experts_per_tok)
    assert np.allclose(np.asarray(gate).sum(-1), 1.0, atol=1e-6)
    assert int(counts[1]) == len(set(np.asarray(choice)[
        np.asarray(valid)].ravel()))


# -- through the paged pool ----------------------------------------------------

def _serve(served, mode, steps, prompts, monkeypatch):
    """Serve `prompts` for `steps` new tokens each through an engine with
    the paged kernel forced `mode`; -> (engine, requests, [(sequence the
    decode step saw, its logits)], the pool's accounting a tick)."""
    model, params = served
    monkeypatch.setattr(paged_attn_pallas, "INTERPRET", True)
    eng = ServingEngine(model, params, ServeConfig(
        max_active=3, num_blocks=200, block_tokens=BT, temperature=0.0,
        eos_id=None, paged_kernel=mode))
    rng = np.random.default_rng(1)
    reqs = [eng.submit(rng.integers(0, CFG.vocab_size, n).tolist(), steps)
            for n in prompts]
    seen, books = [], []
    while not all(r.done for r in reqs):
        before = [len(r.tokens) for r in reqs]
        eng.tick()
        logits = np.asarray(eng.last_logits)
        for r, n in zip(reqs, before):
            if len(r.tokens) > max(n, 1):  # a decode step ran for it
                seen.append((r.prompt + r.tokens[:-1], logits[r.last_slot]))
        pool = eng.pool
        books.append(([pool.free_of(k) for k in (0, 1)],
                      [sorted(s.table) + sorted(s.summary)
                       for s in eng._slots if s is not None]))
    return eng, reqs, seen, books


@pytest.mark.parametrize("mode", ["on", "off"])
def test_prefill_and_decode_through_the_pool_agree_at_every_step(
        served, mode, monkeypatch):
    """Two requests, 2 x window + 3 decode steps each: the ring wraps
    twice and a global block boundary is crossed many times; every
    step's logits against the reference's full forward."""
    _, params = served
    w = CFG.window
    calls = []
    real = paged_attn_pallas.paged_attention
    monkeypatch.setattr(mimo, "paged_attention", lambda *a, **kw: (
        calls.append(kw), real(*a, **kw))[1])
    eng, reqs, seen, _ = _serve(served, mode, 2 * w + 4, (5, 21),
                                monkeypatch)
    assert [r.status for r in reqs] == ["ok", "ok"]
    assert len(seen) == 2 * (2 * w + 3)
    # the kernel ran where it was forced on, for both kinds of layer
    assert bool(calls) == (mode == "on")
    assert {kw["ring"] for kw in calls} == ({0, w} if calls else set())
    idx = np.zeros((len(seen), T), np.int32)
    for i, (seq, _) in enumerate(seen):
        idx[i, :len(seq)] = seq
    pos = np.asarray([len(seq) - 1 for seq, _ in seen], np.int32)
    want = np.asarray(jax.jit(lambda p, ix, ps: REF.logits_at(
        p, ix, ps, CFG))(params, idx, pos))
    gap = np.abs(np.stack([g for _, g in seen]) - want).max(axis=1)
    assert pos.max() == 21 + 2 * w + 2 and gap.max() < 1e-5, gap.max()
    # what the decode program counted came back with its tokens: 4
    # choices in 6 expert layers a live slot, every expert held
    routed = [t for t in eng.tick_records if "pairs" in t]
    assert routed and all(t["pairs"] in (24, 48) for t in routed)
    assert all(6 <= t["experts_touched"] <= t["pairs"] for t in routed)


def test_the_pool_accounts_for_both_kinds_and_the_ring_never_grows(
        served, monkeypatch):
    eng, reqs, _, books = _serve(served, "off", 30, (5, 21, 40),
                                 monkeypatch)
    pool, lay = eng.pool, eng._layout
    ring = CFG.window // BT
    assert [k.blocks for k in pool.kinds] == [3 * lay.table, 3 * ring]
    assert lay.width == lay.table + ring and lay.tables == (0, 1)
    assert pool.bases == [0, 3 * lay.table]
    grew = False
    for (free_g, free_w), slots in books:
        held = [b for row in slots for b in row]
        assert len(held) == len(set(held))
        in_g = [b for b in held if pool.kind_of(b) == 0]
        in_w = [b for b in held if pool.kind_of(b) == 1]
        # free + allocated = usable, kind by kind
        assert free_g + len(in_g) == pool.kinds[0].blocks
        assert free_w + len(in_w) == pool.kinds[1].blocks
        # a live slot holds the whole ring from its admission to its end
        assert len(in_w) == ring * len(slots)
        grew |= len(in_g) > sum(-(-n // BT) for n in (5, 21, 40))
    assert grew and all(r.status == "ok" for r in reqs)
    assert pool.blocks_in_use == 0
    assert pool.free_of(0) == 3 * lay.table and pool.free_of(1) == 3 * ring
    # a slot at position n holds ceil((n + 1) / bt) global blocks
    for n in (0, 7, 8, 100, CFG.block_size - 1):
        assert lay.need(n) == (n // BT + 1, ring)
    # K wider than V, a ring row wider than a table row
    vg, vw = pool.view
    assert vg.k.shape == (3 * lay.table + 1, BT, 2 * 1 * 24)
    assert vg.v.shape == (3 * lay.table + 1, BT, 2 * 1 * 16)
    assert vw.k.shape == (3 * ring + 1, BT, 5 * 2 * 24)
    assert vw.v.shape == (3 * ring + 1, BT, 5 * 2 * 16)


def test_what_the_cache_cannot_follow_is_refused_in_its_own_words(served):
    model, params = served
    lay = model.paged_layout(CFG.block_size, BT)
    assert lay.bounds_pool and lay.fetched == ("pairs", "experts_touched")
    for feature, mechanism in {
            "prefix_cache": "radix tree", "spec_draft": "verify program",
            "quant": "per-vector scales",
            "export_request": "export_blocks / import_blocks",
            "import_request": "export_blocks / import_blocks"}.items():
        assert mechanism in lay.refuses[feature]
    for kw, word in ((dict(prefix_cache=True), "prefix_cache"),
                     (dict(quant="int8"), "quant"),
                     (dict(spec_draft="ngram"), "spec_draft")):
        with pytest.raises(ValueError, match="MiMoModel cannot .*" + word):
            ServingEngine(model, params, ServeConfig(
                max_active=2, num_blocks=64, block_tokens=BT, **kw))
    with pytest.raises(NotImplementedError, match="paged pool"):
        model.generate(params, None, 1)


def test_the_engine_says_why_it_refuses_static_capacity_routing():
    moe = build_model(ALL_PRESETS["moe-tiny"])
    with pytest.raises(ValueError, match="static expert capacity") as e:
        ServingEngine(moe, moe.init(jax.random.PRNGKey(0)), ServeConfig())
    assert "dropless experts are served" in str(e.value)


# -- the configuration file -----------------------------------------------------

def test_the_configuration_file_is_the_preset_where_pins_cannot_hold_it():
    """`pins` hold whole numbers; the two pattern lists, the share's first
    expert and what is cut are held here."""
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "mimo-v2-flash.json")) as f:
        file = json.load(f)
    preset = ALL_PRESETS[file["preset"]]
    full = ALL_PRESETS["mimo-v2-flash"]
    n = file["num_hidden_layers"]
    assert n == preset.n_layer == 7
    # the lists stand whole, as published; the layers that run are their
    # first `num_hidden_layers` entries
    assert tuple(file["hybrid_layer_pattern"]) == full.layer_kinds
    assert tuple(file["moe_layer_freq"]) == full.moe_layers
    assert tuple(file["hybrid_layer_pattern"][:n]) == preset.layer_kinds
    assert tuple(file["moe_layer_freq"][:n]) == preset.moe_layers
    assert preset.layer_kinds == (0, 1, 1, 1, 1, 0, 1)
    assert preset.moe_layers == (0, 1, 1, 1, 1, 1, 1)
    assert sorted(file["reduced"]) == sorted(file["published"]) == sorted([
        "num_hidden_layers", "n_routed_experts", "vocab_size",
        "max_position_embeddings"])
    for key, value in file["published"].items():
        assert getattr(full, file["pins"][key]) == value or (
            key == "n_routed_experts" and value == full.n_routed_experts)
    # every width as published
    for field in ("n_embd", "n_head", "head_dim", "v_head_dim", "n_kv_head",
                  "swa_n_kv_head", "window", "moe_hidden", "ffn_hidden",
                  "n_routed_experts", "n_experts_per_tok", "rotary_dim"):
        assert getattr(preset, field) == getattr(full, field), field
    assert (preset.n_embd, preset.n_head, preset.head_dim,
            preset.v_head_dim, preset.n_kv_head, preset.swa_n_kv_head,
            preset.window, preset.moe_hidden, preset.ffn_hidden,
            preset.n_routed_experts, preset.n_experts_per_tok) == (
        4096, 64, 192, 128, 4, 8, 128, 2048, 16384, 256, 8)
    assert preset.vocab_size * 8 == full.vocab_size
    cell = harness.load_cell("mimo-v2-flash.reason-open")
    assert cell.model_config(param_dtype="bfloat16").experts_held == 16
