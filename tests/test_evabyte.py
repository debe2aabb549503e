# Copyright 2026 tiny-deepspeed-tpu authors
# SPDX-License-Identifier: Apache-2.0

"""EvaByte (models/evabyte.py): EVA chunked linear attention on the normal
path and through the paged pool, held to the plain reference
(benchmarks/reference/evabyte.py, which imports nothing of the program).

All on the CPU at the `evabyte-tiny` preset (2 layers, d 64, 4 heads,
window 32, chunk 4, 2 heads out, float32): forward, loss and gradients;
prefill then decode across window rolls, logits against the reference's
full forward; planted faults that the same comparison must refuse; the
ring, the pool's bound and what is freed; every refusal by name; and
GPT-2's serve programs, which must lower to the text they lowered to
before this family existed.
"""

import dataclasses
import hashlib
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tiny_deepspeed_tpu.models import ALL_PRESETS, build_model
from tiny_deepspeed_tpu.models import evabyte as evabyte_mod
from tiny_deepspeed_tpu.ops import eva_attention as eva_ops
from tiny_deepspeed_tpu.ops import flash_fa2, paged_attn_pallas
from tiny_deepspeed_tpu.ops.dispatch import kernel_target_forced
from tiny_deepspeed_tpu.serving import ServeConfig, ServingEngine

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TINY = ALL_PRESETS["evabyte-tiny"]
W, C = TINY.window_size, TINY.chunk_size
# float32 against float32: rounding alone reads 1e-7 to 1e-6 at logits
# of sigma 0.1; every planted fault below reads over a thousand times that
TOL = 1e-5
FAULT = 50 * TOL


def _load_reference():
    spec = importlib.util.spec_from_file_location(
        "reference_evabyte",
        os.path.join(REPO, "benchmarks", "reference", "evabyte.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load_reference()


@pytest.fixture(scope="module")
def model_params():
    model = build_model(TINY)
    params = model.init(jax.random.PRNGKey(0))
    # norm offsets away from zero, so that the unit offset is exercised
    keys = jax.random.split(jax.random.PRNGKey(7), len(params))
    params = {k: v + 0.1 * jax.random.normal(kk, v.shape)
              if k.endswith(("ln_1.w", "ln_2.w", "ln_f.w")) else v
              for (k, v), kk in zip(params.items(), keys)}
    return model, params


def _tokens(seed, *shape):
    return np.random.default_rng(seed).integers(
        0, TINY.vocab_size, shape).astype(np.int32)


# -- the normal path -----------------------------------------------------------

def test_build_model_gives_the_family_and_its_presets():
    assert type(build_model("evabyte-tiny")).__name__ == "EvaByteModel"
    full, cut = ALL_PRESETS["evabyte-6.5b"], ALL_PRESETS["evabyte-6.5b-6l"]
    assert (full.n_layer, cut.n_layer) == (32, 6)
    assert dataclasses.replace(cut, n_layer=32) == full
    shapes = build_model(full).param_shapes()
    per_layer = sum(int(np.prod(v.shape)) // 32 for k, v in shapes.items()
                    if k.startswith("h."))
    # 4 d^2 + 3 d f + 2 d + 2 H Dh, as ISSUE 30 reckons it
    assert per_layer == 4 * 4096 ** 2 + 3 * 4096 * 11008 + 2 * 4096 \
        + 2 * 32 * 128
    assert shapes["lm_head.w"].shape == (4096, 8 * 320)


@pytest.mark.parametrize("t", [W + 5, 150, 7])
def test_loss_and_every_heads_logits_agree_with_the_reference(
        model_params, t):
    model, params = model_params
    idx = _tokens(1, 2, t)
    tgt = np.roll(idx, -1, axis=1)
    assert float(model.apply(params, idx, tgt)) == pytest.approx(
        float(ref.loss(params, idx, tgt, TINY)), abs=1e-6)
    for pos in sorted({0, t // 2, t - 1}):
        got = model.apply(params, idx, position=pos)[:, 0]
        want = ref.logits_at(params, idx, np.full((2,), pos, np.int32), TINY)
        assert got.shape == (2, TINY.num_pred_heads * TINY.vocab_size)
        assert got.dtype == jnp.float32
        np.testing.assert_allclose(got, want, atol=TOL)


def test_gradients_agree_with_the_reference(model_params):
    model, params = model_params
    idx = _tokens(2, 2, 3 * W + 3)
    tgt = np.roll(idx, -1, axis=1)
    got = jax.grad(lambda p: model.apply(p, idx, tgt))(params)
    want = jax.grad(lambda p: ref.loss(p, idx, tgt, TINY))(params)
    assert set(got) == set(want) == set(params)
    for k in params:
        scale = float(jnp.abs(want[k]).max())
        assert scale > 0, k
        np.testing.assert_allclose(got[k], want[k], atol=1e-5 * scale + 1e-9,
                                   err_msg=k)


def test_the_loss_masks_positions_that_have_no_target(model_params):
    """Head j at position n scores byte n + 1 + j: the last j positions
    have none.  With 2 heads, the last target is scored by head 0 at the
    last position and by head 1 at the one before, and by nothing else;
    head 1 at the last position scores nothing."""
    model, params = model_params
    v = TINY.vocab_size
    idx = _tokens(3, 1, 12)
    tgt = np.roll(idx, -1, axis=1)
    other = tgt.copy()
    other[0, -1] = (other[0, -1] + 1) % v

    def logp(pos, head):
        row = np.asarray(model.apply(params, idx, position=pos))[0, 0]
        return jax.nn.log_softmax(row[head * v:(head + 1) * v])

    # head 0 averages over 12 positions, head 1 over 11; then their mean
    a, b = tgt[0, -1], other[0, -1]
    want = ((logp(11, 0)[a] - logp(11, 0)[b]) / 12
            + (logp(10, 1)[a] - logp(10, 1)[b]) / 11) / 2
    got = float(model.apply(params, idx, other)) - float(
        model.apply(params, idx, tgt))
    assert got == pytest.approx(float(want), abs=1e-6)


def test_the_train_engine_steps_this_family(model_params):
    import tiny_deepspeed_tpu as tds
    model, _ = model_params
    eng = tds.SingleDevice(model, tds.AdamW(lr=1e-2))
    state = eng.init(jax.random.PRNGKey(0))
    idx = jnp.asarray(_tokens(4, 2, 40))
    batch = (idx, jnp.roll(idx, -1, axis=1))
    losses = []
    for _ in range(4):
        state, loss = eng.step(state, batch)
        losses.append(float(loss))
    assert losses[-1] < losses[0]


def test_the_window_term_runs_in_the_fa2_forward_kernel(monkeypatch):
    """Prefill's window term through `fa2_chunk_fwd` (interpreted), merged
    with the summary term by log-sum-exp, against the XLA form."""
    monkeypatch.setattr(flash_fa2, "_INTERPRET", True)
    b, h, t, dh, w, c = 1, 2, 512, 64, 128, 16
    q, k, v = (jax.random.normal(kk, (b, h, t, dh)) for kk in
               jax.random.split(jax.random.PRNGKey(0), 3))
    mu, phi = (jax.random.normal(kk, (h, dh)) / 8 for kk in
               jax.random.split(jax.random.PRNGKey(1), 2))
    kbar, vbar = eva_ops.eva_summaries(k, v, mu, phi, c)
    plain = eva_ops.eva_attention(q, k, v, kbar, vbar, w, c, kernel_ok=True)
    with kernel_target_forced("tpu"):
        kernel = eva_ops.eva_attention(q, k, v, kbar, vbar, w, c,
                                       kernel_ok=True)
        no_grad = eva_ops.eva_attention(q, k, v, kbar, vbar, w, c)
    np.testing.assert_allclose(kernel, plain, atol=2e-5)
    np.testing.assert_array_equal(no_grad, plain)


# -- through the paged pool ----------------------------------------------------

def _engine(model, params, **kw):
    cfg = dict(max_active=3, num_blocks=10 ** 6, block_tokens=8,
               temperature=0.0)
    cfg.update(kw)
    return ServingEngine(model, params, ServeConfig(**cfg))


def _serve_gap(model, params, prompts, new, *, cfg=TINY, reference=ref,
               **kw):
    """Serve `prompts` for `new` bytes each; -> (the largest gap between a
    decode tick's logits through the pool and the reference's full forward
    over the same bytes, how many rows were compared, the engine, the
    requests).  The reference runs at ONE shape a call (a row a request,
    padded to the longest it can grow to), so it compiles once."""
    eng = _engine(model, params, **kw)
    reqs = [eng.submit(p, new) for p in prompts]
    width = -(-(max(len(p) for p in prompts) + new) // 128) * 128
    full_forward = jax.jit(
        lambda p, idx, pos: reference.logits_at(p, idx, pos, cfg))
    worst, checked = 0.0, 0
    while not all(r.done for r in reqs):
        before = [len(r.tokens) for r in reqs]
        eng.tick()
        got = np.asarray(eng.last_logits)
        # a row per request: the bytes its decode step of this tick saw
        idx = np.zeros((len(reqs), width), np.int32)
        pos = np.zeros((len(reqs),), np.int32)
        rows = []
        for j, (r, n) in enumerate(zip(reqs, before)):
            if n >= 1 and len(r.tokens) > n:
                seq = r.prompt + r.tokens[:n]
                idx[j, :len(seq)] = seq
                pos[j] = len(seq) - 1
                rows.append(j)
        if not rows:
            continue
        want = np.asarray(full_forward(params, idx, pos))
        for j in rows:
            worst = max(worst, float(np.abs(
                got[reqs[j].last_slot] - want[j]).max()))
            checked += 1
    return worst, checked, eng, reqs


PROMPTS = [5, W - 1, W, 2 * W + 3, 3 * W]     # under, at and past a window


@pytest.fixture(scope="module")
def served(model_params):
    model, params = model_params
    prompts = [_tokens(10 + n, n).tolist() for n in PROMPTS]
    return _serve_gap(model, params, prompts, 2 * W + 6, max_active=5)


def test_prefill_then_decode_agrees_with_the_full_forward_across_rolls(
        served):
    worst, checked, eng, reqs = served
    # every decode tick but the one that shares a tick with the prefill
    assert checked == len(PROMPTS) * (2 * W + 4)
    assert worst < TOL, worst
    # every request crossed at least two window boundaries while decoding
    rolled = sum(r.get("windows_rolled", 0) for r in eng.tick_records)
    assert rolled >= 2 * len(PROMPTS)
    assert all(r.status == "ok" for r in reqs)
    assert eng.pool.blocks_in_use == 0


def test_greedy_reads_head_zero(served):
    _, _, eng, reqs = served
    r = reqs[0]
    idx = np.asarray([r.prompt + r.tokens[:-1]], np.int32)
    want = np.asarray(ref.logits_at(
        eng.params, idx, np.asarray([idx.shape[1] - 1]), TINY))
    assert r.tokens[-1] == int(np.argmax(want[0, :TINY.vocab_size]))
    assert eng.last_logits.shape == (5, 2 * TINY.vocab_size)


def _faulty_gap(model_params, **kw):
    model, params = model_params
    prompts = [_tokens(20 + n, n).tolist() for n in (W + 9, 2 * W + 3)]
    return _serve_gap(model, params, prompts, W + 4, **kw)[0]


def test_planted_fault_summary_term_dropped(model_params, monkeypatch):
    monkeypatch.setattr(eva_ops, "eva_bounds",
                        lambda pos, w, c: (pos % w, 0 * (pos // w)))
    assert _faulty_gap(model_params) > FAULT


def test_planted_fault_own_windows_chunks_summarised(model_params,
                                                     monkeypatch):
    """R_n holds every CLOSED chunk, those of the query's own window too
    (they are already attended exactly): one softmax term too many."""
    monkeypatch.setattr(eva_ops, "eva_bounds",
                        lambda pos, w, c: (pos % w, pos // c))
    assert _faulty_gap(model_params) > FAULT


def test_planted_fault_pooling_without_its_softmax(model_params,
                                                   monkeypatch):
    monkeypatch.setattr(eva_ops, "_pool_weights",
                        lambda s: jnp.ones_like(s) / s.shape[-1])
    assert _faulty_gap(model_params) > FAULT


def test_planted_fault_bfloat16_where_float32_is_stated(model_params):
    _, params = model_params
    low = build_model(dataclasses.replace(
        TINY, compute_dtype=jnp.bfloat16))
    assert _faulty_gap((low, params)) > FAULT


def test_planted_faults_fail_on_the_normal_path_too(model_params,
                                                    monkeypatch):
    model, params = model_params
    idx = _tokens(5, 1, 3 * W)
    want = ref.logits_at(params, idx, np.asarray([3 * W - 1]), TINY)

    def gap():
        return float(jnp.abs(model.apply(
            params, idx, position=3 * W - 1)[:, 0] - want).max())

    assert gap() < TOL
    with monkeypatch.context() as m:
        m.setattr(eva_ops, "eva_bounds", lambda pos, w, c: (pos % w, 0))
        assert gap() > FAULT
    with monkeypatch.context() as m:
        m.setattr(eva_ops, "_pool_weights",
                  lambda s: jnp.ones_like(s) / s.shape[-1])
        assert gap() > FAULT


@pytest.mark.parametrize("bt", [4, 8, 16])
def test_the_decode_kernel_agrees_with_the_gathered_panels(
        model_params, monkeypatch, bt):
    """`tds_eva_paged_attn`, interpreted, against the XLA form of the same
    tick: two valid ranges a slot, dead blocks neither looked up nor read."""
    monkeypatch.setattr(paged_attn_pallas, "INTERPRET", True)
    model, params = model_params
    prompts = [_tokens(30 + n, n).tolist() for n in (3, W, 2 * W + 5)]
    gaps = {}
    for mode in ("on", "off"):
        gaps[mode] = _serve_gap(model, params, prompts, W + 3,
                                block_tokens=bt, paged_kernel=mode)[0]
    assert gaps["off"] < TOL and gaps["on"] < TOL, gaps


# a hand-built pool for the kernel alone: windows of 64 rows, a summary per
# 16, blocks of 8 rows; a table row of 8 window entries and 6 summary
# entries (48 rows: twelve windows), chunks cut to 32 rows = 4 blocks, so
# that the window is two chunks and the summaries one and a half
_KW, _KC, _KBT, _KSUM, _KSTEP = 64, 16, 8, 6, 32
_EDGES = {
    # position -> (live window rows, visible summaries)
    "position 0: both ranges empty": [0],
    "a window just begun, summaries behind it": [4 * _KW],          # 0, 16
    "a window one row short of full": [_KW - 1, 5 * _KW + _KW - 1],  # 63, 20
    "both ranges end on a chunk boundary": [8 * _KW + 32],          # 32, 32
    "both ranges end on a block boundary": [2 * _KW + 40],          # 40, 8
    "both ranges end inside a block": [3 * _KW + 13],               # 13, 12
    "every summary row the table holds": [12 * _KW + 5],            # 5, 48
}
_EDGES["slots of all these kinds in one call"] = sum(_EDGES.values(), [])


@pytest.mark.parametrize("case", list(_EDGES))
def test_the_decode_kernel_reads_only_what_is_live(monkeypatch, case):
    """`tds_eva_paged_attn`, interpreted and called directly, beside the
    XLA form over the same live rows.  The kernel's pool holds NaN in
    every row past a range's bound, in every block no live entry names
    (the scratch block too, which every dead entry names) and in the other
    layer's columns: what is dead is neither folded nor able to poison a
    sum.  The XLA form multiplies dead rows by a weight of 0, so its pool
    holds zeros there."""
    from tiny_deepspeed_tpu.ops import eva_attn_pallas
    from tiny_deepspeed_tpu.serving.pool import KVPoolView, page_ref

    monkeypatch.setattr(paged_attn_pallas, "INTERPRET", True)
    monkeypatch.setattr(eva_attn_pallas, "_STEP_TOKENS", _KSTEP)
    h, dh, layers, l = 4, 16, 2, 1
    c = h * dh
    lay = evabyte_mod.EvaLayout(window=_KW // _KBT, summary=_KSUM,
                                window_size=_KW, chunk_size=_KC,
                                block_tokens=_KBT)
    assert eva_attn_pallas.eva_steps(lay.window, lay.summary, _KBT) == (
        4, 2, 2)
    pos = np.asarray(_EDGES[case], np.int32)
    s = len(pos)
    rng = np.random.default_rng(len(case))
    blocks = 1 + s * lay.width
    pools = rng.standard_normal((2, blocks, _KBT, layers * c)).astype(
        np.float32)
    live = np.zeros((blocks, _KBT), bool)
    tables = np.zeros((s, lay.width), np.int32)  # dead entries: scratch
    ids = iter(rng.permutation(np.arange(1, blocks)))
    n_win, n_sum = eva_ops.eva_bounds(pos, _KW, _KC)
    for i in range(s):
        for first, rows in ((0, n_win[i]), (lay.window, n_sum[i])):
            for e in range(-(-rows // _KBT)):
                blk = tables[i, first + e] = next(ids)
                live[blk, :rows - e * _KBT] = True
    cols = np.zeros(layers * c, bool)
    cols[l * c:(l + 1) * c] = True
    keep = live[..., None] & cols
    clean, planted = (np.where(keep, pools, fill) for fill in (0.0, np.nan))
    q, sk, sv = (jnp.asarray(rng.standard_normal((s, h, 1, dh)), jnp.float32)
                 for _ in range(3))

    def view(a):
        return KVPoolView(jnp.asarray(a[0]), jnp.asarray(a[1]), None, None)

    page = page_ref(jnp.asarray(tables), jnp.asarray(pos), _KBT)
    with paged_attn_pallas.paged_kernel_forced("off"):
        want = eva_ops.eva_paged_attention(q, view(clean), page, l, (sk, sv),
                                           lay)
    got = eva_attn_pallas.eva_paged_attention_kernel(
        q, view(planted), page.tables, jnp.asarray(n_win),
        jnp.asarray(n_sum), l, (sk, sv), window_blocks=lay.window)
    assert got.shape == want.shape == (s, h, 1, dh)
    assert bool(jnp.isfinite(got).all())
    assert float(jnp.abs(got - want).max()) < TOL
    # and the XLA form over the planted pool IS poisoned wherever a slot
    # holds anything: the test's NaN lies where a careless read finds it
    with paged_attn_pallas.paged_kernel_forced("off"):
        bad = eva_ops.eva_paged_attention(q, view(planted), page, l,
                                          (sk, sv), lay)
    assert bool(jnp.isnan(bad).any())


# -- the ring, the bound, the refusals ----------------------------------------

def test_a_slot_ten_windows_long_holds_no_more_than_its_stated_blocks(
        model_params):
    model, params = model_params
    bt = 8
    eng = _engine(model, params, max_active=2, block_tokens=bt)
    lay = model.paged_layout(TINY.block_size, bt)
    stated = sum(lay.need(TINY.block_size - 1))
    assert lay.window == W // bt and lay.width == eng.max_blocks_per_req
    assert stated == W // bt + TINY.block_size // C // bt
    # the pool is what the slots can hold, whatever num_blocks said
    assert eng.pool.num_usable == 2 * stated
    r = eng.submit(_tokens(40, W + 2).tolist(), 9 * W)
    window_ids = None
    held = []
    while not r.done:
        eng.tick()
        slot = next((s for s in eng._slots if s is not None), None)
        if slot is None:
            continue
        held.append(len(slot.blocks))
        assert len(slot.table) <= lay.window
        if len(slot.table) == lay.window:
            # the ring: the same blocks, never freed, never reallocated
            window_ids = window_ids or list(slot.table)
            assert slot.table == window_ids
        # grown at the start of the tick that wrote position pos - 1
        assert len(slot.summary) == (slot.pos - 1) // C // bt + 1
    assert len(r.prompt) + len(r.tokens) == 10 * W + 2
    # (the last tick's growth is not seen: the slot is gone after it)
    assert max(held) <= stated and max(held) == lay.window + (
        10 * W - 1) // C // bt + 1
    # a slot of full K/V would hold a block per bt positions
    assert max(held) < (10 * W) // bt
    assert eng.pool.blocks_in_use == 0 and eng.pool.blocks_free == 2 * stated
    rec = [t for t in eng.tick_records if "window_blocks" in t]
    assert max(t["window_blocks"] for t in rec) == lay.window
    assert sum(t["windows_rolled"] for t in rec) == 9
    assert rec[-1]["summary_blocks"] == 10 * W // C // bt + 1


def test_a_preempted_request_resumes_exactly(model_params):
    """A pool one slot's worst case wide: the second request waits, and a
    long one re-prefilled from prompt + bytes so far goes on as before."""
    model, params = model_params
    alone = _engine(model, params, max_active=1)
    a = alone.submit(_tokens(50, 2 * W + 1).tolist(), W + 2)
    alone.drain(max_ticks=500)
    eng = _engine(model, params, max_active=2)
    b = eng.submit(a.prompt, W + 2)
    for _ in range(W // 2):
        eng.tick()
    slot_i = next(i for i, s in enumerate(eng._slots) if s is not None)
    eng._preempt(slot_i, eng._slots[slot_i])
    eng.drain(max_ticks=500)
    assert b.preemptions == 1 and b.tokens == a.tokens
    assert eng.pool.blocks_in_use == 0


@pytest.mark.parametrize("kw, mechanism", [
    (dict(prefix_cache=True), "prefix_cache"),
    (dict(spec_draft="ngram"), "spec_draft"),
    (dict(quant="int8"), "quant"),
    (dict(quant="fp8"), "quant"),
])
def test_what_cannot_follow_yet_is_refused_by_name(model_params, kw,
                                                   mechanism):
    model, params = model_params
    with pytest.raises(ValueError, match="EvaByteModel cannot be served "
                       "with " + mechanism):
        _engine(model, params, **kw)


def test_block_export_import_and_the_other_caches_are_refused_by_name(
        model_params):
    model, params = model_params
    eng = _engine(model, params)
    eng.submit(_tokens(60, 9).tolist(), 4)
    eng.tick(decode=False)
    with pytest.raises(ValueError, match="export_blocks / import_blocks"):
        eng.export_request(0)
    with pytest.raises(ValueError, match="export_blocks / import_blocks"):
        eng.import_request(None)
    with pytest.raises(NotImplementedError, match="contiguous decode cache"):
        model.generate(params, jnp.zeros((1, 4), jnp.int32), 2)
    with pytest.raises(NotImplementedError, match="span of more than one"):
        model.paged_verify(None, None, None, None)
    with pytest.raises(ValueError, match="must divide window_size"):
        model.paged_layout(TINY.block_size, 5)
    with pytest.raises(ValueError, match="chunk_size=5 must divide"):
        build_model(dataclasses.replace(TINY, chunk_size=5))


# -- GPT-2's programs are the ones they were -----------------------------------

# sha256 of the StableHLO text `jit(...).lower(...).as_text()` gives for
# the tiny GPT-2 preset's decode and prefill programs (4 slots, 32 blocks of
# 16, greedy), recorded on commit 27981b1, the parent of the PR that added
# this family.  A PR that means to change GPT-2's serve programs records
# them anew; one that adds a family or a hook must leave them as they are.
_GPT2_DECODE = "90524dca8352103ca7e98bcc15dcbf172349df24242cde80e76f4e6c2000933b"
_GPT2_PREFILL = "d338aaa3b60cdcd5210d2de1abca7b923b9e7d32bfb37ecea1b792b6fb0b28d0"


def test_gpt2_serve_programs_lower_to_the_same_text():
    model = build_model(ALL_PRESETS["tiny"])
    params = model.init(jax.random.PRNGKey(0))
    eng = ServingEngine(model, params, ServeConfig(
        max_active=4, num_blocks=32, block_tokens=16, temperature=0.0))
    ints = jax.ShapeDtypeStruct((4,), jnp.int32)
    view = eng.pool.view
    decode = eng._decode_fn.lower(
        params, eng._stacked, view, ints, ints,
        jax.ShapeDtypeStruct((4, eng.max_blocks_per_req), jnp.int32),
        ints, ints, jax.ShapeDtypeStruct((4,), jnp.float32)).as_text()
    prefill = eng._prefill_fn.lower(
        params, eng._stacked, jax.ShapeDtypeStruct((1, 32), jnp.int32), 3,
        jax.ShapeDtypeStruct((2,), jnp.int32), view, np.int32(0),
        np.int32(0)).as_text()
    assert hashlib.sha256(decode.encode()).hexdigest() == _GPT2_DECODE
    assert hashlib.sha256(prefill.encode()).hexdigest() == _GPT2_PREFILL
    # and the engine rests no second copy of weights that already rest in
    # compute dtype (float32 here)
    assert all(eng._stacked[k[2:]] is v for k, v in params.items()
               if k.startswith("h."))


def test_the_tick_record_carries_what_the_slots_hold(model_params):
    """`tick_records` and the `tick` JSONL record gain window_blocks,
    summary_blocks and windows_rolled (schema v17), and kv_steps_live of
    kv_steps in the EVA kernel's chunks (v18's names); a GPT-2 engine's
    records stay as they were."""
    from tiny_deepspeed_tpu.telemetry import schema

    class Sink:
        def __init__(self):
            self.records = []

        def log_meta(self, **rec):
            self.records.append(rec)

    model, params = model_params
    sink = Sink()
    eng = ServingEngine(model, params, ServeConfig(
        max_active=2, num_blocks=64, block_tokens=8, temperature=0.0,
        tick_record_every=1), logger=sink)
    eng.submit(_tokens(70, W - 3).tolist(), 8)
    eng.drain(max_ticks=50)
    ticks = [r for r in sink.records if r["kind"] == "tick"]
    # the prefill's byte, then positions 29 .. 35 decoded, 32 a new window
    assert len(ticks) == 7
    assert [t["windows_rolled"] for t in ticks] == [0, 0, 0, 1, 0, 0, 0]
    assert [t["summary_blocks"] for t in ticks] == [1, 1, 1, 2, 2, 2, 2]
    assert all(t["window_blocks"] == 4 for t in ticks)
    # a chunk is 256 rows of a range: the window's alone until position
    # 32, where the window is empty and 8 summaries are visible, then both
    assert [t["kv_steps_live"] for t in ticks] == [1, 1, 1, 1, 2, 2, 2]
    assert all(t["kv_steps"] == 2 * (1 + 1) for t in ticks)
    kept = [r for r in eng.tick_records if "window_blocks" in r]
    assert [r["kv_steps_live"] for r in kept] == [1, 1, 1, 1, 2, 2, 2]
    for t in ticks:
        assert schema.validate_record(dict(t, ts=0.0)) == []
    plain = build_model(ALL_PRESETS["tiny"])
    other = ServingEngine(plain, plain.init(jax.random.PRNGKey(0)),
                          ServeConfig(max_active=2, num_blocks=8))
    other.submit([1, 2, 3], 2)
    other.drain(max_ticks=10)
    assert not any("window_blocks" in r for r in other.tick_records)
    # its chunks are the dense layout's: one table row of 8 x 16 tokens
    assert [(r["kv_steps_live"], r["kv_steps"]) for r in other.tick_records
            if "kv_steps" in r] == [(1, 2)]


def test_the_reference_in_bfloat16_is_refused_by_the_same_tolerance(
        model_params):
    """The control a tolerance is set against (scripts/evabyte_control.py
    reads it at the published widths on the chip): the reference's own
    forward with every activation in bfloat16, against itself in float32."""
    _, params = model_params
    idx = _tokens(80, 2, 3 * W + 1)
    pos = np.asarray([3 * W, 2 * W - 1], np.int32)
    full = ref.logits_at(params, idx, pos, TINY)
    low = ref.logits_at(params, idx, pos, TINY, dtype=jnp.bfloat16)
    assert low.dtype == jnp.float32
    assert float(jnp.abs(full - low).max()) > FAULT


@pytest.mark.slow
def test_decode_across_a_window_boundary_at_the_published_widths():
    """One layer at the published widths in bfloat16, as the benchmark's
    cell serves them: 26 decode steps from 8 bytes short of the first
    window's boundary, each against the float32 reference and under the
    cell's own tolerance.  The steps from the roll on attend a summary row
    that decode wrote and ring rows written again (the cell's check
    compares one step straight after a prefill; the chip run of
    scripts/evabyte_control.py --decode reads the same at 6 layers)."""
    import json
    spec = importlib.util.spec_from_file_location(
        "evabyte_control", os.path.join(REPO, "scripts",
                                        "evabyte_control.py"))
    script = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(script)
    with open(os.path.join(REPO, "benchmarks", "traffic",
                           "docs-open.json")) as f:
        tol = json.load(f)["check"]["logit_tolerance"]
    cfg = dataclasses.replace(ALL_PRESETS["evabyte-6.5b-6l"], n_layer=1,
                              param_dtype=jnp.bfloat16)
    rows, rolled = script.decode_gaps(cfg, ref, 3, 26, 2, 16, windows=(1,))
    assert rolled == 1 and rows[0]["steps_from_roll"] == 18
    assert rows[0]["gap_max_before_roll"] < tol
    assert rows[0]["gap_max_from_roll"] < tol
