# Copyright 2026 tiny-deepspeed-tpu authors
# SPDX-License-Identifier: Apache-2.0

"""TPU-topology AOT compilation: the round-4 evidence locked as tests.

These compile the REAL engine step against a compile-only v5e topology
(no hardware; libtpu compiles locally) and assert the three properties the
round-3 verdict called assertions:

  * ZeRO-2/3 grads realize as TRUE ring reduce-scatter kernels
    (`AllReduceScatterFusion`), not the CPU backend's all-reduce + slice;
  * collectives schedule asynchronously (start/done structure), the
    compiled form of the engine's overlap claim (engine.py:14-18);
  * the collective ledger's TPU-format parsing (fusion-wrapped collectives,
    layout-annotated constants, done-half dedup) agrees with comm_report.

Slow (~1 min: two TPU compiles); marked `slow`, excluded from `-m quick`.
"""

import importlib.util
import os

import numpy as np
import pytest

from tiny_deepspeed_tpu import AdamW, GPT2Model, GPTConfig, Zero2, Zero3
from tiny_deepspeed_tpu.ops.dispatch import kernel_target_forced
from tiny_deepspeed_tpu.utils.hlo_comm import collective_ledger
from tiny_deepspeed_tpu.utils.profiling import comm_report

pytestmark = pytest.mark.slow

# the abstract-state/batch builders live in the script (single copy)
_spec = importlib.util.spec_from_file_location(
    "aot_topology_script",
    os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                 "scripts", "aot_topology.py"),
)
_aot = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_aot)


@pytest.fixture(scope="module")
def topo_mesh():
    from jax.experimental import topologies
    from jax.sharding import Mesh

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:4x2"
        )
    except Exception as e:  # no libtpu in some environments
        pytest.skip(f"TPU topology unavailable: {e}")
    return Mesh(np.array(topo.devices).reshape(8), ("data",))


CFG = GPTConfig(block_size=128, vocab_size=512, n_layer=4, n_head=8,
                n_embd=256)


def _compiled_text(engine, b=8, t=128):
    state = _aot._state_structs(engine)
    batch = _aot._batch_structs(engine, b, t)
    # trace with the TPU kernel gates ON (ops/dispatch.py): the process
    # backend is CPU but the program targets the topology's TPUs
    with kernel_target_forced("tpu"):
        return engine._step.lower(state, batch).compile().as_text()


class TestTpuTopologyHLO:
    def test_zero2_true_reduce_scatter_and_ledger_agreement(self, topo_mesh):
        eng = Zero2(GPT2Model(CFG), AdamW(lr=1e-3), mesh=topo_mesh)
        text = _compiled_text(eng)
        # ring reduce-scatter kernels, not all-reduce + slice
        assert "AllReduceScatterFusion" in text
        led = collective_ledger(text)
        assert led["wire_bytes"].get("reduce-scatter", 0) > 0
        assert not led["unresolved_loops"], led["unresolved_loops"]
        # grads dominate: the all-reduce residue must stay tiny
        assert led["wire_bytes"].get("all-reduce", 0) < \
            0.05 * led["wire_bytes"]["reduce-scatter"]
        # async scheduling evidence (overlap): tagged async collectives
        assert text.count("async_collective_name") >= 4
        # TPU-format parsing agrees with the ring formulas end-to-end
        predicted = comm_report(eng)["total_bytes_per_step"]
        assert abs(led["total_wire_bytes"] - predicted) <= 0.05 * predicted, \
            (led["total_wire_bytes"], predicted)

    def test_multislice_hybrid_mesh_and_compile(self):
        """make_mesh's hybrid ICI x DCN layout, exercised on REAL
        multi-slice TPU devices (2-slice v5e:2x2 topology, compile-only):
        the 'data' axis must span the slices (DCN — gradient reductions
        amortize), every other axis must stay inside one slice (ICI — its
        collectives sit on the critical path), and the tensor-parallel
        train step must compile against that mesh.  Until round 4 this
        layout was only tested against mocked slice_index devices
        (tests/test_mesh.py)."""
        from jax.experimental import topologies
        from tiny_deepspeed_tpu import Zero1, make_mesh

        try:
            topo = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2", num_slices=2
            )
        except Exception as e:
            pytest.skip(f"multi-slice TPU topology unavailable: {e}")
        devices = list(topo.devices)
        assert len(devices) == 8
        assert {d.slice_index for d in devices} == {0, 1}

        mesh = make_mesh((2, 4), ("data", "model"), devices=devices)
        grid = mesh.devices  # (data=2, model=4)
        # model-axis rows: one slice each (ICI); data-axis pairs: both
        # slices (DCN)
        for row in grid:
            assert len({d.slice_index for d in row}) == 1, grid
        for col in grid.T:
            assert {d.slice_index for d in col} == {0, 1}, grid

        cfg = GPTConfig(block_size=128, vocab_size=512, n_layer=2,
                        n_head=4, n_embd=256)
        # the mesh's "model" axis drives tensor parallelism (an explicit
        # mesh bypasses the engine's own axis carving)
        eng = Zero1(GPT2Model(cfg), AdamW(lr=1e-3), mesh=mesh)
        text = _compiled_text(eng, b=4, t=128)
        led = collective_ledger(text)
        assert led["total_wire_bytes"] > 0
        assert not led["unresolved_loops"], led["unresolved_loops"]

    def test_offload_streamed_update_compiles_on_tpu(self, topo_mesh):
        """offload_opt_state AOT-compiles against the real TPU topology —
        the round-4 compile caught that host-resident moments were being
        consumed without an explicit HBM transfer (TPU XLA rejects
        mixed-memory-space arithmetic), which no CPU test could see.  The
        streamed per-leaf update must compile, keep the moments resting in
        pinned_host, and lower the compiled peak vs the unoffloaded step;
        the dynamic-loss-scale composition exercises the on-device
        keep-old selection (host-space where() also refuses to compile)."""
        import warnings

        from jax.sharding import Mesh
        from tiny_deepspeed_tpu import SingleDevice

        mesh1 = Mesh(np.asarray(topo_mesh.devices).reshape(-1)[:1],
                     ("data",))
        cfg = GPTConfig(block_size=128, vocab_size=512, n_layer=4,
                        n_head=8, n_embd=512)

        def build(**kw):
            with warnings.catch_warnings():
                warnings.simplefilter("ignore")  # CPU-backend notice
                return SingleDevice(GPT2Model(cfg), AdamW(lr=1e-3),
                                    mesh=mesh1, **kw)

        def peak(engine):
            state = _aot._state_structs(engine)
            with kernel_target_forced("tpu"):
                compiled = engine._step.lower(
                    state, _aot._batch_structs(engine, 4, 128)
                ).compile()
            hbm_state = sum(
                int(np.prod(x.shape)) * x.dtype.itemsize
                for x in jax.tree.leaves(state)
                if getattr(x.sharding, "memory_kind", None) != "pinned_host"
            )
            return hbm_state, compiled.memory_analysis().temp_size_in_bytes

        import jax

        plain_state, plain_temp = peak(build())
        off = build(offload_opt_state=True)
        kinds = {s.memory_kind
                 for s in jax.tree.leaves(off._opt_shardings["state"])}
        assert kinds == {"pinned_host"}
        off_state, off_temp = peak(off)
        # moments (2x f32 per param) left the resting device footprint...
        assert off_state < 0.6 * plain_state
        # ...and the streamed update keeps the compiled peak BELOW the
        # unoffloaded one (bulk transfer used to blow it past it)
        assert off_state + off_temp < plain_state + plain_temp

        # dynamic loss scaling composes (selection happens on device)
        dyn = build(offload_opt_state=True, loss_scale="dynamic")
        with kernel_target_forced("tpu"):
            dyn._step.lower(
                _aot._state_structs(dyn), _aot._batch_structs(dyn, 4, 128)
            ).compile()

    def test_zero3_layer_gathers_async_and_counted(self, topo_mesh):
        eng = Zero3(GPT2Model(CFG), AdamW(lr=1e-3), mesh=topo_mesh)
        text = _compiled_text(eng)
        led = collective_ledger(text)
        assert not led["unresolved_loops"], led["unresolved_loops"]
        # per-layer gathers match the 2x-block + 1x-nonblock model to a
        # few percent (measured +0.04% — PROFILE.md finding 4): the remat
        # backward re-gathers each block weight exactly once, and the
        # ledger's async-copy channel dedup reads the TPU dialect right
        predicted = comm_report(eng)["zero3_layer_gather_bytes"]
        ag = led["wire_bytes"].get("all-gather", 0)
        assert 0.95 * predicted <= ag <= 1.05 * predicted, (ag, predicted)
        # the gathers are issued as async start fusions (overlap evidence)
        assert "%async-collective-start" in text or \
            "async_collective_name" in text

    def test_zero3_gather_prefetch_compiles_and_stays_in_loop(
            self, topo_mesh):
        """Round 8: the layer-ahead prefetched gather scan
        (gather_prefetch=2, parallel/schedule.GatherPrefetchScan) AOT-
        compiles against the real TPU topology, keeps the per-layer
        all-gathers loop-resident (a hoisted gather would regrow
        full-model HBM — the scan_unroll footgun, now checkable), keeps
        compiled temp memory in the on-demand regime (double buffer, not
        L buffers), and composes with offload_opt_state."""
        import jax
        import warnings

        from tiny_deepspeed_tpu.utils.hlo_comm import overlap_report

        def build(**kw):
            return Zero3(GPT2Model(CFG), AdamW(lr=1e-3), mesh=topo_mesh,
                         **kw)

        def compiled(eng):
            state = _aot._state_structs(eng)
            with kernel_target_forced("tpu"):
                return eng._step.lower(
                    state, _aot._batch_structs(eng, 8, 128)).compile()

        c_pf = compiled(build(gather_prefetch=2))
        text = c_pf.as_text()
        led = collective_ledger(text)
        assert not led["unresolved_loops"], led["unresolved_loops"]
        rep = overlap_report(text, led=led)
        # the prefetched gathers stay inside the scan loops
        assert rep["gather_wire_bytes_in_loops"] > 0
        assert rep["gather_overlap_frac"] > 0.5
        # memory: at most the double buffer over the on-demand step, not
        # an L-layer (or full-model) regrowth.  Compared at a size whose
        # step HAS compiled temp memory — at the toy CFG above XLA reports
        # temp_size_in_bytes == 0 for both programs and the bound is empty
        big = GPTConfig(block_size=512, vocab_size=2048, n_layer=8,
                        n_head=8, n_embd=1024)

        def temp(**kw):
            eng = Zero3(GPT2Model(big), AdamW(lr=1e-3), mesh=topo_mesh,
                        **kw)
            with kernel_target_forced("tpu"):
                return eng._step.lower(
                    _aot._state_structs(eng),
                    _aot._batch_structs(eng, 16, 512),
                ).compile().memory_analysis().temp_size_in_bytes

        t_base, t_pf = temp(), temp(gather_prefetch=2)
        assert 0 < t_pf < 1.6 * t_base, (t_pf, t_base)
        # composes with host-resident optimizer moments
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")  # CPU-backend offload notice
            off = build(gather_prefetch=2, offload_opt_state=True)
        compiled(off)
        kinds = {s.memory_kind
                 for s in jax.tree.leaves(off._opt_shardings["state"])}
        assert kinds == {"pinned_host"}

    def test_gqa_fa2_compiles_on_tpu(self, topo_mesh):
        """Mosaic accepts the GQA kernels' grouped BlockSpecs (interpret
        mode can't check tiling rules): fwd + both backward passes of the
        kv-indexed FA2 kernel compile against the v5e target at the two
        llama preset shapes, and the pallas custom calls are in the
        program (not silently replaced by an XLA fallback)."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        from tiny_deepspeed_tpu.ops.flash_fa2 import fa2_flash_attention

        mesh_1 = Mesh(np.array(topo_mesh.devices).reshape(-1)[:1], ("d",))
        sh = NamedSharding(mesh_1, P())
        for b, h, kvh, t, d in [(8, 12, 4, 1024, 64), (4, 32, 8, 2048, 64)]:
            f = jax.jit(jax.grad(
                lambda q, k, v: jnp.sum(
                    fa2_flash_attention(q, k, v, 512, 512)
                    .astype(jnp.float32)),
                argnums=(0, 1, 2)))
            args = [
                jax.ShapeDtypeStruct((b, h, t, d), jnp.bfloat16, sharding=sh),
                jax.ShapeDtypeStruct((b, kvh, t, d), jnp.bfloat16,
                                     sharding=sh),
                jax.ShapeDtypeStruct((b, kvh, t, d), jnp.bfloat16,
                                     sharding=sh),
            ]
            with kernel_target_forced("tpu"):
                compiled = f.lower(*args).compile()
            assert compiled.as_text().count("tpu_custom_call") == 3

    def test_ring_fa2_body_compiles_sp8_t32k(self, topo_mesh):
        """Round-5 ring×FA2 evidence: the sp=8 T=32768 ring attention
        program compiled for the v5e target runs its per-chunk compute
        in Pallas custom calls (not jnp online softmax), keeps the
        collective-permute rotation, and its per-chip temp memory stays
        in the O(T/n) regime the round-4 remat proof established."""
        import functools
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from tiny_deepspeed_tpu.parallel.ring_attention import (
            ring_attention_local,
        )

        b, h, t, d = 1, 12, 32768, 64
        spec = P(None, None, "data", None)  # T sharded over the 8 devices
        fn = jax.shard_map(
            functools.partial(ring_attention_local, axis_name="data",
                              axis_size=8),
            mesh=topo_mesh, in_specs=(spec,) * 3, out_specs=spec,
            check_vma=False)
        args = [jax.ShapeDtypeStruct(
            (b, h, t, d), jnp.bfloat16,
            sharding=jax.NamedSharding(topo_mesh, spec))] * 3

        def loss(q, k, v):
            return jnp.sum(fn(q, k, v).astype(jnp.float32))

        with kernel_target_forced("tpu"):
            compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
                *args).compile()
        text = compiled.as_text()
        assert text.count("tpu_custom_call") >= 3  # fwd + dq + dkv kernels
        assert "collective-permute" in text
        temp = compiled.memory_analysis().temp_size_in_bytes
        assert temp < 4 * 2**30, f"temp {temp / 2**30:.2f} GB/chip"

    def test_fp8_gather_beats_unquantized_wire(self, topo_mesh):
        """Round-5 resolution of the three-round fp8 question: on the
        TPU-partitioned HLO the quantized ZeRO-3 step must move FEWER
        total wire bytes than the unquantized one (in-dim shard keeps
        the gathers f8; STE keeps the scale out of the backward), with
        the true reduce-scatter untouched and the ledger agreeing with
        comm_report's stacked-dtype formula."""
        import dataclasses

        def build(gq):
            return Zero3(GPT2Model(dataclasses.replace(
                CFG, n_layer=4, gather_quant=gq)), AdamW(lr=1e-3),
                mesh=topo_mesh)

        led_plain = collective_ledger(_compiled_text(build(None)))
        eng_q = build("fp8")
        text_q = _compiled_text(eng_q)
        led_q = collective_ledger(text_q)
        assert led_q["total_wire_bytes"] < 0.85 * \
            led_plain["total_wire_bytes"], (led_q, led_plain)
        # the win is in the gathers; the grad reduce-scatter is untouched
        assert abs(led_q["wire_bytes"]["reduce-scatter"]
                   - led_plain["wire_bytes"]["reduce-scatter"]) < \
            0.01 * led_plain["wire_bytes"]["reduce-scatter"]
        # scale bytes stay out of the backward (STE): all-reduce at the
        # plain config's noise floor, not the round-4 ~4.8 MB
        assert led_q["wire_bytes"].get("all-reduce", 0) < \
            2.0 * led_plain["wire_bytes"].get("all-reduce", 1)
        # formula agreement
        predicted = comm_report(eng_q)["total_bytes_per_step"]
        assert abs(led_q["total_wire_bytes"] - predicted) <= \
            0.05 * predicted, (led_q["total_wire_bytes"], predicted)

    def test_offload_prefetch_window_schedule(self, topo_mesh):
        """Round-5 offload study, locked: widening the streamed-update
        window at leaf granularity grows compiled temp memory (more
        moment leaves in flight) and does NOT move the inbound host
        copies earlier in the schedule — the scheduler keeps the whole
        moment stream inside the update phase (first inbound copy-start
        in the last third of the program).  This is why offload_prefetch
        defaults to 2; at 1.5B, w=4 compiled to 17.25 GB peak (over the
        16 GB chip) with the first inbound copy still at ~86% of the
        schedule."""
        import warnings

        from jax.sharding import Mesh
        from tiny_deepspeed_tpu import SingleDevice

        mesh1 = Mesh(np.asarray(topo_mesh.devices).reshape(-1)[:1],
                     ("data",))
        cfg = GPTConfig(block_size=128, vocab_size=512, n_layer=4,
                        n_head=8, n_embd=512)

        def compile_w(w):
            with warnings.catch_warnings():
                warnings.simplefilter("ignore")
                eng = SingleDevice(GPT2Model(cfg), AdamW(lr=1e-3),
                                   mesh=mesh1, offload_opt_state=True,
                                   offload_prefetch=w)
            state = _aot._state_structs(eng)
            with kernel_target_forced("tpu"):
                return eng._step.lower(
                    state, _aot._batch_structs(eng, 4, 128)).compile()

        c2, c4 = compile_w(2), compile_w(4)
        assert c4.memory_analysis().temp_size_in_bytes > \
            c2.memory_analysis().temp_size_in_bytes
        lines = c2.as_text().splitlines()
        in_starts = [i for i, ln in enumerate(lines)
                     if "copy-start" in ln and "S(5)" in ln]
        assert in_starts, "no host-space copy-starts found"
        # the moment stream stays in the update phase (no fwd/bwd hoist)
        assert in_starts[0] > len(lines) * 0.5

    def test_pallas_fused_xent_compiles_on_tpu(self, topo_mesh):
        """The round-5 fused lm_head+xent kernel: the FULL single-device
        train step with fused_xent_impl='pallas' compiles for v5e at the
        flagship head shape (D=768, V=50304 — non-divisible vocab tail)
        with the three xent custom calls in the program."""
        import dataclasses
        import warnings

        from jax.sharding import Mesh
        from tiny_deepspeed_tpu import SingleDevice

        mesh1 = Mesh(np.asarray(topo_mesh.devices).reshape(-1)[:1],
                     ("data",))
        cfg = dataclasses.replace(
            CFG, n_layer=2, n_embd=768, n_head=12, vocab_size=50304,
            fused_xent=True, fused_xent_impl="pallas")
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            eng = SingleDevice(GPT2Model(cfg), AdamW(lr=1e-3), mesh=mesh1)
        state = _aot._state_structs(eng)
        with kernel_target_forced("tpu"):
            compiled = eng._step.lower(
                state, _aot._batch_structs(eng, 4, 128)).compile()
        # fwd + dx + dw xent calls (attention kernels add their own)
        assert compiled.as_text().count("tpu_custom_call") >= 3

    def test_paged_attention_compiles_on_tpu(self, topo_mesh):
        """Mosaic accepts the paged-attention kernel over the pool's
        resting (blocks, 16, L * KVH * Dh) at the gpt2-124m serving
        shapes chip_smoke.py runs (12 heads of 64, 16-token blocks,
        21-entry tables, 4 slots) and at a Llama GQA shape (8 query
        heads over 2 KV heads of 128): the decode variant and a 5-wide
        verify span; bf16, f32, int8 and fp8 pools — interpret mode
        cannot check tiling rules."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import SingleDeviceSharding

        from tiny_deepspeed_tpu.ops.paged_attn_pallas import paged_attention
        from tiny_deepspeed_tpu.serving.pool import (
            KVPoolView, page_ref, pool_shape,
        )

        sh = SingleDeviceSharding(
            np.asarray(topo_mesh.devices).reshape(-1)[0])

        def struct(shape, dt):
            return jax.ShapeDtypeStruct(shape, dt, sharding=sh)

        def attend(kvh, q, k, v, ks, vs, tables, pos, l, sk, sv):
            view = KVPoolView(k, v, ks, vs)
            return paged_attention(q, view, page_ref(tables, pos, 16), l,
                                   (sk, sv), kv_heads=kvh)

        ints = (struct((4, 21), jnp.int32), struct((4,), jnp.int32),
                struct((), jnp.int32))
        for hq, kvh, dh in ((12, 12, 64), (8, 2, 128)):
            for dt in (jnp.bfloat16, jnp.float32, jnp.int8,
                       jnp.float8_e4m3fn):
                quant = jnp.dtype(dt).itemsize == 1
                cdt = jnp.bfloat16 if quant else dt
                pool = struct(pool_shape(97, 16, 12, kvh, dh), dt)
                scale = (struct(pool_shape(97, 16, 12, kvh, 1),
                                jnp.float32) if quant else None)
                for k1 in (1, 5):
                    q = struct((4, hq, k1, dh), cdt)
                    span = (struct((4, kvh, k1, dh), cdt),) * 2
                    text = jax.jit(attend, static_argnums=0).lower(
                        kvh, q, pool, pool, scale, scale, *ints, *span
                    ).compile().as_text()
                    assert "tpu_custom_call" in text, (hq, dt, k1)

    def test_serve_programs_never_copy_the_pool(self, topo_mesh):
        """`tds_decode` and `tds_prefill` compiled for the v5e at the
        sizes of the benchmark's serve cell (gpt2-124m, 64 slots, 4097
        blocks of 16 tokens, bf16): no `copy` or `transpose`, alone or
        inside a fusion, touches anything with the pool's element count,
        and the pool's arguments are the unpadded 2 x 4097 x 16 x 12 x
        12 x 64 x 2 bytes, aliased to the outputs.  The engine is built
        with a few blocks (its arrays are real, on the CPU); its two
        programs are lowered from shapes."""
        import re

        import jax
        import jax.numpy as jnp
        from jax.sharding import SingleDeviceSharding

        from tiny_deepspeed_tpu.models import build_model
        from tiny_deepspeed_tpu.serving import ServeConfig, ServingEngine
        from tiny_deepspeed_tpu.serving.pool import KVPoolView

        sh = SingleDeviceSharding(
            np.asarray(topo_mesh.devices).reshape(-1)[0])
        slots, bt, blocks, bucket = 64, 16, 4097, 512
        cfg = GPTConfig(block_size=1024, vocab_size=50304, n_layer=12,
                        n_head=12, n_embd=768, param_dtype=jnp.bfloat16)
        model = build_model(cfg)
        params = jax.jit(model.init)(jax.random.PRNGKey(0))
        eng = ServingEngine(model, params, ServeConfig(
            max_active=slots, num_blocks=4, block_tokens=bt,
            temperature=0.0, eos_id=None))

        def like(a, shape=None):
            return jax.ShapeDtypeStruct(shape or a.shape, a.dtype,
                                        sharding=sh)

        def ints(*shape):
            return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=sh)

        small = eng.pool.view
        view = KVPoolView(
            like(small.k, (blocks,) + small.k.shape[1:]),
            like(small.v, (blocks,) + small.v.shape[1:]), None, None)
        elems = int(np.prod(view.k.shape))
        pool_bytes = 2 * blocks * bt * 12 * 12 * 64 * 2
        assert 2 * elems * 2 == pool_bytes == eng.pool.kv_bytes()[
            "kv_block_bytes"] // 5 * blocks
        p, st = jax.tree.map(like, params), jax.tree.map(like, eng._stacked)
        programs = {
            "tds_decode": (eng._decode_fn, (
                p, st, view, ints(slots), ints(slots),
                ints(slots, cfg.block_size // bt), ints(slots), ints(slots),
                jax.ShapeDtypeStruct((slots,), jnp.float32, sharding=sh))),
            "tds_prefill": (eng._prefill_fn, (
                p, st, ints(1, bucket), ints(), ints(bucket // bt), view,
                ints(), ints())),
        }
        other = sum(int(np.prod(a.shape)) * a.dtype.itemsize
                    for a in jax.tree.leaves((p, st)))
        shape_re = re.compile(r"\w+\[([\d,]+)\]")
        for name, (fn, args) in programs.items():
            with kernel_target_forced("tpu"):
                compiled = fn.lower(*args).compile()
            text = compiled.as_text()
            assert f"jit_{name}" in text and "tpu_custom_call" in text
            for line in text.splitlines():
                if not re.search(r"= .*\b(copy|transpose)\(", line):
                    continue
                sizes = [int(np.prod([int(d) for d in dims.split(",")]))
                         for dims in shape_re.findall(line)]
                assert max(sizes, default=0) < elems, (name, line[:200])
            mem = compiled.memory_analysis()
            assert mem.alias_size_in_bytes == pool_bytes, name
            # what else comes in is weights (a program takes the ones
            # it reads) and a few integers: no padding hides in the sum
            assert (pool_bytes < mem.argument_size_in_bytes
                    < pool_bytes + other + (1 << 20)), name
            # nothing pool-sized in flight either
            assert mem.temp_size_in_bytes < (64 << 20), name

    def test_off_grid_lengths_compile_on_tpu(self, topo_mesh):
        """A forward at a sequence length off the kernels' 128 grid (a
        generate() prompt of 300 tokens) must compile for the chip: the
        attention gate (ops/attention.flash_kernel_ok) and the layernorm
        row-block picker send it to XLA instead of handing Mosaic a block
        it refuses — both failed on the first chip run of PR 21."""
        import dataclasses

        import jax
        import jax.numpy as jnp
        from jax.sharding import SingleDeviceSharding

        sh = SingleDeviceSharding(
            np.asarray(topo_mesh.devices).reshape(-1)[0])
        cfg = dataclasses.replace(CFG, n_layer=2, n_embd=768, n_head=12,
                                  block_size=512)
        model = GPT2Model(cfg)
        params = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh),
            jax.eval_shape(model.init, jax.random.PRNGKey(0)))
        for t in (100, 300):
            idx = jax.ShapeDtypeStruct((1, t), jnp.int32, sharding=sh)
            with kernel_target_forced("tpu"):
                jax.jit(model.apply).lower(params, idx).compile()

    def test_gqa_ring_rotation_bytes_shrink(self, topo_mesh):
        """Round 5: the ring rotates K/V (and the backward's dk/dv
        accumulators) at kv_heads — collective-permute wire bytes of the
        compiled f+b program must shrink toward 1/group vs the
        expand-first ring (q-side traffic is zero in the ring, so unlike
        Ulysses there is no full-head floor; small deviation comes from
        the f32 accumulator halves)."""
        import functools
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from tiny_deepspeed_tpu.parallel.ring_attention import (
            ring_attention_local,
        )

        b, hq, hkv, t, d = 1, 8, 2, 4096, 64
        spec = P(None, None, "data", None)
        sh = NamedSharding(topo_mesh, spec)

        def wire(kvh):
            fn = jax.shard_map(
                functools.partial(ring_attention_local, axis_name="data",
                                  axis_size=8),
                mesh=topo_mesh, in_specs=(spec,) * 3, out_specs=spec,
                check_vma=False)
            args = [
                jax.ShapeDtypeStruct((b, hq, t, d), jnp.bfloat16,
                                     sharding=sh),
                jax.ShapeDtypeStruct((b, kvh, t, d), jnp.bfloat16,
                                     sharding=sh),
                jax.ShapeDtypeStruct((b, kvh, t, d), jnp.bfloat16,
                                     sharding=sh),
            ]

            def loss(q, k, v):
                return jnp.sum(fn(q, k, v).astype(jnp.float32))

            with kernel_target_forced("tpu"):
                text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
                    *args).compile().as_text()
            led = collective_ledger(text)
            assert not led["unresolved_loops"], led["unresolved_loops"]
            return led["wire_bytes"].get("collective-permute", 0)

        grouped = wire(hkv)
        expanded = wire(hq)
        assert grouped < 0.35 * expanded, (grouped, expanded)


def test_eva_decode_step_compiles_for_the_chip_at_published_widths(
        topo_mesh):
    """EvaByte's decode program at the benchmark cell's sizes (6 layers,
    d 4096, 32 heads of 128, 16 slots, 16-row blocks, the pool of 16 x 256
    blocks): Mosaic takes `tds_eva_paged_attn` (the pool's K and V as two
    operands left in HBM, a grid step a slot, and a two-deep VMEM buffer
    of 2 x 4 MiB: two chunks of 16 blocks of (16, 4096) bf16 for K and
    two for V, which the kernel fills by its own copies), the pool is
    aliased from argument to result, and no temporary of the pool's size
    is made."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from tiny_deepspeed_tpu.models import ALL_PRESETS, build_model
    from tiny_deepspeed_tpu.serving.pool import KVPoolView, pool_shape

    one = SingleDeviceSharding(topo_mesh.devices.reshape(-1)[0])

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    cfg = dataclasses.replace(ALL_PRESETS["evabyte-6.5b-6l"],
                              param_dtype=jnp.bfloat16)
    model = build_model(cfg)
    params = {k: sds(v.shape, v.dtype) for k, v in jax.eval_shape(
        model.init, jax.random.PRNGKey(0)).items()}
    stacked = {k[2:]: v for k, v in params.items() if k.startswith("h.")}
    slots, bt = 16, 16
    lay = model.paged_layout(cfg.block_size, bt)
    assert sum(lay.need(cfg.block_size - 1)) == 256
    shape = pool_shape(slots * 256 + 1, bt, cfg.n_layer, cfg.n_head,
                       cfg.head_dim)
    view = KVPoolView(sds(shape, jnp.bfloat16), sds(shape, jnp.bfloat16),
                      None, None)

    def decode(params, stacked, view, tokens, pos, tables):
        x = model._embed_decode(params, tokens, pos)
        page = model.paged_page_ref(tables, pos, bt)
        x, view = model.paged_decode(stacked, x, view, page)
        return model.head(params, x)[:, 0], view

    ints = sds((slots,), jnp.int32)
    with kernel_target_forced("tpu"):
        compiled = jax.jit(decode, donate_argnums=(2,)).lower(
            params, stacked, view, ints, ints,
            sds((slots, lay.width), jnp.int32)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1
    assert "tds_eva_paged_attn" in text
    mem = compiled.memory_analysis()
    pool_bytes = 2 * int(np.prod(shape)) * 2
    assert mem.alias_size_in_bytes >= pool_bytes
    assert mem.temp_size_in_bytes < 64 * 2 ** 20


def test_mimo_decode_step_compiles_for_the_chip_at_published_widths(
        topo_mesh):
    """MiMo-V2-Flash's decode program at the benchmark cell's sizes (7
    layers at hidden 4096, 64 heads, q/k 192 and v 128, 16 held experts,
    64 slots, 16-row blocks): Mosaic takes `tds_paged_attn` with K wider
    than V for both kinds of layer (4 KV heads over a table, 8 over a
    ring with the sink), both kinds of pool are aliased from argument to
    result, the arguments are the 11.6 GiB the cell rests at, and no
    temporary of a weight's size is made: the split of q and k into a
    rotary and a plain part stays off the weights (the barrier in
    `MiMoModel._qkv`; without it the step re-lays 100 MB of q weights a
    layer and holds 324 MiB of temporaries)."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from tiny_deepspeed_tpu.models import ALL_PRESETS, build_model
    from tiny_deepspeed_tpu.serving.pool import KVPoolView, pool_shape

    one = SingleDeviceSharding(topo_mesh.devices.reshape(-1)[0])

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    cfg = dataclasses.replace(ALL_PRESETS["mimo-v2-flash-7l"],
                              param_dtype=jnp.bfloat16)
    model = build_model(cfg)
    params = {k: sds(v.shape, v.dtype) for k, v in jax.eval_shape(
        model.init, jax.random.PRNGKey(0)).items()}
    stacked = {k: v for k, v in params.items()
               if k.split(".")[0] in ("g", "w", "dense", "moe")}
    slots, bt = 64, 16
    lay = model.paged_layout(cfg.block_size, bt)
    assert (lay.table, lay.ring) == (1024, 8)
    view, pool_bytes = [], 0
    for kind in lay.kinds:
        shapes = [pool_shape(slots * kind.blocks + 1, bt, kind.layers,
                             kind.kv_heads, width)
                  for width in (kind.k_dim, kind.v_dim)]
        view.append(KVPoolView(*(sds(s, jnp.bfloat16) for s in shapes),
                               None, None))
        pool_bytes += sum(2 * int(np.prod(s)) for s in shapes)
    assert [v.k.shape[2] for v in view] == [2 * 4 * 192, 5 * 8 * 192]
    assert [v.v.shape[2] for v in view] == [2 * 4 * 128, 5 * 8 * 128]

    def decode(params, stacked, view, tokens, pos, tables):
        x = model._embed_decode(params, tokens, pos)
        page = model.paged_page_ref(tables, pos, bt)
        x, view, counts = model.paged_decode(stacked, x, view, page)
        return model.head(params, x)[:, 0], view, counts

    ints = sds((slots,), jnp.int32)
    with kernel_target_forced("tpu"):
        compiled = jax.jit(decode, donate_argnums=(2,)).lower(
            params, stacked, tuple(view), ints, ints,
            sds((slots, lay.width), jnp.int32)).compile()
    text = compiled.as_text()
    assert "tds_paged_attn" in text
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= pool_bytes
    assert 11.4 < mem.argument_size_in_bytes / 2 ** 30 < 11.8
    assert mem.temp_size_in_bytes < 64 * 2 ** 20
