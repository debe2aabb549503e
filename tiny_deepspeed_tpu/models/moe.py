# Copyright 2026 tiny-deepspeed-tpu authors
# SPDX-License-Identifier: Apache-2.0

"""Mixture-of-Experts GPT: top-k routed experts with expert parallelism.

ABSENT from the reference (SURVEY §2.20: no expert parallelism of any kind —
its parallelism surface is DP + ZeRO-1/2/3 only); first-class here because
the build targets the full tp/pp/dp/sp/ep sharding surface.

TPU-first design:
  * Every block's MLP is replaced by a router + E experts; blocks stay
    UNIFORM so the stacked-layer `lax.scan` (O(1) compile depth) is kept —
    expert tensors just carry an extra (E,) axis after the layer axis.
  * Routing is GShard-style top-k with a STATIC capacity: dispatch/combine
    are dense one-hot einsums over (tokens, experts, capacity) — no dynamic
    shapes, no sorting scatter, so XLA tiles everything onto the MXU.
  * Expert parallelism = sharding the (E,) axis over an "expert" mesh axis
    (`ep_rules`); the dispatch einsum's contraction over tokens makes GSPMD
    emit the all-to-all.  Composes with TP (experts' ff dim over "model")
    and every ZeRO stage (data axis on a remaining dim).
  * Load-balancing auxiliary loss (Switch-Transformer form) accumulates
    through the scan carry and is added to the LM loss.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from ..ops import linear, layernorm
from ..ops.attention import sharded_attention
from .gpt2 import GPTConfig, GPT2Model, _dropout


@dataclasses.dataclass(frozen=True)
class MoEConfig(GPTConfig):
    """GPTConfig + routing hyperparameters."""

    n_expert: int = 8
    expert_top_k: int = 2
    capacity_factor: float = 1.25
    aux_loss_weight: float = 1e-2
    ff_mult: int = 4  # expert hidden = ff_mult * n_embd
    # dispatch/combine mechanism: "einsum" (GShard-style dense one-hot
    # matmuls over (S, E, C) — the all-to-all boundary under expert
    # parallelism) or "sort" (argsort tokens by expert, gather rows into
    # (E, C, D), scatter-add back).  The einsum pair costs 2*2*S*(E*C)*D
    # FLOPs per layer — at moe-8x124m bench shape ~2/3 of the expert
    # matmul FLOPs themselves — while the sort path moves the same rows
    # with O(S*k log) sort + gather.  Round 16: the einsum cost IS now
    # counted as model compute — `dispatch_combine_flops_per_token`
    # below is that term when the effective dispatch is einsum, and
    # tests/test_hlo_cost.py pins the analytic number against the
    # HLO-counted FLOPs of the compiled step.
    # "sort" runs single-device and — round 5 — SHARD-LOCAL under pure
    # data parallelism (experts replicated: each device argsorts its own
    # token shard inside a shard_map, capacity prorated by shard, zero
    # extra communication).  It still falls back to einsum under
    # ep/tp/sp/pipe: with EP the einsum contraction IS what GSPMD turns
    # into the all-to-all, and the other axes would put the gather/
    # scatter on partially-manual meshes (`effective_dispatch` is the
    # single predicate).  Slot
    # assignment differs under capacity overflow: einsum fills all 1st
    # choices before 2nd choices, sort fills token-major — identical
    # outputs whenever nothing drops (pinned by test).
    moe_dispatch: str = "einsum"


def effective_dispatch(cfg, pctx) -> str:
    """The dispatch mechanism a step with this config/mesh actually runs —
    ONE predicate, the one `_moe_mlp` gates on, so a measurement can
    never be labeled with a knob value that fell back."""
    if cfg.moe_dispatch != "sort":
        return cfg.moe_dispatch
    if pctx is None or not pctx.is_multi_device:
        return "sort"
    if (pctx.expert_parallel or pctx.tensor_parallel
            or pctx.seq_parallel or pctx.pipe_parallel):
        return "einsum"
    return "sort"


def dispatch_combine_flops_per_token(cfg, panel_tokens: int) -> float:
    """Analytic TRAIN FLOPs per token of the einsum dispatch/combine pair
    across all layers — the undercount the MoEConfig docstring used to
    only apologize for.

    Per layer the compiled step runs FIVE S-contracting matmuls of
    2*S*E*C*D FLOPs each: dispatch ("sec,sd->ecd") + combine
    ("sec,ecd->sd") forward, then THREE backward — d_xs from the
    dispatch einsum, d_combine and d_ye from the combine einsum.  The
    dispatch one-hot's own cotangent is dead (routing reaches it through
    argmax; only `combine` carries the differentiable gates), so the
    naive 3x-forward rule's sixth matmul never exists.  Divided by the S
    tokens of the routing panel: 10 * n_layer * E * C * D per token,
    with C the same capacity expression `_route` computes from
    `panel_tokens` (= b*t single-device; the per-shard panel under dp
    sharding).  Only the einsum path pays this — `effective_dispatch`
    says whether it runs.  tests/test_hlo_cost.py pins this formula
    against the HLO-counted FLOPs of the compiled moe step."""
    e, k = cfg.n_expert, cfg.expert_top_k
    cap = max(1, int(cfg.capacity_factor * k * panel_tokens / e))
    return 10.0 * cfg.n_layer * e * cap * cfg.n_embd


# Entry-point presets (one flat namespace with gpt2-*/llama-*,
# models/__init__.ALL_PRESETS).  "moe-tiny" smoke-tests on the virtual CPU
# mesh in seconds; "moe-8x124m" is the GPT-2-124M skeleton with 8 experts
# per block (~0.9B params, top-2 routed — the classic Switch/GShard shape).
MOE_PRESETS = {
    "moe-tiny": MoEConfig(
        block_size=256, vocab_size=512, n_layer=2, n_head=2, n_embd=64,
        n_expert=4, expert_top_k=2, compute_dtype=jnp.float32,
    ),
    "moe-8x124m": MoEConfig(
        n_layer=12, n_head=12, n_embd=768, n_expert=8, expert_top_k=2,
    ),
}


class MoEGPT(GPT2Model):
    """GPT-2 skeleton with MoE MLPs.  Same functional API as GPT2Model."""

    # apply() carries the aux load-balance loss through the scan AND through
    # the GPipe pipeline (spmd_pipeline with_aux: bubble ticks masked)
    pipeline_capable = True
    # apply() below re-implements the layer scan with the aux-loss
    # accumulator in the carry and does not thread the scheduler seam
    # (parallel/schedule.py sched=): the grad slot's bucketed release,
    # the gather slot's prefetched/hpZ scan, and the probe slot's
    # health row all sit out — build_schedule refuses each, naming the
    # slot (ScheduleConflictError for compositions)
    grad_bucket_capable = False
    gather_prefetch_capable = False
    layer_health_capable = False
    # ...nor the serving tier's paged decode: expert dispatch routes a
    # whole batch through static per-expert capacity, which a mixed-
    # position slot batch would skew; serving.ServingEngine refuses it
    paged_decode_capable = False
    # 1F1B (round 3): the aux loss joins as a constant-cotangent second
    # output of the layer slab (pipeline.py with_aux), so MoE runs the
    # O(S)-memory schedule too
    supports_1f1b = True
    # ...but NOT the table schedules (interleaved/zbub): the aux loss
    # would have to ride every F tick and replay in W's re-linearization
    # — build_schedule refuses, naming the pipe slot
    supports_pipe_table = False

    def _block_aux_fn(self, pctx):
        """(x, bp) -> (x, aux) with the remat policy applied — shared by
        the GPipe apply() branch and the 1F1B hook."""

        def block_aux(x, bp):
            return self._block(x, bp, pctx)

        if self.config.remat:
            block_aux = jax.checkpoint(block_aux,
                                       policy=self.remat_policy())
        return block_aux

    def _pipeline_1f1b_block(self, pctx):
        c = self.config
        # apply() adds aux_loss_weight * aux_sum / n_layer (below)
        return self._block_aux_fn(pctx), c.aux_loss_weight / c.n_layer, True

    def __init__(self, config: MoEConfig):
        super().__init__(config)

    # -- params ------------------------------------------------------------

    def init(self, key) -> Dict[str, jax.Array]:
        c = self.config
        d, l, v, t, e = c.n_embd, c.n_layer, c.vocab_size, c.block_size, c.n_expert
        f = c.ff_mult * d
        std = 0.02
        pstd = std / math.sqrt(2 * l)
        keys = iter(jax.random.split(key, 16))

        def nrm(k, shape, s):
            return (jax.random.normal(k, shape, jnp.float32) * s).astype(
                c.param_dtype
            )

        def zeros(shape):
            return jnp.zeros(shape, c.param_dtype)

        params = {
            "wte": nrm(next(keys), (v, d), std),
            "wpe": nrm(next(keys), (t, d), std),
            "h.ln_1.w": jnp.ones((l, d), c.param_dtype),
            "h.ln_1.b": zeros((l, d)),
            "h.attn.qkv.w": nrm(next(keys), (l, d, 3 * d), std),
            "h.attn.qkv.b": zeros((l, 3 * d)),
            "h.attn.proj.w": nrm(next(keys), (l, d, d), pstd),
            "h.attn.proj.b": zeros((l, d)),
            "h.ln_2.w": jnp.ones((l, d), c.param_dtype),
            "h.ln_2.b": zeros((l, d)),
            "h.moe.router.w": nrm(next(keys), (l, d, e), std),
            "h.moe.fc.w": nrm(next(keys), (l, e, d, f), std),
            "h.moe.fc.b": zeros((l, e, f)),
            "h.moe.proj.w": nrm(next(keys), (l, e, f, d), pstd),
            "h.moe.proj.b": zeros((l, e, d)),
            "ln_f.w": jnp.ones((d,), c.param_dtype),
            "ln_f.b": zeros((d,)),
            "lm_head.w": nrm(next(keys), (d, v), std),
        }
        if not c.bias:
            # same scope as GPT2Model: projection biases (attn + experts)
            for name in ("h.attn.qkv.b", "h.attn.proj.b",
                         "h.moe.fc.b", "h.moe.proj.b"):
                del params[name]
        if c.tie_weights:
            del params["lm_head.w"]
        return params

    def tp_rules(self) -> Dict[str, int]:
        return {
            "h.attn.qkv.w": 2,
            "h.attn.qkv.b": 1,
            "h.attn.proj.w": 1,
            "h.moe.fc.w": 3,
            "h.moe.fc.b": 2,
            "h.moe.proj.w": 2,
            "lm_head.w": 1,
        }

    def ep_rules(self) -> Dict[str, int]:
        """{param: dim of the (E,) experts axis} — sharded over "expert"."""
        return {
            "h.moe.fc.w": 1,
            "h.moe.fc.b": 1,
            "h.moe.proj.w": 1,
            "h.moe.proj.b": 1,
        }

    # -- routing -----------------------------------------------------------

    def _route(self, x, router_w, capacity=None):
        """Top-k dispatch/combine tensors.  x: (S, D) float32 router input.

        Returns (dispatch (S,E,C) bool-ish, combine (S,E,C), aux scalar).
        Static capacity C = cf * k * S / E; overflow tokens drop (standard
        GShard semantics — the residual stream still carries them).
        `capacity` overrides the formula (the decode path passes the
        drop-free bound S*k: at one position S is tiny, so the train-time
        formula would collapse to ~1 slot and drop tokens the full-sequence
        path keeps).
        """
        c = self.config
        s = x.shape[0]
        e, k = c.n_expert, c.expert_top_k
        cap = capacity or max(1, int(c.capacity_factor * k * s / e))

        gate_vals, expert_idx, aux = self._router(x, router_w)

        dispatch = jnp.zeros((s, e, cap), jnp.float32)
        combine = jnp.zeros((s, e, cap), jnp.float32)
        counts = jnp.zeros((e,), jnp.float32)  # slots used per expert
        for j in range(k):  # k is tiny + static: unrolled
            m = jax.nn.one_hot(expert_idx[:, j], e, dtype=jnp.float32)
            pos = jnp.cumsum(m, axis=0) - 1 + counts[None]  # (S, E)
            keep = m * (pos < cap)
            slot = jax.nn.one_hot(pos.astype(jnp.int32), cap) * keep[..., None]
            dispatch = dispatch + slot
            combine = combine + gate_vals[:, j, None, None] * slot
            counts = counts + jnp.sum(keep, axis=0)

        return dispatch, combine, aux

    def _router(self, x, router_w):
        """Shared router head: (gate_vals (S,k) renormalized, expert_idx
        (S,k), Switch-Transformer aux scalar E * <frac_tokens_e * prob_e>)."""
        c = self.config
        e, k = c.n_expert, c.expert_top_k
        logits = jnp.einsum(
            "sd,de->se", x, router_w, preferred_element_type=jnp.float32
        )
        probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
        gate_vals, expert_idx = jax.lax.top_k(probs, k)  # (S, k)
        gate_vals = gate_vals / (
            jnp.sum(gate_vals, axis=-1, keepdims=True) + 1e-9
        )
        frac = jnp.mean(
            jax.nn.one_hot(expert_idx[:, 0], e, dtype=jnp.float32), axis=0
        )
        aux = e * jnp.sum(frac * jnp.mean(probs, axis=0))
        return gate_vals, expert_idx, aux

    def _route_sort(self, x, router_w, capacity=None):
        """Sort-based dispatch tables (moe_dispatch="sort").

        Returns (src (E*C,) int32 token index per expert slot — S for an
        empty slot, gate (E*C,) f32 combine weight per slot, aux).  Same
        router head and capacity formula as `_route`; slots fill
        token-major (stable argsort by expert), so under overflow the
        dropped SET can differ from the einsum path's
        first-choices-first fill — outputs are identical whenever
        capacity drops nothing."""
        c = self.config
        s = x.shape[0]
        e, k = c.n_expert, c.expert_top_k
        cap = capacity or max(1, int(c.capacity_factor * k * s / e))
        gate_vals, expert_idx, aux = self._router(x, router_w)

        flat_e = expert_idx.reshape(-1)              # (S*k,) token-major
        order = jnp.argsort(flat_e, stable=True)
        sorted_e = flat_e[order]
        counts = jnp.sum(
            jax.nn.one_hot(flat_e, e, dtype=jnp.int32), axis=0)  # (E,)
        starts = jnp.cumsum(counts) - counts
        pos_in_e = jnp.arange(s * k, dtype=jnp.int32) - starts[sorted_e]
        keep = pos_in_e < cap
        # kept slots are unique; overflow entries all land on dump slot E*C
        slot = jnp.where(keep, sorted_e * cap + pos_in_e, e * cap)
        tok = (order // k).astype(jnp.int32)
        gate = gate_vals.reshape(-1)[order]
        src = jnp.full((e * cap + 1,), s, jnp.int32).at[slot].set(
            jnp.where(keep, tok, s))[: e * cap]
        gate_tab = jnp.zeros((e * cap + 1,), jnp.float32).at[slot].set(
            jnp.where(keep, gate, 0.0))[: e * cap]
        return src, gate_tab, aux

    # -- forward -----------------------------------------------------------

    def _moe_mlp(self, x, bp, pctx=None, capacity=None):
        """x: (B, T, D) -> (B, T, D), plus aux loss."""
        c = self.config
        b, t, d = x.shape
        xs = x.reshape(b * t, d)
        if c.moe_dispatch not in ("einsum", "sort"):
            raise ValueError(
                f"moe_dispatch={c.moe_dispatch!r}: expected 'einsum' or "
                "'sort' (a typo here would silently run the einsum path "
                "while being recorded as a sort A/B)")
        ep = pctx is not None and pctx.expert_parallel
        disp = effective_dispatch(c, pctx)
        if disp == "sort":
            # gather/scatter dispatch: skips the two dense (S,E*C,D)
            # one-hot matmuls (config docstring)
            if pctx is None or not pctx.is_multi_device:
                y, aux = self._moe_mlp_sort(xs, bp, pctx, capacity)
                return y.reshape(b, t, d), aux
            # pure-DP multi-device (round 5): experts are replicated, so
            # each device dispatches its LOCAL token shard with a local
            # argsort (capacity prorated by shard size) — mathematically
            # the same routing, no global sort, no extra communication.
            # The fp8 '#scale' companions MUST cross the shard_map
            # boundary too, or _bw inside the manual region would see no
            # scale and hand the expert einsums raw float8 weights; the
            # _bw sharding constraint itself is skipped in there
            # (pctx=None — the gathers are forced at the boundary).
            from jax.sharding import PartitionSpec as P
            names = [n for base in ("moe.router.w", "moe.fc.w",
                                    "moe.fc.b", "moe.proj.w",
                                    "moe.proj.b")
                     for n in (base, base + "#scale") if n in bp]
            dax = pctx.data_axis
            if capacity is not None:
                # an explicit capacity names a GLOBAL slot budget; applied
                # as-is inside the shard-local sort it would multiply
                # n_shard-fold on a multi-device mesh.  Prorate by the
                # token-shard count (ceil, so tiny decode budgets never
                # hit zero) — same proration the formula-driven default
                # gets for free from the local S
                n_sh = int(pctx.mesh.shape[dax])
                capacity = -(-int(capacity) // n_sh)

            def local(xs_l, *ws):
                y_l, aux_l = self._moe_mlp_sort(
                    xs_l, dict(zip(names, ws)), None, capacity)
                return y_l, jax.lax.pmean(aux_l, dax)

            y, aux = jax.shard_map(
                local, mesh=pctx.mesh,
                in_specs=(P(dax),) + (P(),) * len(names),
                out_specs=(P(dax), P()), check_vma=False,
            )(xs, *[bp[n] for n in names])
            return y.reshape(b, t, d), aux
        dispatch, combine, aux = self._route(
            xs.astype(jnp.float32), bp["moe.router.w"].astype(jnp.float32),
            capacity=capacity,
        )
        dispatch = dispatch.astype(x.dtype)
        # (S,E,C) x (S,D) -> (E,C,D): the all-to-all boundary under EP
        xe = jnp.einsum("sec,sd->ecd", dispatch, xs)
        if ep:
            from jax.sharding import NamedSharding, PartitionSpec as P
            xe = jax.lax.with_sharding_constraint(
                xe, NamedSharding(pctx.mesh, P(pctx.expert_axis, None, None))
            )
        ye = self._expert_ffn(xe, bp, pctx)
        y = jnp.einsum("sec,ecd->sd", combine.astype(x.dtype), ye)
        return y.reshape(b, t, d), aux

    def _expert_ffn(self, xe, bp, pctx=None):
        """(E, C, D) -> (E, C, D): the expert MLP body, shared by both
        dispatch mechanisms (pctx threads the TP placement and the fp8
        gather constraint through _bw for BOTH paths)."""
        h = jnp.einsum("ecd,edf->ecf", xe, self._bw(bp, "moe.fc.w", pctx))
        if "moe.fc.b" in bp:
            h = h + bp["moe.fc.b"][:, None]
        h = jax.nn.gelu(h, approximate=True)
        ye = jnp.einsum("ecf,efd->ecd", h, self._bw(bp, "moe.proj.w", pctx))
        if "moe.proj.b" in bp:
            ye = ye + bp["moe.proj.b"][:, None]
        return ye

    def _moe_mlp_sort(self, xs, bp, pctx=None, capacity=None):
        """moe_dispatch="sort" body on a flat (S, D) token panel: gather
        rows per expert slot, run the same (E, C, D) expert einsums,
        scatter-add weighted outputs.  Returns ((S, D), aux) — S is the
        LOCAL shard when called inside the pure-DP shard_map."""
        c = self.config
        s, d = xs.shape
        e = c.n_expert
        src, gate, aux = self._route_sort(
            xs.astype(jnp.float32), bp["moe.router.w"].astype(jnp.float32),
            capacity=capacity,
        )
        cap = src.shape[0] // e
        xpad = jnp.concatenate([xs, jnp.zeros((1, d), xs.dtype)])
        xe = xpad[src].reshape(e, cap, d)        # empty slots -> zero row
        ye = self._expert_ffn(xe, bp, pctx)
        contrib = gate[:, None].astype(ye.dtype) * ye.reshape(e * cap, d)
        y = jnp.zeros((s + 1, d), ye.dtype).at[src].add(contrib)[:s]
        return y.astype(xs.dtype), aux

    def _block(self, x, bp, pctx=None, return_kv=False):
        """Pre-LN block: attention + MoE MLP.  Returns (x, aux)."""
        c = self.config
        b, t, d = x.shape
        dkey = bp.get("dropout_rng")

        h = layernorm(x, bp["ln_1.w"], bp["ln_1.b"])
        qkv = linear(h, self._bw(bp, "attn.qkv.w", pctx), bp.get("attn.qkv.b"))
        q, k, v = jnp.split(qkv, 3, axis=-1)

        def heads(z):
            return z.reshape(b, t, c.n_head, c.head_dim).swapaxes(1, 2)

        kh, vh = heads(k), heads(v)
        y = sharded_attention(heads(q), kh, vh, c.attn_impl, pctx)
        y = y.swapaxes(1, 2).reshape(b, t, d)
        y = linear(y, self._bw(bp, "attn.proj.w", pctx), bp.get("attn.proj.b"))
        if dkey is not None:
            y = _dropout(y, jax.random.fold_in(dkey, 0), c.dropout)
        x = x + y

        h = layernorm(x, bp["ln_2.w"], bp["ln_2.b"])
        y, aux = self._moe_mlp(h, bp, pctx)
        if dkey is not None:
            y = _dropout(y, jax.random.fold_in(dkey, 1), c.dropout)
        x = x + y
        return ((x, aux), (kh, vh)) if return_kv else (x, aux)

    def _prefill_body(self, x, bp):
        """KV-cache prompt pass: aux loss is a training quantity — dropped
        at inference."""
        (x, _aux), kv = self._block(x, bp, None, return_kv=True)
        return x, kv

    def _block_decode(self, x, bp, ks, vs, l, pos):
        """Cached attention (GPT2Model._attn_decode) + routed experts on
        the single position, with DROP-FREE capacity S*k (the train-time
        cf*k*S/E formula collapses to ~1 slot at S=B and would drop tokens
        the full-sequence path keeps).  NB the uncached path can still drop
        an over-capacity token the decode path keeps — inherent to
        static-capacity GShard routing; equality holds whenever neither
        path overflows."""
        x, ks, vs = self._attn_decode(x, bp, ks, vs, l, pos)
        h = layernorm(x, bp["ln_2.w"], bp["ln_2.b"])
        s = x.shape[0]  # one position: S = B tokens routed together
        y, _aux = self._moe_mlp(
            h, bp, None, capacity=s * self.config.expert_top_k
        )
        return x + y, ks, vs

    def _quant_eligible(self, name, v):
        """Router excluded from the fp8 gather: routing logits need full
        precision for a stable softmax/top-k."""
        return super()._quant_eligible(name, v) and "router" not in name

    def stacked_compute_params(self, params):
        """Like GPT2Model's (incl. the optional fp8 gather), but router
        weights stay float32."""
        out = super().stacked_compute_params(params)
        out["moe.router.w"] = params["h.moe.router.w"]
        return out

    def apply(self, params, idx, targets: Optional[jax.Array] = None,
              pctx=None, position=None, rng=None):
        c = self.config
        x = self.embed(params, idx, pctx)
        stacked = self.stacked_compute_params(params)
        stacked, x = self._dropout_setup(stacked, x, rng)

        if pctx is not None and pctx.pipe_parallel:
            from ..parallel.pipeline import spmd_pipeline

            x, aux_sum = spmd_pipeline(
                self._block_aux_fn(pctx), stacked, x,
                mesh=pctx.mesh, pipe_axis=pctx.pipe_axis,
                data_axis=pctx.data_axis,
                microbatches=pctx.pipe_microbatches or None,
                seq_axis=pctx.seq_axis, with_aux=True,
            )
        else:
            def block(carry, bp):
                x, aux_sum = carry
                x, aux = self._block(x, bp, pctx)
                return (x, aux_sum + aux), None

            if c.remat:
                block = jax.checkpoint(block, policy=self.remat_policy())

            (x, aux_sum), _ = jax.lax.scan(
                block, (x, jnp.zeros((), jnp.float32)), stacked,
                unroll=c.scan_unroll,
            )

        out = self.head(params, x, targets, pctx, position)
        if targets is not None:
            return out + c.aux_loss_weight * aux_sum / c.n_layer
        return out
