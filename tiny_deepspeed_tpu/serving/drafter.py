# Copyright 2026 tiny-deepspeed-tpu authors
# SPDX-License-Identifier: Apache-2.0

"""Draft-token proposers for speculative decoding (serving/spec.py).

Two drafters behind ONE interface — `propose(slots) -> (S, K+1) int32`
proposals per decode slot (K verifiable drafts + the bonus position's
proposal, autoregressively consistent: proposal j conditions on
proposals 1..j-1, so any position's proposal is a pure function of the
prefix — the acceptance core's determinism guarantee needs exactly
that), plus an `on_admit` hook fired at every
(re)admission so a drafter with state can rebuild it from the committed
prefix (which is also what makes drafter state compose with preemption,
warm restart, and journal recovery: admission is the ONE path every
resume rides).  `on_admit` returns the drafter's proposal for the first
post-prefix position — the spec prefill commits its token through the
same accept-or-residual rule the verify core uses, so a position's
sampling path never depends on which program reached it first:

  * `NgramDrafter` ("ngram") — model-free prompt-lookup (PLD): propose
    the continuation of the most recent earlier occurrence of the
    context's own suffix n-gram.  Deterministic, zero weights, zero
    device work — the right drafter when outputs echo their context
    (templates, code, the repetition loops untrained models fall into),
    and the cheap default on the CPU tier-1 mesh.

  * `ModelDrafter` ("model:<preset>" / "model:self") — a small
    same-family autoregressive model with its OWN cache: a statically-
    tabled paged pool (slot s permanently owns blocks [1+s*W, (s+1)*W]
    — contiguous per slot, no allocation churn ever) written through
    the same `paged_prefill`/`paged_decode` machinery the target uses.
    Each tick one compiled (K+1)-step greedy rollout proposes for
    every slot at once; the rollout's first step embeds the tick's
    actual committed head token, which simultaneously absorbs the
    previous tick's correction and overwrites any rejected-draft K/V
    at that position — no separate catch-up pass.

Both drafters propose DETERMINISTICALLY (greedy argmax / lookup), i.e.
a point-mass proposal distribution: the acceptance core
(models/sampling.spec_accept_per_slot) stays target-exact with q = 1,
and a request's proposals are a pure function of its committed prefix —
which is exactly what the serving determinism guarantee needs across
preemption/restart/recovery.
"""

from __future__ import annotations

from typing import List

import numpy as np

from .pool import SCRATCH_BLOCK, PagedKVPool, PageRef


class NgramDrafter:
    """Prompt-lookup decoding: match the context's trailing n-gram
    (longest first, `max_n` down to `min_n`) against the most recent
    earlier occurrence in the context itself, and propose the K tokens
    that followed it.  No match (or a short continuation) pads by
    repeating the last proposed/context token — the verify step rejects
    bad guesses for free, so padding costs nothing but wasted verify
    width."""

    def __init__(self, k: int, max_n: int = 3, min_n: int = 1):
        if k < 1:
            raise ValueError("drafter k must be >= 1")
        if not 1 <= min_n <= max_n:
            raise ValueError("need 1 <= min_n <= max_n")
        self.k = int(k)
        self.max_n = int(max_n)
        self.min_n = int(min_n)

    def describe(self) -> str:
        return f"ngram(n<={self.max_n})"

    def on_admit(self, slot_i: int, prompt_now: List[int]) -> int:
        # stateless beyond the context itself; the return value is the
        # drafter's proposal for the FIRST post-prefix position (the
        # prefill program's accept-or-residual operand) — the same
        # lookup `propose_one` would make there
        t = self._lookup_next(prompt_now, self.max_n, self.min_n)
        return int(t if t is not None else
                   (prompt_now[-1] if prompt_now else 0))

    @staticmethod
    def _lookup_next(ctx: List[int], max_n: int, min_n: int):
        """The single next token after the most recent earlier
        occurrence of ctx's trailing n-gram (longest n first), or None
        when nothing matches."""
        n_ctx = len(ctx)
        for n in range(min(max_n, n_ctx - 1), min_n - 1, -1):
            pat = ctx[-n:]
            # most recent occurrence ENDING before the final position,
            # so a continuation token exists
            for start in range(n_ctx - n - 1, -1, -1):
                if ctx[start:start + n] == pat:
                    return ctx[start + n]
        return None

    def propose_one(self, ctx: List[int]) -> List[int]:
        """K+1 proposed continuation tokens, AUTOREGRESSIVELY
        consistent: proposal j re-runs the lookup on ctx extended by
        proposals 1..j-1, so the proposal for any position is a pure
        function of the (hypothetically committed) prefix at that
        position — the property the acceptance core's determinism
        guarantee rests on (a span-START-only lookup would make
        proposals depend on where the scheduler's spans happen to
        align, which shifts across preemption/restart replays)."""
        ext = list(ctx)
        out: List[int] = []
        for _ in range(self.k + 1):
            t = self._lookup_next(ext, self.max_n, self.min_n)
            if t is None:
                t = ext[-1] if ext else 0  # pad: verify rejects free
            out.append(t)
            ext.append(t)
        return out

    def propose(self, slots) -> np.ndarray:
        """(S, K+1) proposals — K verifiable drafts plus the bonus
        position's proposal: row i continues slot i's committed context
        (prompt + produced tokens); empty slots propose zeros (their
        verify lanes compute on scratch and commit nothing)."""
        drafts = np.zeros((len(slots), self.k + 1), np.int32)
        for i, s in enumerate(slots):
            if s is None:
                continue
            drafts[i] = self.propose_one(s.req.prompt + s.req.tokens)
        return drafts


class ModelDrafter:
    """Small-model drafter over its own statically-tabled paged cache.

    The drafter cache's invariant mirrors the scheduler's: after a
    tick committing `a` drafts + one resampled token, the cache holds
    the drafter's K/V for every COMMITTED position (accepted drafts'
    rollout writes ARE that K/V; the resampled token is absorbed by the
    next rollout's first step, overwriting the rejected draft's stale
    entry at its position).  (Re)admission prefills the slot's region
    from prompt + produced, so preemption/restart/recovery resume from
    the same state an uninterrupted run would hold."""

    def __init__(self, model, params, k: int, *, max_active: int,
                 max_seq: int, block_tokens: int):
        if k < 1:
            raise ValueError("drafter k must be >= 1")
        if not getattr(model, "paged_decode_capable", False):
            raise ValueError(
                f"draft model {type(model).__name__} is not paged-decode "
                "capable (paged_decode_capable=False)"
            )
        import jax

        from ..models.gpt2 import resolved_cache_dtype
        c = model.config
        if c.block_size < max_seq:
            raise ValueError(
                f"draft model context block_size={c.block_size} is "
                f"smaller than the engine's max_seq_tokens={max_seq} — "
                "the drafter must be able to prefill any committed "
                "prefix the engine can hold (a longer prefix would "
                "crash at (re)admission); serve with max_seq_tokens <= "
                "the draft context or pick a longer-context drafter"
            )
        self.model = model
        self.params = params
        self.k = int(k)
        self._bt = int(block_tokens)
        self.max_seq = min(int(max_seq), c.block_size)
        self._W = -(-self.max_seq // self._bt)
        kv_heads = getattr(c, "kv_heads", c.n_head)
        self.pool = PagedKVPool.dense(
            n_layer=c.n_layer, kv_heads=kv_heads, head_dim=c.head_dim,
            num_blocks=max_active * self._W, block_tokens=self._bt,
            dtype=resolved_cache_dtype(c),
        )
        w = self._W
        self._tables = np.asarray(
            [[1 + s * w + j for j in range(w)] for s in range(max_active)],
            np.int32,
        )
        self._stacked = jax.jit(model.stacked_compute_params)(params)
        self._rollout = jax.jit(self._rollout_impl, donate_argnums=(2,))

        def _prefill(params, stacked, idx, last_pos, block_ids, view):
            return model.paged_prefill(
                params, idx, last_pos, block_ids, view, self._bt,
                stacked=stacked,
            )

        self._prefill = jax.jit(_prefill, donate_argnums=(5,))

    def describe(self) -> str:
        c = self.model.config
        return f"model({c.n_layer}L{c.n_embd}D)"

    def _rollout_impl(self, params, stacked, view, tok, pos):
        """K+1 greedy decode steps for every slot at once: (S,) head
        tokens at (S,) head positions -> ((S, K+1) proposals, new
        view) — K verifiable drafts plus the bonus position's proposal,
        autoregressively consistent by construction (each step
        conditions on the previous proposals through the cache).
        Positions at/past the cache horizon route their writes to
        scratch and clamp their reads — a slot near its length limit
        proposes garbage the verify step simply rejects."""
        import jax
        import jax.numpy as jnp

        tables = jnp.asarray(self._tables)
        bt, w, ms = self._bt, self._W, self.max_seq

        def step(carry, _):
            tok, pos, view = carry
            safe = jnp.minimum(pos, ms - 1)
            x = self.model._embed_decode(params, tok, safe)
            j = jnp.minimum(pos // bt, w - 1)
            blk = jnp.take_along_axis(tables, j[:, None], axis=1)[:, 0]
            blk = jnp.where(pos < ms, blk, SCRATCH_BLOCK)
            page = PageRef(tables, blk, off=pos % bt, pos=safe)
            x, view = self.model.paged_decode(stacked, x, view, page)
            logits = self.model.head(params, x)[:, 0]
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return (nxt, pos + 1, view), nxt

        (_, _, view), toks = jax.lax.scan(
            step, (tok, pos, view), None, length=self.k + 1)
        return jnp.swapaxes(toks, 0, 1), view

    def _bucket(self, p: int) -> int:
        """Prefill pad length (same power-of-two-blocks rule as the
        engine, so drafter prefill shapes stay O(log T) too)."""
        nb = -(-p // self._bt)
        b = 1
        while b < nb:
            b *= 2
        return min(b * self._bt, self.model.config.block_size)

    def on_admit(self, slot_i: int, prompt_now: List[int]) -> int:
        """(Re)build slot_i's drafter cache from the committed prefix;
        returns the draft model's greedy proposal for the first
        post-prefix position (argmax of its own prefill logits — the
        same token its rollout would propose there), which the engine's
        spec prefill consumes as the accept-or-residual operand."""
        p = len(prompt_now)
        bucket = self._bucket(p)
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :p] = prompt_now
        block_ids = np.full((bucket // self._bt,), SCRATCH_BLOCK, np.int32)
        n = min(len(block_ids), self._W)
        block_ids[:n] = self._tables[slot_i, :n]
        logits, view = self._prefill(
            self.params, self._stacked, padded, p - 1, block_ids,
            self.pool.view,
        )
        self.pool.view = view
        return int(np.argmax(np.asarray(logits)[0]))

    def propose(self, slots) -> np.ndarray:
        s_count = len(slots)
        tok = np.zeros((s_count,), np.int32)
        # empty slots park at the horizon: scratch writes, clamped reads
        pos = np.full((s_count,), self.max_seq, np.int32)
        for i, s in enumerate(slots):
            if s is None:
                continue
            tok[i] = s.last
            pos[i] = s.pos
        drafts, view = self._rollout(
            self.params, self._stacked, self.pool.view, tok, pos)
        self.pool.view = view
        return np.asarray(drafts)


def make_drafter(spec: str, model, params, k: int, *, max_active: int,
                 max_seq: int, block_tokens: int, seed: int = 0):
    """Drafter factory for the `spec_draft` knob:

      * "ngram"          -> NgramDrafter (model-free prompt lookup)
      * "model:self"     -> ModelDrafter over the TARGET model/params
                            (a perfect-acceptance reference: every
                            rollout step costs a full target pass, so
                            it never wins throughput — tests and
                            acceptance-rate ceilings use it)
      * "model:<preset>" -> ModelDrafter over a fresh-initialized
                            preset (models.ALL_PRESETS) sharing the
                            target's vocab.  NOTE: random-init weights
                            exercise the machinery; a THROUGHPUT win
                            needs a trained drafter that actually
                            predicts the target.
    """
    if spec == "ngram":
        return NgramDrafter(k)
    if spec.startswith("model:"):
        name = spec[len("model:"):]
        if name == "self":
            dmodel, dparams = model, params
        else:
            import jax

            from ..models import ALL_PRESETS, build_model
            if name not in ALL_PRESETS:
                raise ValueError(
                    f"unknown draft preset {name!r}; spec_draft takes "
                    f"'ngram', 'model:self', or 'model:<preset>' with a "
                    f"preset in {sorted(ALL_PRESETS)}"
                )
            dmodel = build_model(name)
            if dmodel.config.vocab_size != model.config.vocab_size:
                raise ValueError(
                    f"draft preset {name!r} has vocab_size "
                    f"{dmodel.config.vocab_size} but the target serves "
                    f"{model.config.vocab_size} — drafts are token ids, "
                    "the vocabularies must match"
                )
            dparams = dmodel.init(jax.random.PRNGKey(seed))
        return ModelDrafter(dmodel, dparams, k, max_active=max_active,
                            max_seq=max_seq, block_tokens=block_tokens)
    raise ValueError(
        f"spec_draft {spec!r} not understood: use 'ngram', "
        "'model:self', or 'model:<preset>'"
    )
