# Copyright 2026 tiny-deepspeed-tpu authors
# SPDX-License-Identifier: Apache-2.0

"""Speculative decoding over the continuous-batching scheduler.

Plain serving decode commits exactly ONE token per request per tick —
each token pays a full target-model pass, and on small decode batches
the chips idle on memory-bound work.  Speculative decoding (Leviathan
et al., arXiv:2211.17192) converts that idle into parallel
verification: a cheap DRAFTER proposes up to K continuation tokens per
slot (serving/drafter.py — model-free prompt-lookup, or a small
same-family model), and ONE target pass scores all K+1 span positions
per slot at once.  The acceptance core keeps the target distribution
exact (greedy short-circuits to token equality, so greedy speculative
output is bit-identical to `generate`); each verify commits between 1
and K+1 tokens.

The verify program is ONE shape-stable jit, the spec analogue of the
engine's decode step — same (S,) slot-array discipline, same block
tables, same per-slot (seed, position) sampling keys:

  * span embeddings at vector per-(slot, offset) positions;
  * `paged_verify` reads the COMMITTED prefix through the block tables
    (read-only pool view) while the span attends to itself under a
    windowed causal mask — draft K/V never touch the pool during
    scoring;
  * acceptance (models/sampling.spec_accept_per_slot) runs in-program,
    and `pool.paged_append_span` commits exactly the accepted prefix's
    K/V in the same program — rejected-draft K/V route to the scratch
    block, so nothing speculative ever rests in the pool;
  * the per-slot non-finite health flag covers the WHOLE span (the
    decode-health guard quarantines a poisoned slot exactly as on the
    plain path).

The engine (`ServingEngine._decode_spec`) owns scheduling around it:
block growth covers the span horizon, committed tokens journal, and
the SLO shed price re-bases on wall per COMMITTED token.
"""

from __future__ import annotations

import numpy as np

from .drafter import make_drafter
from .pool import page_ref, paged_append_span

# hard ceiling on the draft span: k+1 verify positions multiply decode
# FLOPs and the span must stay well under a pool block in practice
MAX_SPEC_K = 16


class SpecDecoder:
    """One engine's speculative-decoding state: the drafter and the
    compiled verify program.  Stateless across ticks beyond the
    drafter's own cache — everything positional comes from the engine's
    slots each call, which is what keeps preemption/restart/recovery
    composition free."""

    def __init__(self, model, params, config, base_key, *,
                 max_seq: int):
        import jax
        import jax.numpy as jnp

        from ..models.sampling import spec_accept_per_slot

        k = int(config.spec_k)
        if not 1 <= k <= MAX_SPEC_K:
            raise ValueError(
                f"spec_k={config.spec_k} out of range [1, {MAX_SPEC_K}]"
            )
        self.k = k
        self.drafter = make_drafter(
            config.spec_draft, model, params, k,
            max_active=config.max_active, max_seq=max_seq,
            block_tokens=config.block_tokens, seed=config.seed,
        )
        k1 = k + 1
        bt = config.block_tokens
        temp, top_k = config.temperature, config.top_k
        block_size = model.config.block_size

        def tds_verify(params, stacked, view, spanx, pos0, tables,
                       seeds, nprod, limit_kv, poison):
            """spanx (S, K1+1) = [committed head, d_1..d_K, extra] —
            the scored span is the first K1 columns; the trailing
            `extra` is the drafter's bonus-position proposal, consumed
            only by the acceptance rule.  pos0 (S,) is the head's
            position; limit_kv (S,) the last position whose K/V the
            request will ever need (total-2; -1 for empty slots).
            Returns (accepted drafts (S,), final token (S,), bad (S,),
            view with the accepted prefix's K/V committed)."""
            span = spanx[:, :k1]
            extra = spanx[:, k1]
            positions = jnp.minimum(
                pos0[:, None] + jnp.arange(k1)[None, :], block_size - 1)
            x = model._embed_decode_span(params, span, positions)
            page = page_ref(tables, pos0, bt)
            x, sks, svs = model.paged_verify(stacked, x, view, page)
            logits = model.head_span(params, x) + poison[:, None, None]
            bad = ~jnp.all(jnp.isfinite(logits), axis=(1, 2))
            acc, final = spec_accept_per_slot(
                logits, span, extra, base_key, seeds, nprod, temp,
                top_k)
            # K/V commit count: the accepted prefix (head + acc drafts),
            # clamped to the request's K/V horizon — the final sampled
            # token's K/V is next tick's head write, never this one's
            count = jnp.clip(
                acc + 1, 0, jnp.maximum(limit_kv + 1 - pos0, 0))
            view = paged_append_span(view, sks, svs, tables, pos0,
                                     count, bt)
            return acc, final, bad, view

        # NOTE: a forced ServeConfig.paged_kernel mode is applied by the
        # ENGINE, which wraps this program (and a model drafter's) with
        # the same _kwrap bracketing as its own decode/prefill jits —
        # one copy of the discipline, in one place (engine.__init__)
        self._verify = jax.jit(tds_verify, donate_argnums=(2,))

    def describe(self) -> str:
        return f"spec(k={self.k}, drafter={self.drafter.describe()})"

    def propose(self, slots) -> np.ndarray:
        """(S, K+1) draft proposals for the engine's slot array: K
        verifiable drafts + the bonus position's proposal."""
        return self.drafter.propose(slots)

    def on_admit(self, slot_i: int, prompt_now) -> int:
        """Rebuild the drafter's slot state; returns the drafter's
        proposal for the first post-prefix position (the spec prefill's
        accept-or-residual operand)."""
        return self.drafter.on_admit(slot_i, prompt_now)

    def verify(self, params, stacked, view, span, pos0, tables, seeds,
               nprod, limit_kv, poison):
        return self._verify(params, stacked, view, span, pos0, tables,
                            seeds, nprod, limit_kv, poison)
