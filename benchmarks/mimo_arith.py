"""Operations and bytes a MiMo-V2-Flash serving tick needs, from shapes and
from what the program counted; the readers under metrics/ share them.

The program counts, a decode tick, on its `tds.tick.route` span
(serving/engine.py, models/mimo.MiMoLayout): `active` slots, the rows the
step attended in a global layer (`rows_global`) and in a window layer
(`rows_window`), the blocks its slots hold by kind (`global_blocks`,
`window_blocks`) and, from the decode program itself, the (token, expert)
`pairs` computed here and the held experts that got a token
(`experts_touched`), both summed over the expert layers.  Nothing here
guesses them.  Operations are those the mathematics needs of THIS CHIP'S
SHARE: two per multiply-add, attention over the entries a query really
sees, an expert's three matrices once per pair routed to it.  The trace
reductions are `evabyte_arith`'s (they know no family).
"""

from __future__ import annotations

import functools
from typing import Dict, Optional

import numpy as np

from benchmarks.evabyte_arith import (  # noqa: F401  (readers use them)
    _device_ops, host_span_ids, kernel_seconds, prefill_tokens, trace_path,
)
from benchmarks.reduce import spans
from benchmarks.reduce.intervals import head, self_times
from benchmarks.reduce.xplane import OPS_LINE

KERNEL = "tds_paged_attn"
# the grouped products as the trace names them: XLA lowers
# `jax.lax.ragged_dot` to instructions `%ragged-dot-..` and drops their
# op_name, so no `tds.` scope reaches them and they are found by name
GROUPED = "ragged-dot"
ROUTE_SPAN = "tds.tick.route"
COUNTED = ("active", "rows_global", "rows_window", "global_blocks",
           "window_blocks", "pairs", "experts_touched")


def layers(cfg) -> Dict[str, int]:
    """How many layers of each kind the configuration runs."""
    return {"global": sum(k == 0 for k in cfg.layer_kinds),
            "window": sum(k == 1 for k in cfg.layer_kinds),
            "dense": sum(m == 0 for m in cfg.moe_layers),
            "moe": sum(m == 1 for m in cfg.moe_layers)}


def row_bytes(kind: int, cfg) -> int:
    """K and V of one position in ALL layers of attention kind `kind` (0
    global, 1 window): what one row of that kind of pool block holds, in
    the type the cache rests in."""
    n = layers(cfg)["window" if kind else "global"]
    rests = np.dtype(cfg.cache_dtype or cfg.compute_dtype).itemsize
    return n * cfg.kv_heads_of(kind) * (cfg.head_dim + cfg.v_head_dim) \
        * rests


def expert_params(cfg) -> int:
    """One expert's gate, up and down."""
    return 3 * cfg.n_embd * cfg.moe_hidden


def dense_params(cfg) -> int:
    """Parameters every token is multiplied with, whatever it is routed
    to: attention of every layer, the dense MLPs, the routers, the head
    over the held slice (the embedding is a gather)."""
    d, h = cfg.n_embd, cfg.n_head
    n = layers(cfg)

    def attention(kind):
        kv = cfg.kv_heads_of(kind) * (cfg.head_dim + cfg.v_head_dim)
        return d * (h * cfg.head_dim + kv) + h * cfg.v_head_dim * d

    return (n["global"] * attention(0) + n["window"] * attention(1)
            + n["dense"] * 3 * d * cfg.ffn_hidden
            + n["moe"] * d * cfg.n_routed_experts + d * cfg.vocab_size)


def attention_flops(rows_global: float, rows_window: float, cfg) -> float:
    """q . k and p . v over the attended rows, every query head, the
    layers of each kind."""
    n = layers(cfg)
    per_row = 2.0 * cfg.n_head * (cfg.head_dim + cfg.v_head_dim)
    return per_row * (rows_global * n["global"] + rows_window * n["window"])


def decode_flops(tokens: float, pairs: float, rows_global: float,
                 rows_window: float, cfg) -> float:
    """`tokens` decoded, of which `pairs` (token, expert) products were
    computed here, over the attended pool rows (and each token itself)."""
    return (2.0 * dense_params(cfg) * tokens
            + 2.0 * expert_params(cfg) * pairs
            + attention_flops(rows_global + tokens, rows_window + tokens,
                              cfg))


def expected_pairs(tokens: float, cfg) -> float:
    """Pairs this chip computes for `tokens` under even routing: top-k a
    token an expert layer, the held share of them."""
    return (tokens * cfg.n_experts_per_tok * cfg.experts_held
            / cfg.n_routed_experts * layers(cfg)["moe"])


def prefill_flops(p: int, cfg) -> float:
    """One prompt of p tokens: causal rows in the global layers, at most
    the window's in the window layers; the program does not count a
    prefill's pairs, so the even share stands for them."""
    w = cfg.window
    seen_global = p * (p + 1) / 2
    seen_window = sum(min(n + 1, w) for n in range(min(p, w))) \
        + max(p - w, 0) * w
    return (2.0 * dense_params(cfg) * p
            + 2.0 * expert_params(cfg) * expected_pairs(p, cfg)
            + attention_flops(seen_global, seen_window, cfg))


def attention_bytes(rows_global: float, rows_window: float, cfg) -> float:
    """HBM bytes the decode kernels must move: K and V of the attended
    rows, all layers of each kind."""
    return rows_global * row_bytes(0, cfg) + rows_window * row_bytes(1, cfg)


def experts_bytes(experts_touched: float, cfg) -> float:
    """Weights of the experts that HAD a token, in the type the matrix
    products read them in: an implementation that reads every held expert
    reads lower against this, never over."""
    return (experts_touched * expert_params(cfg)
            * np.dtype(cfg.compute_dtype).itemsize)


def block_mib(kind: int, block_tokens: int, cfg) -> float:
    return block_tokens * row_bytes(kind, cfg) / 2**20


@functools.lru_cache(maxsize=2)
def _named_ops(path: str):
    """[(program, scopes, instruction name, self time ns)] of every
    operation of the first chip (`evabyte_arith._scoped_ops`, with the
    instruction's name kept)."""
    plane = _device_ops(path)
    if plane is None:
        return []
    ops = plane.lines[OPS_LINE]
    modules = sorted(plane.lines.get(spans.MODULES_LINE, ()),
                     key=lambda m: m.start)
    names = spans._own_scopes(ops, plane.meta)
    out, k = [], 0
    for op, own in sorted(self_times(ops), key=lambda p: p[0].start):
        while k < len(modules) and modules[k].end <= op.start:
            k += 1
        inside = k < len(modules) and modules[k].start <= op.start
        out.append((spans.program_of(modules[k].name) if inside else None,
                    spans.scopes_of(names.get(op.mid)),
                    head(op.name).lstrip("%"), own))
    return out


def moe_seconds(path: str, program: str,
                scope: str = "tds.moe") -> Optional[float]:
    """Device time of the expert layer in one program: the operations
    whose op_name holds `scope` and the grouped products, which carry no
    scope (GROUPED); with `scope` "tds.moe.experts" the grouped products
    and their epilogue alone.  None where there are none."""
    return sum(own for prog, scopes, name, own in _named_ops(path)
               if prog == program and (scope in scopes
                                       or name.startswith(GROUPED))
               ) * 1e-9 or None


def tick_counters(path: str) -> Optional[Dict[str, float]]:
    """Sums over the traced ticks of what `tds.tick.route` carries, and
    `ticks`.  None where the program wrote no such span."""
    ids = host_span_ids(path, ROUTE_SPAN)
    if not ids:
        return None
    out = {k: float(sum(int(i.get(k, 0)) for i in ids)) for k in COUNTED}
    out["ticks"] = float(len(ids))
    return out
