"""Operations and bytes an EvaByte serving tick needs, from shapes and from
what the program counted, and the trace reductions its readers share.

EVA attention at decode reads, for a query at position n, the n - w(n) W
live rows of its window and the w(n) W / C visible chunk summaries, K and V,
of every layer: `kernel_bytes`.  The program counts those rows a tick
(`rows` on the `tds.tick.roll` span, serving/engine.py); nothing here
guesses them.  Operations are those the mathematics needs: two per
multiply-add, the matmuls' parameters once a byte, attention over the
entries a query really sees (`attended`), not over what a table holds.
"""

from __future__ import annotations

import functools
from typing import Dict, List, Optional

from benchmarks.reduce import spans
from benchmarks.reduce.intervals import head, self_times
from benchmarks.reduce.xplane import DEVICE_PLANE, OPS_LINE

KERNEL = "tds_eva_paged_attn"
ROLL_SPAN = "tds.tick.roll"
PREFILL_SPAN = "tds.tick.prefill.dispatch"


def matmul_params(cfg) -> int:
    """Parameters multiplied once per byte: q, k, v, o, gate, up, down of
    every block and the 8 heads (the embedding is a gather, norms and the
    pooling vectors are elementwise or Dh wide)."""
    d = cfg.n_embd
    per_block = 4 * d * d + 3 * d * cfg.ffn
    return cfg.n_layer * per_block + d * cfg.num_pred_heads * cfg.vocab_size


def attended(n: int, cfg) -> int:
    """Entries under the softmax of the query at position n: its window up
    to itself, and a summary per chunk of the windows before."""
    w = cfg.window_size
    return n % w + 1 + (n // w) * (w // cfg.chunk_size)


def attention_flops(entries: float, cfg) -> float:
    """q . k and p . v over `entries` keys, all heads and layers."""
    return 4.0 * entries * cfg.n_embd * cfg.n_layer


def prefill_flops(p: int, cfg) -> float:
    """One prompt of p bytes: the matmuls, attention over what each
    position sees, and the pooling of p / C chunks (2 * 2 * C * Dh a head
    for the two weighted sums, the two logit products as much again)."""
    w, per = cfg.window_size, cfg.window_size // cfg.chunk_size
    full, rest = divmod(p, w)
    entries = (full * (w * (w + 1) // 2) + rest * (rest + 1) // 2
               + per * w * (full * (full - 1) // 2) + per * full * rest)
    pooling = 8.0 * p * cfg.n_embd * cfg.n_layer
    return 2.0 * matmul_params(cfg) * p + attention_flops(entries, cfg) \
        + pooling


def decode_flops(tokens: int, rows: int, cfg) -> float:
    """`tokens` decoded bytes that attended `rows` pool rows in all (and
    themselves)."""
    return (2.0 * matmul_params(cfg) * tokens
            + attention_flops(rows + tokens, cfg))


def kernel_bytes(rows: int, slots: int, cfg, itemsize: int = 2) -> float:
    """HBM bytes the decode kernel must move for `rows` attended pool rows
    over `slots` queries: K and V rows of every layer, the queries in and
    the results out (the block-diagonal query rows the kernel is handed
    are its own device: not counted)."""
    row = cfg.n_embd * cfg.n_layer * itemsize
    return 2.0 * rows * row + 4.0 * slots * row


# -- what the trace holds -----------------------------------------------------

@functools.lru_cache(maxsize=2)
def _planes(path: str):
    return spans.read_xspace(path)


def trace_path(ctx) -> Optional[str]:
    """The run's trace file (None in an untraced run).  The host's spans
    are in it on any backend; a reader of device time asks `ctx.trace` as
    well, which is None where the file holds no TPU plane."""
    return getattr(ctx.env.tracer, "path", None)


def _device_ops(path: str):
    devices = [p for p in _planes(path)
               if DEVICE_PLANE.match(p.name) and p.lines.get(OPS_LINE)]
    return devices[0] if devices else None


def kernel_seconds(path: str, name: str = KERNEL) -> Optional[float]:
    """Device time of the operations named `%<name>..`, first chip; None
    where the trace holds none."""
    plane = _device_ops(path)
    if plane is None:
        return None
    total = sum(own for op, own in self_times(plane.lines[OPS_LINE])
                if head(op.name).lstrip("%").startswith(name))
    return total * 1e-9 or None


@functools.lru_cache(maxsize=2)
def _scoped_ops(path: str):
    """[(program, scopes, self time ns)] of every operation of the first
    chip: the program whose run covers it, the `tds.` scopes of its
    op_name (own or borrowed from the operation it feeds)."""
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
                    spans.scopes_of(names.get(op.mid)), own))
    return out


def scope_seconds(path: str, program: str, scope: str) -> Optional[float]:
    """Self time of the operations of one program (`jit_tds_decode`) whose
    op_name holds the scope, first chip; None where there are none."""
    return sum(own for prog, scopes, own in _scoped_ops(path)
               if prog == program and scope in scopes) * 1e-9 or None


def host_span_ids(path: str, name: str) -> List[Dict[str, object]]:
    """The ids of every host span of that name, in order."""
    found = sorted((e for p in _planes(path) if p.name.startswith("/host:")
                    for evs in p.lines.values() for e in evs
                    if e.name == name), key=lambda e: e.start)
    return [dict(e.stats) for e in found]


def tick_counters(path: str) -> Optional[Dict[str, float]]:
    """Sums over the traced ticks of what `tds.tick.roll` carries: ticks,
    active slots, attended rows, window and summary blocks, windows
    rolled.  None where the program wrote no such span."""
    ids = host_span_ids(path, ROLL_SPAN)
    if not ids:
        return None
    keys = ("active", "rows", "window_blocks", "summary_blocks",
            "windows_rolled")
    out = {k: float(sum(int(i.get(k, 0)) for i in ids)) for k in keys}
    out["ticks"] = float(len(ids))
    return out


def prefill_tokens(path: str) -> List[int]:
    """The true prompt length of every prefill dispatched in the trace."""
    return [int(i["tokens"]) for i in host_span_ids(path, PREFILL_SPAN)
            if "tokens" in i]
