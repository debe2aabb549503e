# Copyright 2026 tiny-deepspeed-tpu authors
# SPDX-License-Identifier: Apache-2.0

"""Compute/HBM cost ledger from compiled HLO: the roofline's other two axes.

`hlo_comm.collective_ledger` prices the WIRE axis of a compiled step from
the post-SPMD HLO text.  Compute, until now, was a hand formula
(6 x the matmul parameters a token) and HBM traffic was not measured at
all — so "MFU" compared a measured time against an analytic numerator,
and nothing could say whether a program is compute-, HBM-, or wire-bound.

This module closes the loop with the same machinery: split the HLO into
computations, multiply while bodies by their static trip counts, and walk
the call graph from the entry — but ledger FLOPs and HBM bytes instead of
collective payloads.

FLOPs
  dot:  2 * prod(result dims) * prod(lhs contracting dim sizes) — the
        contracting-dim product is read off `lhs_contracting_dims={...}`
        against the lhs operand's shape, so batched attention dots
        (lhs_batch_dims) come out right without special-casing.  XLA
        prints operands by NAME (`dot(%a.1, %b.1)`; older builds inlined
        `f32[4,5] %a`), so each operand resolves to the shape of its
        defining instruction in the same computation.  An operand that
        resolves to nothing raises: a ledger that silently counted 0
        FLOPs read as a 0 MFU on every run.
  convolution:  2 * prod(result dims) * (rhs elems / out_channels) with
        out_channels inferred as the largest dim shared by rhs and result
        — an approximation (no conv in this repo today); such lines are
        flagged in `approx_ops` so a future conv user sees the caveat.
  Dots inside fusion payload computations are reached through the fusion
  call edge and attributed to the fusion's calling computation — on TPU
  the backend moves dots into fusions and a top-level-only scan would
  count zero FLOPs.

HBM bytes (a traffic model, not a profile)
  Per instruction: operand bytes + result bytes, i.e. every kernel reads
  its inputs from HBM and writes its output.  Bookkeeping ops that move
  no data (parameter, constant, tuple, get-tuple-element, bitcast) and
  container ops whose bodies are walked separately (while, conditional,
  call) are skipped.  A fusion LINE is counted — its operands + result
  are exactly the fused kernel's HBM traffic — and its payload
  computation is then excluded from HBM accounting (the intermediates
  live in registers/VMEM; counting them would price fusion at zero).
  `dynamic-update-slice` roots (including `*dynamic-update-slice*`
  fusions) alias their destination: only the updated slice is read into
  and written back, so the destination operand is dropped and the update
  operand counted twice (read + write).  Without this, the 1024-trip
  embedding-scatter loops in the 124M step would charge ~150 MB of
  fictitious accumulator traffic per trip.

Everything is loop-aware: while bodies multiply by `_trip_count` trips
(the 12-layer scan, the seq-length scatter loops), with an in-loop vs
top-level split mirroring the wire ledger, and a per-loop attribution
list (`loops`).

tests/test_hlo_cost.py pins the dot math exactly on tiny synthetic HLO,
pins trip-count multiplication against the scan length, and pins the
124M GPT-2 train step within 2% of bench's analytic matmul formula.
"""

from __future__ import annotations

import re
from typing import Dict, List, Optional, Tuple

from .hlo_comm import (
    _BRANCH_RE,
    _CALL_RE,
    _FUSION_CALL_RE,
    _SHAPE_RE,
    _TRUE_FALSE_RE,
    _WHILE_RE,
    _shape_bytes,
    _split_computations,
    _trip_count,
    collective_ledger,
)

# ---------------------------------------------------------------------------
# Per-device roofline tables (public spec-sheet numbers), keyed by a
# substring of `jax.devices()[0].device_kind` ("TPU v5 lite" on a v5e).
#
# Peak dense bf16 FLOP/s per chip: one table, so the MFU denominator and
# the roofline verdict cannot drift.
# HBM and interchip (ICI) bandwidths are per chip:
#   HBM    v4 1228 GB/s · v5e 819 GB/s · v5p 2765 GB/s · v6e 1640 GB/s
#   ICI    v4 300 GB/s  · v5e 200 GB/s · v5p 600 GB/s  · v6e 448 GB/s
# A device that is not in the table (the CPU mesh) has NO peak: the
# lookups return None, and everything priced against a peak (mfu_hlo,
# step_mfu_hlo, the roofline bound) is not emitted for it.
# ---------------------------------------------------------------------------

_PEAK_FLOPS_TABLE: Tuple[Tuple[str, float], ...] = (
    ("v5 lite", 197e12), ("v5e", 197e12), ("v5p", 459e12),
    ("v6", 918e12), ("v4", 275e12),
)

_HBM_BW_TABLE: Tuple[Tuple[str, float], ...] = (
    ("v5 lite", 819e9), ("v5e", 819e9), ("v5p", 2765e9),
    ("v6", 1640e9), ("v4", 1228e9),
)

_WIRE_BW_TABLE: Tuple[Tuple[str, float], ...] = (
    ("v5 lite", 200e9), ("v5e", 200e9), ("v5p", 600e9),
    ("v6", 448e9), ("v4", 300e9),
)


def _lookup(table: Tuple[Tuple[str, float], ...],
            device_kind: Optional[str]) -> Optional[float]:
    kind = (device_kind or "").lower()
    for key, val in table:
        if key in kind:
            return val
    return None


def peak_flops_per_chip(device_kind: Optional[str]) -> Optional[float]:
    """Peak dense bf16 FLOP/s for a device-kind string (substring match);
    None for a device the table does not know."""
    return _lookup(_PEAK_FLOPS_TABLE, device_kind)


def hbm_bw_per_chip(device_kind: Optional[str]) -> Optional[float]:
    """HBM bandwidth (bytes/s) for a device-kind string, or None."""
    return _lookup(_HBM_BW_TABLE, device_kind)


def wire_bw_per_chip(device_kind: Optional[str]) -> Optional[float]:
    """Interchip (ICI) bandwidth (bytes/s) for a device-kind string, or
    None."""
    return _lookup(_WIRE_BW_TABLE, device_kind)


# ---------------------------------------------------------------------------
# Line parsing
# ---------------------------------------------------------------------------

# "%name = <result type> opcode(" — tuple-typed results "(s32[], ...)" are
# a parenthesized group, plain results a non-space token
_DEF_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w\.\-]+)\s*=\s*(\([^=]*?\)|\S+)\s+([\w\-]+)\(")
_LHS_CONTRACT_RE = re.compile(r"lhs_contracting_dims=\{([\d,]*)\}")
_COMMENT_RE = re.compile(r"/\*.*?\*/")

# ops that move no HBM data of their own
_HBM_SKIP_OPS = frozenset({
    "parameter", "constant", "tuple", "get-tuple-element", "bitcast",
    "after-all", "partition-id", "replica-id", "opt-barrier",
})
# container ops whose bodies are walked separately
_HBM_CONTAINER_OPS = frozenset({"while", "conditional", "call"})


_OP_NAME_RE = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')


def scope_map(hlo_text: str) -> Dict[str, str]:
    """{instruction name: op_name} over every computation of a compiled
    module's text: the `metadata={op_name="jit(tds_train_step)/..."}` the
    parsers below strip is where jax.named_scope (`tds.*`) and the
    transform (`jvp`, `transpose`, `checkpoint`) of each instruction are
    written.  An instruction without one is left out.  The v5e's device
    trace carries the same string as `tf_op` on each operation
    (benchmarks/reduce/spans.py reads it there); this is the same join on
    a program that has not run."""
    out: Dict[str, str] = {}
    for line in hlo_text.splitlines():
        d = _DEF_RE.match(line)
        if d is None:
            continue
        m = _OP_NAME_RE.search(line)
        if m is not None:
            out[d.group(1)] = m.group(1)
    return out


def _strip_metadata(line: str) -> str:
    """Drop `metadata={...}` and what follows it — op_name strings may
    contain shape-like text that would be mis-summed as payload."""
    i = line.find(", metadata=")
    return line[:i] if i >= 0 else line


def _operand_types(line: str, op: str, defs: Dict[str, str]) -> List[str]:
    """Type text of each operand of `op(...)` on an instruction line, in
    order.  An operand printed with an inline type ("f32[4,5] %a") keeps
    it; one printed by name ("%a.1") takes the type of its defining
    instruction in the same computation (`defs`).  Raises ValueError on
    an operand with neither."""
    i = line.index(f" {op}(") + len(op) + 2
    toks: List[str] = []
    depth, start = 0, i
    for j in range(i, len(line)):
        ch = line[j]
        if ch in "([{":
            depth += 1
        elif ch in ")]}":
            if depth == 0:
                toks.append(line[start:j])
                break
            depth -= 1
        elif ch == "," and depth == 0:
            toks.append(line[start:j])
            start = j + 1
    else:
        raise ValueError(f"unterminated operand list: {line.strip()[:160]}")
    out: List[str] = []
    for tok in toks:
        tok = tok.strip()
        if not tok:
            continue
        if "[" in tok:
            out.append(tok.rsplit("%", 1)[0] if "%" in tok else tok)
            continue
        name = tok.lstrip("%")
        if name not in defs:
            raise ValueError(
                f"operand {tok!r} of {op} has no inline shape and no "
                f"defining instruction in its computation: "
                f"{line.strip()[:160]}")
        out.append(defs[name])
    return out


def _dims(type_txt: str, line: str) -> Tuple[str, List[int]]:
    """(dtype, dims) of an array type text."""
    m = _SHAPE_RE.search(type_txt)
    if m is None:
        raise ValueError(f"no array shape in {type_txt!r}: "
                         f"{line.strip()[:160]}")
    return m.group(1), [int(d) for d in m.group(2).split(",") if d]


def _dot_flops(line: str, res_type: str,
               defs: Dict[str, str]) -> Tuple[float, str]:
    """(FLOPs, signature) of one `dot` instruction line.

    FLOPs = 2 * prod(result dims) * prod(lhs contracting dim sizes).
    Batch dims are already part of the result, so no special handling.
    """
    ops = _operand_types(line, "dot", defs)
    res_dt, res_dims = _dims(res_type, line)
    lhs_dt, lhs_dims = _dims(ops[0], line)
    cm = _LHS_CONTRACT_RE.search(line)
    contract = [int(d) for d in cm.group(1).split(",") if d] if cm else []
    k = 1
    for c in contract:
        k *= lhs_dims[c]
    n = 1
    for d in res_dims:
        n *= d
    # signature: result <- lhs x rhs, for cost-center aggregation
    rhs_dt, rhs_dims = _dims(ops[1], line)

    def fmt(dt, dims):
        return "%s[%s]" % (dt, ",".join(map(str, dims)))

    sig = "dot %s <- %s x %s" % (
        fmt(res_dt, res_dims), fmt(lhs_dt, lhs_dims), fmt(rhs_dt, rhs_dims))
    return 2.0 * n * k, sig


def _conv_flops(line: str, res_type: str,
                defs: Dict[str, str]) -> Tuple[float, str]:
    """Approximate convolution FLOPs: 2 * out_elems * rhs_elems /
    out_channels, with out_channels = the largest dim shared by rhs and
    result.  Flagged via `approx_ops` — this repo emits no convolutions."""
    ops = _operand_types(line, "convolution", defs)
    res_dt, res_dims = _dims(res_type, line)
    _, rhs_dims = _dims(ops[1], line)
    shared = [d for d in rhs_dims if d in res_dims]
    out_ch = max(shared) if shared else 1
    n = 1
    for d in res_dims:
        n *= d
    k = 1
    for d in rhs_dims:
        k *= d
    sig = "convolution %s[%s]" % (res_dt, ",".join(map(str, res_dims)))
    return 2.0 * n * (k / max(out_ch, 1)), sig


def _hbm_bytes_of_line(line: str, name: str, op: str, res_type: str,
                       defs: Dict[str, str]) -> float:
    """HBM traffic model for one instruction: operands + result, with the
    dynamic-update-slice aliasing special case (see module docstring)."""
    result = _shape_bytes(res_type)
    operands = [_shape_bytes(t) for t in _operand_types(line, op, defs)]
    if op == "dynamic-update-slice" or "dynamic-update-slice" in name:
        # the destination operand aliases the result — drop both, count
        # the update slice for read AND write
        dest_i = next((i for i, b in enumerate(operands) if b == result),
                      None)
        if dest_i is not None:
            rest = operands[:dest_i] + operands[dest_i + 1:]
            upd = max(rest) if rest else 0
            return float(sum(rest) + upd)
    return float(result + sum(operands))


# ---------------------------------------------------------------------------
# The ledger
# ---------------------------------------------------------------------------

def cost_ledger(compiled_text: str) -> Dict[str, object]:
    """Per-device compute/HBM totals from post-SPMD HLO text.

    Returns {
      "flops":              {op: FLOPs, loop-multiplied},
      "total_flops":        float,
      "flops_in_loops":     float,
      "hbm_bytes":          float  (modeled: operands + results),
      "hbm_bytes_in_loops": float,
      "count":              {op: flop-op executions, loop-multiplied},
      "cost_centers":       [{"sig","op","flops","count","in_loop"}] desc,
      "loops":              [{"body","trips","resolved","flops",
                              "hbm_bytes"}]  (one entry per while line,
                             totals include the trip multiplier and any
                             outer-loop multiplicity),
      "unresolved_loops":   [bodies whose trip count defaulted to 1],
      "approx_ops":         [conv lines whose FLOPs are approximate],
    }
    """
    comps = _split_computations(compiled_text)

    # fusion payload computations: reached via `calls=`; their HBM-level
    # traffic is the calling fusion line, not their internals
    fusion_payloads: set = set()
    for lines in comps.values():
        for ln in lines:
            m = _FUSION_CALL_RE.search(ln)
            if m:
                fusion_payloads.add(m.group(1))

    # per-computation local stats + call edges
    local_flops: Dict[str, List[Tuple[str, float, str]]] = {}
    local_hbm: Dict[str, float] = {}
    edges: Dict[str, List[Tuple[str, float, str, bool]]] = {}
    unresolved: List[str] = []
    approx_ops: List[str] = []

    for name, lines in comps.items():
        local_flops[name] = []
        local_hbm[name] = 0.0
        edges[name] = []
        count_hbm = name not in fusion_payloads
        # long operand / tuple-type lists carry position comments
        # ("/*index=5*/%x") whose "=" would derail the parse; metadata
        # (always after the attributes read below) carries op_name
        # strings with shape-like text
        lines = [_strip_metadata(_COMMENT_RE.sub("", ln)) for ln in lines]
        parsed = [(ln, _DEF_RE.match(ln)) for ln in lines]
        # instruction name -> result type text, for operands printed by
        # name only
        defs = {m.group(1): m.group(2) for _, m in parsed if m}
        for ln, dm in parsed:
            if dm is None:
                continue
            iname, res_type, op = dm.groups()
            if op == "dot":
                fl, sig = _dot_flops(ln, res_type, defs)
                local_flops[name].append(("dot", fl, sig))
            elif op == "convolution":
                fl, sig = _conv_flops(ln, res_type, defs)
                local_flops[name].append(("convolution", fl, sig))
                approx_ops.append(ln.strip()[:160])
            wm = _WHILE_RE.search(ln)
            if wm:
                cond, body = wm.group(1), wm.group(2)
                trips, resolved = _trip_count(comps.get(cond, []))
                if not resolved:
                    unresolved.append(body)
                edges[name].append((body, float(trips), "while", resolved))
                edges[name].append((cond, float(trips), "while-cond",
                                    resolved))
                continue
            cm = _CALL_RE.search(ln)
            if cm and cm.group(1) in comps:
                edges[name].append((cm.group(1), 1.0, "call", True))
            fm = _FUSION_CALL_RE.search(ln)
            if fm and fm.group(1) in comps:
                edges[name].append((fm.group(1), 1.0, "fusion", True))
            bm = _BRANCH_RE.search(ln)
            if bm:
                for b in re.findall(r"%?([\w\.\-]+)", bm.group(1)):
                    if b in comps:
                        edges[name].append((b, 1.0, "branch", True))
            for tm in _TRUE_FALSE_RE.finditer(ln):
                if tm.group(1) in comps:
                    edges[name].append((tm.group(1), 1.0, "branch", True))
            if count_hbm and op not in _HBM_SKIP_OPS \
                    and op not in _HBM_CONTAINER_OPS:
                local_hbm[name] += _hbm_bytes_of_line(
                    ln, iname, op, res_type, defs)

    # entry = computation nobody calls (prefer one whose name says so)
    called = {b for es in edges.values() for b, _, _, _ in es}
    roots = [c for c in comps if c not in called]
    entry = next((c for c in roots if "main" in c or "entry" in c.lower()),
                 roots[0] if roots else next(iter(comps), None))

    flops_by_op: Dict[str, float] = {}
    count_by_op: Dict[str, float] = {}
    flops_in_loops = 0.0
    hbm_total = 0.0
    hbm_in_loops = 0.0
    centers: Dict[str, Dict[str, object]] = {}
    loops: List[Dict[str, object]] = []

    # memoized one-trip subtree totals (nested whiles multiplied inside)
    _sub_memo: Dict[str, Tuple[float, float]] = {}

    def _subtree(comp: str, seen: tuple) -> Tuple[float, float]:
        if comp in seen:
            return 0.0, 0.0
        if comp in _sub_memo:
            return _sub_memo[comp]
        fl = sum(f for _, f, _ in local_flops.get(comp, []))
        hb = local_hbm.get(comp, 0.0)
        for tgt, trips, kind, _res in edges.get(comp, []):
            m = trips if kind in ("while", "while-cond") else 1.0
            sfl, shb = _subtree(tgt, seen + (comp,))
            fl += m * sfl
            hb += m * shb
        _sub_memo[comp] = (fl, hb)
        return fl, hb

    def walk(comp: str, mult: float, seen: tuple,
             in_loop: bool = False) -> None:
        nonlocal flops_in_loops, hbm_total, hbm_in_loops
        if comp in seen:
            return
        for op, fl, sig in local_flops.get(comp, []):
            flops_by_op[op] = flops_by_op.get(op, 0.0) + mult * fl
            count_by_op[op] = count_by_op.get(op, 0.0) + mult
            if in_loop:
                flops_in_loops += mult * fl
            c = centers.setdefault(sig, {
                "sig": sig, "op": op, "flops": 0.0, "count": 0.0,
                "in_loop": in_loop,
            })
            c["flops"] = float(c["flops"]) + mult * fl
            c["count"] = float(c["count"]) + mult
            c["in_loop"] = bool(c["in_loop"]) or in_loop
        hbm_here = mult * local_hbm.get(comp, 0.0)
        hbm_total += hbm_here
        if in_loop:
            hbm_in_loops += hbm_here
        for tgt, trips, kind, resolved in edges.get(comp, []):
            if kind in ("while", "while-cond"):
                if kind == "while":
                    sfl, shb = _subtree(tgt, seen + (comp,))
                    loops.append({
                        "body": tgt, "trips": int(trips),
                        "resolved": bool(resolved),
                        "flops": mult * trips * sfl,
                        "hbm_bytes": mult * trips * shb,
                    })
                walk(tgt, mult * trips, seen + (comp,), True)
            else:
                walk(tgt, mult, seen + (comp,), in_loop)

    if entry is not None:
        walk(entry, 1.0, ())

    top = sorted(centers.values(), key=lambda c: -float(c["flops"]))
    return {
        "flops": flops_by_op,
        "total_flops": float(sum(flops_by_op.values())),
        "flops_in_loops": flops_in_loops,
        "hbm_bytes": hbm_total,
        "hbm_bytes_in_loops": hbm_in_loops,
        "count": count_by_op,
        "cost_centers": top,
        "loops": loops,
        "unresolved_loops": unresolved,
        "approx_ops": approx_ops,
    }


# ---------------------------------------------------------------------------
# Roofline verdict
# ---------------------------------------------------------------------------

def roofline_verdict(total_flops: float, hbm_bytes: float,
                     wire_bytes: float = 0.0,
                     device_kind: Optional[str] = None,
                     peak: Optional[float] = None,
                     hbm_bw: Optional[float] = None,
                     wire_bw: Optional[float] = None) -> Dict[str, object]:
    """Name the bound: compute-, hbm-, or wire-bound.

    Each axis gets a lower-bound time (work / peak rate); the slowest axis
    is the bound.  `arithmetic_intensity` (FLOPs/HBM byte) vs
    `ridge_intensity` (peak FLOPs / HBM BW) is the classic roofline view
    of the compute-vs-HBM race; the wire axis extends it with the ledger's
    measured collective bytes.  Raises ValueError for a device the peak
    tables do not know (and no explicit peaks): a roofline needs a roof.
    """
    peak = peak if peak is not None else peak_flops_per_chip(device_kind)
    hbm_bw = hbm_bw if hbm_bw is not None else hbm_bw_per_chip(device_kind)
    wire_bw = wire_bw if wire_bw is not None \
        else wire_bw_per_chip(device_kind)
    if peak is None or hbm_bw is None or wire_bw is None:
        raise ValueError(
            f"no peak FLOP/s / bandwidth known for device_kind="
            f"{device_kind!r}: a roofline verdict needs the device's peaks")
    t_compute = total_flops / peak
    t_hbm = hbm_bytes / hbm_bw
    t_wire = wire_bytes / wire_bw
    times = {"compute": t_compute, "hbm": t_hbm, "wire": t_wire}
    bound = max(times, key=lambda k: times[k]) if any(times.values()) \
        else "compute"
    return {
        "bound": bound,
        "arithmetic_intensity": (total_flops / hbm_bytes)
        if hbm_bytes > 0 else 0.0,
        "ridge_intensity": peak / hbm_bw,
        "t_compute_s": t_compute,
        "t_hbm_s": t_hbm,
        "t_wire_s": t_wire,
        "peak_flops": peak,
        "hbm_bw": hbm_bw,
        "wire_bw": wire_bw,
    }


def cost_summary(led: Dict[str, object],
                 device_kind: Optional[str] = None,
                 wire_bytes: float = 0.0,
                 top_n: int = 3) -> Dict[str, object]:
    """Compact JSON-safe summary of a cost ledger — what rides in
    telemetry run_meta and bench `extra.hlo_cost`.  The counts (FLOPs,
    modeled HBM bytes, wire bytes, arithmetic intensity, cost centers)
    are always present; the roofline fields (`bound`, `ridge_intensity`,
    `t_*_s`) only for a device the peak tables know — a CPU run emits
    counts, never a verdict priced at some other chip's peaks."""
    total_flops = float(led["total_flops"])
    hbm_bytes = float(led["hbm_bytes"])
    total = total_flops or 1.0
    out: Dict[str, object] = {
        "total_flops": total_flops,
        "flops_in_loops": float(led["flops_in_loops"]),
        "hbm_bytes": hbm_bytes,
        "hbm_bytes_in_loops": float(led["hbm_bytes_in_loops"]),
        "wire_bytes": float(wire_bytes),
        "arithmetic_intensity": (total_flops / hbm_bytes)
        if hbm_bytes > 0 else 0.0,
        "top_cost_centers": [
            {"sig": c["sig"], "flops": float(c["flops"]),
             "count": float(c["count"]), "in_loop": bool(c["in_loop"]),
             "share": float(c["flops"]) / total}
            for c in list(led["cost_centers"])[:top_n]
        ],
        "unresolved_loops": len(list(led["unresolved_loops"])),
        "approx_ops": len(list(led["approx_ops"])),
    }
    if peak_flops_per_chip(device_kind) is not None:
        verdict = roofline_verdict(
            total_flops, hbm_bytes, wire_bytes=wire_bytes,
            device_kind=device_kind)
        out.update({k: verdict[k] for k in (
            "ridge_intensity", "bound", "t_compute_s", "t_hbm_s",
            "t_wire_s")})
    return out


def hlo_cost_report(engine, state, batch) -> Dict[str, object]:
    """Convenience: compile an engine's step and return its cost ledger +
    summary (post-hoc analysis only — does not touch the cached step)."""
    compiled = engine._step.lower(state, batch).compile()
    text = compiled.as_text()
    led = cost_ledger(text)
    wire = float(collective_ledger(text).get("total_wire_bytes", 0.0))
    import jax
    dev = jax.devices()[0].device_kind
    return {"ledger": led,
            "summary": cost_summary(led, device_kind=dev, wire_bytes=wire)}
