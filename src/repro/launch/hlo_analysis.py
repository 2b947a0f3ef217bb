"""Post-compile HLO analysis for the roofline.

``compiled.cost_analysis()`` does NOT multiply while-loop bodies by their
trip counts, so with scan-over-layers it undercounts by ~n_layers. This
parser walks the optimized HLO text, attributes ops to computations,
propagates ``known_trip_count`` multipliers through the while call graph,
and reports:

- per-kind collective bytes (per-device message sizes x trip counts),
- dot FLOPs (2 * result_elems * contracted_dim x trip counts),
- scatter/gather op counts and operand+result bytes (the query-latency
  floor the ROADMAP's Pallas item targets; scatters usually sit inside
  fusion computations, so fusion call edges propagate multipliers too),
- top-level operand+result bytes (memory-traffic proxy).

Validated against cost_analysis() on unrolled lowers in tests.
"""
from __future__ import annotations

import json
import re
from collections import defaultdict
from typing import Dict, Tuple

DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "token": 0, "s4": 1, "u4": 1, "f8e4m3fn": 1, "f8e5m2": 1,
}

COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_OP_RE = re.compile(r"^\s*(?:ROOT )?%(\S+) = (.*?) (\S+?)\(")
_COMP_RE = re.compile(r"^(?:ENTRY )?%?([\w\.\-_]+)\s*\(.*\)\s*->.*\{")
_TRIP_RE = re.compile(r'known_trip_count[^0-9]*(\d+)')
_BODY_RE = re.compile(r"body=%?([\w\.\-_]+)")
_CALLS_RE = re.compile(r"calls=%?([\w\.\-_]+)")
# one operand, with or without its inline type: older XLA prints
# ``dot(%a, %b)``; newer prints ``dot(f32[128,64]{1,0} %a, ...)`` and
# TPU lowers add tiled layouts ``f32[128,64]{1,0:T(8,128)}``
_OPND_RE = re.compile(
    r"(?:(\w+\[[\d,]*\](?:\{[^}]*\})?)\s+)?%([\w\.\-_]+)")


def _call_operands(line: str, opcode: str):
    """[(inline_type_or_None, operand_name), ...] of an op's call args.
    Tiled layout annotations contain parens (``{1,0:T(8,128)}``), so the
    operand list ends at the ')' that closes '<opcode>(' at depth 0 —
    not at the first ')' in the line."""
    i = line.find(opcode + "(")
    if i < 0:
        return []
    start = i + len(opcode) + 1
    depth = 1
    end = start
    for end in range(start, len(line)):
        ch = line[end]
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
            if depth == 0:
                break
    return [(t or None, n)
            for t, n in _OPND_RE.findall(line[start:end])]


def _shape_bytes(type_str: str) -> int:
    """Total bytes of a (possibly tuple) HLO type string."""
    total = 0
    for m in _SHAPE_RE.finditer(type_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * DTYPE_BYTES[dt]
    return total


def _shape_elems(type_str: str):
    m = _SHAPE_RE.search(type_str)
    if not m:
        return None, 0
    dims = [int(d) for d in m.group(2).split(",") if d]
    n = 1
    for d in dims:
        n *= d
    return dims, n


def analyze(hlo_text: str) -> Dict:
    comps: Dict[str, Dict] = {}
    cur = None
    result_types: Dict[str, str] = {}

    lines = hlo_text.splitlines()
    for line in lines:
        mc = _COMP_RE.match(line)
        if mc and ("->" in line):
            cur = mc.group(1)
            comps[cur] = {"colls": defaultdict(int), "coll_counts": defaultdict(int),
                          "dot_flops": 0, "bytes": 0, "dot_bytes": 0,
                          "whiles": [], "op_count": 0,
                          "sg": defaultdict(lambda: [0, 0])}
            continue
        if cur is None or not line.strip().startswith(("%", "ROOT")):
            continue
        mo = _OP_RE.match(line)
        if not mo:
            continue
        name, rtype, opcode = mo.groups()
        result_types[name] = rtype
        c = comps[cur]
        c["op_count"] += 1
        out_bytes = _shape_bytes(rtype)
        c["bytes"] += out_bytes
        base = opcode.split(".")[0]
        for kind in COLLECTIVES:
            if base == kind or base == kind + "-start":
                c["colls"][kind] += out_bytes
                c["coll_counts"][kind] += 1
        if base in ("scatter", "select-and-scatter", "gather"):
            # io bytes = result + every operand (operand array, indices,
            # updates) — the traffic a gather/scatter actually moves
            io = out_bytes
            for t, n in _call_operands(line, opcode):
                t = t if t is not None else result_types.get(n)
                if t:
                    io += _shape_bytes(t)
            c["sg"][base][0] += 1
            c["sg"][base][1] += io
        if base == "while":
            mt = _TRIP_RE.search(line)
            mb = _BODY_RE.search(line)
            if mb:
                trip = int(mt.group(1)) if mt else 1
                c["whiles"].append((mb.group(1), trip))
        elif base in ("dot", "convolution"):
            dims, out_elems = _shape_elems(rtype)
            # contracted size from lhs operand shape + contracting dims
            ops = _call_operands(line, opcode)
            md = re.search(r"lhs_contracting_dims=\{([\d,]*)\}", line)
            contracted = 1
            dot_io = out_bytes
            op_types = [t if t is not None else result_types.get(n)
                        for t, n in ops]
            for t in op_types:
                if t:
                    dot_io += _shape_bytes(t)
            if md and op_types and op_types[0]:
                ldims, _ = _shape_elems(op_types[0])
                if ldims:
                    for ci in md.group(1).split(","):
                        if ci:
                            contracted *= ldims[int(ci)]
            c["dot_flops"] += 2 * out_elems * contracted
            c["dot_bytes"] += dot_io
        elif base == "fusion":
            mf = _CALLS_RE.search(line)
            if mf:
                c.setdefault("fusions", []).append(mf.group(1))

    # propagate multipliers from ENTRY through whiles (memoized DFS; each
    # while body has a unique name so the call graph is a DAG)
    entry = None
    for line in lines:
        if line.startswith("ENTRY"):
            m = re.match(r"ENTRY %?([\w\.\-_]+)", line)
            if m:
                entry = m.group(1)
    if entry is None:
        entry = next(iter(comps))
    callers: Dict[str, list] = defaultdict(list)
    for cname, c in comps.items():
        for body, trip in c["whiles"]:
            callers[body].append((cname, trip))
        for callee in c.get("fusions", ()):
            callers[callee].append((cname, 1))

    memo: Dict[str, float] = {}

    def mult_of(cname: str) -> float:
        if cname == entry:
            return 1.0
        if cname in memo:
            return memo[cname]
        memo[cname] = 0.0  # cycle guard
        m = sum(mult_of(p) * t for p, t in callers.get(cname, []))
        memo[cname] = m
        return m

    mult = {cname: mult_of(cname) for cname in comps}

    colls = defaultdict(int)
    coll_counts = defaultdict(int)
    dot_flops = 0.0
    raw_bytes = 0.0
    dot_bytes = 0.0
    census: Dict[str, Dict[str, float]] = {}
    for cname, c in comps.items():
        m = mult.get(cname, 0.0)
        if m == 0.0:
            continue
        for k, v in c["colls"].items():
            colls[k] += v * m
            coll_counts[k] += c["coll_counts"][k] * m
        dot_flops += c["dot_flops"] * m
        raw_bytes += c["bytes"] * m
        dot_bytes += c["dot_bytes"] * m
        for op, (n, io) in c["sg"].items():
            e = census.setdefault(
                op, {"count": 0, "executed": 0.0, "bytes": 0.0})
            e["count"] += n
            e["executed"] += n * m
            e["bytes"] += io * m

    # entry argument bytes (params + inputs read once)
    arg_bytes = 0
    in_entry = False
    for line in lines:
        if line.startswith("ENTRY"):
            in_entry = True
        if in_entry and re.search(r"= .* parameter\(", line):
            m = re.match(r"^\s*(?:ROOT )?%\S+ = (.*?) parameter\(", line)
            if m:
                arg_bytes += _shape_bytes(m.group(1))

    coll_total = float(sum(colls.values()))
    scatter_ops = sum(e["executed"] for op, e in census.items()
                      if op != "gather")
    gather_ops = census.get("gather", {}).get("executed", 0.0)
    scatter_bytes = sum(e["bytes"] for op, e in census.items()
                        if op != "gather")
    gather_bytes = census.get("gather", {}).get("bytes", 0.0)
    return {
        "collective_bytes": dict(colls),
        "collective_bytes_total": coll_total,
        "collective_counts": {k: float(v) for k, v in coll_counts.items()},
        "dot_flops": float(dot_flops),
        "scatter_ops": float(scatter_ops),
        "gather_ops": float(gather_ops),
        "scatter_bytes": float(scatter_bytes),
        "gather_bytes": float(gather_bytes),
        # TPU-realistic HBM traffic: matmul operands/results (elementwise
        # chains fuse into them) + collective payloads + scatter/gather
        # traffic (the query floor) + one read of args
        "bytes_touched": float(dot_bytes + coll_total + scatter_bytes
                               + gather_bytes + arg_bytes),
        "bytes_touched_raw": float(raw_bytes),
        "argument_bytes": float(arg_bytes),
        "scatter_census": census,
    }


def scatter_census(hlo_text: str) -> Dict[str, Dict[str, float]]:
    """Trip-weighted scatter/gather census of one compiled module:
    ``opcode -> {count (static), executed (x trips), bytes (io x
    trips)}``. The per-plan-shape numbers any Pallas query kernel has
    to beat (ROADMAP "Break the scatter floor")."""
    return analyze(hlo_text)["scatter_census"]


# ---------------------------------------------------------------------------
# Roofline terms (TPU v5e targets; see DESIGN.md §7)
# ---------------------------------------------------------------------------
PEAK_FLOPS = 197e12        # bf16 / chip
HBM_BW = 819e9             # bytes/s / chip
ICI_BW = 50e9              # bytes/s per link
ICI_LINKS = 4              # usable links/chip on a 2D-torus axis pair


def roofline_terms(flops_per_device: float, bytes_per_device: float,
                   coll_bytes_per_device: float) -> Dict[str, float]:
    t_compute = flops_per_device / PEAK_FLOPS
    t_memory = bytes_per_device / HBM_BW
    t_coll = coll_bytes_per_device / (ICI_LINKS * ICI_BW)
    dom = max((t_compute, "compute"), (t_memory, "memory"),
              (t_coll, "collective"))
    return {"compute_s": t_compute, "memory_s": t_memory,
            "collective_s": t_coll, "dominant": dom[1],
            "bound_s": dom[0]}
