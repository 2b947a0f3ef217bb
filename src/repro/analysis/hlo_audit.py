"""Pass 2 — HLO audit: verify the *compiled* program (post-XLA) keeps
the promises the jaxpr made.

Layered on ``launch.hlo_analysis``: that module's parser already
attributes ops to computations and propagates while trip counts; this
pass adds the call-graph edge *types* needed for control-flow-sensitive
checks and audits:

- **host transfers**: no ``infeed`` / ``outfeed``, no
  ``is_host_transfer=true`` send/recv/copy, no host-callback
  custom-calls survive compilation. (A host hop the jaxpr lint missed —
  e.g. introduced by lowering — still fails here.)
- **collective balance**: no collective op is reachable from ENTRY
  through a ``conditional`` branch. Inside a ``shard_map`` body every
  shard must execute the identical collective sequence; a
  partition-id-predicated ``psum`` deadlocks the mesh (or silently
  corrupts under ``check_vma=False``). The sharded property suite can
  only catch this probabilistically — the call graph catches it
  structurally.
- **op accounting**: ``launch.hlo_analysis.analyze`` op counts plus its
  ``scatter_census`` (trip-weighted scatter/gather ops and bytes) — the
  compiled-side view of the query-latency floor.
"""
from __future__ import annotations

import re
from collections import defaultdict
from typing import Dict, List, Mapping, Tuple

from repro.launch.hlo_analysis import (COLLECTIVES, analyze, scatter_census)

_COMP_RE = re.compile(r"^(ENTRY )?%?([\w\.\-_]+)\s*\(.*\)\s*->.*\{")
_OPCODE_RE = re.compile(r"^\s*(?:ROOT )?%\S+ = \S+ ([\w\-\.]+)\(")
_CALLEE_RES = {
    "call": re.compile(r"(?:calls|to_apply|body|condition)=%?([\w\.\-_]+)"),
    "branch": re.compile(
        r"(?:true_computation|false_computation)=%?([\w\.\-_]+)"),
}
_BRANCHES_RE = re.compile(r"branch_computations=\{([^}]*)\}")
_NAME_RE = re.compile(r"%?([\w\.\-_]+)")


def _parse_graph(hlo_text: str):
    """computations -> {ops: [opcode/line], edges: [(callee, kind)]}
    plus the ENTRY computation name. ``kind`` is 'branch' for
    conditional branch computations, 'call' otherwise."""
    comps: Dict[str, Dict] = {}
    entry = None
    cur = None
    for line in hlo_text.splitlines():
        mc = _COMP_RE.match(line)
        if mc and "->" in line:
            cur = mc.group(2)
            comps[cur] = {"ops": [], "edges": []}
            if mc.group(1):
                entry = cur
            continue
        if cur is None or not line.strip().startswith(("%", "ROOT")):
            continue
        mo = _OPCODE_RE.match(line)
        if not mo:
            continue
        opcode = mo.group(1)
        comps[cur]["ops"].append((opcode, line.strip()))
        is_cond = opcode.split(".")[0] == "conditional"
        mb = _BRANCHES_RE.search(line)
        if mb:
            for name in _NAME_RE.findall(mb.group(1)):
                comps[cur]["edges"].append((name, "branch"))
        for kind, rx in _CALLEE_RES.items():
            for name in rx.findall(line):
                comps[cur]["edges"].append(
                    (name, "branch" if (is_cond and kind == "call")
                     else kind))
    if entry is None and comps:
        entry = next(iter(comps))
    return comps, entry


def _is_collective(opcode: str) -> bool:
    base = opcode.split(".")[0]
    return any(base == k or base == k + "-start" for k in COLLECTIVES)


def audit_hlo(hlo_text: str, invariants: Mapping[str, object]
              ) -> Tuple[List[Dict], Dict]:
    """Audit one compiled HLO module. Returns ``(violations, info)``
    where ``info`` carries the op accounting (``analyze`` aggregates +
    ``scatter_census``)."""
    violations: List[Dict] = []

    def violate(check: str, detail: str, path: str):
        violations.append({"pass": "hlo", "check": check,
                           "detail": detail, "path": path})

    comps, entry = _parse_graph(hlo_text)

    # ---- host transfers ------------------------------------------------
    if invariants.get("no_host_transfers"):
        for cname, c in comps.items():
            for opcode, line in c["ops"]:
                base = opcode.split(".")[0]
                if base in ("infeed", "outfeed"):
                    violate("host_transfer", f"{base} op", cname)
                elif "is_host_transfer=true" in line:
                    violate("host_transfer",
                            f"{base} with is_host_transfer=true", cname)
                elif base == "custom-call":
                    m = re.search(r'custom_call_target="([^"]+)"', line)
                    target = m.group(1) if m else ""
                    if "callback" in target.lower() \
                            or "host" in target.lower():
                        violate("host_transfer",
                                f"host custom-call {target!r}", cname)

    # ---- collective balance -------------------------------------------
    if invariants.get("balanced_collectives"):
        # DFS from ENTRY; remember whether the path crossed a
        # conditional-branch edge. A collective in a computation only
        # reachable through a branch is shard-divergent.
        reach: Dict[str, bool] = {}      # comp -> reachable-under-branch

        def visit(cname: str, under_branch: bool):
            if cname not in comps:
                return
            prev = reach.get(cname)
            if prev is not None and (prev or not under_branch):
                return                    # already visited at least as bad
            reach[cname] = under_branch or bool(prev)
            for callee, kind in comps[cname]["edges"]:
                visit(callee, under_branch or kind == "branch")

        if entry is not None:
            visit(entry, False)
        for cname, under in reach.items():
            if not under:
                continue
            for opcode, _line in comps[cname]["ops"]:
                if _is_collective(opcode):
                    violate("unbalanced_collective",
                            f"{opcode} under a conditional branch "
                            f"(shards would diverge)", cname)

    # ---- op accounting -------------------------------------------------
    stats = analyze(hlo_text)
    info = {
        "op_counts": {
            "collective_counts": stats["collective_counts"],
            "scatter_ops": stats["scatter_ops"],
            "gather_ops": stats["gather_ops"],
            "dot_flops": stats["dot_flops"],
            "bytes_touched": stats["bytes_touched"],
            "scatter_bytes": stats["scatter_bytes"],
            "gather_bytes": stats["gather_bytes"],
        },
        "scatter_census": scatter_census(hlo_text),
        "n_computations": len(comps),
    }
    return violations, info
