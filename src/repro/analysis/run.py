"""The auditor driver: run all three passes over every registered
engine, write ``ANALYSIS.json``, and (``--compare``) fail on
regressions against a committed baseline.

Per engine:

1. build the tiny example, trace it, **jaxpr-lint** the closed jaxpr
   (callbacks / f64 / weak outputs / scatter+gather modes) and take the
   trip-weighted scatter census;
2. lower + compile, **HLO-audit** the optimized module (host
   transfers, collective balance) and record its op accounting;
3. **dispatch-account** with the engine's jit-cache probe: one warm
   call must add at most ``max_new_executables`` executables and a
   second identical call must add zero (``zero_recompile``).

Then one **source lint** over ``src/`` (np-under-jit, Python branches
on operands, tracer-leaking globals, static-arg hygiene) plus the
registry-coverage cross-reference: a jitted def in ``core/`` /
``warehouse/`` / ``distribution/`` that no engine ``covers`` is an
``unregistered_jit`` violation, and a registered engine without a
cache probe is ``missing_probe``.

Exit status: non-zero on any violation, or on ``--compare``
regressions (new violations, per-engine dispatch-count growth, or a
baseline engine disappearing).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Dict, List, Optional

import jax

from repro.analysis import registry
from repro.analysis.hlo_audit import audit_hlo
from repro.analysis.jaxpr_lint import lint_jaxpr, trace_closed_jaxpr
from repro.analysis.source_lint import lint_tree

SCHEMA = 1
_SRC_ROOT = os.path.normpath(
    os.path.join(os.path.dirname(__file__), "..", ".."))


def audit_engine(engine: registry.Engine) -> Dict:
    """All three per-engine passes. Returns the engine's record for
    ``ANALYSIS.json`` (violations list included, possibly empty)."""
    record: Dict = {"violations": []}

    try:
        ex = engine.build()
    except registry.SkipEngine as e:
        record["skipped"] = str(e)
        return record

    # -- pass 1: jaxpr lint + census ------------------------------------
    closed = trace_closed_jaxpr(ex.fn, ex.args, ex.kwargs)
    v, census = lint_jaxpr(closed, engine.invariants)
    record["violations"].extend(v)
    record["jaxpr_census"] = census

    # -- pass 2: HLO audit ----------------------------------------------
    lowered = ex.fn.lower(*ex.args, **ex.kwargs)
    hlo = lowered.compile().as_text()
    v, info = audit_hlo(hlo, engine.invariants)
    record["violations"].extend(v)
    record["hlo"] = info

    # -- pass 3: dispatch accounting ------------------------------------
    if engine.probe is None:
        record["violations"].append({
            "pass": "registry", "check": "missing_probe",
            "detail": "engine registered without a jit-cache probe "
                      "(dispatch count is unverifiable)",
            "path": engine.name})
    else:
        p0 = engine.probe()
        jax.block_until_ready(ex.run())
        p1 = engine.probe()
        jax.block_until_ready(ex.run())
        p2 = engine.probe()
        new_exec, recompiles = p1 - p0, p2 - p1
        record["dispatch"] = {"new_executables": new_exec,
                              "recompiles": recompiles}
        cap = engine.invariants.get("max_new_executables")
        if cap is not None and new_exec > cap:
            record["violations"].append({
                "pass": "dispatch", "check": "dispatch_count",
                "detail": f"one warm call added {new_exec} executables "
                          f"(max {cap})", "path": engine.name})
        if engine.invariants.get("zero_recompile") and recompiles > 0:
            record["violations"].append({
                "pass": "dispatch", "check": "recompile",
                "detail": f"second identical call added {recompiles} "
                          f"executables", "path": engine.name})
    return record


def coverage_violations() -> List[Dict]:
    """Cross-reference the three observability registries (satellite of
    the obs PR — a counter that exists but is never audited or traced is
    a blind spot, so all three must agree):

    - every ``register_cache_probe`` site must be claimed by at least
      one registry engine via ``probe_name=`` (``probe_without_engine``),
    - every ``probe_name`` must point at a probe that actually exists
      (``unknown_probe_name`` — catches typos and renames),
    - every registered engine must be traceable by ``repro.obs``
      (``untraced_engine`` — i.e. it has a probe).
    """
    # deferred imports: switcher/obs both (transitively) import this
    # package's registry at module scope
    from repro.core.switcher import _CACHE_PROBES
    from repro.obs.trace import traceable_engine_names

    registry.import_engine_modules()
    violations: List[Dict] = []
    probes = set(_CACHE_PROBES)
    claimed = registry.claimed_probe_names()
    for name in sorted(probes - claimed):
        violations.append({
            "pass": "coverage", "check": "probe_without_engine",
            "detail": "cache probe has no registry engine claiming it "
                      "via probe_name= (recompiles there are invisible "
                      "to the auditor and the obs tracer)",
            "path": name})
    for name in sorted(claimed - probes):
        violations.append({
            "pass": "coverage", "check": "unknown_probe_name",
            "detail": "engine probe_name= does not match any "
                      "register_cache_probe site", "path": name})
    traced = traceable_engine_names()
    for name in sorted(set(registry.engines()) - traced):
        violations.append({
            "pass": "coverage", "check": "untraced_engine",
            "detail": "registered engine is invisible to the obs "
                      "tracer (no jit-cache probe)", "path": name})
    return violations


def run_audit(only: Optional[str] = None, skip_source: bool = False
              ) -> Dict:
    registry.import_engine_modules()
    engines = registry.engines()
    if only:
        engines = {k: v for k, v in engines.items() if only in k}

    report: Dict = {"schema": SCHEMA,
                    "topology": {"n_devices": jax.device_count()},
                    "engines": {}, "violations": []}
    for name, engine in engines.items():
        rec = audit_engine(engine)
        for v in rec["violations"]:
            v.setdefault("engine", name)
        report["engines"][name] = rec
        report["violations"].extend(rec["violations"])

    if not skip_source and not only:
        src_v, jit_defs = lint_tree(_SRC_ROOT)
        covered = registry.covered_jit_names()
        for missing in sorted(jit_defs - covered):
            src_v.append({"pass": "source", "check": "unregistered_jit",
                          "detail": "jitted entry point has no analysis-"
                                    "registry entry (no invariants, no "
                                    "probe)", "path": missing})
        report["source"] = {"violations": src_v,
                            "jit_defs": sorted(jit_defs)}
        report["violations"].extend(src_v)
        cov_v = coverage_violations()
        report["coverage"] = {"violations": cov_v}
        report["violations"].extend(cov_v)

    report["n_violations"] = len(report["violations"])
    return report


def compare(new: Dict, old: Dict) -> List[str]:
    """Regressions of ``new`` vs a committed baseline ``old``."""
    regressions: List[str] = []
    if new.get("n_violations", 0) > 0:
        regressions.append(
            f"{new['n_violations']} violations (baseline is clean)")
    if new["topology"] != old.get("topology"):
        # per-engine numbers are topology-dependent; violations above
        # still count, dispatch growth does not.
        print(f"[analysis] topology changed "
              f"{old.get('topology')} -> {new['topology']}; "
              f"skipping per-engine dispatch compare", file=sys.stderr)
        return regressions
    for name, old_rec in old.get("engines", {}).items():
        new_rec = new["engines"].get(name)
        if new_rec is None:
            regressions.append(f"engine {name!r} disappeared from audit")
            continue
        od = old_rec.get("dispatch", {}).get("new_executables")
        nd = new_rec.get("dispatch", {}).get("new_executables")
        if od is not None and nd is not None and nd > od:
            regressions.append(
                f"{name}: dispatch count grew {od} -> {nd}")
    return regressions


def _summary(report: Dict) -> str:
    lines = [f"audit: {len(report['engines'])} engines, "
             f"{report['n_violations']} violations "
             f"({report['topology']['n_devices']} devices)"]
    for name, rec in report["engines"].items():
        if "skipped" in rec:
            lines.append(f"  {name:28s} SKIP ({rec['skipped']})")
            continue
        t = rec["jaxpr_census"]["totals"]
        d = rec.get("dispatch", {})
        lines.append(
            f"  {name:28s} dispatch={d.get('new_executables', '?')} "
            f"recompile={d.get('recompiles', '?')} "
            f"scatter x{t['scatter_executed']:.0f} "
            f"gather x{t['gather_executed']:.0f} "
            f"viol={len(rec['violations'])}")
    for v in report["violations"]:
        lines.append(f"  VIOLATION [{v['pass']}/{v['check']}] "
                     f"{v.get('engine', v.get('path', ''))}: {v['detail']}")
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.analysis",
        description="static program auditor (jaxpr lint, HLO audit, "
                    "source lint) over every registered engine")
    ap.add_argument("--json", default="ANALYSIS.json",
                    help="output path (default ./ANALYSIS.json)")
    ap.add_argument("--compare", metavar="OLD",
                    help="fail on regressions vs a baseline ANALYSIS.json")
    ap.add_argument("--only", help="substring filter on engine names "
                    "(debug; disables source lint + compare coverage)")
    ap.add_argument("--no-source", action="store_true",
                    help="skip the source-lint pass")
    args = ap.parse_args(argv)

    old = None
    if args.compare:
        with open(args.compare) as fh:
            old = json.load(fh)

    report = run_audit(only=args.only, skip_source=args.no_source)
    print(_summary(report))

    with open(args.json, "w") as fh:
        json.dump(report, fh, indent=1, sort_keys=True)
        fh.write("\n")
    print(f"[analysis] wrote {args.json}")

    rc = 0
    if report["n_violations"] > 0:
        rc = 1
    if old is not None:
        regs = compare(report, old)
        for r in regs:
            print(f"[analysis] REGRESSION: {r}")
        if regs:
            rc = 1
        else:
            print(f"[analysis] compare vs {args.compare}: OK")
    return rc


if __name__ == "__main__":
    sys.exit(main())
