"""``python -m repro.obs`` — the dispatch audit's command line.

Audits every registry engine (see ``repro.obs.trace``), writes
``OBS.json`` (per-engine structural counts, the committed baseline),
and with ``--compare OLD.json`` exits non-zero on regressions —
mirroring the ``ANALYSIS.json`` gating pattern:

- **ceilings** (structural, host-independent, zero headroom): a warm
  recompile, a host-transfer op, or extra executables vs baseline;
- a baseline engine that disappears (or degrades to skipped) fails —
  a gate that goes green when its engine vanishes is no gate.

Topology changes (e.g. the forced-8-device tier1 leg) skip per-engine
numeric gates, exactly like the analysis compare. Times are the
profiler's business (``repro.obs.spans``), not this report's.
"""
from __future__ import annotations

import argparse
import json
import sys
from typing import Dict, List

import jax

from repro.obs.trace import trace_all

SCHEMA = 2
_CEILINGS = ("new_executables", "recompiles", "host_transfers")


def run_obs(only=None, reps: int = 3, with_hlo: bool = True) -> Dict:
    """Audit the registry; return the report."""
    records = trace_all(only=only, reps=reps, with_hlo=with_hlo)
    return {
        "schema": SCHEMA,
        "topology": {"n_devices": jax.device_count()},
        "engines": records,
        "n_engines": len(records),
        "n_skipped": sum(1 for r in records.values() if "skipped" in r),
    }


def compare(new: Dict, old: Dict) -> List[str]:
    """Regressions of ``new`` vs a committed ``OBS.json`` baseline."""
    regressions: List[str] = []
    if new.get("topology") != old.get("topology"):
        print(f"[obs] topology changed {old.get('topology')} -> "
              f"{new.get('topology')}; skipping per-engine gates",
              file=sys.stderr)
        return regressions
    for name, old_rec in sorted(old.get("engines", {}).items()):
        if "skipped" in old_rec:
            continue
        new_rec = new.get("engines", {}).get(name)
        if new_rec is None:
            regressions.append(f"engine {name!r} disappeared from trace")
            continue
        if "skipped" in new_rec:
            regressions.append(
                f"engine {name!r} now skipped: {new_rec['skipped']}")
            continue
        for key in _CEILINGS:
            ov, nv = old_rec.get(key), new_rec.get(key)
            if isinstance(ov, (int, float)) \
                    and isinstance(nv, (int, float)) and nv > ov:
                regressions.append(
                    f"{name}: {key} grew {ov} -> {nv} [ceiling]")
    return regressions


def _summary(report: Dict) -> str:
    lines = [f"obs: {report['n_engines']} engines audited "
             f"({report['n_skipped']} skipped, "
             f"{report['topology']['n_devices']} devices)"]
    for name, rec in report["engines"].items():
        if "skipped" in rec:
            lines.append(f"  {name:30s} SKIP ({rec['skipped']})")
            continue
        lines.append(
            f"  {name:30s} exec+{rec['new_executables']} "
            f"recompile={rec['recompiles']} "
            f"hosttx={rec.get('host_transfers', '?')} "
            f"out={rec['out_bytes']}B")
    return "\n".join(lines)


def main(argv=None) -> int:
    """CLI for the dispatch audit (``python -m repro.obs``): runs every
    registered engine cold and warm, writes OBS.json, and
    regression-gates against ``--compare``."""
    ap = argparse.ArgumentParser(
        prog="python -m repro.obs",
        description="dispatch audit over every registered engine: "
                    "regression-gated OBS.json")
    ap.add_argument("--json", default="OBS.json",
                    help="report path (default ./OBS.json)")
    ap.add_argument("--compare", metavar="OLD",
                    help="fail on regressions vs a baseline OBS.json")
    ap.add_argument("--only", help="substring filter on engine names "
                                   "(debug; compare gates still apply "
                                   "to the audited subset)")
    ap.add_argument("--smoke", action="store_true",
                    help="single warm rep per engine (CI smoke)")
    ap.add_argument("--reps", type=int, default=None,
                    help="warm calls per engine (default 3; smoke 1)")
    args = ap.parse_args(argv)

    old = None
    if args.compare:
        with open(args.compare) as fh:
            old = json.load(fh)

    reps = args.reps if args.reps is not None else (1 if args.smoke else 3)
    report = run_obs(only=args.only, reps=reps)
    print(_summary(report))

    with open(args.json, "w") as fh:
        json.dump(report, fh, indent=1, sort_keys=True)
        fh.write("\n")
    print(f"[obs] wrote {args.json}")

    rc = 0
    if old is not None:
        regs = compare(report, old)
        for r in regs:
            print(f"[obs] REGRESSION: {r}")
        if regs:
            rc = 1
        else:
            print(f"[obs] compare vs {args.compare}: OK")
    return rc


if __name__ == "__main__":
    sys.exit(main())
