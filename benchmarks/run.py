"""Run every paper-table/figure benchmark. Prints ``name,us_per_call,
derived`` CSV rows (one module per paper artifact — see DESIGN.md §6).

    PYTHONPATH=src:. python benchmarks/run.py [only] [--json [OUT]]
                                              [--compare OLD.json]

``only`` filters modules by substring. ``--json [OUT]`` additionally
writes a perf snapshot (bench name -> metric dict, with the numeric
fields of each row's ``derived`` string parsed out) so the repo's bench
trajectory can be tracked across PRs. OUT defaults to
``BENCH_HEAD.json`` — the rolling committed baseline; older PR-tagged
snapshots remain valid ``--compare`` inputs::

    python benchmarks/run.py --json                  # -> BENCH_HEAD.json
    python benchmarks/run.py --json BENCH_NEW.json --compare BENCH_HEAD.json

``--compare OLD.json`` loads a prior snapshot after the run, prints the
per-metric deltas, and exits non-zero if any FLOOR metric (a metric
whose key contains one of ``_FLOOR_KEYS`` — speedup factors and scan
throughputs, the numbers the engine benches assert lower bounds on)
regressed by more than 20%::

    python benchmarks/run.py --json BENCH_NEW.json --compare BENCH_HEAD.json

Floor metrics are ratios of two timings measured on the SAME host, so
they only compare across snapshots from the same machine class: each
snapshot records a ``host`` fingerprint (CPU core count), and when it
differs from the baseline's, floor regressions are reported as
warnings instead of failures (a 2-core baseline says nothing about a
1-core container's python-loop denominators). The structural CEILING
metrics (dispatch counts, scatter census, recompiles, violations) are
host-independent properties of the compiled programs and stay hard
failures everywhere.
"""
from __future__ import annotations

import json
import os
import re
import sys
import traceback

_NUM = re.compile(r"-?\d+(?:\.\d+)?(?:[eE]-?\d+)?")

# metric-name substrings treated as perf FLOORS (bigger is better);
# --compare fails the run when one drops >20% vs the old snapshot
_FLOOR_KEYS = ("speedup", "scan")
_FLOOR_DROP = 0.20

# metric-name substrings treated as CEILINGS (smaller is better, with
# zero headroom): the static-audit headline numbers. A dispatch count,
# scatter census, recompile count or violation count that GROWS at all
# vs the baseline fails the compare — these are structural properties
# of the compiled programs, not noisy timings.
_CEILING_KEYS = ("dispatch", "scatter_ops", "recompile", "violation")


def _metric_dict(row) -> dict:
    """Row -> metric dict: the leading number of every ``k=v`` part of
    the derived string (``speedup=12.3x`` -> ``{"speedup": 12.3}``);
    non-numeric parts keep their raw string."""
    out = {"us_per_call": row["us_per_call"]}
    for part in row["derived"].split(";"):
        if "=" not in part:
            continue
        key, val = part.split("=", 1)
        m = _NUM.match(val.strip())
        out[key.strip()] = float(m.group(0)) if m else val
    return out


def _host_cores(snap: dict):
    """Host fingerprint of a snapshot: the dedicated ``host`` record,
    falling back to the core count the sharded bench row has always
    carried (pre-fingerprint baselines)."""
    for rec in ("host", "sharded_query_bench"):
        v = snap.get(rec, {}).get("host_cores")
        if isinstance(v, (int, float)):
            return v
    return None


def _compare(snap: dict, old_path: str) -> int:
    """Print per-metric deltas vs a prior snapshot; return the number of
    >20% floor-metric regressions. A floor metric that existed in the
    baseline but is MISSING from this run (the bench errored out, was
    filtered away, or its derived key was renamed) counts as a
    regression too — a gate that goes green when its benchmark
    disappears is no gate. Floor deltas are only GATED when both
    snapshots come from the same host class (see module docstring);
    ceilings are gated unconditionally."""
    with open(old_path) as f:
        old = json.load(f)
    old_cores, new_cores = _host_cores(old), _host_cores(snap)
    same_host = (old_cores is None or new_cores is None
                 or old_cores == new_cores)
    if not same_host:
        print(f"# host class changed ({old_cores:.0f} -> "
              f"{new_cores:.0f} cores): floor deltas advisory, "
              f"ceilings still gated")
    # engines registered since the baseline legitimately grow the
    # audit's dispatch_total; gate the total over the engines BOTH
    # snapshots cover (every shared engine keeps its own per-engine
    # ceiling either way, and new engines get one from the first
    # committed snapshot that includes them)
    new_sa, old_sa = snap.get("static_audit"), old.get("static_audit")
    if isinstance(new_sa, dict) and isinstance(old_sa, dict) \
            and isinstance(new_sa.get("dispatch_total"), (int, float)):
        extra = sum(v for k, v in new_sa.items()
                    if k.startswith("dispatch.") and k not in old_sa
                    and isinstance(v, (int, float)))
        if extra:
            print(f"# static_audit.dispatch_total: {extra:.0f} "
                  f"dispatches from engines new since the baseline "
                  f"excluded from the ceiling")
            new_sa = dict(new_sa)
            new_sa["dispatch_total"] -= extra
            snap = {**snap, "static_audit": new_sa}
    regressions = []
    for name in sorted(snap):
        if name not in old:
            print(f"# {name}: new bench (no baseline)")
            continue
        for key, new_v in sorted(snap[name].items()):
            old_v = old[name].get(key)
            if not isinstance(new_v, (int, float)) \
                    or not isinstance(old_v, (int, float)):
                continue
            is_floor = any(fk in key for fk in _FLOOR_KEYS)
            is_ceiling = any(ck in key for ck in _CEILING_KEYS)
            if old_v == 0 and not is_ceiling:
                continue                  # ratio undefined; ceilings
            delta = (new_v - old_v) / abs(old_v) if old_v else 0.0
            flag = " [floor]" if is_floor else \
                " [ceiling]" if is_ceiling else ""
            if is_floor and new_v < old_v * (1.0 - _FLOOR_DROP):
                if same_host:
                    flag = " [floor] REGRESSION >20%"
                    regressions.append(f"{name}.{key}")
                else:
                    flag = " [floor] WARNING >20% (host class changed)"
            elif is_ceiling and new_v > old_v:
                flag = " [ceiling] REGRESSION (grew)"
                regressions.append(f"{name}.{key}")
            print(f"{name}.{key}: {old_v:.4g} -> {new_v:.4g} "
                  f"({delta:+.1%}){flag}")
    # baseline floor/ceiling metrics this run no longer reports at all
    for name, metrics in sorted(old.items()):
        missing = [key for key, old_v in metrics.items()
                   if isinstance(old_v, (int, float))
                   and any(k in key for k in _FLOOR_KEYS + _CEILING_KEYS)
                   and not isinstance(snap.get(name, {}).get(key),
                                      (int, float))]
        if name not in snap:
            print(f"# {name}: missing from this run (was in baseline)")
        for key in missing:
            print(f"{name}.{key}: {metrics[key]:.4g} -> MISSING "
                  f"REGRESSION (gated metric disappeared)")
            regressions.append(f"{name}.{key}")
    if regressions:
        print(f"FAIL: gated metrics regressed (floor drop >20% or "
              f"ceiling growth): {', '.join(regressions)}",
              file=sys.stderr)
    return len(regressions)


def _audit_record() -> dict:
    """Static-audit headline numbers for the perf snapshot: per-engine
    dispatch counts (ONE warm call = N executables) and the scatter
    census of every warehouse query plan — the structural floor the
    Pallas query-kernel work has to beat. All ceilings: growth fails
    ``--compare``."""
    from repro.analysis.run import run_audit
    report = run_audit(skip_source=True)
    recs = report["engines"]
    out = {
        "engines": float(len(recs)),
        "violations": float(report["n_violations"]),
        "dispatch_total": float(sum(
            r["dispatch"]["new_executables"] for r in recs.values()
            if "dispatch" in r)),
        "recompiles_total": float(sum(
            r["dispatch"]["recompiles"] for r in recs.values()
            if "dispatch" in r)),
    }
    for name, r in sorted(recs.items()):
        if "jaxpr_census" in r:
            out[f"dispatch.{name}"] = float(
                r.get("dispatch", {}).get("new_executables", 0))
        if name.startswith("warehouse_query") and "jaxpr_census" in r:
            t = r["jaxpr_census"]["totals"]
            out[f"scatter_ops.{name}"] = float(t["scatter_executed"])
    # aggregated ceiling over every fused-Pallas query engine: the
    # scatter floor the kernel breaks is pinned at literally ZERO, so
    # any single scatter creeping into any Pallas-path plan fails
    # --compare even if a new engine is registered without its own
    # per-engine baseline
    out["scatter_ops.query_pallas"] = float(sum(
        r["jaxpr_census"]["totals"]["scatter_executed"]
        for name, r in recs.items()
        if "_pallas" in name and "jaxpr_census" in r))
    return out


def main() -> None:
    from benchmarks import (ablation, common, cost_quality,
                            design_alternatives, forecaster_bench,
                            fused_ingest_bench, kernels_bench,
                            multi_stream_bench, offline_phase, overheads,
                            pool_scale_bench, roofline, sharded_warehouse_bench,
                            standing_query_bench, switcher_accuracy,
                            warehouse_bench)
    args = list(sys.argv[1:])
    json_out = compare_to = None
    for flag in ("--json", "--compare"):
        if flag in args:
            i = args.index(flag)
            if i + 1 >= len(args) or args[i + 1].startswith("--"):
                # --json defaults to the rolling head snapshot; --compare
                # has no sensible default (the baseline is the input)
                if flag == "--json":
                    json_out = "BENCH_HEAD.json"
                    del args[i:i + 1]
                    continue
                sys.exit(f"usage: run.py [only] [--json [OUT]] "
                         f"[--compare OLD.json] — missing {flag} value")
            if flag == "--json":
                json_out = args[i + 1]
            else:
                compare_to = args[i + 1]
            del args[i:i + 2]
    only = args[0] if args else None

    print("name,us_per_call,derived")
    # the engine benches with hard perf-floor asserts run first, while
    # a fresh process (and any host CPU-quota burst budget) gives the
    # least noisy timings
    modules = [
        ("fused_ingest", fused_ingest_bench),
        ("warehouse(Load)", warehouse_bench),
        ("sharded_warehouse(Load)", sharded_warehouse_bench),
        ("standing_queries(Load)", standing_query_bench),
        ("multi_stream(AppD)", multi_stream_bench),
        ("pool_scale", pool_scale_bench),
        ("overheads(Fig13)", overheads),
        ("offline_phase(Table3)", offline_phase),
        ("kernels", kernels_bench),
        ("roofline(g)", roofline),
        ("switcher_accuracy(Fig15/T4)", switcher_accuracy),
        ("forecaster(T5/T6/Fig14/18)", forecaster_bench),
        ("design_alternatives(AppB)", design_alternatives),
        ("ablation(Figs6-13)", ablation),
        ("cost_quality(Fig4/T2)", cost_quality),
    ]
    errors = {}
    for name, mod in modules:
        if only and only not in name:
            continue
        try:
            mod.run(verbose=True)
        except Exception as e:  # noqa: BLE001
            print(f"{name}/ERROR,0,{str(e)[:120]}")
            errors[name] = str(e)
            traceback.print_exc(file=sys.stderr)
    snap = {row["name"]: _metric_dict(row) for row in common.records()}
    # host fingerprint: floor metrics only gate against same-class hosts
    snap["host"] = {"host_cores": float(os.cpu_count() or 1)}
    for name, err in errors.items():
        snap[f"{name}/ERROR"] = {"error": err}
    if not only or only in "static_audit":
        try:
            snap["static_audit"] = _audit_record()
        except Exception as e:  # noqa: BLE001
            snap["static_audit/ERROR"] = {"error": str(e)}
            errors["static_audit"] = str(e)
            traceback.print_exc(file=sys.stderr)
    if json_out:
        with open(json_out, "w") as f:
            json.dump(snap, f, indent=2, sort_keys=True)
            f.write("\n")
        print(f"# wrote {len(snap)} bench records to {json_out}",
              file=sys.stderr)
    if compare_to:
        n_regressed = _compare(snap, compare_to)
        if n_regressed:
            sys.exit(1)
    if errors:
        # a module that raised recorded no metrics: its floors are gone
        # from the snapshot, so the run fails rather than pass without them
        sys.exit(f"run.py: {len(errors)} module(s) raised: "
                 f"{sorted(errors)}")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
