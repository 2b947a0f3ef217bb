"""Roofline report: reads the dry-run JSON and prints the per-cell
three-term roofline table (deliverable g)."""
from __future__ import annotations

import json
import os

from benchmarks.common import RESULTS_DIR, emit


def run(verbose: bool = True, path: str = None, tag: str = "baseline",
        mesh: str = "16x16"):
    path = path or os.path.join(RESULTS_DIR, "dryrun.json")
    if not os.path.exists(path):
        if verbose:
            emit("roofline/missing", 0, f"run repro.launch.dryrun first ({path})")
        return []
    with open(path) as f:
        recs = json.load(f)
    rows = []
    for r in recs:
        if r.get("tag") != tag or r.get("mesh") != mesh:
            continue
        name = f"roofline/{r['arch']}/{r['shape']}"
        if "skipped" in r:
            if verbose:
                emit(name, 0, f"SKIP:{r['skipped'][:60]}")
            continue
        if "error" in r:
            if verbose:
                emit(name, 0, f"ERROR:{r['error'][:60]}")
            continue
        rl = r["roofline"]
        rows.append(r)
        if verbose:
            emit(name, rl["bound_s"] * 1e6,
                 f"dom={rl['dominant']};comp={rl['compute_s']:.4f}s"
                 f";mem={rl['memory_s']:.4f}s"
                 f";coll={rl['collective_s']:.4f}s"
                 f";useful={r['useful_ratio']:.2f}"
                 f";mfu_bound={min(1.0, r['model_flops_per_device'] / max(rl['bound_s'], 1e-12) / 197e12):.3f}")
    return rows


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    run()
