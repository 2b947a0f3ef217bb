"""Paper §5.4 (Figs. 6-13): ablate buffering and cloud bursting
independently, across cloud:on-prem cost ratios, plus the work-quality
comparison against the ground-truth Optimum (2a/2b/2c)."""
from __future__ import annotations

import numpy as np

from benchmarks.common import emit, fitted, stream
from repro.core import ingest as IG

VARIANTS = {
    "no_buffer_no_cloud": dict(buffer_gb=1e-6, cloud=0.0),
    "only_buffer": dict(buffer_gb=4.0, cloud=0.0),
    "only_cloud": dict(buffer_gb=1e-6, cloud=None),   # None -> generous
    "buffer_and_cloud": dict(buffer_gb=4.0, cloud=None),
}


def run(verbose: bool = True):
    rows = []
    for wname in ("covid", "mosei-high", "mosei-long"):
        ncat = 3 if wname == "covid" else 5
        # low provisioning — the regime where buffering/cloud matter
        cores = 4
        f = fitted(wname, cores, ncat)
        s = stream(wname, days=1.0)
        for vname, v in VARIANTS.items():
            cloud = v["cloud"] if v["cloud"] is not None else cores * 2000.0
            res = IG.run_skyscraper(f, s, n_cores=cores,
                                    cloud_budget_core_s=cloud,
                                    buffer_gb=v["buffer_gb"],
                                    plan_days=0.25)
            rows.append((wname, vname, res.quality_pct, res.work_core_s,
                         res.cloud_core_s))
            if verbose:
                emit(f"ablation/{wname}/{vname}", res.work_core_s,
                     f"quality={res.quality_pct:.1f}%"
                     f";cloud_core_s={res.cloud_core_s:.0f}")
        # work-quality vs optimum (Figs 7/9/11/13)
        opt = IG.run_optimum(f, s, n_cores=cores,
                             cloud_budget_core_s=cores * 2000.0)
        k = IG.best_static_config(f, cores)
        stat = IG.run_static(f, s, k, n_cores=cores)
        full = IG.run_skyscraper(f, s, n_cores=cores,
                                 cloud_budget_core_s=cores * 2000.0,
                                 plan_days=0.25)
        if verbose:
            emit(f"ablation/{wname}/work_static", stat.work_core_s,
                 f"quality={stat.quality_pct:.1f}%")
            emit(f"ablation/{wname}/work_skyscraper", full.work_core_s,
                 f"quality={full.quality_pct:.1f}%")
            emit(f"ablation/{wname}/work_optimum", opt.work_core_s,
                 f"quality={opt.quality_pct:.1f}%")
    return rows


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    run()
