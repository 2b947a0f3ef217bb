"""Paper Fig. 15 + Table 4 (§5.6): knob-switcher content-classification
accuracy, the Type-A (1-D projection) vs Type-B (timing lag) error
split, and accuracy vs the number of content categories."""
from __future__ import annotations

import numpy as np

from benchmarks.common import emit, fitted, stream
from repro.configs.workloads import COVID
from repro.core import ingest as IG
from repro.core.offline import fit
from repro.data.stream import generate


def run(verbose: bool = True):
    rows = []
    for wname in ("covid", "mot"):
        f = fitted(wname, 8, 3)     # paper App. K: 3 categories
        s = stream(wname, days=1.0)
        res = IG.run_skyscraper(f, s, n_cores=8,
                                cloud_budget_core_s=5000.0, plan_days=0.25)
        quals = s.quality(f.power, seed=0)
        d = ((quals[:, None, :] - f.centers[None]) ** 2).sum(-1)
        true_cat = d.argmin(1)                 # category of each segment
        pred = res.c_trace
        T = len(pred)
        # switcher classifies segment t from segment t-1's quality:
        total_err = (pred[1:] != true_cat[1:]).mean()
        # Type-B: the content actually changed between t-1 and t
        type_b = ((true_cat[:-1] != true_cat[1:])
                  & (pred[1:] == true_cat[:-1])).mean()
        type_a = total_err - type_b
        rows.append((wname, total_err, type_a, type_b))
        if verbose:
            emit(f"switcher_acc/{wname}/total_err", total_err * 1e6,
                 f"err={total_err * 100:.2f}%  (paper: 2.1% covid, "
                 f"6.6% mot)")
            emit(f"switcher_acc/{wname}/type_a", max(type_a, 0) * 1e6,
                 f"typeA={max(type_a, 0) * 100:.2f}%")
            emit(f"switcher_acc/{wname}/type_b", type_b * 1e6,
                 f"typeB={type_b * 100:.2f}%")
    # Table 4: accuracy vs number of categories
    for ncat in (1, 2, 3, 4, 8):
        f = fit(COVID, n_cores=8, days_unlabeled=6.0, n_categories=ncat,
                seed=0)
        s = generate(COVID, days=0.5, seed=5)
        res = IG.run_skyscraper(f, s, n_cores=8,
                                cloud_budget_core_s=5000.0, plan_days=0.25)
        quals = s.quality(f.power, seed=0)
        d = ((quals[:, None, :] - f.centers[None]) ** 2).sum(-1)
        true_cat = d.argmin(1)
        acc = (res.c_trace[1:] == true_cat[1:]).mean()
        if verbose:
            emit(f"switcher_acc/covid/ncat{ncat}", acc * 1e6,
                 f"acc={acc * 100:.1f}%;quality={res.quality_pct:.1f}%")
    return rows


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    run()
