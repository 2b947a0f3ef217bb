"""Paper Table 3 (App. E): runtime of each offline-phase step."""
from __future__ import annotations

from benchmarks.common import emit
from repro.configs.workloads import COVID
from repro.core.offline import fit


def run(verbose: bool = True):
    f = fit(COVID, n_cores=8, days_unlabeled=8.0, n_categories=4, seed=0)
    total = sum(f.timings.values())
    for step, sec in f.timings.items():
        if verbose:
            emit(f"offline/{step}", sec * 1e6,
                 f"{sec:.2f}s ({100 * sec / total:.0f}% of offline)")
    if verbose:
        emit("offline/total", total * 1e6,
             f"{total:.2f}s; forecaster val_mae="
             f"{f.forecast_metrics['val_mae']:.4f}; K={len(f.configs)}")
    return f.timings


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    run()
