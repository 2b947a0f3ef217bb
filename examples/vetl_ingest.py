"""Figure-3 style end-to-end V-ETL run: 24 h of a synthetic traffic
stream on constrained hardware with buffering + cloud bursting.

    PYTHONPATH=src python examples/vetl_ingest.py

Whole-run fused engine: ``run_skyscraper_fused`` compiles the ENTIRE
online phase — forecast, LP planning, and reactive switching for every
planning window — into one ``lax.scan`` program, so a T-segment run is
a single dispatch instead of T/W host round-trips (>=5x faster at
T>=10k, see benchmarks/fused_ingest_bench.py) and reproduces the
windowed loop's results to float32 tolerance::

    from repro.core import ingest as IG
    from repro.core.offline import fit
    from repro.data.stream import generate

    fitted = fit(COVID, n_cores=8, days_unlabeled=6.0)
    stream = generate(COVID, days=1.0, seed=99)
    res = IG.run_skyscraper_fused(fitted, stream, n_cores=8,
                                  cloud_budget_core_s=15_000.0,
                                  forecast_mode="model")   # | oracle | uniform
    print(res.quality_pct, res.cloud_core_s)

Multi-stream ingestion (paper App. D) gets the same treatment: the
joint LP over all streams' categories runs ON DEVICE inside the outer
scan (``solve_lp_stacked`` on the sentinel-padded (V, C_max, K) category
stack), so ``run_skyscraper_multi`` performs zero host planning work::

    streams = [generate(COVID, days=1.0, seed=s) for s in range(8)]
    res = IG.run_skyscraper_multi([fitted] * 8, streams, n_cores_each=8,
                                  cloud_budget_core_s=8000.0)
    print(res["quality_pct"], res["per_stream_pct"])

For online serving (one decision per arriving segment across V live
cameras in a single dispatch) use ``repro.core.api.SkyscraperPool`` —
it runs on the same fused planning engine: per-stream label histories
live in a device-side rolling buffer and replanning is one compiled
vmapped forecast + LP call.
"""
import sys
import os
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np

from repro.configs.workloads import COVID
from repro.core import ingest as IG
from repro.core.offline import fit
from repro.data.stream import generate


def sparkline(xs, width=64):
    xs = np.asarray(xs, float)
    xs = xs[:: max(1, len(xs) // width)]
    lo, hi = xs.min(), xs.max()
    ticks = " .:-=+*#%@"
    if hi - lo < 1e-9:
        return ticks[0] * len(xs)
    return "".join(ticks[int((x - lo) / (hi - lo) * (len(ticks) - 1))]
                   for x in xs)


def main():
    print("== offline phase (fit on 6 days of historical stream) ==")
    fitted = fit(COVID, n_cores=8, days_unlabeled=6.0, n_categories=4)
    print(f"K={len(fitted.configs)} Pareto configs, costs="
          f"{np.round(fitted.cost, 2)} core-s/seg")
    print(f"forecaster val MAE: {fitted.forecast_metrics['val_mae']:.4f}")

    print("\n== online: 24h ingestion, 8 cores + 4GB buffer + cloud ==")
    print("   (fused engine: the whole day is ONE compiled scan)")
    stream = generate(COVID, days=1.0, seed=99)
    res = IG.run_skyscraper_fused(fitted, stream, n_cores=8,
                                  cloud_budget_core_s=15_000.0,
                                  buffer_gb=4.0, plan_days=0.25)
    k = IG.best_static_config(fitted, 8)
    static = IG.run_static(fitted, stream, k, n_cores=8)
    opt = IG.run_optimum(fitted, stream, n_cores=8,
                         cloud_budget_core_s=15_000.0)

    print(f"skyscraper quality: {res.quality_pct:6.2f}%  "
          f"(work {res.work_core_s / 1e3:.0f}k core-s, "
          f"cloud {res.cloud_core_s:.0f} core-s)")
    print(f"static-best quality: {static.quality_pct:6.2f}%")
    print(f"optimum (oracle):    {opt.quality_pct:6.2f}%")
    print(f"knob switches: "
          f"{int((np.diff(res.k_trace) != 0).sum())} over "
          f"{len(res.k_trace)} segments")
    print("\nbuffer fill over the day (paper Fig. 3, third panel):")
    print("  " + sparkline(res.buffer_trace))
    print("difficulty (content) over the day:")
    print("  " + sparkline(stream.difficulty))
    print("chosen config cost over the day (second panel):")
    print("  " + sparkline(fitted.cost[res.k_trace]))
    assert res.quality_pct > static.quality_pct
    print("\nOK: content-adaptive ingestion beat the static baseline.")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
