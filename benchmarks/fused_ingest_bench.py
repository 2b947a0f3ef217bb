"""Fused whole-run ingestion engine vs the windowed host loop.

The windowed ``run_skyscraper`` dispatches one window scan per planning
window and does its forecast/LP/label bookkeeping in host numpy between
windows, so a T-segment run costs T/W python round-trips. The fused
engine (``run_skyscraper_fused``) lowers forecast -> LP -> switch into
ONE ``lax.scan`` program: a whole run is a single dispatch and exactly
one compiled executable after warmup. Reports wall-clock for both,
speedup, per-decision cost, and the fused jit cache size.

    PYTHONPATH=src:. python benchmarks/fused_ingest_bench.py [--tiny]

``--tiny`` runs a seconds-scale smoke configuration (used by
``scripts/tier1.sh --bench-smoke`` so this path cannot silently rot).
"""
from __future__ import annotations

import sys
import time

import jax
import numpy as np

from benchmarks.common import emit
from repro.configs.workloads import COVID
from repro.core import ingest as IG
from repro.core.forecaster import init_forecaster
from repro.core.offline import Fitted
from repro.data.stream import generate

N_CORES = 8


def _synthetic_fitted(K=8, C=4, n_split=4, interval=64, seed=0) -> Fitted:
    """A Fitted profile with controlled shapes — skips the (expensive)
    offline phase; the online engines only read its tables."""
    rng = np.random.default_rng(seed)
    tau = COVID.segment_seconds
    power = np.sort(rng.random(K)).astype(np.float32)
    cost = np.sort(rng.random(K) * 20 + 0.5).astype(np.float32)
    cost[0] = min(cost[0], N_CORES * tau * 0.9)   # throughput guarantee
    rt = np.stack([cost / N_CORES, cost / N_CORES * 0.6,
                   cost / N_CORES * 0.3], 1)
    cl = np.stack([np.zeros(K), cost * 0.4, cost * 0.7], 1)
    on = np.stack([cost, cost * 0.6, cost * 0.3], 1)
    centers = np.sort(rng.random((C, K)), axis=0).astype(np.float32)
    params = init_forecaster(jax.random.PRNGKey(seed), n_split, C)
    return Fitted(workload=COVID, configs=[{"cfg": i} for i in range(K)],
                  power=power, cost=cost, place_rt=rt, place_on=on,
                  place_cl=cl, place_valid=np.ones((K, 3), bool),
                  centers=centers, forecaster=params, n_split=n_split,
                  interval_segments=interval, horizon_segments=256,
                  n_cores=N_CORES)


def _bench_one(fitted, stream, W, mode, verbose):
    tau = fitted.workload.segment_seconds
    T = stream.n_segments
    # +0.5 so float division can never floor the window length to W-1
    kw = dict(n_cores=N_CORES, cloud_budget_core_s=5_000.0,
              plan_days=(W + 0.5) * tau / 86400, forecast_mode=mode)

    # best-of-3 on both sides: single-shot timings flake badly on
    # shared/throttled CPUs, and a perf floor should compare the
    # engines, not the noisy-neighbor schedule
    IG.run_skyscraper(fitted, stream, **kw)               # warmup
    dt_loop = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        ref = IG.run_skyscraper(fitted, stream, **kw)
        dt_loop = min(dt_loop, time.perf_counter() - t0)

    IG.run_skyscraper_fused(fitted, stream, **kw)         # warmup
    cache = IG.fused_cache_size()
    dt_fused = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        got = IG.run_skyscraper_fused(fitted, stream, **kw)
        dt_fused = min(dt_fused, time.perf_counter() - t0)
    recompiles = IG.fused_cache_size() - cache

    assert abs(got.quality_sum - ref.quality_sum) \
        < 1e-3 * max(abs(ref.quality_sum), 1.0), \
        f"fused diverged: {ref.quality_sum} vs {got.quality_sum}"
    assert recompiles == 0, f"{recompiles} recompiles after warmup"
    assert cache == 1, f"expected ONE fused executable, cache={cache}"
    speedup = dt_loop / dt_fused
    if verbose:
        emit(f"fused_ingest/{mode}/T{T}_W{W}",
             dt_fused / T * 1e6,
             f"loop={dt_loop * 1e3:.1f}ms;fused={dt_fused * 1e3:.1f}ms;"
             f"speedup={speedup:.1f}x;windows={-(-T // W)};"
             f"fused_cache={cache}")
    return speedup


def run(verbose: bool = True, tiny: bool = False):
    fitted = _synthetic_fitted()
    if tiny:
        stream = generate(COVID, days=0.02, seed=3)       # T = 864
        speedup = _bench_one(fitted, stream, 64, "model", verbose)
        return [speedup]
    stream = generate(COVID, days=0.25, seed=3)           # T = 10800
    assert stream.n_segments >= 10_000
    speedup = _bench_one(fitted, stream, 128, "model", verbose)
    assert speedup >= 5.0, \
        f"fused engine must be >=5x the windowed loop, got {speedup:.1f}x"
    return [speedup]


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    print("name,us_per_call,derived")
    run(tiny="--tiny" in sys.argv[1:])
