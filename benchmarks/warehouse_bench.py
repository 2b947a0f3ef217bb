"""Warehouse Load + query engine vs the pre-warehouse numpy host loop.

Without the Load layer, answering "which five-minute windows had the
worst quality above a confidence floor?" means re-walking the run's
trace on the host: a Python loop over time windows doing numpy masking
and aggregation per window. The warehouse answers the same question as
ONE compiled dispatch over the device-resident columnar store
(vmapped filter mask -> segment_sum window aggregation -> lax.top_k).

Reports:
  - ingest: device-side ``SegmentStore.ingest_fused`` throughput for a
    full fused run (zero per-segment host transfers), plus the
    ingest-to-first-query-answer latency (cold: includes the one-time
    plan compile; warm: the steady-state answer latency).
  - query: scan throughput over >=100k stored segments for a batch of
    Filter -> WindowAgg -> TopK queries with varying thresholds,
    vs the equivalent numpy host-loop baseline. Asserts >=5x speedup,
    ZERO recompiles across the repeated queries, and exact (fp32)
    agreement with the numpy reference.

    PYTHONPATH=src:. python benchmarks/warehouse_bench.py [--tiny]

``--tiny`` runs a seconds-scale smoke configuration (used by
``scripts/tier1.sh --bench-smoke``) that keeps the correctness and
zero-recompile assertions but skips the speedup floor.
"""
from __future__ import annotations

import sys
import time

import jax
import numpy as np

from benchmarks.common import emit
from benchmarks.fused_ingest_bench import _synthetic_fitted
from repro.configs.workloads import COVID
from repro.core import ingest as IG
from repro.data.stream import generate
from repro.warehouse import (Filter, SegmentStore, TopK, WindowAgg,
                             execute, execute_ref, windows_for)
from repro.warehouse import query as Q

N_CORES = 8
WINDOW = 60           # 2 minutes of 2 s segments per query window
N_QUERIES = 16
TOP_K = 10


def _plan(thr: float, nw: int):
    return (Filter("quality", "ge", thr),
            WindowAgg(window=WINDOW, value="quality", agg="mean",
                      num_windows=nw),
            TopK(TOP_K, by="quality"))


def _host_loop_query(cols, n_rows, thr, nw):
    """The pre-warehouse implementation: walk the windows on the host,
    numpy-masking the rows that belong to each, then sort for the top
    k. Like the compiled engine (which must serve multi-stream stores),
    it makes NO row-order assumption — window membership is a predicate
    on the t column, not a slice."""
    t = cols["t"][:n_rows]
    q = cols["quality"][:n_rows]
    qok = q >= thr                      # one pass, shared by all windows
    means = np.zeros(nw, np.float32)
    counts = np.zeros(nw, np.float32)
    wid = t // WINDOW
    for w in range(nw):
        keep = (wid == w) & qok
        c = keep.sum()
        counts[w] = c
        if c:
            means[w] = q[keep].astype(np.float32).sum() / c
    score = np.where(counts > 0, means, -np.inf)
    idx = np.argsort(-score, kind="stable")[:TOP_K]
    return idx, score[idx]


def run(verbose: bool = True, tiny: bool = False):
    days = 0.02 if tiny else 2.5
    fitted = _synthetic_fitted()
    tau = fitted.workload.segment_seconds
    K = len(fitted.configs)
    stream = generate(COVID, days=days, seed=3)
    T = stream.n_segments
    if not tiny:
        assert T >= 100_000, T
    W = 64 if tiny else 256
    kw = dict(n_cores=N_CORES, cloud_budget_core_s=5_000.0,
              plan_days=(W + 0.5) * tau / 86400, forecast_mode="oracle")

    # ---- ingest: fused run -> store, all on device --------------------
    # warm BOTH the engine and the T-specialized ingest kernel (on a
    # throwaway store) so the timed run measures device-side ingest
    # throughput, not one-time compiles
    warm = SegmentStore(out_dim=K, chunk_rows=T // 4)
    IG.run_skyscraper_fused(fitted, stream, sink=warm, **kw)
    jax.block_until_ready(warm.columns)
    # chunk size divides T: the query kernel scans no capacity padding
    store = SegmentStore(out_dim=K, chunk_rows=T // 4)
    t0 = time.perf_counter()
    IG.run_skyscraper_fused(fitted, stream, sink=store, **kw)
    jax.block_until_ready(store.columns)
    dt_ingest = time.perf_counter() - t0
    assert store.n_rows == T
    nw = windows_for(store, WINDOW)

    # ---- ingest-to-first-answer: cold (plan compiles) then warm -------
    t0 = time.perf_counter()
    jax.block_until_ready(execute(store, _plan(0.5, nw)))
    dt_first = time.perf_counter() - t0
    t0 = time.perf_counter()
    jax.block_until_ready(execute(store, _plan(0.5, nw)))
    dt_warm = time.perf_counter() - t0
    if verbose:
        emit(f"warehouse/ingest/T{T}", dt_ingest / T * 1e6,
             f"ingest={dt_ingest * 1e3:.1f}ms;"
             f"first_answer={dt_first * 1e3:.1f}ms;"
             f"warm_answer={dt_warm * 1e3:.2f}ms;rows={T}")

    # ---- query scan throughput vs the numpy host loop -----------------
    thrs = np.linspace(0.2, 0.8, N_QUERIES)
    cols_np = store.host_rows()

    cache0 = Q.compile_cache_size()
    t0 = time.perf_counter()
    for thr in thrs:
        table, mask = execute(store, _plan(float(thr), nw))
    jax.block_until_ready((table, mask))
    dt_jax = time.perf_counter() - t0
    recompiles = Q.compile_cache_size() - cache0
    assert recompiles == 0, f"{recompiles} recompiles across queries"

    t0 = time.perf_counter()
    for thr in thrs:
        idx_np, score_np = _host_loop_query(cols_np, store.n_rows,
                                            float(thr), nw)
    dt_np = time.perf_counter() - t0

    # correctness: the compiled answer == the numpy reference, exactly
    ref, rmask = execute_ref(cols_np, store.n_rows, _plan(float(thrs[-1]),
                                                          nw))
    assert np.array_equal(np.asarray(table["quality"]), ref["quality"])
    assert np.array_equal(np.asarray(table["window"]), ref["window"])
    assert np.array_equal(np.asarray(mask), rmask)
    # and the host-loop baseline agrees with it (same top windows)
    assert np.array_equal(idx_np[rmask], ref["window"][rmask])

    speedup = dt_np / dt_jax
    scanned = N_QUERIES * store.n_rows
    if verbose:
        emit(f"warehouse/query/T{T}_q{N_QUERIES}",
             dt_jax / N_QUERIES * 1e6,
             f"host_loop={dt_np * 1e3:.1f}ms;fused={dt_jax * 1e3:.1f}ms;"
             f"speedup={speedup:.1f}x;"
             f"scan={scanned / dt_jax / 1e6:.0f}Mrows/s;recompiles=0")
    if not tiny:
        assert speedup >= 5.0, \
            f"warehouse query must be >=5x the host loop, got {speedup:.1f}x"

    # ---- fused Pallas path: exactness + the broken scatter floor ------
    # interpret mode on CPU is a correctness path, so this leg records
    # the census (ZERO executed scatters for the groupby-style plan),
    # not a timing claim; on TPU the same kernel compiles natively.
    pplan = (Filter("quality", "ge", float(thrs[-1])),
             WindowAgg(window=WINDOW, value="quality", agg="mean",
                       num_windows=nw))
    pref, prmask = execute_ref(cols_np, store.n_rows, pplan)
    pt, pm = execute(store, pplan, use_pallas=True)
    assert np.array_equal(np.asarray(pm), prmask)
    assert np.array_equal(np.asarray(pt["count"]), pref["count"])
    assert np.allclose(np.asarray(pt["quality"]), pref["quality"],
                       rtol=1e-5, atol=1e-4)
    from repro.analysis import DEFAULT_INVARIANTS
    from repro.analysis.jaxpr_lint import lint_jaxpr, trace_closed_jaxpr
    spec, fvals = Q.normalize(pplan)
    args = (store.columns, np.int32(store.n_rows), fvals)
    _, census = lint_jaxpr(trace_closed_jaxpr(
        lambda c, n, fv: Q._run_plan(c, n, fv, spec=spec,
                                     use_pallas=True), args, {}),
        DEFAULT_INVARIANTS)
    n_scatter = census["totals"]["scatter_executed"]
    assert n_scatter == 0, f"Pallas query path executes {n_scatter} scatters"
    if verbose:
        emit(f"warehouse/query_pallas/T{T}", 0.0,
             f"scatter_ops=0;exact=count,window;mean_rtol=1e-5;"
             f"interpret={jax.default_backend() != 'tpu'}")
    return [speedup]


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    print("name,us_per_call,derived")
    run(tiny="--tiny" in sys.argv[1:])
