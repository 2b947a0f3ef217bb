"""Sharded warehouse: query scan throughput vs shard count.

The partial/merge engine's point is horizontal scale: the same plan
(Filter -> WindowAgg -> TopK) over the same rows, executed by a
``ShardedStore`` at 1/2/4/8 shards — each shard scans its own rows in
parallel (its own device) and the merge combiner reduces the
fixed-shape partials. Reports per-shard-count scan throughput plus the
``sharded_query_bench`` summary row: the shard-count scaling curve and
the 8-shard speedup over the 1-shard engine.

It runs in the calling process on the devices that process has, and
fails when there are fewer devices than the largest shard count: on a
CPU host give it ``XLA_FLAGS=--xla_force_host_platform_device_count=8``
before jax initializes (``scripts/tier1.sh --bench-smoke`` does).

``--tiny`` is the seconds-scale smoke configuration (correctness +
zero-recompile assertions, no speedup floor). The full run asserts the
8-shard engine >= 2x the 1-shard engine and exact-count / tolerant-sum
agreement with the numpy reference.
"""
from __future__ import annotations

import os
import sys

N_QUERIES = 16
WINDOW = 500
TOP_K = 10


def run(verbose: bool = True, tiny: bool = False):
    """Scan-throughput curve over 1..8 shards plus the fused Pallas
    partial check; returns ``[speedup8]``."""
    import time

    import jax
    import numpy as np

    from repro.warehouse import (Filter, ShardedStore, TopK, WindowAgg,
                                 execute_ref, windows_for)
    from repro.warehouse import query as Q

    from benchmarks.common import emit

    def row(name, us, derived):
        if verbose:
            emit(name, us, derived)

    counts = (1, 8) if tiny else (1, 2, 4, 8)
    if jax.device_count() < counts[-1]:
        raise RuntimeError(
            f"sharded warehouse bench needs {counts[-1]} devices, found "
            f"{jax.device_count()} ({jax.default_backend()})")
    T = 16_000 if tiny else 240_000
    n_streams = 64                      # divisible by every shard count
    rng = np.random.default_rng(7)
    rows = {
        "stream_id": (np.arange(T, dtype=np.int32) % n_streams),
        "t": np.arange(T, dtype=np.int32),
        "category": rng.integers(0, 4, T).astype(np.int32),
        "k": rng.integers(0, 4, T).astype(np.int32),
        "quality": rng.random(T).astype(np.float32),
        "on_core_s": (rng.random(T) * 20).astype(np.float32),
        "cloud_core_s": (rng.random(T) * 5).astype(np.float32),
        "buffer_s": (rng.random(T) * 40).astype(np.float32),
        "out": rng.random((T, 4)).astype(np.float32),
    }

    def plan(thr, nw):
        return (Filter("quality", "ge", thr),
                WindowAgg(window=WINDOW, value="quality", agg="mean",
                          num_windows=nw),
                TopK(TOP_K, by="quality"))

    thrs = np.linspace(0.2, 0.8, N_QUERIES)
    thr_mrows = {}
    for S in counts:
        # chunk = exact per-shard rows: the scan covers zero padding at
        # every shard count, so the curve isolates the engine
        store = ShardedStore(out_dim=4, n_shards=S, chunk_rows=T // S)
        assert store.mesh is not None, \
            f"need {S} devices, have {jax.device_count()}"
        store.append_rows(rows)
        assert store.capacity == T // S, store
        nw = windows_for(store, WINDOW)
        jax.block_until_ready(store.query(plan(0.5, nw)))   # warm
        cache0 = Q.sharded_compile_cache_size()
        best = float("inf")
        for _ in range(1 if tiny else 3):     # best-of: CPU-quota noise
            t0 = time.perf_counter()
            for thr in thrs:
                table, mask = store.query(plan(float(thr), nw))
            jax.block_until_ready((table, mask))
            best = min(best, time.perf_counter() - t0)
        assert Q.sharded_compile_cache_size() == cache0, "recompiled"
        ref, rmask = execute_ref(store.host_rows(), T,
                                 plan(float(thrs[-1]), nw))
        np.testing.assert_array_equal(np.asarray(table["count"]),
                                      ref["count"])
        np.testing.assert_allclose(np.asarray(table["quality"]),
                                   ref["quality"], rtol=1e-5, atol=1e-5)
        np.testing.assert_array_equal(np.asarray(mask), rmask)
        thr_mrows[S] = N_QUERIES * T / best / 1e6
        row(f"warehouse_sharded/query/S{S}_T{T}", best / N_QUERIES * 1e6,
            f"scan={thr_mrows[S]:.1f}Mrows/s;shards={S};recompiles=0")
    speedup = thr_mrows[counts[-1]] / thr_mrows[1]
    cores = os.cpu_count() or 1
    curve = ";".join(f"s{S}={thr_mrows[S]:.1f}Mrows/s" for S in counts)
    row("sharded_query_bench", 0.0,
        f"{curve};speedup8={speedup:.2f}x;host_cores={cores};"
        f"rows={T};recompiles=0")
    # the scan is compute-bound, so S shards can only beat 1 shard by
    # min(S, physical cores): enforce the 8-shard >=2x floor where the
    # host can physically run >=8 shard devices in parallel (an 8-core
    # box); on smaller hosts the curve itself is the artifact (e.g. a
    # 2-core container tops out around 2x at 4 shards / ~1.4x at 8,
    # where 8 runtime threads thrash 2 cores)
    if not tiny and cores >= 8:
        assert speedup >= 2.0, \
            f"8-shard engine must be >=2x the 1-shard engine, got " \
            f"{speedup:.2f}x"

    # ---- fused Pallas partials inside the shard_map dispatch ----------
    # same plan minus the TopK post node (runs after the merge), each
    # shard's partial through the fused kernel: exactness vs the numpy
    # reference plus a zero-scatter census of the per-shard kernel —
    # the scatter floor stays broken under sharding.
    import jax.numpy as jnp

    from repro.analysis import DEFAULT_INVARIANTS
    from repro.analysis.jaxpr_lint import lint_jaxpr, trace_closed_jaxpr
    S = counts[-1]
    store = ShardedStore(out_dim=4, n_shards=S, chunk_rows=T // S)
    store.append_rows(rows)
    nw = windows_for(store, WINDOW)
    pplan = plan(0.5, nw)[:2]
    ptable, pmask = store.query(pplan, use_pallas=True)
    pref, prmask = execute_ref(store.host_rows(), T, pplan)
    np.testing.assert_array_equal(np.asarray(pmask), prmask)
    np.testing.assert_array_equal(np.asarray(ptable["count"]),
                                  pref["count"])
    np.testing.assert_allclose(np.asarray(ptable["quality"]),
                               pref["quality"], rtol=1e-5, atol=1e-4)
    spec, fvals = Q.normalize(pplan)
    pre, node, _post = Q.split_plan(spec)
    shard_cols = {k: v[0] for k, v in store.columns.items()}
    _, census = lint_jaxpr(trace_closed_jaxpr(
        lambda c, n, fv: Q._shard_partial_pallas(c, n, fv, jnp.int32(0),
                                                 pre=pre, node=node),
        (shard_cols, jnp.int32(T // S), fvals), {}), DEFAULT_INVARIANTS)
    n_scatter = census["totals"]["scatter_executed"]
    assert n_scatter == 0, \
        f"sharded Pallas partial executes {n_scatter} scatters"
    row(f"warehouse_sharded/query_pallas/S{S}_T{T}", 0.0,
        f"scatter_ops=0;shards={S};exact=count;mean_rtol=1e-5")
    return [speedup]


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    print("name,us_per_call,derived")
    run(tiny="--tiny" in sys.argv[1:])
