"""Standing queries at ingest rate vs the rescan loop.

Without the standing registry, keeping N registered queries fresh
means re-executing N full-store scans after every ingest tick — the
per-tick cost grows with BOTH the query count and the stored row
count. The registry folds every query's partial inside the ingest
dispatch itself (one vmapped fold for all same-shape queries, zero
extra dispatches) and answers from the maintained accumulators in
O(result), so the per-tick refresh cost is flat in the store size.

Reports, for 1000 registered same-shape queries (distinct thresholds):
  - standing: per-tick cost of ingest-with-fold + a whole-group answer
    snapshot (every query's table refreshed), with ZERO warm
    recompiles asserted across the timed ticks.
  - rescan: per-tick cost of the same ingest plus the query engine's
    zero-recompile rescan loop over the 1000 thresholds (the
    pre-standing implementation; itself already compiled + warm).
  - speedup: rescan / standing per-tick cost. Asserts >=10x, and
    bit-exact (fp32) agreement of standing answers with the numpy
    reference.

    PYTHONPATH=src:. python benchmarks/standing_query_bench.py [--tiny]

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
from repro.core.switcher import compile_cache_sizes
from repro.warehouse import (Filter, GroupBy, SegmentStore,
                             StandingQueries, execute, execute_ref)

N_QUERIES = 1000
N_GROUPS = 16
BATCH = 512
N_TICKS = 8
N_TICKS_RESCAN = 2


def _plan(thr: float):
    return (Filter("quality", "ge", float(thr)),
            GroupBy("category", "quality", agg="sum",
                    num_groups=N_GROUPS))


def _batches(n_ticks, batch, seed=0):
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n_ticks):
        n = batch
        out.append({
            "stream_id": rng.integers(0, 8, n).astype(np.int32),
            "t": (i * n + np.arange(n)).astype(np.int32),
            "category": rng.integers(0, N_GROUPS, n).astype(np.int32),
            "k": rng.integers(0, 4, n).astype(np.int32),
            "quality": rng.random(n).astype(np.float32),
            "on_core_s": (rng.random(n) * 20).astype(np.float32),
            "cloud_core_s": (rng.random(n) * 5).astype(np.float32),
            "buffer_s": (rng.random(n) * 40).astype(np.float32),
            "out": rng.random((n, 4)).astype(np.float32),
        })
    return out


def run(verbose: bool = True, tiny: bool = False):
    n_q = 64 if tiny else N_QUERIES
    batch = 128 if tiny else BATCH
    n_ticks = 3 if tiny else N_TICKS
    n_ticks_rescan = 1 if tiny else N_TICKS_RESCAN
    thrs = np.linspace(0.05, 0.95, n_q)
    # capacity covers every tick of both legs: no growth recompiles in
    # the timed region (growth is bucketed + pinned by its own test)
    cap = batch * (2 * n_ticks + n_ticks_rescan + 4)

    # ---- standing leg: register 1k queries on the EMPTY store --------
    # (registration backfill is skipped when there is nothing to scan;
    # every row's contribution arrives through the in-dispatch fold)
    store = SegmentStore(out_dim=4, chunk_rows=cap)
    reg = StandingQueries(store)
    t0 = time.perf_counter()
    handles = [reg.register(_plan(t)) for t in thrs]
    dt_reg = time.perf_counter() - t0
    (group,) = reg._groups.values()
    assert group.q == n_q

    ticks = _batches(2 * n_ticks, batch, seed=1)
    warm, timed = ticks[:n_ticks], ticks[n_ticks:]
    for rows in warm:                     # compile fold + answer once
        store.append_rows(rows)
    jax.block_until_ready(reg.group_answers(group))

    cache0 = sum(compile_cache_sizes().values())
    t0 = time.perf_counter()
    for rows in timed:
        store.append_rows(rows)          # fold rides the one dispatch
        table, mask = reg.group_answers(group)   # all n_q answers
    jax.block_until_ready((table, mask))
    dt_standing = (time.perf_counter() - t0) / n_ticks
    recompiles = sum(compile_cache_sizes().values()) - cache0
    assert recompiles == 0, \
        f"{recompiles} recompiles across warm standing ticks"

    # ---- rescan leg: same ingest, query engine re-executed per query --
    rescan = SegmentStore(out_dim=4, chunk_rows=cap)
    for rows in ticks:                   # same rows, same store size
        rescan.append_rows(rows)
    jax.block_until_ready(execute(rescan, _plan(thrs[0])))   # warm
    cache0 = sum(compile_cache_sizes().values())
    extra = _batches(n_ticks_rescan, batch, seed=2)
    t0 = time.perf_counter()
    for rows in extra:
        rescan.append_rows(rows)
        for thr in thrs:
            rtable, rmask = execute(rescan, _plan(thr))
    jax.block_until_ready((rtable, rmask))
    dt_rescan = (time.perf_counter() - t0) / n_ticks_rescan
    assert sum(compile_cache_sizes().values()) == cache0, \
        "rescan loop recompiled (unfair baseline)"

    # ---- correctness: standing == numpy reference, bit-exact ----------
    cols = store.host_rows()
    for i in (0, n_q // 2, n_q - 1):
        table, mask = reg.answer(handles[i])
        ref, rm = execute_ref(cols, store.n_rows, _plan(thrs[i]))
        assert np.array_equal(np.asarray(mask), rm)
        assert np.array_equal(np.asarray(table["quality"]),
                              ref["quality"])
        assert np.array_equal(np.asarray(table["count"]), ref["count"])

    speedup = dt_rescan / dt_standing
    if verbose:
        emit(f"standing/refresh/q{n_q}", dt_standing * 1e6,
             f"standing_tick={dt_standing * 1e3:.2f}ms;"
             f"rescan_tick={dt_rescan * 1e3:.1f}ms;"
             f"speedup={speedup:.1f}x;recompiles=0;"
             f"register={dt_reg * 1e3:.0f}ms;rows={store.n_rows}")
    if not tiny:
        assert speedup >= 10.0, \
            f"standing refresh must be >=10x the rescan loop at " \
            f"{n_q} queries, got {speedup:.1f}x"
    return [speedup]


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    print("name,us_per_call,derived")
    run(tiny="--tiny" in sys.argv[1:])
