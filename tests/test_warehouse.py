"""The Load subsystem: device-resident columnar store, compiled query
plans vs the numpy reference, zero-recompile guarantees, hot/cold
tiering, and checkpoint persistence."""

import jax
import numpy as np

from benchmarks.fused_ingest_bench import _synthetic_fitted
from repro.configs.workloads import COVID
from repro.core import ingest as IG
from repro.data.stream import generate
from repro.warehouse import (Filter, GroupBy, MultiGroupBy, Project,
                             SegmentStore, TieredStore, TopK, WindowAgg,
                             execute, execute_ref, load_warehouse,
                             save_warehouse, to_host, windows_for)
from repro.warehouse import query as Q

N_CORES = 8  # matches the profile baked into _synthetic_fitted


def _random_rows(n, D, seed=0, t0=0):
    rng = np.random.default_rng(seed)
    return {
        "stream_id": rng.integers(0, 4, n).astype(np.int32),
        "t": (t0 + np.arange(n)).astype(np.int32),
        "category": rng.integers(0, 4, n).astype(np.int32),
        "k": rng.integers(0, D, n).astype(np.int32),
        "quality": rng.random(n).astype(np.float32),
        "on_core_s": (rng.random(n) * 20).astype(np.float32),
        "cloud_core_s": (rng.random(n) * 5).astype(np.float32),
        "buffer_s": (rng.random(n) * 40).astype(np.float32),
        "out": rng.random((n, D)).astype(np.float32),
    }


def _host_cols(store):
    return {k: np.asarray(v) for k, v in store.columns.items()}


# ---------------------------------------------------------------------------
# ingestion
# ---------------------------------------------------------------------------

def test_fused_sink_matches_run_traces():
    """A full fused run lands in the store with exactly the traces the
    RunResult reports, and the output column carries the (T, K) quality
    vectors. Everything in the store is a device array."""
    fitted = _synthetic_fitted()
    stream = generate(COVID, days=0.02, seed=3)            # T = 864
    T = stream.n_segments
    K = len(fitted.configs)
    tau = fitted.workload.segment_seconds
    store = SegmentStore(out_dim=K, chunk_rows=512)
    res = IG.run_skyscraper_fused(
        fitted, stream, n_cores=N_CORES, cloud_budget_core_s=5_000.0,
        plan_days=64.5 * tau / 86400, forecast_mode="model", sink=store)
    assert store.n_rows == T and store.t_max == T - 1
    assert all(isinstance(v, jax.Array) for v in store.columns.values())
    h = store.host_rows()
    np.testing.assert_array_equal(h["k"], res.k_trace)
    np.testing.assert_array_equal(h["category"], res.c_trace)
    np.testing.assert_allclose(h["buffer_s"], res.buffer_trace, rtol=1e-6)
    np.testing.assert_allclose(h["quality"].sum(), res.quality_sum,
                               rtol=1e-5)
    quals = np.asarray(stream.quality(fitted.power, seed=0), np.float32)
    np.testing.assert_array_equal(h["out"], quals[:T])
    np.testing.assert_array_equal(h["stream_id"], np.zeros(T, np.int32))
    np.testing.assert_array_equal(h["t"], np.arange(T, dtype=np.int32))


def test_sink_appends_across_runs_and_grows():
    """Two runs append (chunked growth), each under its own stream id."""
    fitted = _synthetic_fitted()
    K = len(fitted.configs)
    tau = fitted.workload.segment_seconds
    store = SegmentStore(out_dim=K, chunk_rows=500)
    kw = dict(n_cores=N_CORES, plan_days=64.5 * tau / 86400,
              forecast_mode="uniform")
    s0 = generate(COVID, days=0.02, seed=3)
    s1 = generate(COVID, days=0.01, seed=4)
    IG.run_skyscraper_fused(fitted, s0, sink=store, sink_stream_id=0, **kw)
    IG.run_skyscraper_fused(fitted, s1, sink=store, sink_stream_id=7, **kw)
    T0, T1 = s0.n_segments, s1.n_segments
    assert store.n_rows == T0 + T1
    assert store.capacity % 500 == 0 and store.capacity >= T0 + T1
    h = store.host_rows()
    np.testing.assert_array_equal(
        h["stream_id"], np.r_[np.zeros(T0, np.int32),
                              np.full(T1, 7, np.int32)])
    np.testing.assert_array_equal(h["t"][T0:], np.arange(T1))


def test_multi_sink_stream_major_rows():
    fitted = _synthetic_fitted()
    K = len(fitted.configs)
    tau = fitted.workload.segment_seconds
    V = 3
    streams = [generate(COVID, days=0.01, seed=s) for s in range(V)]
    T = min(s.n_segments for s in streams)
    store = SegmentStore(out_dim=K, chunk_rows=512)
    IG.run_skyscraper_multi([fitted] * V, streams, n_cores_each=N_CORES,
                            cloud_budget_core_s=900.0,
                            plan_days=64 * tau / 86400, sink=store,
                            sink_stream_base=10)
    assert store.n_rows == V * T
    h = store.host_rows()
    np.testing.assert_array_equal(
        h["stream_id"], np.repeat(np.arange(10, 10 + V, dtype=np.int32), T))
    np.testing.assert_array_equal(h["t"], np.tile(np.arange(T), V))
    # padding never lands: every row's quality is a real measured value
    assert h["quality"].min() >= 0.0 and store.t_max == T - 1


def test_pool_sink_one_row_per_stream_per_tick():
    from repro.core.api import Skyscraper, SkyscraperPool
    sky = Skyscraper(segment_seconds=2.0, n_categories=3)
    sky.set_resources(num_cores=4)
    sky.register_knob("det", [1, 5, 10])
    segs = list(np.linspace(0, 1, 40))

    def proc(seg, kv):
        return seg, float(np.clip(1 - seg * (1 - 1.0 / kv["det"]), 0, 1))

    sky.fit(segs, proc, plan_segments=16)
    V = 4
    store = SegmentStore(out_dim=len(sky.configs), chunk_rows=64)
    pool = SkyscraperPool(sky, n_streams=V, sink=store)
    n_ticks = 6
    for _ in range(n_ticks):
        pool.process([0.2, 0.5, 0.7, 0.9])
    assert store.n_rows == V * n_ticks
    h = store.host_rows()
    np.testing.assert_array_equal(h["t"], np.repeat(np.arange(n_ticks), V))
    np.testing.assert_array_equal(h["stream_id"], np.tile(np.arange(V),
                                                          n_ticks))
    # the quality column is the TRANSFORM-measured quality, and the out
    # column carries it one-hot at the chosen config
    k = h["k"]
    np.testing.assert_allclose(h["out"][np.arange(len(k)), k], h["quality"],
                               rtol=1e-6)


# ---------------------------------------------------------------------------
# query engine vs the numpy reference
# ---------------------------------------------------------------------------

def test_query_filter_window_topk_exact():
    store = SegmentStore(out_dim=4, chunk_rows=2048)
    store.append_rows(_random_rows(6000, 4, seed=1))
    nw = windows_for(store, 250)
    plan = (Filter("quality", "ge", 0.4), Filter("stream_id", "ne", 3),
            WindowAgg(window=250, value="on_core_s", agg="mean",
                      num_windows=nw),
            TopK(7, by="on_core_s"))
    table, mask = execute(store, plan)
    ref, rmask = execute_ref(_host_cols(store), store.n_rows, plan)
    # same fp32 row-order summation on both sides -> bit-exact
    np.testing.assert_array_equal(np.asarray(table["on_core_s"]),
                                  ref["on_core_s"])
    np.testing.assert_array_equal(np.asarray(table["window"]),
                                  ref["window"])
    np.testing.assert_array_equal(np.asarray(mask), rmask)


def test_query_groupby_aggs_exact():
    store = SegmentStore(out_dim=4, chunk_rows=2048)
    store.append_rows(_random_rows(5000, 4, seed=2))
    cols = _host_cols(store)
    for agg in ("sum", "mean", "count", "max", "min"):
        plan = (Filter("buffer_s", "lt", 30.0),
                GroupBy("category", "cloud_core_s", agg=agg, num_groups=4))
        table, mask = execute(store, plan)
        ref, rmask = execute_ref(cols, store.n_rows, plan)
        np.testing.assert_array_equal(np.asarray(table["cloud_core_s"]),
                                      ref["cloud_core_s"], err_msg=agg)
        np.testing.assert_array_equal(np.asarray(table["count"]),
                                      ref["count"])
        np.testing.assert_array_equal(np.asarray(mask), rmask)


def test_query_project_and_row_topk():
    store = SegmentStore(out_dim=4, chunk_rows=2048)
    store.append_rows(_random_rows(3000, 4, seed=5))
    plan = (Project(("t", "quality", "k")),
            Filter("quality", "le", 0.9),
            TopK(11, by="quality", largest=False))
    table, mask = execute(store, plan)
    ref, rmask = execute_ref(_host_cols(store), store.n_rows, plan)
    assert set(table) == {"t", "quality", "k", "index"}
    np.testing.assert_array_equal(np.asarray(table["index"]), ref["index"])
    np.testing.assert_array_equal(np.asarray(table["quality"]),
                                  ref["quality"])
    # to_host compacts to the valid rows only
    host = to_host(table, mask)
    assert len(host["quality"]) == int(np.asarray(mask).sum())


def test_query_multigroupby_window_x_category_exact():
    """Multi-key GroupBy (time window x content category) fuses the key
    tuple into ONE segment_sum pass and matches the numpy reference
    bit-exact; decoded key columns enumerate the full cross product."""
    store = SegmentStore(out_dim=3, chunk_rows=2048)
    store.append_rows(_random_rows(5000, 3, seed=21))
    cols = _host_cols(store)
    nw = windows_for(store, 400)
    for agg in ("sum", "mean", "count", "max", "min"):
        plan = (Filter("quality", "ge", 0.3),
                MultiGroupBy(keys=("t", "category"), value="on_core_s",
                             agg=agg, nums=(nw, 4), windows=(400, 0)))
        table, mask = execute(store, plan)
        ref, rmask = execute_ref(cols, store.n_rows, plan)
        np.testing.assert_array_equal(np.asarray(table["on_core_s"]),
                                      ref["on_core_s"], err_msg=agg)
        np.testing.assert_array_equal(np.asarray(table["count"]),
                                      ref["count"])
        np.testing.assert_array_equal(np.asarray(table["t"]), ref["t"])
        np.testing.assert_array_equal(np.asarray(table["category"]),
                                      ref["category"])
        np.testing.assert_array_equal(np.asarray(mask), rmask)
    # three keys, no windowing, composed with a TopK over the result
    plan = (MultiGroupBy(keys=("stream_id", "category", "k"),
                         value="quality", agg="sum", nums=(4, 4, 3)),
            TopK(6, by="quality"))
    table, mask = execute(store, plan)
    ref, rmask = execute_ref(cols, store.n_rows, plan)
    for k in ref:
        np.testing.assert_array_equal(np.asarray(table[k]), ref[k],
                                      err_msg=k)
    np.testing.assert_array_equal(np.asarray(mask), rmask)
    # the fused encoding equals an equivalent single-key GroupBy over a
    # hand-fused id column: window*4 + category
    h = _host_cols(store)
    fused_ids = (np.asarray(h["t"]) // 400) * 4 + np.asarray(h["category"])
    plan_m = (MultiGroupBy(keys=("t", "category"), value="quality",
                           agg="sum", nums=(nw, 4), windows=(400, 0)),)
    tm, _ = execute(store, plan_m)
    hand = {**h, "fused": fused_ids.astype(np.int32)}
    rg, _ = execute_ref(hand, store.n_rows,
                        (GroupBy("fused", "quality", agg="sum",
                                 num_groups=nw * 4),))
    np.testing.assert_array_equal(np.asarray(tm["quality"]), rg["quality"])


def test_query_groupby_wide_out_column():
    """GroupBy over the (row, D) embedding column aggregates per lane
    and matches the reference bit-exact (sum/mean) on one shard."""
    store = SegmentStore(out_dim=4, chunk_rows=1024)
    store.append_rows(_random_rows(3000, 4, seed=22))
    cols = _host_cols(store)
    for agg in ("sum", "mean"):
        plan = (Filter("quality", "ge", 0.5),
                GroupBy("category", "out", agg=agg, num_groups=4))
        table, mask = execute(store, plan)
        ref, rmask = execute_ref(cols, store.n_rows, plan)
        assert np.asarray(table["out"]).shape == (4, 4)
        np.testing.assert_array_equal(np.asarray(table["out"]),
                                      ref["out"], err_msg=agg)
        np.testing.assert_array_equal(np.asarray(mask), rmask)


def test_query_int_filter_exact_past_f32_precision():
    """Integer columns filter exactly even past 2^24 (where a float32
    cast would collapse neighboring values) — the append-only ``t``
    column crosses that after ~388 days of 2 s segments."""
    n = 64
    base = 2 ** 24
    rows = _random_rows(n, 2, seed=9)
    rows["t"] = (base + np.arange(n)).astype(np.int32)
    store = SegmentStore(out_dim=2, chunk_rows=64)
    store.append_rows(rows)
    for op, want in (("ge", n - 1), ("gt", n - 2), ("le", 2), ("lt", 1),
                     ("eq", 1), ("ne", n - 1)):
        plan = (Filter("t", op, float(base + 1)),)
        _, mask = execute(store, plan)
        assert int(np.asarray(mask).sum()) == want, (op, want)
        _, rmask = execute_ref(_host_cols(store), n, plan)
        assert int(rmask.sum()) == want, (op, want)
    # non-integral thresholds stay well-defined too
    _, m = execute(store, (Filter("t", "ge", base + 0.5),))
    _, rm = execute_ref(_host_cols(store), n,
                        (Filter("t", "ge", base + 0.5),))
    np.testing.assert_array_equal(np.asarray(m), rm)
    # extreme thresholds clamp without int32 wraparound
    for op, v in (("lt", float(-2 ** 31)), ("gt", float(2 ** 31))):
        _, m = execute(store, (Filter("t", op, v),))
        _, rm = execute_ref(_host_cols(store), n, (Filter("t", op, v),))
        assert not np.asarray(m).any()
        np.testing.assert_array_equal(np.asarray(m), rm)
    # infinite thresholds degenerate to all/none, like the reference
    for op, v, cnt in (("lt", float("inf"), n), ("ge", float("inf"), 0),
                       ("ge", float("-inf"), n), ("lt", float("-inf"), 0)):
        _, m = execute(store, (Filter("t", op, v),))
        _, rm = execute_ref(_host_cols(store), n, (Filter("t", op, v),))
        assert int(np.asarray(m).sum()) == cnt, (op, v)
        np.testing.assert_array_equal(np.asarray(m), rm)


def test_query_empty_result_and_sparse_groups():
    """Predicates that kill every row, and group ids beyond the static
    count, stay well-defined (clip + masked no-op semantics)."""
    store = SegmentStore(out_dim=2, chunk_rows=256)
    rows = _random_rows(400, 2, seed=6)
    rows["category"] = np.full(400, 9, np.int32)     # clips into last group
    store.append_rows(rows)
    plan = (Filter("quality", "gt", 2.0),            # nothing matches
            GroupBy("category", "quality", agg="mean", num_groups=4),
            TopK(3, by="quality"))
    table, mask = execute(store, plan)
    ref, rmask = execute_ref(_host_cols(store), store.n_rows, plan)
    assert not np.asarray(mask).any() and not rmask.any()
    np.testing.assert_array_equal(np.asarray(table["quality"]),
                                  ref["quality"])


def test_query_100k_single_dispatch_zero_recompiles():
    """The acceptance-criteria shape: Filter -> WindowAgg -> TopK over
    >=100k stored segments is ONE compiled dispatch, re-querying with
    new filter values / more rows reuses the executable, and the answer
    matches the numpy reference exactly."""
    store = SegmentStore(out_dim=4, chunk_rows=60_000)
    store.append_rows(_random_rows(100_000, 4, seed=7))
    nw = windows_for(store, 500)
    plan = (Filter("quality", "ge", 0.25),
            WindowAgg(window=500, value="quality", agg="sum",
                      num_windows=nw),
            TopK(10, by="quality"))
    before = Q.compile_cache_size()
    table, mask = execute(store, plan)
    after_first = Q.compile_cache_size()
    assert after_first == before + 1        # ONE new executable, total
    for thr in (0.1, 0.5, 0.8):
        plan_i = (Filter("quality", "ge", thr),) + plan[1:]
        table_i, mask_i = execute(store, plan_i)
        ref_i, rmask_i = execute_ref(_host_cols(store), store.n_rows,
                                     plan_i)
        np.testing.assert_array_equal(np.asarray(table_i["quality"]),
                                      ref_i["quality"])
        np.testing.assert_array_equal(np.asarray(mask_i), rmask_i)
    # appending within the reserved capacity keeps the same executable
    store.append_rows(_random_rows(10_000, 4, seed=8, t0=100_000))
    execute(store, plan)
    assert Q.compile_cache_size() == after_first, "query recompiled"


# ---------------------------------------------------------------------------
# tiering + persistence
# ---------------------------------------------------------------------------

def _tiered_fixture(n=4096, chunk=512, seed=11):
    store = SegmentStore(out_dim=3, chunk_rows=chunk)
    store.append_rows(_random_rows(n, 3, seed=seed))
    full_ref = _host_cols(store)      # fp32 snapshot before quantization
    ts = TieredStore(store, seed=1)
    spilled = ts.spill(keep_hot=n // 2)
    assert spilled > 0 and spilled % chunk == 0
    assert ts.n_rows == n and ts.hot.n_rows == n - spilled
    return ts, full_ref, n, spilled


def test_tiered_query_within_quantization_tolerance():
    ts, full_ref, n, spilled = _tiered_fixture()
    plan = (GroupBy("category", "quality", agg="mean", num_groups=4),)
    table, mask = ts.query(plan)
    ref, _ = execute_ref(full_ref, n, plan)
    # per-element cold error <= per-chunk scale (stochastic rounding),
    # and means only shrink it; counts are integer-column exact
    tol = ts.max_cold_scale() + 1e-6
    np.testing.assert_allclose(np.asarray(table["quality"]),
                               ref["quality"], atol=tol)
    np.testing.assert_array_equal(np.asarray(table["count"]), ref["count"])
    # hot rows stayed fp32: querying only recent times is exact
    t_lo = float(np.sort(full_ref["t"])[spilled])
    plan_hot = (Filter("t", "ge", t_lo),
                GroupBy("category", "quality", agg="sum", num_groups=4))
    table_h, _ = ts.query(plan_hot)
    ref_h, _ = execute_ref(full_ref, n, plan_hot)
    np.testing.assert_array_equal(np.asarray(table_h["quality"]),
                                  ref_h["quality"])


def test_tiered_spill_guards_and_memoized_view():
    ts, _, n, _ = _tiered_fixture(seed=17)
    np.testing.assert_raises(AssertionError, ts.spill, -1)
    # spilling everything never quantizes capacity padding: only whole
    # chunks of LIVE rows move, and no row is lost or invented
    ts.spill(0)
    assert ts.n_rows == n
    assert ts.n_cold % ts.hot.chunk_rows == 0 and ts.n_cold <= n
    # repeat queries reuse the memoized combined view...
    cols1, _ = ts.materialize()
    cols2, _ = ts.materialize()
    assert cols1 is cols2
    # ...and an append refreshes it
    ts.hot.append_rows(_random_rows(8, 3, seed=18, t0=n))
    cols3, n_tot = ts.materialize()
    assert cols3 is not cols1 and n_tot == n + 8


def test_warehouse_ckpt_roundtrip_bit_exact(tmp_path):
    ts, full_ref, n, _ = _tiered_fixture(seed=13)
    plan = (Filter("quality", "ge", 0.5),
            WindowAgg(window=256, value="quality", agg="mean",
                      num_windows=windows_for(ts, 256)),
            TopK(4, by="quality"))
    want_table, want_mask = ts.query(plan)
    path = str(tmp_path / "warehouse.rsk")
    save_warehouse(path, ts)
    back = load_warehouse(path)
    # hot tier restores bit-exact; cold tier's int8 codes + scales too
    for k, v in ts.hot.columns.items():
        np.testing.assert_array_equal(np.asarray(back.hot.columns[k]),
                                      np.asarray(v))
        assert back.hot.columns[k].dtype == v.dtype
    for k in ts.cold_q:
        np.testing.assert_array_equal(np.asarray(back.cold_q[k]),
                                      np.asarray(ts.cold_q[k]))
        np.testing.assert_array_equal(np.asarray(back.cold_scales[k]),
                                      np.asarray(ts.cold_scales[k]))
    assert (back.n_cold, back.hot.n_rows, back.hot.t_max,
            back.hot.chunk_rows) == (ts.n_cold, ts.hot.n_rows,
                                     ts.hot.t_max, ts.hot.chunk_rows)
    got_table, got_mask = back.query(plan)
    for k in want_table:
        np.testing.assert_array_equal(np.asarray(got_table[k]),
                                      np.asarray(want_table[k]))
    np.testing.assert_array_equal(np.asarray(got_mask),
                                  np.asarray(want_mask))


def test_store_is_a_pytree():
    store = SegmentStore(out_dim=2, chunk_rows=128)
    store.append_rows(_random_rows(100, 2, seed=3))
    leaves, treedef = jax.tree.flatten(store)
    assert all(isinstance(x, jax.Array) for x in leaves)
    back = jax.tree.unflatten(treedef, leaves)
    assert isinstance(back, SegmentStore)
    assert back.n_rows == store.n_rows and back.t_max == store.t_max
    # a store passes through jit like any other pytree
    total = jax.jit(lambda s: s.columns["quality"].sum())(store)
    np.testing.assert_allclose(
        float(total), float(store.columns["quality"].sum()), rtol=1e-6)


def test_blocked_sums_hold_million_row_groups_to_float64():
    """Float group sums add in blocks of ``SUM_BLOCK_ROWS`` rows, then
    the block partials in block order. At 2^20 rows of one group, where
    a row-order fp32 sum drifts by 7.6e-4, the XLA path equals
    ``execute_ref`` bit for bit and both stay within 1e-5 of the
    float64 sum — scalar and wide (``out``) columns alike."""
    n = 1 << 20
    rows = _random_rows(n, 2, seed=7)
    rows["quality"] = (0.9 + np.random.default_rng(8).normal(0, 0.02, n)
                       ).astype(np.float32)
    rows["out"] = np.stack([rows["quality"], rows["quality"] * 3], 1)
    row_order = np.cumsum(rows["quality"], dtype=np.float32)[-1]
    exact = rows["quality"].astype(np.float64).sum()
    assert abs(row_order / exact - 1) > 5e-4     # the drift being fixed
    store = SegmentStore(out_dim=2, chunk_rows=n)
    store.append_rows(rows)
    plans = [
        ((WindowAgg(window=n, value="quality", agg="sum", num_windows=1),),
         "quality"),
        ((Filter("quality", "ge", 0.88),
          GroupBy("category", "quality", agg="mean", num_groups=4)),
         "quality"),
        ((GroupBy("stream_id", "out", agg="sum", num_groups=4),), "out"),
    ]
    for plan, col in plans:
        got, _ = store.query(plan, use_pallas=False)
        ref32, _ = execute_ref(_host_cols(store), n, plan)
        ref64, _ = execute_ref(_host_cols(store), n, plan,
                               dtype=np.float64)
        np.testing.assert_array_equal(np.asarray(got[col]), ref32[col])
        np.testing.assert_allclose(np.asarray(got[col], np.float64),
                                   ref64[col], rtol=1e-5)
