"""Shard-aware warehouse: stream-hash routed ingestion, the partial/
merge query engine (1-shard bit-exact, multi-shard tolerance-bounded),
zero-recompile guarantees, per-shard tiering, and the compressed merge.

On a 1-device host every test runs the SAME kernels through the stacked
single-device fallback (``store.mesh is None``); ``scripts/tier1.sh``
re-runs this module under ``XLA_FLAGS=--xla_force_host_platform_
device_count=8`` where the stores get a real ``('shard',)`` mesh and
queries/ingests execute as ONE shard_map dispatch with collective
merges — the assertions are identical in both modes."""

import jax
import numpy as np

from benchmarks.fused_ingest_bench import _synthetic_fitted
from repro.configs.workloads import COVID
from repro.core import ingest as IG
from repro.data.stream import generate
from repro.warehouse import (Filter, GroupBy, MultiGroupBy, Project,
                             SegmentStore, ShardedStore,
                             ShardedTieredStore, TopK, WindowAgg,
                             execute_ref, to_host, windows_for)
from repro.warehouse import query as Q
from test_warehouse import _random_rows

N_CORES = 8  # matches the profile baked into _synthetic_fitted


def _stores(n, D, n_shards, seed=0, chunk=256, streams=16):
    rows = _random_rows(n, D, seed=seed)
    rows["stream_id"] = (np.arange(n, dtype=np.int32) * 7) % streams
    single = SegmentStore(out_dim=D, chunk_rows=max(chunk, 64))
    single.append_rows(rows)
    sharded = ShardedStore(out_dim=D, n_shards=n_shards, chunk_rows=chunk)
    sharded.append_rows(rows)
    return single, sharded, rows


# ---------------------------------------------------------------------------
# routing / ingestion
# ---------------------------------------------------------------------------

def test_append_routes_by_stream_hash():
    single, sharded, rows = _stores(3000, 3, n_shards=4)
    assert sharded.n_rows == 3000
    # device row counts agree with the host-metadata mirror
    np.testing.assert_array_equal(np.asarray(sharded.n_rows_dev),
                                  sharded.n_rows_by_shard)
    h = sharded.host_rows()
    # every row lands exactly once, on its owner shard, in time order
    assert sorted(h["t"].tolist()) == sorted(rows["t"].tolist())
    off = 0
    for s in range(4):
        blk = slice(off, off + sharded.n_rows_by_shard[s])
        assert (h["stream_id"][blk] % 4 == s).all()
        assert (np.diff(h["t"][blk]) > 0).all()       # append order kept
        off += sharded.n_rows_by_shard[s]
    # full content equality against the unsharded store (row-order free)
    hf = single.host_rows()
    of = np.lexsort((hf["t"], hf["stream_id"]))
    os_ = np.lexsort((h["t"], h["stream_id"]))
    for k in hf:
        np.testing.assert_array_equal(hf[k][of], h[k][os_], err_msg=k)


def test_fused_multi_sink_shards_without_host_gathers():
    """The SAME fused multi-stream run lands in a flat and a sharded
    sink; the sharded one holds identical rows, each stream's whole
    trace on shard (stream_base + v) % n_shards."""
    fitted = _synthetic_fitted()
    K = len(fitted.configs)
    tau = fitted.workload.segment_seconds
    V = 3
    streams = [generate(COVID, days=0.01, seed=s) for s in range(V)]
    T = min(s.n_segments for s in streams)
    flat = SegmentStore(out_dim=K, chunk_rows=512)
    sharded = ShardedStore(out_dim=K, n_shards=2, chunk_rows=256)
    kw = dict(n_cores_each=N_CORES, cloud_budget_core_s=900.0,
              plan_days=64 * tau / 86400, sink_stream_base=10)
    IG.run_skyscraper_multi([fitted] * V, streams, sink=flat, **kw)
    IG.run_skyscraper_multi([fitted] * V, streams, sink=sharded, **kw)
    assert sharded.n_rows == flat.n_rows == V * T
    hf, hs = flat.host_rows(), sharded.host_rows()
    of = np.lexsort((hf["t"], hf["stream_id"]))
    os_ = np.lexsort((hs["t"], hs["stream_id"]))
    for k in hf:
        np.testing.assert_array_equal(hf[k][of], hs[k][os_], err_msg=k)
    # streams 10, 12 -> shard 0; stream 11 -> shard 1
    np.testing.assert_array_equal(
        np.unique(hs["stream_id"][: sharded.n_rows_by_shard[0]]), [10, 12])
    assert all(isinstance(v, jax.Array)
               for v in sharded.columns.values())


def test_single_stream_fused_sink_owns_one_shard():
    fitted = _synthetic_fitted()
    tau = fitted.workload.segment_seconds
    stream = generate(COVID, days=0.01, seed=7)
    store = ShardedStore(out_dim=len(fitted.configs), n_shards=4,
                        chunk_rows=128)
    IG.run_skyscraper_fused(fitted, stream, n_cores=N_CORES,
                            plan_days=64.5 * tau / 86400,
                            forecast_mode="uniform", sink=store,
                            sink_stream_id=6)
    T = stream.n_segments
    assert store.n_rows == T and store.n_rows_by_shard[6 % 4] == T
    h = store.host_rows()
    np.testing.assert_array_equal(h["t"], np.arange(T, dtype=np.int32))


def test_pool_tick_sink_sharded():
    from repro.core.api import Skyscraper, SkyscraperPool
    sky = Skyscraper(segment_seconds=2.0, n_categories=3)
    sky.set_resources(num_cores=4)
    sky.register_knob("det", [1, 5, 10])
    segs = list(np.linspace(0, 1, 40))

    def proc(seg, kv):
        return seg, float(np.clip(1 - seg * (1 - 1.0 / kv["det"]), 0, 1))

    sky.fit(segs, proc, plan_segments=16)
    V, S = 4, 3
    store = ShardedStore(out_dim=len(sky.configs), n_shards=S,
                        chunk_rows=32)
    pool = SkyscraperPool(sky, n_streams=V, sink=store)
    for _ in range(5):
        pool.process([0.2, 0.5, 0.7, 0.9])
    assert store.n_rows == 5 * V
    h = store.host_rows()
    off = 0
    for s in range(S):
        blk = slice(off, off + store.n_rows_by_shard[s])
        assert (h["stream_id"][blk] % S == s).all()
        off += store.n_rows_by_shard[s]


def test_sharded_growth_is_chunk_aligned():
    store = ShardedStore(out_dim=2, n_shards=2, chunk_rows=100)
    for i in range(4):
        rows = _random_rows(130, 2, seed=i, t0=130 * i)
        store.append_rows(rows)
    assert store.n_rows == 4 * 130
    assert store.capacity % 100 == 0
    assert store.capacity >= store.n_rows_by_shard.max()


# ---------------------------------------------------------------------------
# partial/merge engine vs the single-device engine
# ---------------------------------------------------------------------------

def test_one_shard_is_bit_exact_with_single_device():
    """The tentpole's degenerate case: n_shards=1 partial+merge IS the
    single-device engine — bit-exact fp32, not just close."""
    single, sharded, _ = _stores(4000, 4, n_shards=1, seed=2)
    cols = {k: np.asarray(v) for k, v in single.columns.items()}
    nw = windows_for(single, 250)
    plans = [
        (Filter("quality", "ge", 0.4), Filter("stream_id", "ne", 3),
         WindowAgg(window=250, value="on_core_s", agg="mean",
                   num_windows=nw), TopK(7, by="on_core_s")),
        (Filter("buffer_s", "lt", 30.0),
         GroupBy("category", "cloud_core_s", agg="sum", num_groups=4)),
        (Project(("t", "quality", "k")), Filter("quality", "le", 0.9),
         TopK(11, by="quality", largest=False)),
    ]
    for plan in plans:
        table, mask = sharded.query(plan)
        ref, rmask = execute_ref(cols, single.n_rows, plan)
        for k in ref:
            if k == "index":
                continue       # sharded index is a global (shard*cap+i) id
            np.testing.assert_array_equal(np.asarray(table[k]), ref[k],
                                          err_msg=str((k, plan)))
        np.testing.assert_array_equal(np.asarray(mask), rmask)


def test_multi_shard_matches_single_device():
    """Aggregations over shards: counts / integer-valued sums exact,
    float sums within regrouping tolerance, groups and masks identical."""
    single, sharded, _ = _stores(6000, 4, n_shards=4, seed=3)
    cols = {k: np.asarray(v) for k, v in single.columns.items()}
    for agg in ("sum", "mean", "count", "max", "min"):
        plan = (Filter("quality", "ge", 0.2),
                GroupBy("category", "on_core_s", agg=agg, num_groups=4))
        table, mask = sharded.query(plan)
        ref, rmask = execute_ref(cols, single.n_rows, plan)
        np.testing.assert_array_equal(np.asarray(table["count"]),
                                      ref["count"], err_msg=agg)
        np.testing.assert_array_equal(np.asarray(mask), rmask)
        if agg in ("max", "min", "count"):
            # order-independent: exact across any shard split
            np.testing.assert_array_equal(np.asarray(table["on_core_s"]),
                                          ref["on_core_s"], err_msg=agg)
        else:
            np.testing.assert_allclose(np.asarray(table["on_core_s"]),
                                       ref["on_core_s"], rtol=1e-5,
                                       atol=1e-4, err_msg=agg)
    # integer-valued column sums are exact in f32 no matter the split
    plan = (GroupBy("category", "k", agg="sum", num_groups=4),)
    table, _ = sharded.query(plan)
    ref, _ = execute_ref(cols, single.n_rows, plan)
    np.testing.assert_array_equal(np.asarray(table["k"]), ref["k"])


def test_sharded_row_topk_same_survivors():
    single, sharded, _ = _stores(3000, 3, n_shards=3, seed=4)
    cols = {k: np.asarray(v) for k, v in single.columns.items()}
    plan = (Filter("stream_id", "eq", 5), TopK(9, by="quality"))
    table, mask = sharded.query(plan)
    ref, rmask = execute_ref(cols, single.n_rows, plan)
    m, rm = np.asarray(mask), rmask
    assert m.sum() == rm.sum()
    np.testing.assert_allclose(np.sort(np.asarray(table["quality"])[m]),
                               np.sort(ref["quality"][rm]), rtol=1e-6)
    # surviving rows are the same multiset of (t, quality) pairs
    got = sorted(zip(np.asarray(table["t"])[m].tolist(),
                     np.asarray(table["quality"])[m].tolist()))
    want = sorted(zip(ref["t"][rm].tolist(), ref["quality"][rm].tolist()))
    assert got == want


def test_sharded_pure_row_plan_concat():
    single, sharded, _ = _stores(1000, 2, n_shards=4, seed=6)
    cols = {k: np.asarray(v) for k, v in single.columns.items()}
    plan = (Filter("quality", "ge", 0.5), Project(("t", "quality")))
    table, mask = sharded.query(plan)
    ref, rmask = execute_ref(cols, single.n_rows, plan)
    got = to_host(table, mask)
    want = to_host(ref, rmask)
    assert sorted(got["t"].tolist()) == sorted(want["t"].tolist())
    np.testing.assert_allclose(np.sort(got["quality"]),
                               np.sort(want["quality"]), rtol=1e-6)


def test_sharded_multigroupby_window_x_category():
    single, sharded, _ = _stores(5000, 3, n_shards=4, seed=7)
    cols = {k: np.asarray(v) for k, v in single.columns.items()}
    nw = windows_for(single, 500)
    plan = (Filter("quality", "ge", 0.3),
            MultiGroupBy(keys=("t", "category"), value="on_core_s",
                         agg="mean", nums=(nw, 4), windows=(500, 0)),
            TopK(5, by="on_core_s"))
    table, mask = sharded.query(plan)
    ref, rmask = execute_ref(cols, single.n_rows, plan)
    np.testing.assert_array_equal(np.asarray(mask), rmask)
    np.testing.assert_array_equal(np.asarray(table["count"]),
                                  ref["count"])
    np.testing.assert_array_equal(np.asarray(table["t"]), ref["t"])
    np.testing.assert_array_equal(np.asarray(table["category"]),
                                  ref["category"])
    np.testing.assert_allclose(np.asarray(table["on_core_s"]),
                               ref["on_core_s"], rtol=1e-5, atol=1e-4)


def test_empty_shards_and_empty_result():
    """Streams hashing onto two shards leave the rest empty; predicates
    that kill every row stay well-defined."""
    rows = _random_rows(500, 2, seed=8)
    rows["stream_id"] = (np.arange(500, dtype=np.int32) % 2) * 4  # 0 or 4
    store = ShardedStore(out_dim=2, n_shards=8, chunk_rows=64)
    store.append_rows(rows)
    assert (store.n_rows_by_shard[[0, 4]] > 0).all()
    assert store.n_rows_by_shard[[1, 2, 3, 5, 6, 7]].sum() == 0
    single = SegmentStore(out_dim=2, chunk_rows=64)
    single.append_rows(rows)
    cols = {k: np.asarray(v) for k, v in single.columns.items()}
    plan = (GroupBy("category", "quality", agg="mean", num_groups=4),)
    table, mask = store.query(plan)
    ref, rmask = execute_ref(cols, 500, plan)
    np.testing.assert_array_equal(np.asarray(table["count"]), ref["count"])
    np.testing.assert_allclose(np.asarray(table["quality"]),
                               ref["quality"], rtol=1e-5, atol=1e-5)
    # nothing matches at all
    dead = (Filter("quality", "gt", 2.0),
            GroupBy("category", "quality", agg="sum", num_groups=4),
            TopK(3, by="quality"))
    _, m = store.query(dead)
    assert not np.asarray(m).any()
    # a store with no rows yet still answers a row-level top-k (its
    # zero-size partials skip the cross-shard gather)
    empty = ShardedStore(out_dim=2, n_shards=1, chunk_rows=64)
    _, m = empty.query((TopK(3, by="quality"),))
    assert np.asarray(m).shape == (0,)


def test_sharded_zero_recompiles():
    """Repeated queries at a fixed shard count — new thresholds, new
    rows within capacity — reuse ONE executable per plan shape."""
    store = ShardedStore(out_dim=3, n_shards=4, chunk_rows=4096)
    store.append_rows(_random_rows(10_000, 3, seed=9))
    nw = windows_for(store, 500)
    plan = (Filter("quality", "ge", 0.25),
            WindowAgg(window=500, value="quality", agg="sum",
                      num_windows=nw),
            TopK(10, by="quality"))
    before = Q.sharded_compile_cache_size()
    store.query(plan)
    after_first = Q.sharded_compile_cache_size()
    assert after_first == before + 1
    for thr in (0.1, 0.5, 0.8):
        store.query((Filter("quality", "ge", thr),) + plan[1:])
    rows2 = _random_rows(2_000, 3, seed=10, t0=10_000)
    store.append_rows(rows2)          # fits the reserved capacity
    store.query(plan)
    assert Q.sharded_compile_cache_size() == after_first, "recompiled"


def test_compressed_merge_bounded_error():
    """Opt-in int8-compressed partial-sum merge (embedding columns):
    counts stay exact; sums land within the per-shard quantization
    scale bound (scale = max|partial|/127, one per shard)."""
    single, sharded, _ = _stores(4000, 4, n_shards=4, seed=11)
    cols = {k: np.asarray(v) for k, v in single.columns.items()}
    plan = (GroupBy("category", "out", agg="sum", num_groups=4),)
    exact, _ = sharded.query(plan)
    comp, _ = sharded.query(plan, compressed=True)
    ref, _ = execute_ref(cols, single.n_rows, plan)
    np.testing.assert_array_equal(np.asarray(comp["count"]), ref["count"])
    np.testing.assert_allclose(np.asarray(exact["out"]), ref["out"],
                               rtol=1e-5, atol=1e-3)
    # per-shard error <= that shard's scale; 4 shards of |sum| <= ~250
    bound = 4 * (np.abs(ref["out"]).max() / 127 + 1e-3)
    err = np.abs(np.asarray(comp["out"]) - ref["out"]).max()
    assert err <= bound, (err, bound)


# ---------------------------------------------------------------------------
# per-shard tiering
# ---------------------------------------------------------------------------

def test_sharded_tier_spill_and_query():
    single, sharded, _ = _stores(4096, 3, n_shards=4, seed=12, chunk=128)
    cols = {k: np.asarray(v) for k, v in single.columns.items()}
    ts = ShardedTieredStore(sharded, seed=1)
    spilled = ts.spill(keep_hot=300)
    assert spilled > 0 and spilled % (128 * 4) == 0
    assert ts.n_rows == 4096
    np.testing.assert_raises(AssertionError, ts.spill, -1)
    plan = (GroupBy("category", "quality", agg="mean", num_groups=4),)
    table, _ = ts.query(plan)
    ref, _ = execute_ref(cols, 4096, plan)
    np.testing.assert_array_equal(np.asarray(table["count"]), ref["count"])
    tol = ts.max_cold_scale() + 1e-4
    np.testing.assert_allclose(np.asarray(table["quality"]),
                               ref["quality"], atol=tol)
    # memoized combined view across repeat queries; refreshed by appends
    c1, _ = ts.shard_source()
    c2, _ = ts.shard_source()
    assert c1 is c2
    ts.hot.append_rows(_random_rows(8, 3, seed=13, t0=5000))
    c3, _ = ts.shard_source()
    assert c3 is not c1 and ts.n_rows == 4096 + 8


def test_sharded_tier_ragged_spill_with_empty_shards():
    """Shards that own no streams (n_streams < n_shards, or hash gaps)
    must never block the populated shards from spilling: depths are
    ragged per shard. Regression test for the min-across-shards no-op."""
    n = 2000
    rows = _random_rows(n, 2, seed=31)
    rows["stream_id"] = ((np.arange(n, dtype=np.int32) % 2) * 4)  # 0 / 4
    store = ShardedStore(out_dim=2, n_shards=8, chunk_rows=256)
    store.append_rows(rows)
    single = SegmentStore(out_dim=2, chunk_rows=256)
    single.append_rows(rows)
    cols = {k: np.asarray(v) for k, v in single.columns.items()}
    ts = ShardedTieredStore(store, seed=2)
    spilled = ts.spill(keep_hot=0)
    assert spilled == 2 * (1000 // 256) * 256      # both live shards
    assert ts.n_cold_by_shard[[0, 4]].sum() == spilled
    assert ts.n_cold_by_shard[[1, 2, 3, 5, 6, 7]].sum() == 0
    assert ts.n_rows == n
    # a second, imbalanced spill: only shard 0 receives new rows
    more = _random_rows(600, 2, seed=32, t0=n)
    more["stream_id"] = np.zeros(600, np.int32)
    ts.hot.append_rows(more)
    # shard 0 now holds 232 + 600 = 832 hot rows -> spills 3 chunks;
    # shard 4 holds 232 (< one chunk) -> spills nothing
    spilled2 = ts.spill(keep_hot=0)
    assert spilled2 == (832 // 256) * 256
    assert ts.n_cold_by_shard[0] == 768 + 768
    assert ts.n_cold_by_shard[4] == 768
    # the deep shard's write window must be fully reserved: a shallow
    # shard's junk block at a clamped offset would otherwise overwrite
    # the deep shard's valid cold rows (dynamic_update_slice clamps
    # out-of-range starts backward instead of erroring)
    assert ts.cold_capacity >= ts.n_cold_by_shard.max()
    plan = (GroupBy("category", "quality", agg="mean", num_groups=4),)
    table, _ = ts.query(plan)
    # counts must stay exact across BOTH tiers despite ragged depths
    got_cnt = np.asarray(table["count"]).copy()
    ref2, _ = execute_ref({k: np.concatenate([cols[k][:n],
                                              np.asarray(more[k])])
                           for k in cols}, n + 600, plan)
    np.testing.assert_array_equal(got_cnt, ref2["count"])
    np.testing.assert_allclose(np.asarray(table["quality"]),
                               ref2["quality"],
                               atol=ts.max_cold_scale() + 1e-4)


def test_sharded_tier_shallow_spill_never_clamps_into_deep_shard():
    """Regression: when one shard's cold tier sits exactly at capacity
    and a LATER spill only moves rows on a shallower shard, the deep
    shard's junk write window must still be inside capacity —
    ``dynamic_update_slice`` clamps an out-of-range start backward, so
    an unreserved tail would silently overwrite valid cold rows."""
    chunk = 256
    store = ShardedStore(out_dim=2, n_shards=2, chunk_rows=chunk)
    ts = ShardedTieredStore(store, seed=3)
    all_rows = []

    def add(n, stream, t0, seed):
        rows = _random_rows(n, 2, seed=seed, t0=t0)
        rows["stream_id"] = np.full(n, stream, np.int32)
        store.append_rows(rows)
        all_rows.append(rows)

    # 8-chunk spills land exactly ON the bucketed capacity ladder
    # (chunk * 2^j), so the deep shard's cold tier sits EXACTLY at
    # capacity — the tight layout this regression needs
    add(8 * chunk, 0, 0, 41)            # shard 0 deep
    add(100, 1, 8 * chunk, 42)
    assert ts.spill(keep_hot=0) == 8 * chunk
    add(8 * chunk, 0, 8 * chunk + 100, 43)   # shard 0 deeper: at capacity
    assert ts.spill(keep_hot=0) == 8 * chunk
    assert ts.n_cold_by_shard[0] == ts.cold_capacity == 16 * chunk
    add(chunk, 1, 17 * chunk, 44)       # now ONLY shard 1 can spill
    assert ts.spill(keep_hot=0) == chunk
    assert ts.cold_capacity >= ts.n_cold_by_shard[0] + chunk
    # shard 0's cold rows survived: two-tier counts match the reference
    n_all = sum(len(r["t"]) for r in all_rows)
    cols = {k: np.concatenate([np.asarray(r[k]) for r in all_rows])
            for k in all_rows[0]}
    plan = (GroupBy("category", "quality", agg="count", num_groups=4),)
    table, _ = ts.query(plan)
    ref, _ = execute_ref(cols, n_all, plan)
    np.testing.assert_array_equal(np.asarray(table["count"]), ref["count"])
    np.testing.assert_allclose(np.asarray(table["quality"]),
                               ref["quality"],
                               atol=ts.max_cold_scale() + 1e-4)


def test_mesh_mode_active_when_devices_exist():
    """On the forced-8-device CI leg the stores must actually be on a
    mesh (ONE shard_map dispatch, collective merge) — on a 1-device
    host they must fall back to the stacked layout."""
    store = ShardedStore(out_dim=2, n_shards=2, chunk_rows=64)
    if jax.device_count() >= 2:
        assert store.mesh is not None
        assert set(store.mesh.axis_names) == {"shard"}
        store.append_rows(_random_rows(100, 2, seed=14))
        devs = {d for v in store.columns.values()
                for d in v.sharding.device_set}
        assert len(devs) == 2, "columns not spread across shard devices"
    else:
        assert store.mesh is None
