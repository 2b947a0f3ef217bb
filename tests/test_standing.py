"""Standing queries: incremental partial maintenance fused into the
ingest dispatch. Registration + backfill, same-shape query batching
into power-of-two buckets, alert subscriptions, spill invariance, the
Pallas delta path, and the zero-warm-recompile pins (standing folds AND
the bucketed capacity ladder).

``scripts/tier1.sh`` re-runs this module under
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` so the sharded
legs execute on a real mesh."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.switcher import compile_cache_sizes
from repro.warehouse import (Filter, GroupBy, MultiGroupBy, SegmentStore,
                             ShardedStore, ShardedTieredStore,
                             StandingQueries, TieredStore, TopK,
                             WindowAgg, execute_ref)
from repro.warehouse.query import SUM_BLOCK_ROWS
from repro.warehouse.store import _bucket_cap
from test_warehouse import _host_cols, _random_rows

D = 3


def _eq(a, b, **kw):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b), **kw)


def _close(a, b, **kw):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), **kw)


def _ref(store, plan):
    return execute_ref(_host_cols(store), store.n_rows, plan)


# ---------------------------------------------------------------------------
# single store: registration, backfill, incremental answers
# ---------------------------------------------------------------------------

def test_register_then_ingest_matches_rescan_bit_exact():
    """Backfill over existing rows + incremental folds over later
    appends equals a full rescan BIT-exactly (fp32 sums included): the
    fold continues each group's addition sequence in ingest order."""
    store = SegmentStore(out_dim=D, chunk_rows=256)
    store.append_rows(_random_rows(500, D, seed=1))
    reg = StandingQueries(store)
    plans = [
        (Filter("quality", "ge", 0.25),
         GroupBy("category", "quality", agg="sum", num_groups=4)),
        (GroupBy("category", "quality", agg="max", num_groups=4),
         TopK(2, by="quality")),
        (WindowAgg(window=128, value="on_core_s", agg="mean",
                   num_windows=8),),
        (MultiGroupBy(keys=("k", "category"), value="quality", agg="sum",
                      nums=(D, 4), windows=(0, 0)),),
    ]
    handles = [reg.register(p) for p in plans]
    store.append_rows(_random_rows(300, D, seed=2, t0=500))
    store.append_rows(_random_rows(200, D, seed=3, t0=800))
    for h, plan in zip(handles, plans):
        table, mask = reg.answer(h)
        ref, rmask = _ref(store, plan)
        _eq(mask, rmask)
        for k in ref:
            _eq(table[k], ref[k], err_msg=f"{plan}:{k}")


def test_registration_after_ingest_and_empty_store_seed():
    """Registering on an EMPTY store skips the backfill (init state is
    the seed) and folds catch every later row; registering mid-stream
    backfills exactly the rows already present."""
    store = SegmentStore(out_dim=D, chunk_rows=128)
    reg = StandingQueries(store)
    plan = (Filter("quality", "lt", 0.5),
            GroupBy("category", "quality", agg="mean", num_groups=4))
    h_empty = reg.register(plan)
    store.append_rows(_random_rows(200, D, seed=4))
    h_mid = reg.register(plan)            # same shape: joins the group
    store.append_rows(_random_rows(150, D, seed=5, t0=200))
    ref, rmask = _ref(store, plan)
    for h in (h_empty, h_mid):
        table, mask = reg.answer(h)
        _eq(mask, rmask)
        _eq(table["quality"], ref["quality"])
        _eq(table["count"], ref["count"])
    assert len(reg._groups) == 1          # one vmapped group, two slots


def test_same_shape_thresholds_batch_one_group_zero_warm_recompiles():
    """Queries of one plan SHAPE share a single vmapped fold: operands
    stack, state buckets to powers of two, and once a bucket is warm,
    further ingests and registrations inside it add ZERO executables."""
    store = SegmentStore(out_dim=D, chunk_rows=2048)   # capacity fixed:
    store.append_rows(_random_rows(256, D, seed=6))    # growth recompiles
    reg = StandingQueries(store)                       # tested elsewhere

    def plan(thr):
        return (Filter("quality", "ge", thr),
                GroupBy("category", "quality", agg="sum", num_groups=4))

    handles = {thr: reg.register(plan(thr)) for thr in (0.2, 0.5)}
    store.append_rows(_random_rows(256, D, seed=7, t0=256))
    reg.answer(handles[0.2])
    warm = sum(compile_cache_sizes().values())
    # same batch shape again: the fold is warm
    store.append_rows(_random_rows(256, D, seed=8, t0=512))
    # two more registrations land inside the qb=4 bucket
    handles[0.8] = reg.register(plan(0.8))
    handles[0.05] = reg.register(plan(0.05))
    store.append_rows(_random_rows(256, D, seed=9, t0=768))
    for thr, h in handles.items():
        table, mask = reg.answer(h)
        ref, rmask = _ref(store, plan(thr))
        _eq(mask, rmask)
        _eq(table["quality"], ref["quality"])
    g = next(iter(reg._groups.values()))
    assert g.q == 4 and g.qb == 4         # power-of-two bucket
    grew = sum(compile_cache_sizes().values()) - warm
    # bucket 1->2->4 growth re-traces the fold + answer once per
    # crossing; the second registration in the bucket and every warm
    # ingest/answer add nothing
    assert grew <= 4, f"{grew} new executables after warm point"
    before = sum(compile_cache_sizes().values())
    store.append_rows(_random_rows(256, D, seed=10, t0=1024))
    reg.answer(handles[0.8])
    assert sum(compile_cache_sizes().values()) == before, \
        "warm standing refresh recompiled"


def test_answer_is_rescan_free():
    """``answer`` never touches the stored rows: growing the store by
    10x between answers does not change the answer executable, and the
    un-refreshed answer still reflects only folded rows."""
    store = SegmentStore(out_dim=D, chunk_rows=64)
    store.append_rows(_random_rows(64, D, seed=11))
    reg = StandingQueries(store)
    h = reg.register((GroupBy("category", "quality", agg="sum",
                              num_groups=4),))
    t1, _ = reg.answer(h)
    ref1, _ = _ref(store, (GroupBy("category", "quality", agg="sum",
                                   num_groups=4),))
    _eq(t1["quality"], ref1["quality"])
    g = reg._group_of(reg._queries[h])
    frozen = {k: np.asarray(v) for k, v in g.state.items()}
    store.append_rows(_random_rows(640, D, seed=12, t0=64))
    t2, _ = reg.answer(h)
    ref2, _ = _ref(store, (GroupBy("category", "quality", agg="sum",
                                   num_groups=4),))
    _eq(t2["quality"], ref2["quality"])   # folds kept it current
    # and the state really is the only input: restoring it restores t1
    import jax.numpy as jnp
    g.state = {k: jnp.asarray(v) for k, v in frozen.items()}
    t3, _ = reg.answer(h)
    _eq(t3["quality"], t1["quality"])


# ---------------------------------------------------------------------------
# alerts
# ---------------------------------------------------------------------------

def test_subscription_fires_fixed_shape_and_counts():
    store = SegmentStore(out_dim=D, chunk_rows=128)
    reg = StandingQueries(store)
    plan = (GroupBy("category", "quality", agg="count", num_groups=4),)
    sid = reg.subscribe(plan, Filter("count", "ge", 120),
                        name="hot-category")
    assert reg.has_subscriptions
    store.append_rows(_random_rows(100, D, seed=13))
    quiet = reg.poll()
    assert len(quiet) == 1 and quiet[0].fired.shape == (4,)
    assert quiet[0].n_fired == 0 and quiet[0].sub == sid
    rows = _random_rows(400, D, seed=14, t0=100)
    rows["category"][:] = 2               # slam one group
    store.append_rows(rows)
    (alert,) = reg.poll()
    assert alert.fired.shape == (4,)      # fixed shape every tick
    assert alert.n_fired == 1 and bool(alert.fired[2])
    assert alert.table["count"][2] >= 120
    tel = store.telemetry()
    assert tel.alerts_checked == 2 and tel.alerts_fired == 1
    assert tel.standing_queries == 1 and tel.standing_refreshes == 2
    assert "alerts=1/2" in tel.summary()


def test_alert_on_float_column_and_predicate_validation():
    store = SegmentStore(out_dim=D, chunk_rows=128)
    reg = StandingQueries(store)
    plan = (WindowAgg(window=64, value="on_core_s", agg="sum",
                      num_windows=4),)
    reg.subscribe(plan, Filter("on_core_s", "gt", 100.0))
    with pytest.raises(AssertionError):
        reg.subscribe(plan, predicate=TopK(3, by="on_core_s"))
    store.append_rows(_random_rows(256, D, seed=15))
    (alert,) = reg.poll()
    ref, rmask = _ref(store, plan)
    want = rmask & (ref["on_core_s"] > 100.0)
    _eq(alert.fired, want)


# ---------------------------------------------------------------------------
# validation / attachment
# ---------------------------------------------------------------------------

def test_register_rejects_non_aggregating_and_unknown_columns():
    store = SegmentStore(out_dim=D, chunk_rows=64)
    reg = StandingQueries(store)
    with pytest.raises(ValueError, match="aggregating reducer"):
        reg.register((Filter("quality", "ge", 0.5), TopK(3, "quality")))
    with pytest.raises(ValueError, match="unknown column"):
        reg.register((Filter("nope", "ge", 0.5),
                      GroupBy("category", "quality", agg="sum",
                              num_groups=4)))
    with pytest.raises(ValueError, match="unknown columns"):
        reg.register((GroupBy("category", "latency", agg="mean",
                              num_groups=4),))
    with pytest.raises(AssertionError, match="already has"):
        StandingQueries(store)            # one registry per store
    assert len(reg) == 0


# ---------------------------------------------------------------------------
# tiering: spills never change a standing answer
# ---------------------------------------------------------------------------

def test_spill_invariance_single():
    """Every row's exact fp32 contribution is folded at INGEST, so
    demoting rows to the int8 cold tier afterwards cannot move a
    standing answer — while a rescan of the same store drifts."""
    store = SegmentStore(out_dim=D, chunk_rows=256)
    ts = TieredStore(store, seed=2)
    reg = StandingQueries(ts)
    assert ts.standing is reg             # tiered wrapper forwards
    plan = (Filter("quality", "ge", 0.1),
            GroupBy("category", "quality", agg="sum", num_groups=4))
    h = reg.register(plan)
    store.append_rows(_random_rows(2048, D, seed=16))
    before_t, before_m = reg.answer(h)
    before = {k: np.asarray(v) for k, v in before_t.items()}
    spilled = ts.spill(keep_hot=512)
    assert spilled > 0
    after_t, after_m = reg.answer(h)
    _eq(after_m, before_m)
    for k in before:
        _eq(after_t[k], before[k], err_msg=k)
    # the rescan over the two-tier view is only tolerance-close
    rescan, _ = ts.query(plan)
    _close(rescan["quality"], before["quality"],
           atol=ts.max_cold_scale() * 2048 + 1e-6)
    # and folds after the spill stay exact vs pre-quantization history
    store.append_rows(_random_rows(256, D, seed=17, t0=2048))
    ref_rows = _random_rows(2048, D, seed=16)
    new_rows = _random_rows(256, D, seed=17, t0=2048)
    full = {k: np.concatenate([ref_rows[k], new_rows[k]]) for k in ref_rows}
    ref, rmask = execute_ref(full, 2048 + 256, plan)
    got_t, got_m = reg.answer(h)
    _eq(got_m, rmask)
    _eq(got_t["quality"], ref["quality"])


def test_spill_invariance_sharded():
    hot = ShardedStore(out_dim=D, n_shards=2, chunk_rows=128)
    ts = ShardedTieredStore(hot, seed=3)
    reg = StandingQueries(ts)
    plan = (GroupBy("category", "quality", agg="max", num_groups=4),)
    h = reg.register(plan)
    hot.append_rows(_random_rows(1024, D, seed=18))
    before_t, before_m = reg.answer(h)
    before = np.asarray(before_t["quality"])
    assert ts.spill(keep_hot=256) > 0
    after_t, after_m = reg.answer(h)
    _eq(after_m, before_m)
    _eq(after_t["quality"], before)       # max: bit-exact across spill


# ---------------------------------------------------------------------------
# Pallas delta path
# ---------------------------------------------------------------------------

def test_pallas_delta_fold_matches_ref():
    """use_pallas=True folds via the fused zero-scatter delta kernel;
    max/count stay exact (the documented Pallas trade applies only to
    float sums)."""
    store = SegmentStore(out_dim=D, chunk_rows=256)
    store.append_rows(_random_rows(300, D, seed=19))
    reg = StandingQueries(store)
    plan = (Filter("k", "gt", 0.5),
            GroupBy("category", "quality", agg="max", num_groups=4))
    h = reg.register(plan, use_pallas=True)
    assert reg._group_of(reg._queries[h]).use_pallas
    store.append_rows(_random_rows(300, D, seed=20, t0=300))
    table, mask = reg.answer(h)
    ref, rmask = _ref(store, plan)
    _eq(mask, rmask)
    _eq(table["quality"], ref["quality"])
    _eq(table["count"], ref["count"])


def _tick(store, rng, V, t, valid):
    """One elastic-pool tick of random rows; ``valid`` marks live slots."""
    traces = {"c": rng.integers(0, 4, V).astype(np.int32),
              "k": rng.integers(0, D, V).astype(np.int32),
              "qual": rng.random(V).astype(np.float32),
              "on_s": (rng.random(V) * 20).astype(np.float32),
              "cl_s": (rng.random(V) * 5).astype(np.float32),
              "buffer_s": (rng.random(V) * 40).astype(np.float32)}
    store.ingest_tick({k: jnp.asarray(v) for k, v in traces.items()},
                      quality=traces["qual"],
                      out_vecs=rng.random((V, D)).astype(np.float32),
                      t=t, stream_ids=np.arange(V), valid=valid)


def test_pallas_delta_fold_masked_tick_matches_ref():
    """The Pallas delta folds the elastic pool's masked ticks as well:
    the live-slot mask enters the fused kernel as one more filter, so
    retired slots fold nothing; max/count stay exact, float sums match
    to tolerance."""
    store = SegmentStore(out_dim=D, chunk_rows=256)
    store.append_rows(_random_rows(300, D, seed=21))
    reg = StandingQueries(store)
    plans = [(Filter("k", "gt", 0.5),
              GroupBy("category", "quality", agg="max", num_groups=4)),
             (GroupBy("category", "on_core_s", agg="sum", num_groups=4),)]
    hs = [reg.register(p, use_pallas=True) for p in plans]
    assert all(reg._group_of(reg._queries[h]).use_pallas for h in hs)
    rng = np.random.default_rng(22)
    for tick in range(3):
        _tick(store, rng, 64, 300 + tick, rng.random(64) < 0.6)
    for h, plan in zip(hs, plans):
        table, mask = reg.answer(h)
        ref, rmask = _ref(store, plan)
        _eq(mask, rmask)
        _eq(table["count"], ref["count"])
        _eq(table["category"], ref["category"])
        value = plan[-1].value
        if plan[-1].agg == "max":
            _eq(table[value], ref[value])
        else:
            _close(table[value], ref[value], rtol=1e-5)


def test_standing_folds_bit_exact_across_sum_blocks():
    """Appends and masked ticks that start, span and end inside the
    ``SUM_BLOCK_ROWS`` blocks of the blocked float sums stay bit-exact
    with the rescan: the state carries the open block's partial and
    the row positions folded so far (inactive slots take none)."""
    B = SUM_BLOCK_ROWS
    store = SegmentStore(out_dim=D, chunk_rows=4096)
    store.append_rows(_random_rows(B - 5, D, seed=40))
    reg = StandingQueries(store)
    plans = [
        (Filter("quality", "ge", 0.25),
         GroupBy("category", "quality", agg="sum", num_groups=4)),
        (WindowAgg(window=8192, value="on_core_s", agg="mean",
                   num_windows=10),),
        (GroupBy("k", "out", agg="sum", num_groups=D),),
    ]
    handles = [reg.register(p, use_pallas=False) for p in plans]
    t0 = B - 5
    for i, n in enumerate((3, 2 * B + 11, B)):
        store.append_rows(_random_rows(n, D, seed=41 + i, t0=t0))
        t0 += n
    rng = np.random.default_rng(45)
    for tick in range(3):
        _tick(store, rng, 4096, t0 + tick, rng.random(4096) < 0.7)
    assert store.n_rows > 4 * B
    for h, plan in zip(handles, plans):
        table, mask = reg.answer(h)
        ref, rmask = _ref(store, plan)
        _eq(mask, rmask)
        for k in ref:
            _eq(table[k], ref[k], err_msg=f"{plan}:{k}")


def test_pallas_flag_ignored_on_sharded():
    store = ShardedStore(out_dim=D, n_shards=2, chunk_rows=128)
    reg = StandingQueries(store)
    h = reg.register((GroupBy("category", "quality", agg="max",
                              num_groups=4),), use_pallas=True)
    assert not reg._group_of(reg._queries[h]).use_pallas


# ---------------------------------------------------------------------------
# sharded stores
# ---------------------------------------------------------------------------

def test_sharded_standing_matches_rescan():
    """Sharded folds run inside the one shard_map ingest dispatch;
    answers match the rescan under the sharded-merge contract (counts /
    max exact, float sums tolerance-bounded)."""
    store = ShardedStore(out_dim=D, n_shards=2, chunk_rows=256)
    store.append_rows(_random_rows(400, D, seed=21))
    reg = StandingQueries(store)
    plans = [
        (Filter("quality", "ge", 0.3),
         GroupBy("category", "quality", agg="sum", num_groups=4)),
        (GroupBy("category", "quality", agg="max", num_groups=4),),
        (WindowAgg(window=128, value="on_core_s", agg="count",
                   num_windows=8),),
    ]
    handles = [reg.register(p) for p in plans]
    store.append_rows(_random_rows(300, D, seed=22, t0=400))
    store.append_rows(_random_rows(300, D, seed=23, t0=700))
    flat = store.host_rows()              # shard-major row order: fine
    for h, plan in zip(handles, plans):   # under the merge contract
        table, mask = reg.answer(h)
        ref, rmask = execute_ref(flat, store.n_rows, plan)
        _eq(mask, rmask, err_msg=str(plan))
        agg = plan[-1].agg
        val = plan[-1].value
        if agg in ("max", "count"):
            _eq(table[val], ref[val], err_msg=str(plan))
        else:
            _close(table[val], ref[val], rtol=2e-6, atol=1e-4)
        _eq(table["count"], ref["count"], err_msg=str(plan))


def test_sharded_one_shard_equals_single_store():
    """n_shards=1 standing answers equal the unsharded store's BIT-
    exactly — the per-shard fold is the single-store fold."""
    rows0 = _random_rows(200, D, seed=24)
    rows1 = _random_rows(150, D, seed=25, t0=200)
    plan = (Filter("quality", "lt", 0.7),
            GroupBy("category", "quality", agg="sum", num_groups=4))
    answers = []
    for store in (SegmentStore(out_dim=D, chunk_rows=128),
                  ShardedStore(out_dim=D, n_shards=1, chunk_rows=128)):
        store.append_rows(rows0)
        reg = StandingQueries(store)
        h = reg.register(plan)
        store.append_rows(rows1)
        answers.append(reg.answer(h))
    (t_single, m_single), (t_shard, m_shard) = answers
    _eq(m_single, m_shard)
    for k in t_single:
        _eq(t_single[k], t_shard[k], err_msg=k)


# ---------------------------------------------------------------------------
# capacity ladder: growth without warm recompiles
# ---------------------------------------------------------------------------

def test_bucket_cap_ladder():
    assert _bucket_cap(1, 64) == 64
    assert _bucket_cap(64, 64) == 64
    assert _bucket_cap(65, 64) == 128
    assert _bucket_cap(129, 64) == 256
    assert _bucket_cap(1000, 64) == 1024
    for need in range(1, 2000, 37):
        cap = _bucket_cap(need, 64)
        assert cap >= need and cap % 64 == 0
        assert (cap // 64) & (cap // 64 - 1) == 0    # power-of-two units


def test_capacity_growth_is_bucketed_zero_warm_recompiles():
    """Growing 0 -> ~5k rows touches only ladder capacities
    {chunk * 2^j} — O(log) compiles — and a SECOND store grown the same
    way reuses every executable."""
    def grow(chunk=64, batches=40, n=128, seed0=30):
        store = SegmentStore(out_dim=D, chunk_rows=chunk)
        caps = set()
        t0 = 0
        for i in range(batches):
            store.append_rows(_random_rows(n, D, seed=seed0 + i, t0=t0))
            t0 += n
            caps.add(store.capacity)
        return store, caps

    store, caps = grow()
    assert store.n_rows == 40 * 128
    assert all(c % 64 == 0 and ((c // 64) & (c // 64 - 1)) == 0
               for c in caps)
    assert len(caps) <= 8                 # ladder, not per-batch growth
    warm = sum(compile_cache_sizes().values())
    store2, caps2 = grow(seed0=70)
    assert caps2 == caps
    assert sum(compile_cache_sizes().values()) == warm, \
        "regrowth recompiled despite bucketed capacities"
    h1, h2 = store.host_rows(), store2.host_rows()
    assert h1["t"].shape == h2["t"].shape == (40 * 128,)


def test_sharded_capacity_growth_bucketed():
    def grow(seed0):
        store = ShardedStore(out_dim=D, n_shards=2, chunk_rows=64)
        caps = set()
        for i in range(12):
            store.append_rows(_random_rows(96, D, seed=seed0 + i,
                                           t0=96 * i))
            caps.add(store.capacity)
        return store, caps

    s1, caps = grow(100)
    assert all(c % 64 == 0 and ((c // 64) & (c // 64 - 1)) == 0
               for c in caps)
    warm = sum(compile_cache_sizes().values())
    s2, caps2 = grow(200)
    assert caps2 == caps and s2.n_rows == s1.n_rows == 12 * 96
    assert sum(compile_cache_sizes().values()) == warm, \
        "sharded regrowth recompiled"


# ---------------------------------------------------------------------------
# ownership: every write consumes (donates) the columns it was given
# ---------------------------------------------------------------------------

def _trace_cols(rng, shape):
    """Random switch traces with ``shape`` leaves, as host numpy."""
    return {"c": rng.integers(0, 4, shape).astype(np.int32),
            "k": rng.integers(0, D, shape).astype(np.int32),
            "qual": rng.random(shape).astype(np.float32),
            "on_s": (rng.random(shape) * 20).astype(np.float32),
            "cl_s": (rng.random(shape) * 5).astype(np.float32),
            "buffer_s": (rng.random(shape) * 40).astype(np.float32)}


def _rows_from(tr, quality, out, stream_id, t):
    """The rows a write of these (flat) traces should land."""
    return {"stream_id": np.asarray(stream_id, np.int32),
            "t": np.asarray(t, np.int32), "category": tr["c"],
            "k": tr["k"], "quality": quality, "on_core_s": tr["on_s"],
            "cloud_core_s": tr["cl_s"], "buffer_s": tr["buffer_s"],
            "out": out}


def _dev(tr):
    return {k: jnp.asarray(v) for k, v in tr.items()}


def _write(path, store, rng, i):
    """Write ``i`` of one kind through the store's public API; returns
    the rows it should land, computed on the host."""
    t0 = 1000 * i
    if path == "append_rows":
        rows = _random_rows(40, D, seed=50 + i, t0=t0)
        store.append_rows(rows)
        return rows
    if path in ("tick", "tick_masked"):
        V = 16
        tr = _trace_cols(rng, (V,))
        q = rng.random(V).astype(np.float32)
        out = rng.random((V, D)).astype(np.float32)
        if path == "tick":
            store.ingest_tick(_dev(tr), quality=q, out_vecs=out, t=t0)
            return _rows_from(tr, q, out, np.arange(V), np.full(V, t0))
        ids = rng.permutation(100)[:V]
        keep = rng.random(V) < 0.6
        store.ingest_tick(_dev(tr), quality=q, out_vecs=out, t=t0,
                          stream_ids=ids, valid=keep)
        return {k: v[keep] for k, v in _rows_from(
            tr, q, out, ids, np.full(V, t0)).items()}
    n_w, W, T = 3, 8, 21                       # T < n_w * W: tail padding
    if path == "fused":
        tr = _trace_cols(rng, (n_w, W))
        out = rng.random((T, D)).astype(np.float32)
        store.ingest_fused(_dev(tr), out, stream_id=5, t0=t0)
        flat = {k: v.reshape(-1)[:T] for k, v in tr.items()}
        return _rows_from(flat, flat["qual"], out, np.full(T, 5),
                          t0 + np.arange(T))
    V = 3                                      # fused_multi
    tr = _trace_cols(rng, (n_w, V, W))
    out = rng.random((V, T, D)).astype(np.float32)
    store.ingest_fused_multi(_dev(tr), out, stream_base=7, t0=t0)
    flat = {k: np.swapaxes(v, 0, 1).reshape(V, -1)[:, :T].reshape(-1)
            for k, v in tr.items()}
    return _rows_from(flat, flat["qual"], out.reshape(V * T, D),
                      7 + np.repeat(np.arange(V), T),
                      t0 + np.tile(np.arange(T), V))


@pytest.mark.parametrize("standing", [True, False],
                         ids=["standing", "plain"])
@pytest.mark.parametrize("path", ["tick_masked", "tick", "fused",
                                  "fused_multi", "append_rows"])
def test_every_write_consumes_the_columns_it_was_given(path, standing):
    """Each write kernel donates the store's columns: the arrays the
    store held before a write read ``is_deleted()`` after it, every
    write counts in ``donated_dispatches``, and the rows and standing
    answers equal the numpy reference of the same rows."""
    store = SegmentStore(out_dim=D, chunk_rows=64)
    plan = (Filter("quality", "ge", 0.25),
            GroupBy("category", "quality", agg="sum", num_groups=4))
    reg = StandingQueries(store)
    h = reg.register(plan) if standing else None
    rng = np.random.default_rng(40)
    landed, in_place = [], 0
    for i in range(4):
        old, cap = store.columns, store.capacity
        landed.append(_write(path, store, rng, i))
        # a write that grows the store donates the grown copy instead
        if store.capacity == cap:
            assert all(v.is_deleted() for v in old.values()), (path, i)
            in_place += 1
        assert not any(v.is_deleted() for v in store.columns.values())
        tel = store.telemetry()
        assert tel.donated_dispatches == tel.ingest_dispatches == i + 1
    assert in_place >= 1 and store.capacity > 0
    want = {k: np.concatenate([r[k] for r in landed]) for k in landed[0]}
    got = store.host_rows()
    for k in want:
        _eq(got[k], want[k], err_msg=k)
    if standing:
        table, mask = reg.answer(h)
        ref, rmask = execute_ref(want, len(want["t"]), plan)
        _eq(mask, rmask)
        for k in ref:
            _eq(table[k], ref[k], err_msg=k)


def test_tiered_spill_and_materialize_across_donating_ingests():
    """The tiered view survives the hot tier's donated writes: a write
    retires the hot columns the memoized view was keyed on (the next
    ``materialize`` rebuilds it with the new rows), a view a caller
    already holds stays alive (it is a copy, never donated), and
    spills between writes lose no row."""
    store = SegmentStore(out_dim=D, chunk_rows=16)
    ts = TieredStore(store, seed=5)
    reg = StandingQueries(ts)
    plan = (GroupBy("category", "quality", agg="max", num_groups=4),)
    h = reg.register(plan)
    count = (GroupBy("category", "t", agg="count", num_groups=4),)
    rng = np.random.default_rng(41)
    landed = []
    for i in range(4):
        landed.append(_write("append_rows", store, rng, 2 * i))
        assert ts.spill(keep_hot=0) > 0
        view, n = ts.materialize()
        assert ts.materialize()[0] is view        # memoized
        hot, cap = store.columns, store.capacity
        landed.append(_write("tick_masked", store, rng, 2 * i + 1))
        assert store.capacity == cap
        assert all(v.is_deleted() for v in hot.values())
        assert not any(v.is_deleted() for v in view.values())
        view2, n2 = ts.materialize()
        assert view2 is not view and n2 == n + len(landed[-1]["t"])
        want = {k: np.concatenate([r[k] for r in landed])
                for k in landed[0]}
        table, mask = ts.query(count)
        ref, rmask = execute_ref(want, n2, count)
        _eq(mask, rmask)
        _eq(table["count"], ref["count"])          # integer: lossless
        stand, smask = reg.answer(h)
        ref, rmask = execute_ref(want, n2, plan)
        _eq(smask, rmask)
        _eq(stand["quality"], ref["quality"])      # folded at ingest
    tel = ts.telemetry()
    assert tel.donated_dispatches == tel.ingest_dispatches == 8
