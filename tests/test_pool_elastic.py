"""Elastic serving pool: admit/retire lifecycle vs a per-stream oracle,
priority shedding, the zero-warm-recompile-within-a-bucket contract,
admission control, and live shard rebalancing.

The property test drives RANDOM interleavings of admit / retire / tick
(with random priorities and arrival multipliers) through the pool and
checks every stream's decision trajectory bit-exactly against running
that stream ALONE through the single-stream switcher — the elastic
slot machinery (masks, slot reuse, capacity growth) must be invisible
to the decisions. Runs through real ``hypothesis`` when installed,
else the bundled deterministic fallback (tests/_hypothesis_fallback.py).

The rebalance tests pin the 1-shard == N-shard property contract across
a repartition: row sets bit-identical, ownership law restored, standing
registrations replayed handle-stably. On the forced-8-device CI leg the
rebalance kernels run as real shard_map collectives."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core.api import AdmissionError, Skyscraper, SkyscraperPool
from repro.core.switcher import (compile_cache_sizes, init_state,
                                 switch_step)
from repro.runtime.elastic import rebalance
from repro.warehouse import (Filter, GroupBy, SegmentStore, ShardedStore,
                             StandingQueries)


@pytest.fixture(autouse=True, scope="module")
def _fresh_compile_caches():
    # the bucket-growth tests compile the pool executables at several
    # capacities; start and end with empty caches so this module's
    # compile load doesn't stack on the rest of the suite
    jax.clear_caches()
    yield
    jax.clear_caches()


def _quality_of(knobs):
    return min(0.5 + 0.1 * knobs["q"], 1.0)


def _proc(seg, knobs):
    return ("out", _quality_of(knobs))


_SKY_CACHE = []


def _fitted_sky():
    if not _SKY_CACHE:
        rng = np.random.default_rng(0)
        s = Skyscraper(fps=2, segment_seconds=1.0, n_categories=2, seed=0)
        s.set_resources(num_cores=4, buffer_gb=1.0,
                        cloud_budget_core_s=0.0)
        s.register_knob("q", [1, 2, 3])
        s.fit([rng.random((3,)) for _ in range(12)], _proc)
        _SKY_CACHE.append(s)
    return _SKY_CACHE[0]


@pytest.fixture(scope="module")
def sky():
    return _fitted_sky()


# ---------------------------------------------------------------------------
# property: random admit/retire/priority interleavings vs per-stream oracle
# ---------------------------------------------------------------------------

@st.composite
def _schedules(draw):
    """A short op schedule over stream ids: each entry is
    ('admit', prio) / ('retire',) / ('tick', [arrival mults seed])."""
    ops = []
    n_ops = draw(st.integers(min_value=4, max_value=10))
    for _ in range(n_ops):
        kind = draw(st.sampled_from(["admit", "admit", "tick", "tick",
                                     "tick", "retire"]))
        if kind == "admit":
            ops.append(("admit", draw(st.floats(min_value=0.5,
                                                max_value=4.0))))
        elif kind == "retire":
            ops.append(("retire", draw(st.integers(min_value=0,
                                                   max_value=100))))
        else:
            ops.append(("tick", draw(st.integers(min_value=0,
                                                 max_value=10_000))))
    return ops


@settings(max_examples=10, deadline=None)
@given(_schedules())
def test_admit_retire_interleavings_match_per_stream_oracle(ops):
    sky = _fitted_sky()
    pool = SkyscraperPool(sky, n_streams=1, slot_chunk=2)
    plan_every0 = sky._plan_every
    sky._plan_every = 10_000               # plans pinned: oracle uses alpha0
    try:
        _run_oracle_case(sky, pool, ops)
    finally:
        sky._plan_every = plan_every0


def _run_oracle_case(sky, pool, ops):
    alpha0 = jnp.asarray(sky.alpha)
    # oracle: per-stream single-stream switcher state + pending quality
    ostate = {0: init_state(sky.tables)}
    opending = {0: None}
    next_sid = 1
    seg = np.zeros(3)
    for op in ops:
        if op[0] == "admit":
            pool.admit(next_sid, priority=op[1])
            ostate[next_sid] = init_state(sky.tables)
            opending[next_sid] = None
            next_sid += 1
        elif op[0] == "retire":
            if pool.V > 1:                 # keep at least one stream live
                sid = pool.streams[op[1] % pool.V]
                pool.retire(sid)
                del ostate[sid], opending[sid]
        else:
            rng = np.random.default_rng(op[1])
            mults = {s: 0.5 + rng.random() for s in pool.streams}
            statuses, _ = pool.process({s: seg for s in pool.streams},
                                       arrival_mults=mults)
            for stat in statuses:
                sid = stat["stream_id"]
                stt = dict(ostate[sid])
                if opending[sid] is not None:
                    stt["qual_prev"] = jnp.float32(opending[sid])
                stt, outs = switch_step(stt, jnp.zeros(len(sky.configs)),
                                        jnp.float32(mults[sid]), alpha0,
                                        sky.tables)
                ostate[sid] = stt
                assert stat["k"] == int(outs["k"]), (sid, stat)
                assert stat["category"] == int(outs["c"]), (sid, stat)
                np.testing.assert_array_equal(
                    np.float32(stat["buffer_s"]),
                    np.asarray(outs["buffer_s"], np.float32),
                    err_msg=f"stream {sid}")
                assert stat["dropped"] == bool(outs["dropped"])
                assert not stat["shed"]    # no capacity/watermark set
                opending[sid] = (None if stat["dropped"]
                                 else _quality_of(stat["config"]))


# ---------------------------------------------------------------------------
# priority shedding + alerts
# ---------------------------------------------------------------------------

def test_shed_order_respects_priority(sky):
    prios = [4.0, 3.0, 2.0, 1.0]
    pool = SkyscraperPool(sky, n_streams=4, priorities=prios,
                          telemetry=True)
    seg = np.zeros(3)
    # one unconstrained tick to measure per-stream planned demand (all
    # four streams see identical content, so all demands are equal)
    pool.process([seg] * pool.V)
    demand = float(pool.telemetry().counters["onprem_core_s"][0])
    assert demand > 0
    # capacity_core_s is a traced operand: set it between 2 and 3
    # stream-demands without touching any compiled program
    pool.capacity_core_s = demand * 2.5
    n_ticks = 6
    shed_count = np.zeros(4)
    for tick in range(n_ticks):
        statuses, results = pool.process([seg] * pool.V)
        shed = [s["shed"] for s in statuses]
        # the kept set is always a PREFIX of the priority order: a shed
        # stream never outranks a kept one
        for i in range(1, 4):
            assert not (shed[i - 1] and not shed[i]), (tick, shed)
        if tick == 0:
            # first constrained tick: identical demands, room for two
            assert shed == [False, False, True, True], shed
        for i, s in enumerate(shed):
            if s:
                assert results[i] is None
        shed_count += shed
    assert shed_count[0] == 0              # highest priority never shed
    assert shed_count[3] == n_ticks        # lowest priority always shed
    stats = pool.shed_stats()
    for sid, prio in enumerate(prios):
        assert stats[sid]["priority"] == prio
        assert stats[sid]["segments"] == n_ticks + 1
    # the flight recorder carries the shed fraction per stream
    tel = pool.telemetry()
    np.testing.assert_array_equal(tel.counters["seg_dropped"],
                                  shed_count)


def test_shed_surfaces_as_standing_alerts(sky):
    sink = SegmentStore(out_dim=len(sky.configs), chunk_rows=32)
    reg = StandingQueries(sink)
    # a shed stream's row lands with quality 0: alert on any stream
    # whose minimum recorded quality hits the floor
    reg.subscribe(
        [GroupBy("stream_id", "quality", agg="min", num_groups=8)],
        Filter("quality", "le", 0.0), name="shed-watch")
    pool = SkyscraperPool(sky, n_streams=3, priorities=[3.0, 2.0, 1.0],
                          sink=sink, telemetry=True)
    pool.process([np.zeros(3)] * pool.V)   # unconstrained: measure demand
    demand = float(pool.telemetry().counters["onprem_core_s"][0])
    pool.capacity_core_s = demand * 1.5    # room for one stream
    for _ in range(3):
        pool.process([np.zeros(3)] * pool.V)
    assert len(pool.alerts) == 1 and pool.alerts[0].name == "shed-watch"
    fired = pool.alerts[0].fired
    assert not fired[0]                    # highest priority never shed
    assert fired[2]                        # lowest priority shed -> alert


def test_pallas_standing_fold_skips_inactive_slots(sky):
    """The masked tick lands rows for active slots only; a standing
    query whose fold runs the fused kernel must count only those rows
    (empty slots carry stream id 0 on the slot axis)."""
    sink = SegmentStore(out_dim=len(sky.configs), chunk_rows=32)
    reg = StandingQueries(sink)
    h = reg.register([Filter("stream_id", "eq", 0.0),
                      GroupBy("k", "quality", agg="count",
                              num_groups=len(sky.configs))],
                     use_pallas=True)
    pool = SkyscraperPool(sky, n_streams=3, sink=sink, slot_chunk=8)
    assert pool.cap == 8
    for _ in range(4):
        pool.process([np.zeros(3)] * pool.V)
    table, _ = reg.answer(h)
    assert float(np.asarray(table["count"]).sum()) == 4.0


def test_admission_control_refuses_infeasible(sky):
    cost_min = float(np.min(np.asarray(sky.tables.cost)))
    pool = SkyscraperPool(sky, n_streams=2,
                          capacity_core_s=cost_min * 3.5)
    pool.admit(77)                         # 3 streams fit at min cost
    with pytest.raises(AdmissionError):
        pool.admit(79)                     # a 4th cannot, even degraded
    assert 79 not in pool.streams
    pool.admit(79, force=True)             # explicit override admits
    assert 79 in pool.streams
    pool.retire(79)
    pool.retire(77)
    pool.admit(78)                         # back under the bar: admitted
    with pytest.raises(ValueError):
        pool.admit(78)                     # duplicate id refused


def test_joint_plan_weights_priorities(sky):
    pool = SkyscraperPool(sky, n_streams=3, priorities=[3.0, 2.0, 1.0],
                          joint_plan=True)
    for _ in range(2 * sky._plan_every):
        pool.process([np.zeros(3)] * pool.V)
    alpha = np.asarray(pool._alpha)
    active = np.asarray(pool._active)
    # every ACTIVE stream's plan stays a per-category simplex
    np.testing.assert_allclose(alpha[active].sum(-1), 1.0, atol=1e-5)
    assert np.isfinite(alpha).all()


# ---------------------------------------------------------------------------
# zero warm recompiles within a capacity bucket, across >= 3 buckets
# ---------------------------------------------------------------------------

def test_zero_warm_recompiles_within_bucket_across_three_buckets(sky):
    rng = np.random.default_rng(1)
    pool = SkyscraperPool(sky, n_streams=2, telemetry=True)
    sid = [1000]

    def admit_n(n):
        for _ in range(n):
            sid[0] += 1
            pool.admit(sid[0], priority=float(sid[0] % 5))

    def warm_bucket():
        # touch every executable once at this capacity: admit, retire,
        # tick, and a replan window
        admit_n(1)
        pool.retire(sid[0])
        for _ in range(2 * sky._plan_every):
            pool.process({s: rng.random(3) for s in pool.streams})

    seen_buckets = []
    for target_extra in (3, 7, 14):        # drives cap through 8, 16, 32
        warm_bucket()
        cap0 = pool.cap
        warm = compile_cache_sizes()
        # churn admits/retires/ticks INSIDE the bucket
        admit_n(target_extra)
        pool.retire(pool.streams[0])
        for _ in range(2 * sky._plan_every):
            pool.process({s: rng.random(3) for s in pool.streams})
        after = compile_cache_sizes()
        grew = {k: (warm.get(k, 0), v) for k, v in after.items()
                if v != warm.get(k, 0)}
        # churn that crossed into a NEW bucket is allowed its one
        # compile per executable; within the bucket, zero growth
        if pool.cap == cap0:
            assert not grew, (cap0, grew)
        seen_buckets.append(pool.cap)
    assert len(set(seen_buckets)) >= 2 and pool.cap >= 32
    # and the largest bucket itself holds the contract after warmup
    warm_bucket()
    warm = compile_cache_sizes()
    admit_n(2)
    pool.retire(pool.streams[-1])
    for _ in range(2 * sky._plan_every):
        pool.process({s: rng.random(3) for s in pool.streams})
    grew = {k: (warm.get(k, 0), v)
            for k, v in compile_cache_sizes().items()
            if v != warm.get(k, 0)}
    assert not grew, grew


# ---------------------------------------------------------------------------
# live shard rebalancing
# ---------------------------------------------------------------------------

def _random_rows(n, seed=0):
    rng = np.random.default_rng(seed)
    return {
        "stream_id": rng.integers(0, 11, n).astype(np.int32),
        "t": np.sort(rng.integers(0, 50, n)).astype(np.int32),
        "category": rng.integers(0, 4, n).astype(np.int32),
        "k": rng.integers(0, 4, n).astype(np.int32),
        "quality": rng.random(n).astype(np.float32),
        "on_core_s": rng.random(n).astype(np.float32),
        "cloud_core_s": rng.random(n).astype(np.float32),
        "buffer_s": rng.random(n).astype(np.float32),
        "out": rng.random((n, 3)).astype(np.float32),
    }


def _sorted_rows(hr):
    order = np.lexsort((np.asarray(hr["t"]), np.asarray(hr["quality"]),
                        np.asarray(hr["stream_id"])))
    return {k: np.asarray(v)[order] for k, v in hr.items()}


@pytest.mark.parametrize("s_old,s_new", [(2, 4), (2, 8), (4, 2), (3, 1)])
def test_rebalance_rows_bit_identical(s_old, s_new):
    store = ShardedStore(out_dim=3, n_shards=s_old, chunk_rows=8)
    store.append_rows(
        {k: jnp.asarray(v) for k, v in _random_rows(57).items()})
    new = rebalance(store, s_new)
    assert new.n_rows == store.n_rows
    a, b = _sorted_rows(store.host_rows()), _sorted_rows(new.host_rows())
    for k in a:
        np.testing.assert_array_equal(a[k], b[k], err_msg=k)
    # ownership law restored under the new shard count
    ids = np.asarray(new.columns["stream_id"])
    for s in range(s_new):
        nn = int(new.n_rows_by_shard[s])
        assert (ids[s, :nn] % s_new == s).all()
    # the source store is untouched
    assert store.n_shards == s_old and len(store) == 57


def test_rebalance_preserves_queries_and_standing():
    store = ShardedStore(out_dim=3, n_shards=2, chunk_rows=8)
    reg = StandingQueries(store)
    h = reg.register(
        [GroupBy("category", "quality", agg="sum", num_groups=4)])
    reg.subscribe([GroupBy("k", "quality", agg="sum", num_groups=4)],
                  Filter("quality", "gt", 0.5), name="hot-k")
    store.append_rows(
        {k: jnp.asarray(v) for k, v in _random_rows(43, seed=3).items()})
    t0, m0 = reg.answer(h)
    new = rebalance(store, 4)
    # standing registry replays handle-stably on the new store
    t1, m1 = new.standing.answer(h)
    np.testing.assert_array_equal(np.asarray(m0), np.asarray(m1))
    np.testing.assert_array_equal(np.asarray(t0["count"]),
                                  np.asarray(t1["count"]))
    np.testing.assert_allclose(np.asarray(t0["quality"]),
                               np.asarray(t1["quality"]),
                               rtol=1e-5, atol=1e-5)
    alerts = new.standing.poll()
    assert [a.name for a in alerts] == ["hot-k"]
    # ad-hoc queries obey the 1-shard == N-shard contract across the move
    plan = [Filter("quality", "gt", 0.3),
            GroupBy("category", "quality", agg="mean", num_groups=4)]
    tbl_old, mask_old = store.query(plan)
    tbl_new, mask_new = new.query(plan)
    np.testing.assert_array_equal(np.asarray(mask_old),
                                  np.asarray(mask_new))
    np.testing.assert_array_equal(np.asarray(tbl_old["count"]),
                                  np.asarray(tbl_new["count"]))
    np.testing.assert_allclose(np.asarray(tbl_old["quality"]),
                               np.asarray(tbl_new["quality"]),
                               rtol=1e-5, atol=1e-5)


def test_rebalance_roundtrip_through_one_shard():
    store = ShardedStore(out_dim=3, n_shards=4, chunk_rows=8)
    store.append_rows(
        {k: jnp.asarray(v) for k, v in _random_rows(29, seed=5).items()})
    down = rebalance(store, 1)
    back = rebalance(down, 4)
    a, b = _sorted_rows(store.host_rows()), _sorted_rows(back.host_rows())
    for k in a:
        np.testing.assert_array_equal(a[k], b[k], err_msg=k)
    # identical partitioning law => identical per-shard counts
    np.testing.assert_array_equal(store.n_rows_by_shard,
                                  back.n_rows_by_shard)


def test_pool_sink_rebalance_end_to_end(sky):
    """admit -> tick -> retire -> rebalance: rows carry REAL stream ids
    so the repartition groups each stream's history onto its new
    owner."""
    sink = ShardedStore(out_dim=len(sky.configs), n_shards=2,
                        chunk_rows=32)
    pool = SkyscraperPool(sky, n_streams=2, sink=sink)
    pool.admit(9)
    for _ in range(4):
        pool.process([np.zeros(3)] * pool.V)
    pool.retire(1)
    for _ in range(2):
        pool.process([np.zeros(3)] * pool.V)
    assert len(sink) == 3 * 4 + 2 * 2
    new = rebalance(sink, 4)
    hr = new.host_rows()
    assert set(np.asarray(hr["stream_id"]).tolist()) == {0, 1, 9}
    a, b = _sorted_rows(sink.host_rows()), _sorted_rows(hr)
    for k in a:
        np.testing.assert_array_equal(a[k], b[k], err_msg=k)
