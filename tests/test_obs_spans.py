"""Program spans and host counters of the served tick: one span of each
layer per tick, nested as ``repro.obs`` lists them and carrying the
tick's ``t``; none inside the per-slot loop; every Python collection
named; and the pool's transfer counters independent of the flight
recorder."""
import gc
import glob
import os

import jax
import numpy as np
import pytest

from repro.core.api import Skyscraper, SkyscraperPool
from repro.obs import install_gc_spans
from repro.warehouse import (Filter, GroupBy, SegmentStore, ShardedStore,
                             StandingQueries)

PLAN_EVERY = 4
# spans of every tick, and those of some (a replan, a store growth)
EVERY_TICK = ("pool.tick", "pool.dispatch", "pool.pull", "pool.transform",
              "pool.recorder", "pool.load", "sink.ingest", "sink.alert_poll")
PARENT = {"pool.dispatch": "pool.tick", "pool.pull": "pool.tick",
          "pool.transform": "pool.tick", "pool.recorder": "pool.tick",
          "pool.load": "pool.tick", "sink.ingest": "pool.tick",
          "sink.alert_poll": "pool.tick",
          "pool.replan": "pool.dispatch", "sink.grow": "sink.ingest"}


def _proc(seg, knobs):
    return seg, float(np.clip(1 - seg * (1 - 1.0 / knobs["det"]), 0, 1))


@pytest.fixture(scope="module")
def sky():
    s = Skyscraper(segment_seconds=2.0, n_categories=3)
    s.set_resources(num_cores=4)
    s.register_knob("det", [1, 5, 10])
    s.fit(list(np.linspace(0, 1, 40)), _proc, plan_segments=PLAN_EVERY)
    return s


def _pool(sky, V, telemetry=True, sharded=False):
    store = (ShardedStore(out_dim=len(sky.configs), n_shards=2,
                          chunk_rows=16) if sharded
             else SegmentStore(out_dim=len(sky.configs), chunk_rows=16))
    reg = StandingQueries(store)
    reg.register((GroupBy("k", "quality", "mean", num_groups=8),))
    reg.subscribe((GroupBy("stream_id", "buffer_s", "max",
                           num_groups=64),),
                  Filter("buffer_s", "gt", 0.0))
    return SkyscraperPool(sky, n_streams=V, sink=store,
                          telemetry=telemetry), store


def _ticks(pool, V, n, seed=0):
    rng = np.random.default_rng(seed)
    for _ in range(n):
        pool.process(list(rng.random(V)))


def _traced(tmp_path, fn):
    """Run ``fn`` under the profiler; returns the program's spans
    ``[(name, start_ns, end_ns, stats)]`` sorted by start."""
    jax.profiler.start_trace(str(tmp_path))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    pd = jax.profiler.ProfileData.from_file(path)
    out = [(e.name, e.start_ns, e.end_ns, {k: v for k, v in e.stats})
           for plane in pd.planes if plane.name.startswith("/host:")
           for line in plane.lines for e in line.events
           if e.name.startswith(("pool.", "sink.", "host."))]
    return sorted(out, key=lambda x: (x[1], -x[2]))


def _by_tick(spans):
    """{t: [spans inside that tick's pool.tick]} (pool.tick included)."""
    ticks = [(s, e, st["t"]) for n, s, e, st in spans if n == "pool.tick"]
    out = {t: [] for _, _, t in ticks}
    for sp in spans:
        for s, e, t in ticks:
            if s <= sp[1] and sp[2] <= e:
                out[t].append(sp)
    return out


@pytest.mark.parametrize("sharded", [False, True])
def test_each_tick_has_one_span_per_layer_nested_with_its_t(sky, tmp_path,
                                                            sharded):
    V, n = 4, 2 * PLAN_EVERY
    pool, store = _pool(sky, V, sharded=sharded)
    _ticks(pool, V, 2)                       # compile outside the trace
    t0 = pool._seen
    spans = _traced(tmp_path, lambda: _ticks(pool, V, n, seed=1))
    ticks = _by_tick(spans)
    assert sorted(ticks) == list(range(t0, t0 + n))
    for t, inside in ticks.items():
        names = [sp[0] for sp in inside if sp[0] != "host.gc"]
        for name in EVERY_TICK:
            assert names.count(name) == 1, (t, name, names)
        replans = (t + 1) % PLAN_EVERY == 0
        assert names.count("pool.replan") == int(replans), (t, names)
        assert names.count("sink.grow") <= 1
        for name, s, e, st in inside:
            if "t" in st:
                assert st["t"] == t, (name, st)
            if name in PARENT:
                # the innermost span around it is its parent
                around = [x for x in inside if x[1] <= s and e <= x[2]
                          and x[:3] != (name, s, e)]
                assert max(around, key=lambda x: x[1])[0] == PARENT[name]
        stats = {sp[0]: sp[3] for sp in inside}
        # one blocking read at each sync point: the decisions, the poll
        assert stats["pool.pull"]["pulls"] == 1
        assert stats["sink.alert_poll"]["pulls"] == 1
        assert stats["pool.tick"]["host_pulls"] == 2
        assert stats["pool.transform"]["transformed"] \
            + stats["pool.transform"]["dropped"] == V
        assert stats["sink.ingest"]["rows"] == V
        # the single store's write consumed its columns; the sharded
        # store does not donate
        assert stats["sink.ingest"]["donated"] == int(not sharded)
        assert stats["sink.alert_poll"]["subscriptions"] == 1
    tel = store.telemetry()
    assert tel.uploads > 0
    assert tel.donated_dispatches == (0 if sharded
                                      else tel.ingest_dispatches)


def test_span_count_per_tick_does_not_grow_with_streams(sky, tmp_path):
    counts = {}
    for V in (8, 64):
        pool, _ = _pool(sky, V)
        _ticks(pool, V, 2)
        spans = _traced(tmp_path / str(V), lambda: _ticks(pool, V, 2))
        ticks = _by_tick([sp for sp in spans if sp[0] != "host.gc"])
        counts[V] = sorted(len(v) for v in ticks.values())
    assert counts[8] == counts[64], counts


def test_a_collection_is_a_host_gc_span(tmp_path):
    install_gc_spans()
    install_gc_spans()                       # installs once
    spans = _traced(tmp_path, lambda: gc.collect())
    full = [st for n, _, _, st in spans
            if n == "host.gc" and st.get("generation") == 2]
    assert len(full) == 1 and full[0]["collected"] >= 0


def test_transfer_counts_do_not_need_the_flight_recorder(sky):
    V, n = 4, PLAN_EVERY + 1
    seen = {}
    for telemetry in (False, True):
        pool, store = _pool(sky, V, telemetry=telemetry)
        _ticks(pool, V, n)
        tel = pool._tel
        seen[telemetry] = (tel.ticks, tel.replans, tel.uploads,
                           tel.host_pulls, store.obs["uploads"],
                           store.obs["host_pulls"])
    off, on = seen[False], seen[True]
    # the recorder reads nothing of its own: on_s and cl_s ride in the
    # tick's one packed read
    assert on == off
    ticks, replans, uploads, pulls, s_up, s_pulls = off
    assert (ticks, replans) == (n, 1)
    # per tick: one read of the decisions; per replan three operands;
    # per tick five dispatch operands and the measured qualities
    assert pulls == n and uploads == 6 * n + 3 * replans
    # per tick the store uploads t, row offset, stream ids, the mask;
    # the poll reads the alert's answer row and mask in one read
    assert s_up == 4 * n and s_pulls == n
    assert pool.telemetry().extras["host_pulls"] == on[3]
