"""One blocking read per sync point of the served tick: the decisions
unpacked from the tick's one packed read are the arrays read one by one,
bit for bit, and the alert poll's one read gives every subscription the
row and mask that ``answer`` gives."""
import numpy as np
import pytest

from repro.core import api
from repro.core.api import Skyscraper, SkyscraperPool
from repro.obs import telemetry_ref
from repro.obs.packing import unpack32
from repro.warehouse import (Filter, GroupBy, SegmentStore, ShardedStore,
                             StandingQueries)
from repro.warehouse.standing import _CMP
from test_warehouse import _random_rows

ONE_BY_ONE = ("k", "c", "buffer_s", "dropped", "shed", "on_s", "cl_s")


def _proc(seg, knobs):
    return seg, float(np.clip(1 - seg * (1 - 1.0 / knobs["det"]), 0, 1))


@pytest.fixture(scope="module")
def sky():
    s = Skyscraper(segment_seconds=2.0, n_categories=3)
    s.set_resources(num_cores=4, buffer_gb=0.001)
    s.register_knob("det", [1, 5, 10])
    s.fit(list(np.linspace(0, 1, 40)), _proc, plan_segments=4)
    return s


def test_packed_decisions_equal_the_arrays_read_one_by_one(sky,
                                                          monkeypatch):
    seen = []
    real = api._pool_tick

    def spy(state, q_meas, q_valid, quals, arr, active, *rest):
        new_state, outs = real(state, q_meas, q_valid, quals, arr, active,
                               *rest)
        seen.append((dict(outs), active))
        return new_state, outs

    monkeypatch.setattr(api, "_pool_tick", spy)
    V, n = 6, 12                  # 8 slots: two of them inactive
    store = SegmentStore(out_dim=len(sky.configs), chunk_rows=16)
    reg = StandingQueries(store)
    reg.subscribe((GroupBy("stream_id", "buffer_s", "max",
                           num_groups=8),), Filter("buffer_s", "gt", 1.0))
    pool = SkyscraperPool(sky, n_streams=V, sink=store, telemetry=True,
                          shed_watermark=0.5)
    rng = np.random.default_rng(3)
    # arrivals in units of the budget (4 cores x 2 s) over the costliest
    # configuration: half of it, then a spike of ~3x that fills the
    # buffers past the watermark (sheds) and their capacity (drops)
    unit = 4 * 2.0 / float(np.max(np.asarray(sky.cost)))
    traces = {name: [] for name in ONE_BY_ONE + ("active",)}
    for t in range(n):
        spike = unit * (3.0 if t >= 2 else 0.5)
        statuses, results = pool.process(
            list(rng.random(V)),
            arrival_mults=list(spike * (0.5 + rng.random(V))))
        outs, active = seen[-1]
        one = {name: np.asarray(outs[name]) for name in ONE_BY_ONE}
        one["active"] = np.asarray(active)
        got = unpack32(np.asarray(outs["decisions"]),
                       [one[name] for name in api._DECISIONS])
        for name, arr in zip(api._DECISIONS, got):
            assert arr.dtype == one[name].dtype, name
            assert arr.shape == one[name].shape, name
            assert arr.tobytes() == one[name].tobytes(), (t, name)
        slots = sorted(pool._stream_of)
        assert len(statuses) == len(results) == len(slots)
        for status, result, slot in zip(statuses, results, slots):
            assert status["k"] == int(one["k"][slot])
            assert status["category"] == int(one["c"][slot])
            assert status["buffer_s"] == float(one["buffer_s"][slot])
            assert status["dropped"] == bool(one["dropped"][slot])
            assert status["shed"] == bool(one["shed"][slot])
            assert (result is None) == status["dropped"]
        for name in traces:
            traces[name].append(one[name])
    tr = {name: np.stack(v, axis=1) for name, v in traces.items()}
    assert tr["shed"].any() and (tr["dropped"] & ~tr["shed"]).any()
    assert not tr["active"].all()
    # the recorder counted the unpacked on_s / cl_s: bit-exact with the
    # reference replay of the one-by-one reads
    ref = telemetry_ref(tr, pool._tel.k0, valid=tr["active"])
    tel = pool.telemetry()
    for key, want in ref.items():
        np.testing.assert_array_equal(tel.counters[key], want[slots],
                                      err_msg=key)


@pytest.mark.parametrize("sharded", [False, True])
def test_poll_is_one_read_and_equals_the_answers(sharded):
    D = 3
    store = (ShardedStore(out_dim=D, n_shards=2, chunk_rows=64) if sharded
             else SegmentStore(out_dim=D, chunk_rows=64))
    reg = StandingQueries(store)

    def by_category(lo):
        return (Filter("quality", "ge", lo),
                GroupBy("category", "on_core_s", "sum", num_groups=4))

    reg.register(by_category(0.0))      # slot 0 of the group, no alert
    subs = [
        (by_category(0.2), Filter("on_core_s", "gt", 200.0)),
        ((GroupBy("stream_id", "buffer_s", "max", num_groups=4),),
         Filter("stream_id", "le", 2)),
        (by_category(0.6), Filter("count", "ge", 20.0)),
    ]
    for plan, pred in subs:
        reg.subscribe(plan, pred)
    store.append_rows(_random_rows(300, D, seed=21))
    pulls = store.obs["host_pulls"]
    alerts = reg.poll()
    assert store.obs["host_pulls"] - pulls == 1
    assert len(alerts) == len(subs)
    fired_any = []
    for alert, (_plan, pred) in zip(alerts, subs):
        table, mask = reg.answer(alert.handle)
        want = {k: np.asarray(v) for k, v in table.items()}
        valid = np.asarray(mask)
        assert list(alert.table) == list(want)
        for k, v in want.items():
            got = alert.table[k]
            assert got.dtype == v.dtype and got.shape == v.shape, k
            assert got.tobytes() == v.tobytes(), k
        col = want[pred.column]
        dt = (np.float64 if np.issubdtype(col.dtype, np.integer)
              else np.float32)
        fired = valid & np.asarray(
            _CMP[pred.op](col.astype(dt), dt(pred.value)))
        assert alert.fired.dtype == fired.dtype
        np.testing.assert_array_equal(alert.fired, fired)
        fired_any.append(alert.n_fired)
    assert any(fired_any)
    # the next poll reuses its layout and still reads once
    store.append_rows(_random_rows(50, D, seed=22, t0=300))
    reg.poll()
    assert store.obs["host_pulls"] - pulls == 2
