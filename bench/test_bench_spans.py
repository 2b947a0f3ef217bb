"""The program-span reduction on hand-made traces whose answers are
counted by hand, and on a recorded trace: the program's spans add keys
and methods, and every existing reading stays what it was."""
import jax
import jax.numpy as jnp
import pytest

from bench import spans, trace
from bench.test_bench_trace import _raw


def _prog():
    """A tick [10,40] with a pull [12,22] holding a collection [14,16],
    and an ingest [25,38]; the device is busy [20,35] (``_raw``)."""
    raw = _raw()
    raw["program"] = [
        ["pool.tick", 10, 40, {"t": 7, "host_pulls": 12}],
        ["pool.pull", 12, 22, {"pulls": 6}],
        ["host.gc", 14, 16, {"generation": 0, "collected": 3}],
        ["sink.ingest", 25, 38, {"rows": 4}],
        ["pool.tick", 50, 80, {"t": 8, "host_pulls": 10}],
    ]
    return raw


def test_program_host_time_less_children_and_busy():
    red = spans.Split(_prog())
    # tick 7: 30 long; children cover [12,22] and [25,38], busy [20,35]
    # adds [22,25]: 26 covered, 4 left
    # tick 8: 30 long; busy [60,70], no children: 20 left
    assert red.program_host_s("pool.tick") == [pytest.approx(4e-9),
                                               pytest.approx(20e-9)]
    # pull: 10 long; the collection covers 2, busy [20,22] covers 2
    assert red.program_host_s("pool.pull") == [pytest.approx(6e-9)]
    assert red.program_host_s("host.gc") == [pytest.approx(2e-9)]
    # ingest [25,38]: busy covers [25,35]
    assert red.program_host_s("sink.ingest") == [pytest.approx(3e-9)]
    assert red.program_stat("pool.tick", "host_pulls") == [12, 10]
    assert red.program_stat("pool.pull", "rows") == []


def test_idle_by_program_names_the_innermost_span():
    red = spans.Split(_prog())
    idle = dict(red.idle_by_program())
    # gaps [0,20] (mid 10: tick 7 starts at 10, so the tick), [35,60]
    # (mid 47.5: no program span, the bench.wait), [70,95] (mid 82.5:
    # in no span at all)
    assert idle == {"pool.tick": pytest.approx(20e-9),
                    "bench.wait": pytest.approx(25e-9),
                    "none": pytest.approx(25e-9)}
    raw = _prog()
    raw["program"][0][1] = 0          # tick 7 from 0: gap [0,20] mid 10
    raw["program"][1][1] = 5          # lies in the pull [5,22]
    assert dict(spans.Split(raw).idle_by_program())["pool.pull"] \
        == pytest.approx(20e-9)


@pytest.mark.parametrize("devices", [1, 2])
def test_existing_readings_unchanged(devices):
    """On the same dict, with or without program spans, the readings of
    ``bench.trace.Reduced`` are those of ``Split``."""
    for raw in (_raw(devices), dict(_prog(), devices=_raw(devices)[
            "devices"])):
        old, new = trace.Reduced(_raw(devices)), spans.Split(raw)
        assert new.spans == old.spans and new.busy_s == old.busy_s
        assert new.window_s == old.window_s
        for name in ("bench.tick", "bench.wait"):
            assert new.host_self_s(name) == old.host_self_s(name)
        for stable in ("_pool_tick", "_ingest_tick_masked", "_replan"):
            assert new.program(stable) == old.program(stable)
        assert new.idle_by_host() == old.idle_by_host()
        assert new.top_ops() == old.top_ops()
    assert spans.Split(_raw(devices)).program_host_s("pool.tick") == []


def test_recorded_trace_reduces_as_before(tmp_path):
    span = jax.profiler.TraceAnnotation      # as the program's spans
    f = jax.jit(lambda x: x * 2)
    f(jnp.ones(4)).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        with jax.profiler.TraceAnnotation("bench.tick"):
            with span("pool.tick", t=3) as sp:
                with span("pool.pull", pulls=1):
                    f(jnp.ones(4)).block_until_ready()
                sp.set_metadata(host_pulls=1)
    jax.profiler.stop_trace()
    old, new = trace.collect(str(tmp_path)), spans.collect(str(tmp_path))
    assert {k: v for k, v in new.items() if k in old} == old
    assert [p[0] for p in new["program"]] == ["pool.tick", "pool.pull"]
    assert new["program"][0][3] == {"t": 3, "host_pulls": 1}
    a, b = trace.Reduced(old), spans.Split(new)
    assert (a.spans, a.busy_s, a.host_self_s("bench.tick"),
            a.idle_by_host(), a.top_ops()) == \
        (b.spans, b.busy_s, b.host_self_s("bench.tick"),
         b.idle_by_host(), b.top_ops())
    (lo, hi, st), = b.program_spans("pool.tick")
    assert a.lo <= lo < hi <= a.hi and st["t"] == 3
    # no device plane on this host: a span's host time is its length
    # less its children's
    (pull,) = b.program_host_s("pool.pull")
    (tick,) = b.program_host_s("pool.tick")
    assert tick == pytest.approx((hi - lo) * 1e-9 - pull) and tick > 0
