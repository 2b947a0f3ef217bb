"""The trace reduction and the needed-bytes functions on hand-made
inputs whose answers are counted by hand."""
import pytest

from bench import needs
from bench.trace import Reduced, collect


def _raw(devices=1):
    spans = [["bench.window", 0, 100], ["bench.tick", 10, 40],
             ["bench.wait", 40, 50], ["bench.tick", 50, 80]]
    ops = [["fusion", 20, 30], ["copy", 25, 35], ["fusion", 60, 70],
           ["late", 95, 110]]
    mods = [["jit__pool_tick_fn(7)", 20, 30],
            ["jit__ingest_tick_masked(8)", 25, 35],
            ["jit__pool_tick_fn(7)", 60, 70]]
    return {"spans": spans,
            "devices": {f"/device:TPU:{d}": {"ops": ops, "modules": mods}
                        for d in range(devices)}}


@pytest.mark.parametrize("devices", [1, 2])
def test_busy_idle_and_programs(devices):
    red = Reduced(_raw(devices))
    # ops union inside the window: [20,35] [60,70] [95,100]
    assert red.window_s == pytest.approx(100e-9)
    assert red.busy_s == pytest.approx(30e-9)
    assert red.program("_pool_tick") == (pytest.approx(20e-9), 2)
    assert red.program("_ingest_tick_masked") == (pytest.approx(10e-9), 1)
    assert red.program("_pool_replan") == (0.0, 0)


def test_host_self_time_and_idle_attribution():
    red = Reduced(_raw())
    # tick [10,40] holds 15 of busy, tick [50,80] holds 10
    assert red.host_self_s("bench.tick") == [pytest.approx(15e-9),
                                             pytest.approx(20e-9)]
    idle = dict(red.idle_by_host())
    # gaps [0,20] (mid 10, in a tick), [35,60] (mid 47.5, in the wait),
    # [70,95] (mid 82.5, in no span)
    assert idle == {"bench.tick": pytest.approx(20e-9),
                    "bench.wait": pytest.approx(25e-9),
                    "none": pytest.approx(25e-9)}
    assert sum(idle.values()) == pytest.approx(red.window_s - red.busy_s)
    ops = dict(red.top_ops())
    assert ops == {"fusion": pytest.approx(20e-9),
                   "copy": pytest.approx(10e-9),
                   "late": pytest.approx(5e-9)}


def test_trace_without_window_is_refused():
    with pytest.raises(ValueError):
        Reduced({"spans": [["bench.tick", 0, 1]], "devices": {}})


def test_collect_reads_host_spans_of_a_recorded_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: x * 2)
    f(jnp.ones(4)).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        with jax.profiler.TraceAnnotation("bench.tick"):
            f(jnp.ones(4)).block_until_ready()
    jax.profiler.stop_trace()
    raw = collect(str(tmp_path))
    names = [n for n, _, _ in raw["spans"]]
    assert names.count("bench.window") == 1 and "bench.tick" in names
    red = Reduced(raw)
    (lo, hi), = red.spans_named("bench.tick")
    assert red.lo <= lo < hi <= red.hi
    # no TPU on this host: no device plane, nothing busy
    assert raw["devices"] == {} and red.busy_s == 0.0


def test_needed_bytes_by_hand():
    assert needs.row_bytes(9) == 4 * (8 + 9)
    # 4 rows of 9-wide output; two queries of 3 and 5 result rows:
    # states 3*4*3+4 = 40 and 3*4*5+4 = 64 bytes, read and written
    assert needs.standing_state_bytes([3, 5]) == 104
    assert needs.ingest_bytes(4, 9, [3, 5]) == 4 * 68 + 2 * 104
