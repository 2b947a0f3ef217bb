"""Elastic serving pool scaling: one fused tick for V streams vs the
seed's per-stream switcher loop.

``SkyscraperPool`` serves V live streams from ONE jitted tick program
(`_pool_tick`: vmapped masked switch + shed stage) on a power-of-two
slot ladder, so per-tick dispatch cost is constant in V and admitting
or retiring a stream never recompiles inside a capacity bucket. The
seed semantics — V independent ``switch_step`` dispatches per tick —
pay V host round-trips. This bench sweeps V and reports ticks/sec for
both, the warm recompile count (a ceiling: must stay 0), and the shed
fraction by priority band under a capacity squeeze (must be monotone:
lower priority sheds no less than higher).

Floor: at the top of the sweep (V=512) the fused tick must clear >= 5x
the per-stream loop's tick rate (hard assert), and the snapshot carries
a clamped ``speedup`` floor metric for ``--compare`` — clamped well
below the observed margin so run-to-run loop-timing noise cannot trip
the 20% gate, while a real collapse still fails it.

    PYTHONPATH=src:. python benchmarks/pool_scale_bench.py [--tiny]

``--tiny`` runs a seconds-scale smoke sweep (used by
``scripts/tier1.sh --bench-smoke`` so this entry point cannot rot).
"""
from __future__ import annotations

import sys
import time

import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit
from repro.core.api import Skyscraper, SkyscraperPool
from repro.core.switcher import compile_cache_sizes, init_state, switch_step

SPEEDUP_FLOOR = 5.0
# emitted floor metric is clamped here: stable across noisy loop
# denominators, still fails --compare if the real speedup collapses
FLOOR_CLAMP = 25.0


def _quality_of(knobs):
    return min(0.5 + 0.1 * knobs["q"], 1.0)


def _proc(seg, knobs):
    return ("out", _quality_of(knobs))


_SKY = []


def _sky():
    if not _SKY:
        rng = np.random.default_rng(0)
        s = Skyscraper(fps=2, segment_seconds=1.0, n_categories=2, seed=0)
        s.set_resources(num_cores=4, buffer_gb=1.0, cloud_budget_core_s=0.0)
        s.register_knob("q", [1, 2, 3])
        s.fit([rng.random((3,)) for _ in range(12)], _proc)
        _SKY.append(s)
    return _SKY[0]


def _loop_ticks(sky, V, mults, n_ticks, seg):
    """Seed semantics: V per-stream ``switch_step`` dispatches per tick
    (plus the same per-stream proc call the pool makes)."""
    alpha0 = jnp.asarray(sky.alpha)
    zeros = jnp.zeros(len(sky.configs))
    states = [init_state(sky.tables) for _ in range(V)]
    pending = [None] * V
    for _ in range(n_ticks):
        for v in range(V):
            stt = dict(states[v])
            if pending[v] is not None:
                stt["qual_prev"] = jnp.float32(pending[v])
            stt, outs = switch_step(stt, zeros, jnp.float32(mults[v]),
                                    alpha0, sky.tables)
            states[v] = stt
            if bool(outs["dropped"]):
                pending[v] = None
            else:
                _, q = sky.proc_fn(seg, sky.configs[int(outs["k"])])
                pending[v] = q
    return states


def _pool_ticks(pool, segs, mults, n_ticks):
    for _ in range(n_ticks):
        pool.process(segs, arrival_mults=mults)


def _shed_by_priority(sky, V, n_ticks, verbose):
    """Capacity squeeze at V streams in 4 priority bands; returns
    {priority: shed fraction} from the pool's own telemetry."""
    prios = [1.0 + (v % 4) for v in range(V)]
    pool = SkyscraperPool(sky, n_streams=V, priorities=prios,
                          telemetry=True)
    seg = np.zeros(3)
    pool.process([seg] * V)                # unconstrained: measure demand
    tel = pool.telemetry()
    demand = float(np.asarray(tel.counters["onprem_core_s"]).sum())
    pool.capacity_core_s = demand * 0.5    # room for ~half the fleet
    for _ in range(n_ticks):
        pool.process([seg] * V)
    stats = pool.shed_stats()
    frac = {}
    for p in sorted(set(prios)):
        sids = [s for s in pool.streams if stats[s]["priority"] == p]
        shed = sum(stats[s]["dropped"] for s in sids)
        tot = sum(stats[s]["segments"] for s in sids)
        frac[p] = shed / max(tot, 1)
    ordered = [frac[p] for p in sorted(frac)]
    assert all(a >= b - 1e-9 for a, b in zip(ordered, ordered[1:])), \
        f"shed fraction not monotone in priority: {frac}"
    return frac


def run(verbose: bool = True, tiny: bool = False):
    sky = _sky()
    plan_every0 = sky._plan_every
    sky._plan_every = 10_000               # isolate tick cost from replan
    try:
        return _run(sky, verbose, tiny)
    finally:
        sky._plan_every = plan_every0


def _run(sky, verbose, tiny):
    rows = []
    seg = np.zeros(3)
    sweep = (8, 32) if tiny else (8, 64, 512)
    ticks = 4 if tiny else 12
    loop_ticks = 2 if tiny else 3
    for V in sweep:
        rng = np.random.default_rng(V)
        mults = (0.5 + rng.random(V)).astype(np.float32)
        segs = [seg] * V

        # ---- seed loop ------------------------------------------------
        _loop_ticks(sky, V, mults, 1, seg)                 # warmup
        t0 = time.perf_counter()
        _loop_ticks(sky, V, mults, loop_ticks, seg)
        tps_loop = loop_ticks / (time.perf_counter() - t0)

        # ---- fused pool tick ------------------------------------------
        pool = SkyscraperPool(sky, n_streams=V, telemetry=True)
        _pool_ticks(pool, segs, mults, 1)                  # warmup
        sizes0 = compile_cache_sizes()
        t0 = time.perf_counter()
        _pool_ticks(pool, segs, mults, ticks)
        tps_pool = ticks / (time.perf_counter() - t0)
        recompiles = sum(compile_cache_sizes().values()) \
            - sum(sizes0.values())
        assert recompiles == 0, f"{recompiles} recompiles after warmup"
        tel = pool.telemetry()
        assert int(np.asarray(tel.counters["seg_total"]).sum()) \
            == V * (ticks + 1)

        speedup = tps_pool / tps_loop
        rows.append((V, tps_loop, tps_pool, speedup))
        if verbose:
            # ratio= is informational (loop timing is noisy at few
            # ticks); the gated floor metric is the clamped one below
            emit(f"pool_scale/V{V}", 1e6 / tps_pool,
                 f"loop={tps_loop:.1f}tps;pool={tps_pool:.1f}tps;"
                 f"ratio={speedup:.2f}x;recompiles=0")
    if not tiny:
        V_top, _, tps_pool, speedup = rows[-1]
        assert speedup >= SPEEDUP_FLOOR, \
            f"V={V_top} fused tick {speedup:.2f}x < {SPEEDUP_FLOOR}x floor"
        if verbose:
            emit(f"pool_scale/floor_V{V_top}", 1e6 / tps_pool,
                 f"speedup={min(speedup, FLOOR_CLAMP):.2f}x")

    # ---- shed fraction by priority under a capacity squeeze -----------
    V_shed, shed_ticks = (8, 3) if tiny else (16, 8)
    frac = _shed_by_priority(sky, V_shed, shed_ticks, verbose)
    if verbose:
        parts = ";".join(f"shed_p{int(p)}={frac[p]:.2f}"
                         for p in sorted(frac))
        emit(f"pool_scale/shed_V{V_shed}", 0.0, parts)
    rows.append(("shed", frac))
    return rows


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    print("name,us_per_call,derived")
    run(tiny="--tiny" in sys.argv[1:])
