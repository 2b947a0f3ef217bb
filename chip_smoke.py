#!/usr/bin/env python3
"""Chip smoke: the served V-ETL path once, end to end, at fleet size.

    python chip_smoke.py                         # one TPU: load, serve, query
    python chip_smoke.py --chips 4               # sharded warehouse, 4 TPUs
    JAX_PLATFORMS=cpu python chip_smoke.py --tiny            # CPU rehearsal
    XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
        JAX_PLATFORMS=cpu python chip_smoke.py --tiny --chips 4

Deployment: a COVID traffic-camera fleet (``configs/workloads.py``), 2 s
segments. The offline ``fit`` profiles the ingest engine; an
``api.Skyscraper`` registered with COVID's knob grid and a nonzero cloud
budget serves the live pool. ``proc_fn`` returns the quality the COVID
generator (``data/stream.py``) gives for the segment and configuration,
seeded from ``--seed``: no model, no download.

One chip, three phases in one process:

- load: 64 standing queries of two plan shapes plus one alert
  subscription are registered on a ``SegmentStore``, then the fused
  multi-stream engine (``run_skyscraper_multi``) fills it with 4096
  archived cameras x 8192 segments (2^25 rows, ~2.3 GB on the device);
- serve: ``SkyscraperPool`` (4096-slot bucket, telemetry on, the store
  as sink) runs 32 ticks with admits and retires inside the bucket, a
  ``capacity_core_s`` squeeze that sheds, and two replans;
- query: five ad-hoc plans through ``store.query`` with
  ``use_pallas=True`` and ``False``.

Checks (any failure exits non-zero, after every check has reported):
Pallas and XLA answers equal ``execute_ref`` and each other (counts,
max/min, integer sums, masks and top-k rows exactly; float sums within
``rtol_for(count)`` of the float64 reference and of each other), the
Pallas plans' executables hold the kernel (``tpu_custom_call``),
standing answers equal a rescan,
8 sampled pool streams equal the per-stream ``switch_step`` loop bit
for bit, and the pool's telemetry equals ``telemetry_ref``.

``--chips 4`` runs only the sharded path: a 4-shard ``ShardedStore``
loaded and served the same way, sharded XLA and Pallas queries, standing
answers and ``rebalance(store, 2)``, each compared with a 1-shard store
built from the same rows.

Lines before the last are smoke figures (compile and wall seconds, rows,
device bytes), not benchmark numbers. The last line is the contract:
``{"ok": true, "device": {"platform", "kind", "count"}}``. Without a TPU
(and without ``--tiny``) the script exits non-zero and names the
platform it found.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from dataclasses import dataclass

import numpy as np

_ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(_ROOT, "src"))

DAY_S = 86_400.0
F32_EXACT = 2 ** 24          # f32 counts are exact below this many rows
SPIN_S_PER_CORE_S = 2.5e-4   # fit-time stand-in for the transform's cost
CLOUD_CORE_S_PER_SEGMENT = 0.05


@dataclass(frozen=True)
class Size:
    hist_streams: int        # archived cameras loaded into the store
    hist_segments: int       # segments per archived camera
    chunk_streams: int       # cameras per fused ingest dispatch
    pool_streams: int        # live cameras at the first tick
    churn: int               # admitted at tick 4, retired at 8, 2x at 10
    ticks: int
    plan_segments: int       # pool replan period (ticks)
    unl_segments: int        # unlabeled segments Skyscraper.fit profiles
    fit_days: float          # unlabeled days for the offline fit
    n_standing: int


FULL = Size(hist_streams=4096, hist_segments=8192, chunk_streams=1024,
            pool_streams=4032, churn=32, ticks=32, plan_segments=16,
            unl_segments=80, fit_days=2.0, n_standing=64)
TINY = Size(hist_streams=16, hist_segments=256, chunk_streams=8,
            pool_streams=12, churn=2, ticks=32, plan_segments=16,
            unl_segments=80, fit_days=0.5, n_standing=64)


def _gen_stream(job):
    """One COVID stream of ``n`` segments (a process-pool worker: numpy
    only, never imports jax)."""
    from repro.configs.workloads import COVID
    from repro.data.stream import generate
    seed, n = job
    return generate(COVID, days=(n + 0.5) * COVID.segment_seconds / DAY_S,
                    seed=seed)


def rtol_for(count):
    """Relative tolerance of a float32 sum of ``count`` terms against
    the float64 reference: the suite's 1e-5 at test sizes, growing as
    the probabilistic fp32 summation bound 8 * 2^-24 * sqrt(n)."""
    return np.maximum(1e-5, 8.0 * 2.0 ** -24
                      * np.sqrt(np.maximum(np.asarray(count, float), 1.0)))


class Smoke:
    """Phase timing, device-memory figures and check bookkeeping."""

    def __init__(self):
        import jax
        self.jax = jax
        self.compile_s = 0.0
        self.failures = []
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event.startswith("/jax/core/compile/"):
            self.compile_s += duration

    def mem(self):
        stats = [d.memory_stats() or {} for d in self.jax.local_devices()]
        return (max((s.get("bytes_in_use", 0) for s in stats), default=0),
                max((s.get("peak_bytes_in_use", 0) for s in stats),
                    default=0))

    def phase(self, name, fn, rows=None):
        c0, t0 = self.compile_s, time.perf_counter()
        out = fn()
        wall = time.perf_counter() - t0
        used, peak = self.mem()
        n = rows() if callable(rows) else rows
        print(f"smoke figure (not a benchmark): phase={name} "
              f"compile_s={self.compile_s - c0:.3f} wall_s={wall:.3f} "
              f"rows={n} bytes_in_use={used} peak_bytes_in_use={peak}",
              flush=True)
        return out

    def check(self, name, ok, detail=""):
        print(f"check {name}: {'ok' if ok else 'FAIL'}"
              f"{' ' + detail if detail else ''}", flush=True)
        if not ok:
            self.failures.append(name)

    def compare(self, name, got, want, *, sum_cols=(), counts=None,
                sort_by=None):
        """Exact on every column except ``sum_cols`` (float sums and
        means), which match to ``rtol_for(counts)``. ``sort_by``
        compares a top-k's surviving ``sort_by`` values as a sorted set
        (row indices differ between stores holding the same rows)."""
        (gt, gm), (wt, wm) = got, want
        gm, wm = np.asarray(gm), np.asarray(wm)
        bad = []
        if not np.array_equal(gm, wm):
            bad.append(f"mask ({int((gm != wm).sum())} rows differ)")
        cols = [sort_by] if sort_by else list(wt)
        for col in cols:
            g = np.asarray(gt[col]).astype(np.float64)
            w = np.asarray(wt[col]).astype(np.float64)
            if sort_by:
                g, w = np.sort(g[gm]), np.sort(w[wm])
            if g.shape != w.shape:
                bad.append(f"{col} shape {g.shape} != {w.shape}")
                continue
            if col in sum_cols:
                r = rtol_for(counts)
                r = r.reshape(r.shape + (1,) * (w.ndim - r.ndim))
                err = np.abs(g - w) - (1e-5 + r * np.abs(w))
                if (err > 0).any():
                    rel = np.abs(g - w) / np.maximum(np.abs(w), 1e-30)
                    bad.append(f"{col} max rel err {rel.max():.3g} over "
                               f"rtol_for(count) at {int((err > 0).sum())} "
                               f"entries")
            elif not np.array_equal(g, w, equal_nan=True):
                bad.append(f"{col} ({int((g != w).sum())} entries differ)")
        self.check(name, not bad, "; ".join(bad))


def _sum_cols(plan):
    """The result column that holds a float sum or mean (compared to
    tolerance); integer-valued sums, counts, max and min are exact."""
    from repro.warehouse import GroupBy, MultiGroupBy, WindowAgg
    from repro.warehouse.store import SCALAR_COLUMNS
    ints = {n for n, dt in SCALAR_COLUMNS if dt == np.int32}
    for node in plan:
        if isinstance(node, (GroupBy, WindowAgg, MultiGroupBy)):
            if node.agg == "mean" or (node.agg == "sum"
                                      and node.value not in ints):
                return (node.value,)
            return ()
    return ()


def _compiled_query_text(store, plan, use_pallas):
    """(kernel chosen, compiled HLO text) of the executable
    ``store.query(plan, use_pallas=...)`` dispatches."""
    import jax
    import jax.numpy as jnp
    from repro.warehouse import query as Q
    spec, fvals = Q.normalize(plan)
    pre, node, _ = Q.split_plan(spec)
    if hasattr(store, "shard_source"):
        cols, n_valid = store.shard_source()
        shapes = {k: jax.ShapeDtypeStruct(v.shape[1:], v.dtype)
                  for k, v in cols.items()}
        up = Q._resolve_use_pallas(use_pallas, pre, node, shapes)
        kern = Q._sharded_kernel(store.mesh, store.n_shards)
        low = kern.lower(cols, n_valid, fvals, jax.random.PRNGKey(0),
                         spec=spec, compressed=False, use_pallas=up)
    else:
        up = Q._resolve_use_pallas(use_pallas, pre, node, store.columns)
        low = Q._run_plan.lower(store.columns, jnp.int32(store.n_rows),
                                fvals, spec=spec, use_pallas=up)
    return up, low.compile().as_text()


# ---------------------------------------------------------------------------
# the deployment
# ---------------------------------------------------------------------------

class Fleet:
    """The fitted profiles, the generated streams and the query set."""

    def __init__(self, size: Size, seed: int, workers: int):
        from repro.configs.workloads import COVID
        from repro.core import knobs as KB
        from repro.core.api import Skyscraper
        from repro.core.offline import fit
        self.size, self.seed = size, seed
        tau = COVID.segment_seconds
        # offline profile of the ingest engine (one core per camera,
        # so the LP binds and buffers carry load)
        self.fitted = fit(COVID, n_cores=1, days_unlabeled=size.fit_days,
                          seed=seed)
        self.K = len(self.fitted.configs)
        # the serving Skyscraper: COVID's full knob grid, profiled by
        # timing proc_fn (which spins for each config's modelled work
        # while fit profiles, and returns at once while serving)
        cfgs = KB.enumerate_configs(COVID)
        self._idx = {tuple(c.values()): i for i, c in enumerate(cfgs)}
        self._work = [KB.config_work(COVID, c) for c in cfgs]
        self.powers = np.asarray([KB.config_power(COVID, c) for c in cfgs])
        self._profiling = True
        unl = _gen_stream((seed + 1, size.unl_segments))
        sky = Skyscraper(fps=30, segment_seconds=tau, n_categories=4,
                         seed=seed)
        sky.set_resources(num_cores=1, buffer_gb=4.0,
                          cloud_budget_core_s=CLOUD_CORE_S_PER_SEGMENT
                          * size.ticks)
        for name, dom in COVID.knobs.items():
            sky.register_knob(name, dom)
        sky.fit(list(unl.quality(self.powers, seed=seed + 2)), self.proc_fn,
                plan_segments=size.plan_segments, max_k=self.K)
        self._profiling = False
        # planner budget in the same (profiled) units as sky.cost
        sky.set_budget(sky.num_cores * tau * SPIN_S_PER_CORE_S)
        self.sky = sky
        # stream ids: live cameras [0, n_live), archived cameras after
        from repro.warehouse.store import _bucket_cap
        self.cap = _bucket_cap(size.pool_streams, 8)
        n_live = size.pool_streams + 3 * size.churn
        self.hist_base = n_live
        self.n_ids = n_live + size.hist_streams
        t0 = time.perf_counter()
        jobs = [(seed * 1_000_003 + 10 + i, size.hist_segments)
                for i in range(size.hist_streams)]
        import multiprocessing as mp
        with mp.get_context("spawn").Pool(workers) as pool:
            self.hist = pool.map(_gen_stream, jobs, chunksize=16)
        self.gen_s = time.perf_counter() - t0
        # live segments: each row is the generator's quality of every
        # knob configuration on that segment (proc_fn picks one)
        self.live = {sid: _gen_stream((seed * 7_000_003 + sid, size.ticks))
                     .quality(self.powers, seed=seed + 3 + sid)
                     for sid in range(n_live)}

    def proc_fn(self, seg, knobs):
        i = self._idx[tuple(knobs.values())]
        if self._profiling:
            end = time.perf_counter() + self._work[i] * SPIN_S_PER_CORE_S
            while time.perf_counter() < end:
                pass
        return None, float(seg[i])

    # -- plans ---------------------------------------------------------
    def standing_plans(self):
        """(plans, alert) — two standing shapes over per-camera rows
        plus an alert on every camera's buffer high-water mark."""
        from repro.warehouse import Filter, GroupBy, WindowAgg
        n = self.size.n_standing
        sids = np.linspace(0, self.n_ids - 1, n).astype(int)
        nw = self.size.hist_segments // 1024 + 1
        plans = []
        for j, sid in enumerate(sids):
            if j % 4 != 3:
                plans.append((Filter("stream_id", "eq", float(sid)),
                              WindowAgg(window=1024, value="quality",
                                        agg="mean", num_windows=nw)))
            else:
                plans.append((Filter("stream_id", "eq", float(sid)),
                              GroupBy("k", "category", agg="sum",
                                      num_groups=self.K)))
        alert = ((GroupBy("stream_id", "buffer_s", agg="max",
                          num_groups=self.n_ids),),
                 Filter("buffer_s", "gt", 30.0))
        return plans, alert

    def query_plans(self, nw):
        """The four aggregate shapes (both kernels) and one TopK."""
        from repro.warehouse import Filter, MultiGroupBy, TopK, WindowAgg
        C, K = self.fitted.centers.shape[0], self.K
        return {
            "filter_category_mean": (
                Filter("quality", "ge", 0.6),
                MultiGroupBy(("t", "category"), "quality", agg="mean",
                             nums=(nw, C), windows=(2048, 0))),
            "window2048_sum": (
                WindowAgg(window=2048, value="quality", agg="sum",
                          num_windows=nw),),
            "wide_out_sum": (
                MultiGroupBy(("t", "k"), "out", agg="sum", nums=(nw, K),
                             windows=(2048, 0)),),
            "stream_id_max": (
                Filter("quality", "lt", 0.5),
                WindowAgg(window=2048, value="stream_id", agg="max",
                          num_windows=nw)),
            "topk_buffer": (
                Filter("quality", "lt", 0.5),
                TopK(16, by="buffer_s")),
        }

    # -- phases --------------------------------------------------------
    def register_standing(self, store):
        from repro.warehouse import StandingQueries
        reg = StandingQueries(store)
        plans, (aplan, pred) = self.standing_plans()
        handles = [reg.register(p) for p in plans]
        reg.subscribe(aplan, pred, name="buffer-hwm")
        return reg, handles, plans + [aplan]

    def load(self, store):
        from repro.configs.workloads import COVID
        from repro.core import ingest as IG
        s = self.size
        T, ch = s.hist_segments, s.chunk_streams
        W = T // 4
        for c in range(s.hist_streams // ch):
            IG.run_skyscraper_multi(
                [self.fitted] * ch, self.hist[c * ch:(c + 1) * ch],
                n_cores_each=1,
                cloud_budget_core_s=CLOUD_CORE_S_PER_SEGMENT * T * ch,
                plan_days=(W + 0.5) * COVID.segment_seconds / DAY_S,
                seed=self.seed, sink=store,
                sink_stream_base=self.hist_base + c * ch)
        return store.n_rows

    def serve(self, smoke, store, *, oracle: bool):
        """32 pool ticks: admits/retires inside the bucket, a capacity
        squeeze that sheds, replans. With ``oracle``, 8 sampled streams
        are replayed through the per-stream switch_step loop."""
        import jax.numpy as jnp
        from repro.core.api import SkyscraperPool
        from repro.core.switcher import init_state, switch_step
        s, sky = self.size, self.sky
        rng = np.random.default_rng(self.seed + 5)
        P = s.pool_streams
        prio = rng.uniform(0.5, 4.0, P).astype(np.float32)
        sample = np.linspace(0, P - 1, 8).astype(int)
        prio[sample] = 10.0
        pool = SkyscraperPool(sky, n_streams=P, sink=store, telemetry=True,
                              priorities=prio)
        assert pool.cap == self.cap, (pool.cap, self.cap)
        others = np.setdiff1d(np.arange(P), sample)
        retire = rng.choice(others, s.churn, replace=False)
        next_id = P
        ost = {int(v): init_state(sky.tables) for v in sample}
        opend = {int(v): None for v in sample}
        zeros_k = jnp.zeros(len(sky.configs), jnp.float32)
        log = []                   # per tick: (statuses, shed prios...)
        n_shed_squeeze = 0
        prefix_ok = True
        demand_prev = 0.0
        squeeze = range(12, 16)
        for tick in range(s.ticks):
            if tick in (4, 10):
                for _ in range(s.churn * (1 if tick == 4 else 2)):
                    pool.admit(next_id, priority=float(rng.uniform(0.5, 4)))
                    next_id += 1
            if tick == 8:
                for sid in retire:
                    pool.retire(int(sid))
            if tick == squeeze.start:
                pool.capacity_core_s = 0.8 * demand_prev
            if tick == squeeze.stop:
                pool.capacity_core_s = None
            alpha = np.asarray(pool._alpha)
            on0 = float(pool.telemetry().counters["onprem_core_s"].sum())
            statuses, _ = pool.process(
                {sid: self.live[sid][tick] for sid in pool.streams})
            demand_prev = (float(pool.telemetry()
                                 .counters["onprem_core_s"].sum()) - on0)
            log.append(statuses)
            if tick in squeeze:
                pr = np.asarray(pool._priority)
                shed = [pr[pool._slot_of[st["stream_id"]]]
                        for st in statuses if st["shed"]]
                kept = [pr[pool._slot_of[st["stream_id"]]]
                        for st in statuses if not st["dropped"]]
                n_shed_squeeze += len(shed)
                if shed and kept and min(kept) < max(shed):
                    prefix_ok = False
            if not oracle:
                continue
            by_id = {st["stream_id"]: st for st in statuses}
            for sid in ost:
                st = dict(ost[sid])
                if opend[sid] is not None:
                    st["qual_prev"] = jnp.float32(opend[sid])
                st, out = switch_step(st, zeros_k, jnp.float32(1.0),
                                      jnp.asarray(alpha[sid]), sky.tables)
                ost[sid] = st
                got = by_id[sid]
                same = (got["k"] == int(out["k"])
                        and got["category"] == int(out["c"])
                        and np.float32(got["buffer_s"]).tobytes()
                        == np.asarray(out["buffer_s"], np.float32).tobytes()
                        and got["dropped"] == bool(out["dropped"])
                        and not got["shed"])
                if not same:
                    smoke.check(f"pool_oracle stream {sid} tick {tick}",
                                False, f"pool={got} oracle={out}")
                    ost = {}
                    break
                opend[sid] = None if got["dropped"] else got["quality"]
        tel = pool.telemetry()
        smoke.check("pool_replans", tel.extras["replans"] >= 1,
                    f"replans={tel.extras['replans']}")
        smoke.check("pool_squeeze_sheds", n_shed_squeeze > 0,
                    f"shed={n_shed_squeeze} over {len(squeeze)} ticks")
        smoke.check("pool_shed_priority_prefix", prefix_ok)
        smoke.check("pool_bucket_unchanged", pool.cap == self.cap,
                    f"cap={pool.cap} active={pool.V}")
        if oracle:
            smoke.check("pool_oracle_8_streams_bit_exact",
                        len(ost) == len(sample),
                        f"{len(sample)} streams x {s.ticks} ticks")
        return pool, log


def _check_telemetry(smoke, fleet, pool, log, pool_rows):
    """Replay the pool's ticks through ``telemetry_ref``: k/dropped from
    the statuses, buffer/on-prem/cloud from the sink's rows."""
    from repro.obs import TEL_KEYS, telemetry_ref
    ids = sorted({st["stream_id"] for sts in log for st in sts})
    row_of = {sid: i for i, sid in enumerate(ids)}
    n, T = len(ids), len(log)
    tr = {k: np.zeros((n, T), np.float32)
          for k in ("k", "dropped", "buffer_s", "on_s", "cl_s")}
    valid = np.zeros((n, T), bool)
    off = 0
    aligned = True
    for t, sts in enumerate(log):
        m = len(sts)
        sl = slice(off, off + m)
        off += m
        rid = pool_rows["stream_id"][sl]
        aligned &= bool(np.array_equal(
            rid, [st["stream_id"] for st in sts])
            and (pool_rows["t"][sl] == t).all())
        r = np.asarray([row_of[st["stream_id"]] for st in sts])
        tr["k"][r, t] = [st["k"] for st in sts]
        tr["dropped"][r, t] = [st["dropped"] for st in sts]
        tr["buffer_s"][r, t] = pool_rows["buffer_s"][sl]
        tr["on_s"][r, t] = pool_rows["on_core_s"][sl]
        tr["cl_s"][r, t] = pool_rows["cloud_core_s"][sl]
        valid[r, t] = True
    # a slot reused by an admission restarts its counters: replay each
    # stream id from its first active tick (ids never return)
    smoke.check("pool_rows_align_with_ticks",
                aligned and off == len(pool_rows["t"]))
    k0 = int(np.argmin(np.asarray(fleet.sky.tables.rank_pos)))
    tr["k"] = tr["k"].astype(np.int64)
    ref = telemetry_ref(tr, k0, valid=valid)
    snap = pool.telemetry()
    sel = np.asarray([row_of[sid] for sid in pool.streams])
    bad = [k for k in TEL_KEYS
           if not np.array_equal(np.asarray(snap.counters[k]), ref[k][sel])]
    smoke.check("pool_telemetry_equals_telemetry_ref", not bad,
                f"{len(sel)} streams; differing={bad}")


def _truth(host, n_rows, plan):
    """``execute_ref`` with float32 and with float64 accumulation."""
    from repro.warehouse import execute_ref
    ref32 = execute_ref(host, n_rows, plan)
    ref64 = execute_ref(host, n_rows, plan, dtype=np.float64)
    return ref32, ref64


def _check_plan(smoke, label, got, ref32, ref64, plan):
    """Exact columns against ``execute_ref``; float sums within
    ``rtol_for(count)`` of the float64 reference, with each path's
    error against float64 printed."""
    sums = _sum_cols(plan)
    if "count" not in ref64[0]:                      # TopK row plan
        smoke.compare(f"{label} == execute_ref", got, ref32)
        return
    counts = np.asarray(ref64[0]["count"])
    smoke.check(f"{label} groups below 2^24 rows (f32 count exactness)",
                counts.max() < F32_EXACT, f"max group {int(counts.max())}")
    exact = {k: v for k, v in ref32[0].items() if k not in sums}
    gexact = {k: v for k, v in got[0].items() if k not in sums}
    smoke.compare(f"{label} == execute_ref (exact columns)",
                  (gexact, got[1]), (exact, ref32[1]))
    if sums:
        smoke.compare(f"{label} == execute_ref float64 (sums)", got, ref64,
                      sum_cols=sums, counts=counts)
        print(f"smoke figure (not a benchmark): {label} float sum max "
              f"relative error vs float64 "
              f"{_rel_err(got[0][sums[0]], ref64[0][sums[0]]):.3g}, vs "
              f"fp32 execute_ref "
              f"{_rel_err(got[0][sums[0]], ref32[0][sums[0]]):.3g}",
              flush=True)


def _rel_err(got, want):
    g, w = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float((np.abs(g - w) / np.maximum(np.abs(w), 1e-30)).max())


def _host_table(res):
    t, m = res
    return ({k: np.asarray(v) for k, v in t.items()}, np.asarray(m))


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------

def run_one_chip(smoke, fleet, on_tpu):
    from repro.warehouse import SegmentStore, windows_for
    s = fleet.size
    store = SegmentStore(out_dim=fleet.K)
    reg, handles, splans = smoke.phase(
        "register_standing", lambda: fleet.register_standing(store), 0)
    smoke.phase("load", lambda: fleet.load(store), lambda: store.n_rows)
    n_hist = store.n_rows
    want = s.hist_streams * s.hist_segments
    smoke.check("store_rows", n_hist == want and (s is not FULL
                                                  or n_hist >= 2 ** 25),
                f"rows={n_hist}")
    pool, log = smoke.phase(
        "serve", lambda: fleet.serve(smoke, store, oracle=True),
        lambda: store.n_rows - n_hist)
    host = smoke.phase("pull_rows", store.host_rows, lambda: store.n_rows)
    _check_telemetry(smoke, fleet, pool, log,
                     {k: v[n_hist:] for k, v in host.items()})
    nw = windows_for(store, 2048)
    plans = fleet.query_plans(nw)
    answers = {}

    def run_queries():
        for name, plan in plans.items():
            for up in (True, False):
                answers[name, up] = _host_table(
                    store.query(plan, use_pallas=up))
    smoke.phase("query", run_queries, lambda: store.n_rows)
    for name, plan in plans.items():
        ref32, ref64 = _truth(host, store.n_rows, plan)
        sums = _sum_cols(plan)
        if sums:
            print(f"smoke figure (not a benchmark): query {name} fp32 "
                  f"execute_ref (blocked sum) relative error vs float64 "
                  f"{_rel_err(ref32[0][sums[0]], ref64[0][sums[0]]):.3g}",
                  flush=True)
        for up in (True, False):
            _check_plan(smoke, f"query {name} use_pallas={up}",
                        answers[name, up], ref32, ref64, plan)
        smoke.compare(
            f"query {name} use_pallas=True == use_pallas=False",
            answers[name, True], answers[name, False], sum_cols=sums,
            counts=ref64[0].get("count"))
        if on_tpu:
            chosen, hlo = _compiled_query_text(store, plan, True)
            agg = "count" in ref64[0]
            smoke.check(f"query {name} Pallas kernel in executable",
                        chosen == agg and ("tpu_custom_call" in hlo) == agg,
                        f"pallas={chosen} tpu_custom_call="
                        f"{'tpu_custom_call' in hlo}")

    def standing():
        return {h: _host_table(reg.answer(h))
                for h in range(len(splans))}
    st = smoke.phase("standing_answers", standing, len(splans))
    for h, plan in enumerate(splans):
        rescan = _host_table(store.query(plan, use_pallas=False))
        smoke.compare(f"standing q{h} == rescan", st[h], rescan,
                      sum_cols=_sum_cols(plan),
                      counts=rescan[0].get("count"))
    smoke.check("standing_alert_polled", bool(pool.alerts),
                f"alerts={[a.name for a in pool.alerts]}")


# ---------------------------------------------------------------------------
# four chips: the sharded warehouse and what it is compared with
# ---------------------------------------------------------------------------

def run_sharded(smoke, fleet, on_tpu, n_shards):
    import jax
    from repro.runtime.elastic import rebalance
    from repro.warehouse import SegmentStore, ShardedStore, windows_for
    # chunk sized to a shard's rows (history + ticks, with room): the
    # rebalance puts the whole store on each new device and sizes each
    # new shard for every row, about twice the HBM at the default
    # chunk's doubled capacity
    s = fleet.size
    chunk = (s.hist_streams * s.hist_segments // n_shards
             + s.ticks * fleet.cap * 5 // (4 * n_shards))
    store = ShardedStore(out_dim=fleet.K, n_shards=n_shards,
                         chunk_rows=chunk)
    devs = (set() if store.mesh is None
            else {d.id for d in store.mesh.devices.flat})
    placed = {d.id for d in store.columns["t"].sharding.device_set}
    smoke.check("shard_mesh_distinct_devices",
                store.mesh is not None and len(devs) == n_shards
                and placed == devs, f"mesh devices={sorted(devs)}")
    if store.mesh is None:
        return
    reg, handles, splans = smoke.phase(
        "register_standing", lambda: fleet.register_standing(store), 0)
    smoke.phase("load_sharded", lambda: fleet.load(store),
                lambda: store.n_rows)
    n_hist = store.n_rows
    smoke.phase("serve_sharded",
                lambda: fleet.serve(smoke, store, oracle=False),
                lambda: store.n_rows - n_hist)
    nw = windows_for(store, 2048)
    plans = fleet.query_plans(nw)
    sharded = {}

    def run_queries():
        for name, plan in plans.items():
            for up in (True, False):
                sharded[name, up] = _host_table(
                    store.query(plan, use_pallas=up))
    smoke.phase("query_sharded", run_queries, lambda: store.n_rows)
    if on_tpu:
        for name, plan in plans.items():
            if name == "topk_buffer":            # no aggregate partial
                continue
            chosen, hlo = _compiled_query_text(store, plan, True)
            smoke.check(f"sharded {name} Pallas partial in executable",
                        chosen and "tpu_custom_call" in hlo,
                        f"pallas={chosen}")
    st_sharded = smoke.phase(
        "standing_sharded",
        lambda: {h: _host_table(reg.answer(h)) for h in range(len(splans))},
        len(splans))

    # the 1-shard store from the same rows
    def one_shard():
        one = SegmentStore(out_dim=fleet.K, chunk_rows=store.n_rows)
        one.append_rows(store.host_rows())
        reg1, _, _ = fleet.register_standing(one)
        q = {name: _host_table(one.query(plan, use_pallas=False))
             for name, plan in plans.items()}
        st = {h: _host_table(reg1.answer(h)) for h in range(len(splans))}
        return one.n_rows, q, st
    n1, ref, st_ref = smoke.phase("one_shard_reference", one_shard,
                                  lambda: store.n_rows)
    smoke.check("one_shard_rows", n1 == store.n_rows, f"rows={n1}")

    def compare_all(tag, q, st):
        for name, plan in plans.items():
            for up in (True, False):
                if (name, up) not in q:
                    continue
                kw = ({"sort_by": "buffer_s"} if name == "topk_buffer"
                      else {"sum_cols": _sum_cols(plan),
                            "counts": ref[name][0].get("count")})
                smoke.compare(f"{tag} {name} use_pallas={up} == 1-shard",
                              q[name, up], ref[name], **kw)
        for h, plan in enumerate(splans):
            smoke.compare(f"{tag} standing q{h} == 1-shard", st[h],
                          st_ref[h], sum_cols=_sum_cols(plan),
                          counts=st_ref[h][0].get("count"))
    compare_all(f"sharded{n_shards}", sharded, st_sharded)

    new = smoke.phase("rebalance", lambda: rebalance(store, 2),
                      lambda: store.n_rows)
    nd = ({d.id for d in new.mesh.devices.flat} if new.mesh is not None
          else set())
    smoke.check("rebalance_mesh", len(nd) == 2 and new.n_rows
                == store.n_rows, f"devices={sorted(nd)} rows={new.n_rows}")
    q2 = smoke.phase(
        "query_rebalanced",
        lambda: {(name, False): _host_table(new.query(plan,
                                                      use_pallas=False))
                 for name, plan in plans.items()}, lambda: new.n_rows)
    st2 = {h: _host_table(new.standing.answer(h))
           for h in range(len(splans))}
    compare_all("rebalanced2", q2, st2)
    jax.block_until_ready(new.columns)


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the sharded-warehouse path")
    ap.add_argument("--tiny", action="store_true",
                    help="toy sizes; allowed without a TPU (rehearsal)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    devs = jax.devices()
    platform = devs[0].platform
    if platform != "tpu" and not args.tiny:
        print(f"chip_smoke: no TPU found (JAX platform: {platform}); "
              f"--tiny rehearses at toy size off the chip", file=sys.stderr)
        return 2
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, found {len(devs)} ({platform})", file=sys.stderr)
        return 2
    from repro.launch.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    print(f"compile cache: {cache}", flush=True)
    smoke = Smoke()
    size = TINY if args.tiny else FULL
    workers = 2 if args.tiny else max(1, min(12, (os.cpu_count() or 2) - 1))
    fleet = smoke.phase("fit_and_generate",
                        lambda: Fleet(size, args.seed, workers), 0)
    print(f"smoke figure (not a benchmark): stream generation "
          f"{fleet.gen_s:.3f}s on {workers} workers; K={fleet.K} "
          f"configs; pool cap={fleet.cap}", flush=True)
    on_tpu = platform == "tpu"
    print(f"device: platform={platform} kind={devs[0].device_kind} "
          f"count={len(devs)}", flush=True)
    if args.chips == 1:
        run_one_chip(smoke, fleet, on_tpu)
    else:
        run_sharded(smoke, fleet, on_tpu, args.chips)
    if smoke.failures:
        print(f"chip_smoke: {len(smoke.failures)} check(s) failed: "
              f"{smoke.failures}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
