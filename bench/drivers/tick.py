"""The tick driver: one deployment's serving pool, driven tick by tick
by a traffic file's parameters, and its comparison with the reference.

Parameters (``bench/traffic/<mix>.json`` with ``"driver": "tick"``):

- ``loop``: ``closed`` (the next tick starts when the last one has
  returned) or ``open`` (ticks are due on a fixed schedule, whether or
  not the last one has returned);
- ``tick_rate_hz``: the open loop's schedule;
- ``arrival_mult``: the work multiplier of every stream's segments
  (the paper's arrival spikes).

Every tick: one ``SkyscraperPool.process`` over every live stream, then
``block_until_ready`` on the store's columns and standing state, so a
tick's time runs until its standing answers and alert poll are done.

Set-up ends with warm-up ticks, as many as the deployment needs for
every program of the window to have run once: the store has grown to
the capacity rung above its history's, and a replan with the
forecaster in use has run.
"""
from __future__ import annotations

import json
import math
import os
import time

import jax
import numpy as np

from bench import fleet, needs, oracle

CONTENT_TICKS = 1024    # Transform results made per live stream; cycled

with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "tick.limits.json")) as _f:
    LIMITS = json.load(_f)


class Driver:
    def __init__(self, cfg, traffic, seed: int, scale=None, log=print):
        self.traffic, self.log = traffic, log
        self.dep = fleet.Deployment(cfg, seed, scale)
        self.n = 0                      # ticks run so far
        self.alphas = []                # (first tick, plan in force)
        self.latency_s = []             # open loop: per window tick
        self.service_s = []             # (seconds, tick, start) likewise
        self.window_ticks = 0
        self.window_s = 0.0
        self.late_s = 0.0
        self.failed = 0

    # -- set-up ---------------------------------------------------------
    def setup(self, phase):
        dep = self.dep
        dep.fit()
        phase("fit")
        self.log(dep.profile_line())
        dep.load()
        phase("load")
        dep.serve(CONTENT_TICKS)
        mult = float(self.traffic["arrival_mult"])
        self.arr = (None if mult == 1.0
                    else np.full(dep.V, mult, np.float32))
        phase("pool")
        self.warmup()
        phase("warmup")

    def warmup_ticks(self) -> int:
        """Ticks until a replan with the forecaster in use has run: the
        pool replans at the end of every ``plan_segments``-th tick and
        uses the forecaster once its label buffers have filled."""
        sky = self.dep.sky
        every, filled = sky._plan_every, sky.n_split * sky.interval
        return every * -(-(filled + 1) // every)

    def warmup(self):
        cap0 = self.dep.store.capacity
        while self.n < self.warmup_ticks() or self.dep.store.capacity == cap0:
            self.tick()

    @property
    def capacity(self) -> int:
        return self.dep.store.capacity

    # -- traffic --------------------------------------------------------
    def tick(self):
        pool, store = self.dep.pool, self.dep.store
        plan = pool._alpha
        if not self.alphas or self.alphas[-1][1] is not plan:
            self.alphas.append((self.n, plan))
        with jax.profiler.TraceAnnotation("bench.traffic"):
            segs = self.dep.Q[self.n % len(self.dep.Q)]
        with jax.profiler.TraceAnnotation("bench.tick"):
            pool.process(segs, arrival_mults=self.arr)
            jax.block_until_ready((store.columns,
                                   store.standing.kernel_args()[0]))
        self.n += 1

    def window(self, seconds: float):
        """Run the measured window; returns its end-to-end numbers."""
        if self.traffic["loop"] == "closed":
            t0 = time.perf_counter()
            while time.perf_counter() - t0 < seconds:
                self.tick()
                self.window_ticks += 1
            self.window_s = time.perf_counter() - t0
        else:
            rate = float(self.traffic["tick_rate_hz"])
            n_due = max(1, math.ceil(seconds * rate))
            t0 = time.perf_counter()
            for i in range(n_due):
                due = t0 + i / rate
                now = time.perf_counter()
                if now < due:
                    with jax.profiler.TraceAnnotation("bench.wait"):
                        time.sleep(due - now)
                start = time.perf_counter()
                self.late_s = max(self.late_s, start - due)
                self.tick()
                done = time.perf_counter()
                self.latency_s.append(done - due)
                self.service_s.append((done - start, i, start - t0))
                self.window_ticks += 1
            self.window_s = time.perf_counter() - t0
        return self.end_to_end()

    def end_to_end(self):
        V = self.dep.V
        out = {}
        if self.traffic["loop"] == "closed":
            out["segments_per_s"] = (self.window_ticks * V
                                     / self.window_s)
        else:
            # every segment of a tick shares its tick's answer time
            ms = np.repeat(np.asarray(self.latency_s) * 1e3, V)
            out["answer_p50_ms"] = float(np.percentile(ms, 50))
            out["answer_p95_ms"] = float(np.percentile(ms, 95))
        return out

    @property
    def attempted(self) -> int:
        return self.window_ticks * self.dep.V

    def report(self):
        """Lines about the window, printed before the result."""
        tel = self.dep.pool.telemetry()
        if tel is not None:
            self.log("pool counters: segments=%r dropped_or_shed=%r" % (
                float(tel.counters["seg_total"].sum()),
                float(tel.counters["seg_dropped"].sum())))
        self.log(f"window: ticks={self.window_ticks} "
                 f"seconds={self.window_s!r} "
                 f"generator_late_s={self.late_s!r}")
        if self.service_s:
            self.log("slowest ticks (service_s, tick, start_s): %r"
                     % sorted(self.service_s, reverse=True)[:5])

    def needs(self) -> dict:
        dep = self.dep
        groups = [s["num"] for s in dep.specs] + [dep.alert["num"]]
        return {"ingest_bytes": needs.ingest_bytes(dep.V, dep.K, groups)}

    # -- the comparison ---------------------------------------------------
    def collect(self):
        """Everything the comparison needs from the program, pulled to
        the host before the program's state is freed."""
        dep = self.dep
        store, reg, pool = dep.store, dep.reg, dep.pool
        tail = {k: np.asarray(v[dep.n_hist:store.n_rows])
                for k, v in store.columns.items()}
        live_ids = [s["sid"] for s in dep.specs if s["sid"] < dep.V]
        sample = oracle.sample_streams(dep.seed, dep.V, 64, live_ids)
        plans = [(t, np.asarray(a)[:dep.V]) for t, a in self.alphas]
        answers = []
        for h in dep.handles:
            table, mask = reg.answer(h)
            answers.append(({k: np.asarray(v) for k, v in table.items()},
                            np.asarray(mask)))
        alert = pool.alerts[0]
        return {"tail": tail, "sample": sample, "plans": plans,
                "answers": answers,
                "alert": {"fired": np.asarray(alert.fired),
                          "table": {k: np.asarray(v)
                                    for k, v in alert.table.items()}},
                "fit": {"kept": list(dep.kept),
                        "cost": np.asarray(dep.sky.cost, np.float64),
                        "rank_pos": np.asarray(dep.sky.tables.rank_pos),
                        "centers": np.asarray(dep.sky.centers)},
                "n_ticks": self.n}

    def free(self):
        self.dep.free()

    def numbers(self, got, dtype=np.float32):
        """The numbers compared (``oracle.py``). ``dtype`` below
        float32 makes the control: the reference, in lower precision,
        in the program's place."""
        dep = self.dep
        tail, V, n_ticks = got["tail"], dep.V, got["n_ticks"]
        T = dep.T_hist
        c, f = dep.cfg, dep.cfg["fit"]
        fit_seed, n_cat = dep.fit_seed, dep.n_cat
        unl = dep.unlabeled()
        ref = oracle.reference_fit(unl, dep.work, f["num_cores"],
                                   f["max_k"], n_cat, fit_seed)
        budget = f["num_cores"] * c["segment_seconds"]
        if dtype is np.float32:
            fit, plans = got["fit"], got["plans"]
        else:                       # the control's fit and plan
            fit = oracle.reference_fit(unl, dep.work, f["num_cores"],
                                       f["max_k"], n_cat, fit_seed, dtype)
            fit["centers"] = oracle.kmeans(ref["quals"], n_cat, fit_seed,
                                           dtype)
            plans = [(0, oracle.reference_plan(fit["centers"], dtype)[None])]
        centers = np.asarray(fit["centers"], np.float32)
        base = {"fit_bad": oracle.fit_compare(fit, ref),
                "centers_gap": oracle.centers_gap(ref["quals"], centers),
                "plan_bad": oracle.plans_bad(plans, centers, ref["cost"],
                                             budget)}
        base["rows_missing"] = missing = oracle.rows_missing(tail, n_ticks, V)
        if missing or centers.shape != ref["centers"].shape:
            # the rest would read rows or tables that are not there
            return dict({k: None for k in LIMITS}, **base)
        want = {s["sid"] for s in dep.specs if s["sid"] >= V}
        hist_rows, hmax = {}, np.zeros(dep.H, np.float32)
        for first, n in dep.history_blocks():
            rows = dep.history_block(first, n)
            hmax[first:first + n] = np.asarray(
                rows["buffer_s"].reshape(n, T).max(axis=1))
            for sid in want:
                j = sid - V - first
                if 0 <= j < n:
                    hist_rows[sid] = {
                        col: np.asarray(jax.lax.dynamic_slice_in_dim(
                            rows[col], j * T, T))
                        for col in ("t", "k", "quality", "category")}
            del rows
        live = {}
        for sid in {s["sid"] for s in dep.specs if s["sid"] < V}:
            at = np.arange(n_ticks) * V + sid
            live[sid] = {col: tail[col][at]
                         for col in ("t", "k", "quality", "category")}

        def rows_of(sid):
            return live[sid] if sid < V else hist_rows[sid]

        prof = {"centers": centers, "rank_pos": ref["rank_pos"],
                "cost": ref["cost"], "num_cores": f["num_cores"],
                "tau": c["segment_seconds"],
                "buffer_cap_s": f["buffer_gb"] * 1e9 / 90e3,
                "cloud_budget": f["cloud_budget_core_s"],
                "shed_watermark": c["pool"]["shed_watermark"],
                "arrival": self.traffic["arrival_mult"]}
        sample = got["sample"]
        bad, n = oracle.rows_bad(tail, n_ticks, V, sample, dep.Q,
                                 [(t, a[sample] if len(a) == V else
                                   np.broadcast_to(a, (len(sample),)
                                                   + a.shape[1:]))
                                  for t, a in plans], prof, dtype)
        if dtype is np.float32:
            answers = got["answers"]
        else:                       # the control's standing answers
            answers = []
            for spec in dep.specs:
                val, cnt = oracle.rescan(spec, rows_of(spec["sid"]), dtype)
                col = ("quality" if spec["kind"] == "window_mean"
                       else "category")
                answers.append(({col: val.astype(np.float64), "count": cnt},
                                cnt > 0))
        count_bad, gap = oracle.standing_compare(dep.specs, answers, rows_of)
        buf = tail["buffer_s"].reshape(n_ticks, V)
        ref_max = np.concatenate([buf.max(axis=0), hmax])
        ref_cnt = np.concatenate([np.full(V, n_ticks), np.full(dep.H, T)])
        return dict(base, **{
            "rows_bad_pct": 100.0 * bad / max(n, 1),
            "standing_count_bad": count_bad,
            "standing_gap": gap,
            "alert_bad": oracle.alert_compare(
                got["alert"], dep.alert["threshold"], ref_max, ref_cnt)})
