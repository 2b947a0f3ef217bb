"""Set-up of one deployment: the fitted profile, the warehouse with its
history, the standing queries and the serving pool.

Everything is made from the seed and the configuration file alone. The
program under test is driven through its public entry points
(``Skyscraper``, ``SegmentStore``, ``StandingQueries``,
``SkyscraperPool``); what the oracle needs later is kept here.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench import gen

HIST_BLOCK_STREAMS = 512      # history is made and rebuilt in blocks this size


class DeclaredClock:
    """Stands in for the ``time`` module inside ``Skyscraper.fit``: it
    reads only what the profiled Transform calls declared, so every run
    of a seed profiles the same costs (the fit times its Transform on
    the host clock otherwise)."""

    def __init__(self):
        self.now = 0.0

    def perf_counter(self) -> float:
        return self.now


def standing_specs(cfg, n_ids: int, K: int):
    """The standing queries as plain data: per-stream windowed means of
    quality and per-configuration category sums on streams spread over
    the id range, plus the buffer high-water alert over every stream."""
    st, hist = cfg["standing"], cfg["warehouse"]
    n = st["queries"]
    nw = hist["history_segments"] // st["window"] + 1
    specs = []
    for j, sid in enumerate(np.linspace(0, n_ids - 1, n).astype(int)):
        if j % 4 != 3:
            specs.append({"sid": int(sid), "kind": "window_mean",
                          "window": st["window"], "num": nw})
        else:
            specs.append({"sid": int(sid), "kind": "k_category_sum",
                          "num": K})
    alert = {"kind": "buffer_max", "num": n_ids,
             "threshold": float(st["alert_buffer_s"])}
    return specs, alert


def _program_plan(spec):
    from repro.warehouse import Filter, GroupBy, WindowAgg
    sel = Filter("stream_id", "eq", float(spec["sid"]))
    if spec["kind"] == "window_mean":
        return (sel, WindowAgg(window=spec["window"], value="quality",
                               agg="mean", num_windows=spec["num"]))
    return (sel, GroupBy("k", "category", agg="sum",
                         num_groups=spec["num"]))


class Deployment:
    """One configuration at one seed. ``scale`` overrides sizes (the CPU
    tests run every cell at a tiny size)."""

    def __init__(self, cfg, seed: int, scale=None):
        self.cfg = {k: (dict(v) if isinstance(v, dict) else v)
                    for k, v in cfg.items()}
        for section, vals in (scale or {}).items():
            self.cfg[section].update(vals)
        self.seed = int(seed)
        c = self.cfg
        self.V = c["pool"]["streams"]
        self.H = c["warehouse"]["history_streams"]
        self.T_hist = c["warehouse"]["history_segments"]
        self.n_ids = self.V + self.H
        self.grid = [tuple(v) for v in c["configs"]]
        self.work = np.asarray(c["work_core_s"], np.float64)
        self.power_all = np.asarray(c["power"], np.float64)
        self._index = {v: i for i, v in enumerate(self.grid)}
        self._clock = None
        self.fit_seed = self.seed % (2 ** 31 - 1)
        self.n_cat = min(c["n_categories"], c["fit"]["unlabeled_segments"])

    # -- the offline fit ------------------------------------------------
    def _proc_fit(self, seg, knobs):
        # the fit reads wall seconds on the stream's cores: core-seconds
        # over cores (a power of two, so every profiled sum is exact)
        i = self._index[tuple(knobs.values())]
        self._clock.now += self.work[i] / self.cfg["fit"]["num_cores"]
        return None, float(seg[i])

    def _proc_serve(self, seg, knobs):
        return None, float(seg[self._k_of[id(knobs)]])

    def unlabeled(self) -> np.ndarray:
        """(segments, configurations) float32: every configuration's
        quality on the seed's unlabeled segments, the fit's input."""
        f = self.cfg["fit"]
        q = gen.unlabeled_qualities(
            gen.key_for(self.seed, 1), jnp.asarray(self.power_all,
                                                   jnp.float32),
            T=f["unlabeled_segments"], content=gen.content_key(self.cfg))
        return np.ascontiguousarray(np.asarray(q, np.float32).T)

    def fit(self):
        """``Skyscraper.fit`` on seeded unlabeled segments, its
        Transform's cost declared by the configuration file."""
        from repro.core import api
        c, f = self.cfg, self.cfg["fit"]
        unl = self.unlabeled()
        sky = api.Skyscraper(fps=c["fps"], segment_seconds=c["segment_seconds"],
                             n_categories=c["n_categories"],
                             seed=self.fit_seed)
        sky.set_resources(num_cores=f["num_cores"], buffer_gb=f["buffer_gb"],
                          cloud_budget_core_s=f["cloud_budget_core_s"])
        for name, dom in c["knobs"].items():
            sky.register_knob(name, dom)
        self._clock, real = DeclaredClock(), api.time
        api.time = self._clock
        try:
            sky.fit(list(unl), self._proc_fit,
                    plan_segments=f["plan_segments"], max_k=f["max_k"])
        finally:
            api.time = real
            self._clock = None
        sky.proc_fn = self._proc_serve
        self.sky = sky
        self.K = len(sky.configs)
        self.kept = [self._index[tuple(kv.values())] for kv in sky.configs]
        self._k_of = {id(kv): k for k, kv in enumerate(sky.configs)}
        self.cost = np.asarray(sky.cost, np.float64)
        self.power = self.power_all[self.kept]
        return self

    def profile_line(self) -> str:
        return ("profile: K=%d kept=%s cost_core_s=%s" % (
            self.K, self.kept, [float(x) for x in self.cost]))

    # -- the warehouse --------------------------------------------------
    def history_block(self, first: int, n: int):
        """Device rows of archived streams ``first .. first + n - 1``."""
        ids = np.arange(self.V + first, self.V + first + n)
        return gen.history(self.seed, self.cfg, ids, self.power,
                           self.cost, T=self.T_hist,
                           n_cat=self.sky.centers.shape[0])

    def history_blocks(self):
        B = min(HIST_BLOCK_STREAMS, self.H)
        for first in range(0, self.H, B):
            yield first, min(B, self.H - first)

    def load(self):
        """Register the standing queries, then land the history through
        ``append_rows`` (one dispatch that also folds it into them)."""
        from repro.warehouse import SegmentStore, StandingQueries
        store = SegmentStore(out_dim=self.K,
                             chunk_rows=self.cfg["warehouse"]["chunk_rows"])
        reg = StandingQueries(store)
        self.specs, self.alert = standing_specs(self.cfg, self.n_ids,
                                                self.K)
        self.handles = [reg.register(_program_plan(s)) for s in self.specs]
        from repro.warehouse import Filter, GroupBy
        reg.subscribe(
            (GroupBy("stream_id", "buffer_s", agg="max",
                     num_groups=self.n_ids),),
            Filter("buffer_s", "gt", self.alert["threshold"]),
            name="buffer-hwm")
        blocks = [self.history_block(f, n) for f, n in self.history_blocks()]
        rows = {k: jnp.concatenate([b[k] for b in blocks])
                for k in blocks[0]}
        del blocks
        store.append_rows(rows)
        del rows
        if store.n_rows != store.capacity:
            raise ValueError(
                f"history of {store.n_rows} rows does not fill its capacity "
                f"rung of {store.capacity}: warm-up grows the store to the "
                "next rung, so history_streams x history_segments has to "
                "be chunk_rows times a power of two")
        self.store, self.reg = store, reg
        self.n_hist = store.n_rows
        return self

    # -- the live fleet -------------------------------------------------
    def serve(self, n_ticks: int):
        """The pool, and each live stream's Transform results for
        ``n_ticks`` ticks (host, (ticks, V, K); the driver cycles)."""
        from repro.core.api import SkyscraperPool
        p = self.cfg["pool"]
        q = gen.live_qualities(gen.key_for(self.seed, 2),
                               jnp.arange(self.V, dtype=jnp.int32),
                               jnp.asarray(self.power, jnp.float32),
                               T=n_ticks, content=gen.content_key(self.cfg))
        self.Q = np.ascontiguousarray(np.asarray(q).transpose(2, 0, 1))
        self.pool = SkyscraperPool(
            self.sky, n_streams=self.V, sink=self.store,
            telemetry=p["telemetry"], shed_watermark=p["shed_watermark"],
            capacity_core_s=p["capacity_core_s"])
        return self

    def free(self):
        """Drop the program's device state (the pool, the store and its
        standing queries); the profile and the seeded inputs stay."""
        self.pool = self.store = self.reg = None
