"""User-facing Skyscraper API (paper App. F).

    sky = Skyscraper(fps=30, segment_seconds=2.0)
    sky.set_resources(num_cores=8, buffer_gb=4.0, cloud_budget_core_s=0)
    sky.register_knob("det_interval", [1, 5, 10])
    sky.fit(unlabeled_segments, proc_fn)
    status, out = sky.process(segment)        # online, content-adaptive

``proc_fn(segment, knobs) -> (output, quality)`` is the user's transform
(the V-ETL *T*). fit() profiles every knob configuration's wall-clock
runtime (the paper's offline profiling), Pareto-filters configurations,
builds content categories from measured quality vectors, and trains the
forecaster. process() is the online loop: classify -> look up plan ->
switch -> execute.
"""
from __future__ import annotations

import functools
import itertools
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.registry import example_builder, register_engine
from repro.core.categories import kmeans
from repro.core.forecaster import (forecast_from_labels, init_forecaster,
                                   make_dataset, train_forecaster)
from repro.core.planner import solve_lp_lagrangian, solve_lp_stacked
from repro.core.switcher import (SwitchTables, _masked_switch, init_state,
                                 init_state_multi, register_cache_probe,
                                 stack_tables, switch_step,
                                 switch_step_multi)
from repro.obs.spans import install_gc_spans, span
from repro.obs.telemetry import HostTelemetry


class Skyscraper:
    """User-facing ETL handle: declare a workload (fps, knobs, cores,
    buffer, cloud budget), ``fit()`` offline tables, then ``process()``
    segments online through the fused switch/plan kernels."""

    def __init__(self, fps: int = 30, segment_seconds: float = 2.0,
                 n_categories: int = 4, seed: int = 0):
        self.fps = fps
        self.tau = segment_seconds
        self.n_categories = n_categories
        self.seed = seed
        self.knobs: Dict[str, Sequence] = {}
        self.num_cores = 1
        self.buffer_gb = 4.0
        self.cloud_budget = 0.0
        self._fitted = False

    def set_resources(self, *, num_cores: int, buffer_gb: float = 4.0,
                      cloud_budget_core_s: float = 0.0):
        self.num_cores = num_cores
        self.buffer_gb = buffer_gb
        self.cloud_budget = cloud_budget_core_s
        self.budget_override = None

    def set_budget(self, core_s_per_segment: float):
        """Override the per-segment compute budget used by the planner
        (defaults to num_cores * segment_seconds)."""
        self.budget_override = core_s_per_segment
        if getattr(self, "_fitted", False):
            self._replan()

    def register_knob(self, name: str, domain: Sequence):
        self.knobs[name] = tuple(domain)

    # ------------------------------------------------------------------
    def fit(self, unlabeled: Sequence, proc_fn: Callable, *,
            profile_repeats: int = 1, plan_segments: int = 512,
            n_split: int = 4, max_k: int = 10):
        """unlabeled: list of segments (opaque to Skyscraper)."""
        configs = [dict(zip(self.knobs, v))
                   for v in itertools.product(*self.knobs.values())]
        # --- profile runtimes + quality vectors on the unlabeled data ---
        sample = unlabeled[:: max(1, len(unlabeled) // 40)]
        runtimes = np.zeros(len(configs))
        quals = np.zeros((len(unlabeled), len(configs)), np.float32)
        for ki, kv in enumerate(configs):
            t0 = time.perf_counter()
            for _ in range(profile_repeats):
                for seg in sample:
                    proc_fn(seg, kv)
            runtimes[ki] = ((time.perf_counter() - t0)
                            / (profile_repeats * len(sample)))
            for si, seg in enumerate(unlabeled):
                _, q = proc_fn(seg, kv)
                quals[si, ki] = q
        # --- Pareto-filter configurations -------------------------------
        mq = quals.mean(axis=0)
        order = np.argsort(runtimes)
        keep = []
        best_q = -1.0
        for i in order:
            if mq[i] > best_q + 1e-6:
                keep.append(i)
                best_q = mq[i]
        keep = keep[:max_k]
        self.configs = [configs[i] for i in keep]
        self.cost = runtimes[keep] * self.num_cores  # core-s per segment
        quals = quals[:, keep]
        # --- categories + forecaster ------------------------------------
        import jax
        centers, labels = kmeans(quals, min(self.n_categories, len(unlabeled)),
                                 seed=self.seed)
        self.centers = np.asarray(centers)
        C = self.centers.shape[0]
        interval = max(1, len(labels) // (4 * n_split))
        horizon = max(1, min(plan_segments, len(labels) // 4))
        X, Y = make_dataset(np.asarray(labels), C, interval=interval,
                            n_split=n_split, horizon=horizon)
        params = init_forecaster(jax.random.PRNGKey(self.seed), n_split, C)
        self.forecaster, self.forecast_metrics = train_forecaster(params, X, Y)
        self.n_split, self.interval = n_split, interval
        # --- switcher tables (single all-on-prem placement per config) --
        K = len(self.configs)
        rt = (self.cost / self.num_cores)[:, None]
        self.tables = SwitchTables(
            centers=jnp.asarray(self.centers),
            power=jnp.asarray(mq[keep]),
            cost=jnp.asarray(self.cost, jnp.float32),
            place_rt=jnp.asarray(rt, jnp.float32),
            place_on=jnp.asarray(self.cost[:, None], jnp.float32),
            place_cl=jnp.zeros((K, 1), jnp.float32),
            place_valid=jnp.ones((K, 1), bool),
            rank_pos=jnp.asarray(np.argsort(np.argsort(-mq[keep])), jnp.int32),
            tau=self.tau,
            buffer_cap_s=self.buffer_gb * 1e9 / 90e3,
            cloud_budget=self.cloud_budget,
        )
        self.state = init_state(self.tables)
        self.proc_fn = proc_fn
        self._labels_hist: List[int] = []
        self._plan_every = plan_segments
        self._seen = 0
        self._replan()
        self._fitted = True
        return self

    def _replan(self):
        C = self.centers.shape[0]
        need = self.n_split * self.interval
        if len(self._labels_hist) >= need:
            lab = jnp.asarray(self._labels_hist[-need:], jnp.int32)
            r = np.asarray(forecast_from_labels(
                self.forecaster, lab, C, n_split=self.n_split,
                interval=self.interval))
        else:
            r = np.full(C, 1.0 / C)
        budget = (self.budget_override if getattr(self, "budget_override",
                                                  None)
                  else self.num_cores * self.tau)
        self.alpha = solve_lp_lagrangian(
            jnp.asarray(self.centers), self.tables.cost,
            jnp.asarray(r, jnp.float32), jnp.float32(budget))

    # ------------------------------------------------------------------
    def process(self, segment, arrival_mult: float = 1.0):
        """Run the V-ETL Transform on one segment with adaptive knobs."""
        assert self._fitted, "call fit() first"
        K = len(self.configs)
        dummy_quals = jnp.zeros((K,), jnp.float32)  # filled post-exec
        self.state, out = switch_step(self.state, dummy_quals,
                                      jnp.float32(arrival_mult),
                                      self.alpha, self.tables)
        k = int(out["k"])
        result, q = self.proc_fn(segment, self.configs[k])
        # report the measured quality back (drives the next classification)
        self.state["qual_prev"] = jnp.float32(q)
        self._labels_hist.append(int(out["c"]))
        self._seen += 1
        if self._seen % self._plan_every == 0:
            self._replan()
        return {"config": self.configs[k], "k": k, "category": int(out["c"]),
                "quality": float(q),
                "buffer_s": float(out["buffer_s"])}, result


@functools.partial(jax.jit, static_argnames=("n_split", "interval"))
def _pool_replan(params, bufs, centers, cost, budget, use_model, *,
                 n_split: int, interval: int):
    """Device-side batched replanning for V streams: each stream's
    rolling label buffer -> histogram features -> forecaster MLP -> LP,
    all vmapped into one dispatch. ``use_model`` (traced bool) falls
    back to the uniform prior until the buffers have filled once —
    flipping it never recompiles."""
    C = centers.shape[0]
    with jax.named_scope("pool.forecast"):
        r_model = jax.vmap(lambda b: forecast_from_labels(
            params, b, C, n_split=n_split, interval=interval))(bufs)
        r = jnp.where(use_model, r_model,
                      jnp.full_like(r_model, 1.0 / C))
    with jax.named_scope("pool.lp"):
        return jax.vmap(lambda rv: solve_lp_lagrangian(centers, cost, rv,
                                                       budget))(r)


_pool_shift = jax.jit(lambda bufs, c: jnp.concatenate(
    [bufs[:, 1:], c[:, None].astype(jnp.int32)], axis=1))


@functools.partial(jax.jit, static_argnames=("n_split", "interval"))
def _pool_replan_stacked(params, bufs, centers, cost, budget, use_model,
                         active, priority, *, n_split: int, interval: int):
    """Joint priority-weighted replanning for the elastic pool: every
    ACTIVE stream's forecast feeds ONE stacked LP under a single shared
    pool budget, with each stream's quality term scaled by its
    priority (``solve_lp_stacked``'s ``weights``). Under overload the
    shared Lagrangian multiplier rises and the plan buys quality for
    high-priority streams first — low-priority streams degrade toward
    cheap configs before anyone sheds. Inactive slots get zero rate,
    so they contribute nothing to the joint spend; flipping ``active``
    / ``priority`` / ``budget`` values never recompiles."""
    C = centers.shape[0]
    with jax.named_scope("pool.forecast"):
        r_model = jax.vmap(lambda b: forecast_from_labels(
            params, b, C, n_split=n_split, interval=interval))(bufs)
        r = jnp.where(use_model, r_model,
                      jnp.full_like(r_model, 1.0 / C))
        r = r * jnp.asarray(active, jnp.float32)[:, None]
    V = bufs.shape[0]
    qual = jnp.broadcast_to(centers, (V,) + centers.shape)
    with jax.named_scope("pool.lp"):
        return solve_lp_stacked(qual, cost, r, budget, weights=priority)


def _pool_tick_fn(state, q_meas, q_valid, quals, arr, active, priority,
                  alpha, tables, capacity_core_s, watermark_frac):
    """One elastic-pool tick, fully fused: fold last tick's measured
    qualities into the carried classification state, run the masked
    batched switch (retired/empty slots are exact no-ops), then apply
    priority shedding — all ONE executable per capacity bucket.

    Shedding (the paper's last degradation rung, §3 throughput
    guarantee): two overload triggers, both computed on device —
    (1) the tick's total planned on-prem demand exceeds
    ``capacity_core_s`` (the joint plan's feasible set collapsed for
    the slice of streams that no longer fits), and (2) a stream's
    pre-tick buffer crossed ``watermark_frac`` of its buffer capacity
    (it is falling behind faster than degradation can absorb). Under
    trigger (1) streams are kept in priority order (stable argsort, so
    equal priorities shed by slot index) until the kept demand fits;
    a shed stream's segment reverts to the switch's own drop
    semantics: zero work, zero quality, buffer drains by tau. Both
    thresholds are traced operands — defaults of +inf make the whole
    stage the identity, so the fixed pool pays nothing."""
    state = dict(state, qual_prev=jnp.where(jnp.asarray(q_valid, bool),
                                            q_meas, state["qual_prev"]))
    pre_buf = state["buffer_s"]
    with jax.named_scope("pool.switch"):
        new_state, outs = jax.vmap(_masked_switch)(
            state, quals, arr, active, alpha, tables)
    with jax.named_scope("pool.shed"):
        demand = outs["on_s"]
        order = jnp.argsort(jnp.where(active, -priority, jnp.inf))
        keep = jnp.zeros_like(active).at[order].set(
            jnp.cumsum(demand[order]) <= capacity_core_s)
        hwm_s = watermark_frac * jnp.asarray(tables.buffer_cap_s,
                                             jnp.float32)
        shed = active & ~outs["dropped"] & (~keep | (pre_buf >= hwm_s))
        tau = jnp.asarray(tables.tau, jnp.float32)
        shed_buf = jnp.maximum(pre_buf - tau, 0.0)
        new_state = dict(
            new_state,
            buffer_s=jnp.where(shed, shed_buf, new_state["buffer_s"]),
            cloud_spent=jnp.where(shed,
                                  new_state["cloud_spent"] - outs["cl_s"],
                                  new_state["cloud_spent"]),
            qual_prev=jnp.where(shed, 0.0, new_state["qual_prev"]))
        zero = jnp.float32(0.0)
        outs = dict(outs,
                    qual=jnp.where(shed, zero, outs["qual"]),
                    on_s=jnp.where(shed, zero, outs["on_s"]),
                    cl_s=jnp.where(shed, zero, outs["cl_s"]),
                    rt=jnp.where(shed, zero, outs["rt"]),
                    buffer_s=jnp.where(shed, shed_buf, outs["buffer_s"]),
                    dropped=outs["dropped"] | shed,
                    shed=shed)
    return new_state, outs


_pool_tick = jax.jit(_pool_tick_fn)


def _pool_admit_fn(tables, state, bufs, alpha, active, priority, slot,
                   prio, row_tables, alpha_row):
    """Fill one slot with a freshly admitted stream: write its (possibly
    per-stream) table row, a fresh switcher state, an empty label
    buffer, the current single-stream plan, and flip the slot active.
    Every argument is a traced VALUE — admissions within a capacity
    bucket reuse ONE executable (the zero-warm-recompile contract)."""
    tables = jax.tree.map(
        lambda t, r: t.at[slot].set(jnp.asarray(r, t.dtype)),
        tables, row_tables)
    k0 = jnp.argmin(row_tables.rank_pos).astype(jnp.int32)
    state = {
        "used": state["used"].at[slot].set(0.0),
        "count": state["count"].at[slot].set(0.0),
        "buffer_s": state["buffer_s"].at[slot].set(0.0),
        "cloud_spent": state["cloud_spent"].at[slot].set(0.0),
        "k_cur": state["k_cur"].at[slot].set(k0),
        "qual_prev": state["qual_prev"].at[slot].set(1.0),
    }
    bufs = bufs.at[slot].set(0)
    alpha = alpha.at[slot].set(alpha_row)
    active = active.at[slot].set(True)
    priority = priority.at[slot].set(prio)
    return tables, state, bufs, alpha, active, priority


_pool_admit = jax.jit(_pool_admit_fn)

_pool_retire = jax.jit(lambda active, slot: active.at[slot].set(False))

register_cache_probe("pool_replan", lambda: _pool_replan._cache_size())
register_cache_probe("pool_shift", lambda: _pool_shift._cache_size())
register_cache_probe("pool_replan_stacked",
                     lambda: _pool_replan_stacked._cache_size())
register_cache_probe("pool_tick", lambda: _pool_tick._cache_size())
register_cache_probe("pool_admit", lambda: _pool_admit._cache_size())
register_cache_probe("pool_retire", lambda: _pool_retire._cache_size())
register_engine("pool_replan", example_builder("pool_replan"),
                probe=lambda: _pool_replan._cache_size(),
                covers=("repro.core.api:_pool_replan",),
                probe_name="pool_replan")
register_engine("pool_shift", example_builder("pool_shift"),
                probe=lambda: _pool_shift._cache_size(),
                covers=("repro.core.api:_pool_shift",),
                probe_name="pool_shift")
register_engine("pool_replan_stacked",
                example_builder("pool_replan_stacked"),
                probe=lambda: _pool_replan_stacked._cache_size(),
                covers=("repro.core.api:_pool_replan_stacked",),
                probe_name="pool_replan_stacked")
register_engine("pool_tick", example_builder("pool_tick"),
                probe=lambda: _pool_tick._cache_size(),
                covers=("repro.core.api:_pool_tick",),
                probe_name="pool_tick")
register_engine("pool_admit", example_builder("pool_admit"),
                probe=lambda: _pool_admit._cache_size(),
                covers=("repro.core.api:_pool_admit",),
                probe_name="pool_admit")
register_engine("pool_retire", example_builder("pool_retire"),
                probe=lambda: _pool_retire._cache_size(),
                covers=("repro.core.api:_pool_retire",),
                probe_name="pool_retire")


class AdmissionError(RuntimeError):
    """Raised by ``SkyscraperPool.admit`` when admission control
    determines the pool cannot serve one more stream even at every
    stream's cheapest configuration (the throughput guarantee would be
    unsatisfiable, so the stream is refused instead of admitted into
    guaranteed shedding)."""


class SkyscraperPool:
    """An ELASTIC pool of live streams sharing one fitted profile,
    switched by the batched engine: ONE fused jit dispatch decides all
    slots' knob configs per tick (paper App. D scenario 1 as an online
    serving runtime).

    Slots, not streams: capacity follows the power-of-two slot ladder
    (``_bucket_cap`` on the leading axis of every carried array), and
    an ``active`` mask makes retired/empty slots exact no-ops inside
    the fused tick. ``admit``/``retire`` flip VALUES only, so stream
    churn within a capacity bucket causes ZERO warm recompiles; only
    crossing a bucket boundary compiles once more (O(log V) compiles
    over a pool's lifetime).

        pool = SkyscraperPool(fitted_sky, n_streams=8)
        statuses, outputs = pool.process([seg0, ..., seg7])
        pool.admit(stream_id=99, priority=2.0)
        pool.retire(stream_id=3)
        statuses, outputs = pool.process({99: seg, ...})  # by stream id

    Overload behavior (``capacity_core_s`` / ``shed_watermark``): the
    fused tick sheds lowest-priority streams first when planned demand
    exceeds the pool's provisioned core-seconds per tick, or when a
    stream's buffer crosses the high-water-mark fraction of its
    capacity. Shed segments revert to the switch's drop semantics and
    land in telemetry's ``seg_dropped`` per stream; with a warehouse
    sink, standing alert subscriptions fire on the same tick's rows.
    ``joint_plan=True`` additionally replans all streams through ONE
    priority-weighted stacked LP under a single pool budget
    (``solve_lp_stacked`` weights) instead of independent per-stream
    budgets.

    Fused planning: per-stream category histories live in a device-side
    rolling label buffer (V_cap, hist_len) updated by a jitted shift
    each tick, and replanning is ONE compiled call (vmapped forecaster
    + stacked LP). The replan for window t+1 is ENQUEUED before the
    tick's decisions are pulled to host, so planning overlaps the
    host-side Transform work of window t (async double-buffering; JAX's
    async dispatch does the pipelining — no ``block_until_ready``
    anywhere on the tick path).

    ``sink``: an optional ``warehouse.SegmentStore`` (with
    ``out_dim == len(sky.configs)``) — every tick lands one row per
    ACTIVE stream in the warehouse, carrying the stream's REAL id. A
    ``warehouse.ShardedStore`` sink routes stream ``s``'s row to shard
    ``s % n_shards`` inside the same tick dispatch (after heavy
    admit/retire churn, ``runtime.elastic.rebalance`` re-partitions the
    accumulated rows). Standing queries registered on the sink refresh
    inside that dispatch too, and each tick's fired alert subscriptions
    surface in ``pool.alerts``.

    ``telemetry=True`` attaches the serving-loop flight recorder: a
    host-side sequential float32 accumulator (``repro.obs``'s
    ``HostTelemetry``) fed from the per-tick outs the pool already
    pulls to host for the Transform — zero extra device dispatches,
    and the same bit-exactness contract as the fused engines' carried
    counters. Read it with ``pool.telemetry()`` (active streams, slot
    order) and ``pool.shed_stats()`` (per-stream shed fractions,
    retired streams included). The host counters (ticks, replans,
    host pulls, uploads) count with or without it, and every tick runs
    in ``repro.obs`` spans (see ``process``).
    """

    def __init__(self, sky: Skyscraper, n_streams: int, sink=None,
                 telemetry: bool = False, *, priorities=None,
                 slot_chunk: int = 8, capacity_core_s=None,
                 shed_watermark=None, joint_plan: bool = False):
        assert sky._fitted, "fit() the Skyscraper first"
        from repro.warehouse.store import _bucket_cap
        self.sky = sky
        self.sink = sink
        self._chunk = max(1, int(slot_chunk))
        self._cap = _bucket_cap(max(int(n_streams), 1), self._chunk)
        self.capacity_core_s = capacity_core_s
        self.shed_watermark = shed_watermark
        self._joint_plan = bool(joint_plan)
        # slot-ladder carries: every leading axis is (cap,)
        self.tables = stack_tables([sky.tables] * self._cap)
        self.state = init_state_multi([sky.tables] * self._cap)
        self._hist_len = sky.n_split * sky.interval
        self._bufs = jnp.zeros((self._cap, self._hist_len), jnp.int32)
        self._alpha = jnp.broadcast_to(
            sky.alpha, (self._cap,) + sky.alpha.shape)
        act = np.zeros(self._cap, bool)
        act[:n_streams] = True
        self._active = jnp.asarray(act)
        prio = np.zeros(self._cap, np.float32)
        prio[:n_streams] = (1.0 if priorities is None
                            else np.asarray(priorities, np.float32))
        self._priority = jnp.asarray(prio)
        # host-side slot bookkeeping: stream s starts at slot s
        self._slot_of: Dict[int, int] = {v: v for v in range(n_streams)}
        self._stream_of: Dict[int, int] = {v: v for v in range(n_streams)}
        self._free = list(range(n_streams, self._cap))
        # last tick's measured qualities, folded into the NEXT tick's
        # carried classification state inside the tick kernel
        self._pending_q = np.zeros(self._cap, np.float32)
        self._pending_valid = np.zeros(self._cap, bool)
        self._seen = 0
        # last tick's fired standing-query alerts (see ``process``)
        self.alerts = []
        self._retired_tel: Dict[int, Dict] = {}
        # host counters always; per-stream float32 recording on request
        k0 = int(np.argmin(np.asarray(sky.tables.rank_pos)))
        self._tel = HostTelemetry(self._cap, k0, record=telemetry)
        install_gc_spans()

    # -- lifecycle -----------------------------------------------------
    @property
    def V(self) -> int:
        """Number of ACTIVE streams (the slot capacity is ``cap``)."""
        return len(self._slot_of)

    @property
    def cap(self) -> int:
        """Current slot capacity (a power-of-two ladder rung)."""
        return self._cap

    @property
    def streams(self):
        """Active stream ids, slot order (the ``process`` list order)."""
        return [self._stream_of[s] for s in sorted(self._stream_of)]

    def _min_demand_core_s(self, extra: int = 0) -> float:
        """Lower bound on one tick's on-prem demand: every active
        stream (plus ``extra`` hypothetical ones) at its cheapest
        config — the admission-control feasibility test."""
        return float(np.min(self.sky.cost)) * (self.V + extra)

    def admit(self, stream_id: int, priority: float = 1.0, tables=None,
              force: bool = False) -> int:
        """Admit a live stream into a free slot (growing the slot
        ladder one bucket if none is free). ``tables`` optionally gives
        the stream its OWN ``SwitchTables`` row (same config set);
        ``priority`` orders it in the shed ladder and weights its
        quality term in the joint LP. Returns the assigned slot.

        Admission control: with ``capacity_core_s`` set, a stream whose
        admission would push the pool's cheapest-config demand past the
        provisioned capacity is REFUSED (``AdmissionError``) — the
        throughput guarantee could not hold even with every stream
        fully degraded. ``force=True`` admits anyway (and the priority
        shed ladder resolves the overload at tick time)."""
        if stream_id in self._slot_of:
            raise ValueError(f"stream {stream_id} already admitted")
        if (not force and self.capacity_core_s is not None
                and self._min_demand_core_s(extra=1)
                > float(self.capacity_core_s)):
            raise AdmissionError(
                f"admitting stream {stream_id} needs >= "
                f"{self._min_demand_core_s(extra=1):.3f} core-s/tick at "
                f"the cheapest config, over the provisioned "
                f"{float(self.capacity_core_s):.3f}")
        if not self._free:
            self._grow(self._cap * 2)
        slot = min(self._free)
        self._free.remove(slot)
        row = tables if tables is not None else self.sky.tables
        (self.tables, self.state, self._bufs, self._alpha, self._active,
         self._priority) = _pool_admit(
            self.tables, self.state, self._bufs, self._alpha,
            self._active, self._priority, jnp.int32(slot),
            jnp.float32(priority), row, jnp.asarray(self.sky.alpha))
        self._slot_of[stream_id] = slot
        self._stream_of[slot] = stream_id
        self._pending_valid[slot] = False
        self._tel.reset_slot(slot)
        return slot

    def retire(self, stream_id: int) -> int:
        """Remove a stream: its slot goes inactive (an exact no-op in
        the fused tick) and returns to the free list for the next
        admission. Telemetry counters accumulated for the stream are
        preserved in ``shed_stats()``. Returns the freed slot."""
        slot = self._slot_of.pop(stream_id)
        del self._stream_of[slot]
        if self._tel.record:
            self._retired_tel[stream_id] = {
                "segments": float(self._tel.counters["seg_total"][slot]),
                "dropped": float(self._tel.counters["seg_dropped"][slot]),
                "priority": float(np.asarray(self._priority)[slot]),
            }
        self._active = _pool_retire(self._active, jnp.int32(slot))
        self._pending_valid[slot] = False
        self._free.append(slot)
        return slot

    def _grow(self, new_cap: int) -> None:
        """Double the slot ladder: pad every carried array's leading
        axis with inactive template rows. The ONLY recompile point in
        the stream lifecycle — O(log V) growths over a pool's life."""
        pad = new_cap - self._cap
        sky = self.sky
        pad_tables = stack_tables([sky.tables] * pad)
        self.tables = jax.tree.map(
            lambda a, b: jnp.concatenate([a, b]), self.tables, pad_tables)
        self.state = jax.tree.map(
            lambda a, b: jnp.concatenate([a, b]), self.state,
            init_state_multi([sky.tables] * pad))
        self._bufs = jnp.concatenate(
            [self._bufs, jnp.zeros((pad, self._hist_len), jnp.int32)])
        self._alpha = jnp.concatenate(
            [self._alpha,
             jnp.broadcast_to(sky.alpha, (pad,) + sky.alpha.shape)])
        self._active = jnp.concatenate(
            [self._active, jnp.zeros((pad,), bool)])
        self._priority = jnp.concatenate(
            [self._priority, jnp.zeros((pad,), jnp.float32)])
        self._pending_q = np.concatenate(
            [self._pending_q, np.zeros(pad, np.float32)])
        self._pending_valid = np.concatenate(
            [self._pending_valid, np.zeros(pad, bool)])
        self._free.extend(range(self._cap, new_cap))
        self._tel.grow(new_cap)
        self._cap = new_cap

    # -- observability -------------------------------------------------
    def telemetry(self):
        """Snapshot of the pool's flight recorder (``repro.obs``'s
        ``Telemetry``) restricted to the ACTIVE streams in slot order,
        or None when constructed without one."""
        if not self._tel.record:
            return None
        return self._tel.snapshot(select=sorted(self._stream_of))

    def shed_stats(self) -> Dict[int, Dict]:
        """Per-stream shed accounting from the flight recorder:
        ``{stream_id: {segments, dropped, priority}}`` — retired
        streams keep the counters they accumulated while live."""
        out = {}
        if not self._tel.record:
            return out
        prio = np.asarray(self._priority)
        for slot in sorted(self._stream_of):
            sid = self._stream_of[slot]
            out[sid] = {
                "segments": float(self._tel.counters["seg_total"][slot]),
                "dropped": float(self._tel.counters["seg_dropped"][slot]),
                "priority": float(prio[slot]),
            }
        for sid, rec in self._retired_tel.items():
            out.setdefault(sid, dict(rec))
        return out

    # -- planning ------------------------------------------------------
    def _replan(self):
        """Refresh every slot's plan in ONE fused device call. Default:
        independent per-stream LPs (forecast -> LP, vmapped). With
        ``joint_plan=True``: one stacked priority-weighted LP under a
        shared pool budget (``capacity_core_s`` when set, else the
        per-stream budget times the active count)."""
        sky, tel = self.sky, self._tel
        budget = (sky.budget_override
                  if getattr(sky, "budget_override", None)
                  else sky.num_cores * sky.tau)
        use_model = tel.put(self._seen >= self._hist_len)
        centers = tel.put(sky.centers, jnp.float32)
        if self._joint_plan:
            total = (float(self.capacity_core_s)
                     if self.capacity_core_s is not None
                     else float(budget) * max(self.V, 1))
            self._alpha = _pool_replan_stacked(
                sky.forecaster, self._bufs, centers, sky.tables.cost,
                tel.put(total, jnp.float32), use_model, self._active,
                self._priority, n_split=sky.n_split,
                interval=sky.interval)
        else:
            self._alpha = _pool_replan(
                sky.forecaster, self._bufs, centers, sky.tables.cost,
                tel.put(budget, jnp.float32), use_model,
                n_split=sky.n_split, interval=sky.interval)
        tel.replans += 1

    # -- the tick ------------------------------------------------------
    def process(self, segments, arrival_mults: Optional[Sequence] = None):
        """One fused masked switch + shed decision, then per-stream
        Transform execution for the streams that were not shed.

        ``segments``: a length-V list in slot order (``pool.streams``
        gives the ids), or a ``{stream_id: segment}`` dict.
        ``arrival_mults`` likewise (list in slot order or dict).
        Returns ``(statuses, results)`` for the active streams in slot
        order; a dropped/shed stream's result is None.

        Each layer of the tick runs in a ``repro.obs`` span inside
        ``pool.tick`` (see the table there)."""
        sink_obs = self.sink.obs if self.sink is not None else None
        io0 = self._io(sink_obs)
        with span("pool.tick", t=self._seen) as tick_span:
            statuses, results = self._tick(segments, arrival_mults)
            pulls, uploads = (n - n0 for n, n0 in
                              zip(self._io(sink_obs), io0))
            tick_span.set_metadata(host_pulls=pulls, uploads=uploads)
        return statuses, results

    def _io(self, sink_obs):
        """(host pulls, uploads) so far, the pool's and its sink's."""
        tel = self._tel
        if sink_obs is None:
            return tel.host_pulls, tel.uploads
        return (tel.host_pulls + sink_obs["host_pulls"],
                tel.uploads + sink_obs["uploads"])

    def _tick(self, segments, arrival_mults):
        tel, t = self._tel, self._seen
        slots = sorted(self._stream_of)
        if isinstance(segments, dict):
            segs = [segments[self._stream_of[s]] for s in slots]
        else:
            assert len(segments) == len(slots), \
                f"need {len(slots)} segments (one per active stream)"
            segs = list(segments)
        K = len(self.sky.configs)
        arr_np = np.ones(self._cap, np.float32)
        if arrival_mults is not None:
            if isinstance(arrival_mults, dict):
                for sid, m in arrival_mults.items():
                    arr_np[self._slot_of[sid]] = m
            else:
                arr_np[np.asarray(slots)] = np.asarray(arrival_mults,
                                                       np.float32)
        with span("pool.dispatch", t=t) as sp:
            uploads = tel.uploads
            dummy = jnp.zeros((self._cap, K), jnp.float32)
            cap_op = tel.put(np.inf if self.capacity_core_s is None
                             else self.capacity_core_s, jnp.float32)
            wm_op = tel.put(np.inf if self.shed_watermark is None
                            else self.shed_watermark, jnp.float32)
            self.state, outs = _pool_tick(
                self.state, tel.put(self._pending_q),
                tel.put(self._pending_valid), dummy,
                tel.put(arr_np), self._active, self._priority,
                self._alpha, self.tables, cap_op, wm_op)
            self._bufs = _pool_shift(self._bufs, outs["c"])
            # async double-buffering: when this tick closes a planning
            # window, ENQUEUE the replan dispatch now — before the host
            # blocks on the decisions — so planning for window t+1
            # overlaps the Transform work of window t on the host
            if (self._seen + 1) % self.sky._plan_every == 0:
                with span("pool.replan", t=t, joint=self._joint_plan):
                    self._replan()
            sp.set_metadata(uploads=tel.uploads - uploads)
        with span("pool.pull", t=t, pulls=6):
            ks = tel.pull(outs["k"])
            cats = tel.pull(outs["c"])
            bufs_s = tel.pull(outs["buffer_s"])
            drops = tel.pull(outs["dropped"])
            sheds = tel.pull(outs["shed"])
            active_np = tel.pull(self._active)
        statuses, results = [], []
        q_np = np.zeros(self._cap, np.float32)
        q_valid = np.zeros(self._cap, bool)
        with span("pool.transform", t=t) as sp:
            for i, slot in enumerate(slots):
                k = int(ks[slot])
                status = {"stream_id": self._stream_of[slot],
                          "config": self.sky.configs[k], "k": k,
                          "category": int(cats[slot]),
                          "buffer_s": float(bufs_s[slot]),
                          "dropped": bool(drops[slot]),
                          "shed": bool(sheds[slot])}
                if drops[slot]:
                    # shed/dropped: the segment is NOT transformed (that
                    # is the work the shed saves); quality 0 by contract
                    status["quality"] = 0.0
                    results.append(None)
                else:
                    result, q = self.sky.proc_fn(segs[i],
                                                 self.sky.configs[k])
                    q_np[slot] = q
                    q_valid[slot] = True
                    status["quality"] = float(q)
                    results.append(result)
                statuses.append(status)
            n_done = int(q_valid.sum())
            sp.set_metadata(transformed=n_done,
                            dropped=len(slots) - n_done)
        with span("pool.recorder", t=t) as sp:
            sp.set_metadata(pulls=tel.update(outs, valid=active_np))
        # measured qualities fold into the NEXT tick's carried state
        # (inside the tick kernel — no extra dispatch)
        self._pending_q = q_np
        self._pending_valid = q_valid
        if self.sink is not None:
            # Load: the decision traces are already on device; the only
            # host-born values are the measured qualities themselves.
            # One row per ACTIVE stream, carrying its real stream id.
            with span("pool.load", t=t):
                ids = np.zeros(self._cap, np.int64)
                for slot in slots:
                    ids[slot] = self._stream_of[slot]
                q_dev = tel.put(q_np)
                out_vec = (jax.nn.one_hot(outs["k"], K, dtype=jnp.float32)
                           * q_dev[:, None])
            self.sink.ingest_tick(outs, quality=q_dev, out_vecs=out_vec,
                                  t=t, stream_ids=ids,
                                  valid=active_np)
            # the tick dispatch above already refreshed any registered
            # standing queries; surface the fired alert masks per tick
            from repro.core.ingest import _notify_standing
            self.alerts = _notify_standing(self.sink)
        self._seen += 1
        return statuses, results
