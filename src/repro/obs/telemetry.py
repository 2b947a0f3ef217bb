"""On-device flight-recorder telemetry for the fused engines.

The stack's contract is a *throughput guarantee* (paper Eq. 1), but
every hot path is ONE fused dispatch — buffer occupancy, drops, cloud
spend and config churn are invisible between ingest and final state.
This module threads a fixed-shape ``tel`` counter pytree through the
carries of the existing scans so a run can report what happened
WITHOUT breaking the single-dispatch property:

- counters are float32 scalars (single-stream) or (V,) leaves (multi)
  accumulated SEQUENTIALLY in segment-time order inside the inner
  window scan — the same add order a host ``np.float32`` loop performs,
  so every counter is bit-exact against ``telemetry_ref``;
- padding steps are exact no-ops (``jnp.where(valid, ...)``), matching
  the masked-switch no-op contract;
- the outer scan snapshots the cumulative counters at every window
  boundary as extra ys, so per-window deltas are derived host-side for
  free (no extra dispatches, no host transfers inside the program).

``telemetry=True`` is a static flag on the fused engines: the
no-telemetry program traces to the EXACT pre-telemetry jaxpr, and the
telemetry variant is one additional jit cache entry (still one
dispatch per run) — the overhead contract the auditor pins.

Counter semantics (per stream; all float32):

    seg_total          valid segments executed
    seg_dropped        segments shed by overload (no feasible placement)
    buffer_hwm_s       high-water mark of post-segment buffer fill (s)
    buffer_occ_sum_s   sum of post-segment buffer fill (s) — divide by
                       seg_total for mean occupancy
    onprem_core_s      on-prem work accumulated (core-seconds)
    cloud_core_s       cloud work accumulated (core-seconds)
    config_switches    valid steps whose chosen config differs from the
                       previous step's (dropped segments still switch)
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

TEL_KEYS = ("seg_total", "seg_dropped", "buffer_hwm_s",
            "buffer_occ_sum_s", "onprem_core_s", "cloud_core_s",
            "config_switches")


# ---------------------------------------------------------------------------
# device side: counter pytree + telemetry-extended window scans
# ---------------------------------------------------------------------------

def tel_init(state) -> Dict[str, jnp.ndarray]:
    """Zeroed counter pytree shaped like the switcher state's
    ``buffer_s`` leaf (scalar single-stream, (V,) multi)."""
    z = jnp.zeros_like(state["buffer_s"])
    return {k: z for k in TEL_KEYS}


def tel_step(tel, k_prev, out, valid):
    """One segment's counter update. ``k_prev`` is the PRE-step
    ``k_cur``; ``out`` is the switch-step outs dict; ``valid=False``
    leaves every counter untouched (exact no-op). All adds are single
    float32 ops in carry order — the host mirror replays them exactly."""
    keep = jnp.asarray(valid, bool)

    def add(cur, x):
        return jnp.where(keep, cur + x, cur)

    one = jnp.float32(1.0)
    return {
        "seg_total": add(tel["seg_total"], one),
        "seg_dropped": add(tel["seg_dropped"],
                           out["dropped"].astype(jnp.float32)),
        "buffer_hwm_s": jnp.where(
            keep, jnp.maximum(tel["buffer_hwm_s"], out["buffer_s"]),
            tel["buffer_hwm_s"]),
        "buffer_occ_sum_s": add(tel["buffer_occ_sum_s"], out["buffer_s"]),
        "onprem_core_s": add(tel["onprem_core_s"], out["on_s"]),
        "cloud_core_s": add(tel["cloud_core_s"], out["cl_s"]),
        "config_switches": add(
            tel["config_switches"],
            (out["k"] != k_prev).astype(jnp.float32)),
    }


def masked_switch_tel(carry, qual_row, arrival, valid, alpha, tables):
    """``_masked_switch`` with the telemetry carry alongside the state."""
    # deferred: core.ingest imports this module, so importing the
    # switcher at module scope would close an import cycle
    from repro.core.switcher import _masked_switch
    state, tel = carry
    k_prev = state["k_cur"]
    new_state, out = _masked_switch(state, qual_row, arrival, valid,
                                    alpha, tables)
    return (new_state, tel_step(tel, k_prev, out, valid)), out


def window_scan_tel(state, tel, quals, arrivals, valid, alpha, tables):
    """``switcher.window_scan`` + telemetry carry (pure; inlined by the
    fused engine's outer scan when ``telemetry=True``)."""
    def body(carry, inp):
        q_row, arr, v = inp
        return masked_switch_tel(carry, q_row, arr, v, alpha, tables)

    return jax.lax.scan(body, (state, tel), (quals, arrivals, valid))


def window_scan_multi_tel(state, tel, quals, arrivals, valid, alpha,
                          tables):
    """``switcher.window_scan_multi`` + per-stream telemetry carry:
    the decision AND its counter update vmap over the leading stream
    axis of every pytree, then one scan over time."""
    def step(st, tl, q_row, arr, v, al, tb):
        (st, tl), out = masked_switch_tel((st, tl), q_row, arr, v, al, tb)
        return st, tl, out

    vstep = jax.vmap(step)

    def body(carry, inp):
        st, tl = carry
        q_row, arr, v = inp                         # (V,K), (V,), (V,)
        st, tl, out = vstep(st, tl, q_row, arr, v, alpha, tables)
        return (st, tl), out

    xs = (jnp.swapaxes(quals, 0, 1), jnp.swapaxes(arrivals, 0, 1),
          jnp.swapaxes(valid, 0, 1))
    (state, tel), outs = jax.lax.scan(body, (state, tel), xs)
    outs = jax.tree.map(lambda x: jnp.swapaxes(x, 0, 1), outs)
    return (state, tel), outs


# ---------------------------------------------------------------------------
# host side: run telemetry container + numpy mirror
# ---------------------------------------------------------------------------

@dataclass
class Telemetry:
    """Flight-recorder counters of one run (host-side container).

    ``counters`` holds the FINAL cumulative float32 values (scalars
    single-stream, (V,) arrays multi); ``per_window`` the cumulative
    window-boundary snapshots ((n_w,) / (n_w, V) arrays) the outer scan
    emitted; ``extras`` carries engine-specific host-side counts (pool
    ticks, replans). The raw counters are the bit-exactness contract —
    derived views (means, deltas) are computed here, on host, for
    display only."""
    counters: Dict[str, np.ndarray]
    per_window: Dict[str, np.ndarray] = field(default_factory=dict)
    extras: Dict[str, float] = field(default_factory=dict)

    @classmethod
    def from_device(cls, tel_windows) -> "Telemetry":
        """From the fused engine's stacked per-window snapshots
        ((n_w, ...) leaves): final row = end-of-run cumulative values."""
        per_window = {k: np.asarray(v) for k, v in tel_windows.items()}
        counters = {k: v[-1] for k, v in per_window.items()}
        return cls(counters=counters, per_window=per_window)

    # -- derived views (display only; not part of the exactness contract)
    @property
    def segments(self) -> float:
        return float(np.sum(self.counters["seg_total"]))

    @property
    def dropped(self) -> float:
        return float(np.sum(self.counters["seg_dropped"]))

    @property
    def buffer_hwm_s(self) -> float:
        return float(np.max(self.counters["buffer_hwm_s"]))

    @property
    def buffer_occ_mean_s(self) -> float:
        n = np.sum(self.counters["seg_total"])
        return float(np.sum(self.counters["buffer_occ_sum_s"])
                     / max(n, 1.0))

    @property
    def onprem_core_s(self) -> float:
        return float(np.sum(self.counters["onprem_core_s"]))

    @property
    def cloud_core_s(self) -> float:
        return float(np.sum(self.counters["cloud_core_s"]))

    @property
    def config_switches(self) -> float:
        return float(np.sum(self.counters["config_switches"]))

    def window_deltas(self) -> Dict[str, np.ndarray]:
        """Per-window deltas of the monotone counters (the gauges —
        ``buffer_hwm_s`` — stay cumulative)."""
        out = {}
        for k, v in self.per_window.items():
            if k == "buffer_hwm_s":
                out[k] = v.copy()
            else:
                out[k] = np.diff(v, axis=0, prepend=np.zeros_like(v[:1]))
        return out

    def summary(self) -> str:
        return (f"segments={self.segments:.0f} "
                f"dropped={self.dropped:.0f} "
                f"buffer_hwm={self.buffer_hwm_s:.1f}s "
                f"occ_mean={self.buffer_occ_mean_s:.2f}s "
                f"onprem={self.onprem_core_s:.0f}core-s "
                f"cloud={self.cloud_core_s:.0f}core-s "
                f"switches={self.config_switches:.0f}")


def _accumulate(counters: Dict[str, np.ndarray], k_prev: np.ndarray,
                k, dropped, buffer_s, on_s, cl_s, valid) -> np.ndarray:
    """One segment-time step of the float32 mirror, vectorized over the
    stream axis. Mutates ``counters`` in place; returns the new
    ``k_prev``. Each update is ONE float32 add/max per stream in the
    same order as the device carry — bit-exact by construction."""
    v = np.asarray(valid, bool)
    f32 = np.float32
    counters["seg_total"] = np.where(
        v, (counters["seg_total"] + f32(1.0)).astype(f32),
        counters["seg_total"])
    counters["seg_dropped"] = np.where(
        v, (counters["seg_dropped"]
            + np.asarray(dropped, f32)).astype(f32),
        counters["seg_dropped"])
    counters["buffer_hwm_s"] = np.where(
        v, np.maximum(counters["buffer_hwm_s"],
                      np.asarray(buffer_s, f32)),
        counters["buffer_hwm_s"])
    counters["buffer_occ_sum_s"] = np.where(
        v, (counters["buffer_occ_sum_s"]
            + np.asarray(buffer_s, f32)).astype(f32),
        counters["buffer_occ_sum_s"])
    counters["onprem_core_s"] = np.where(
        v, (counters["onprem_core_s"] + np.asarray(on_s, f32)).astype(f32),
        counters["onprem_core_s"])
    counters["cloud_core_s"] = np.where(
        v, (counters["cloud_core_s"] + np.asarray(cl_s, f32)).astype(f32),
        counters["cloud_core_s"])
    counters["config_switches"] = np.where(
        v, (counters["config_switches"]
            + (np.asarray(k) != k_prev).astype(f32)).astype(f32),
        counters["config_switches"])
    return np.where(v, np.asarray(k, np.int64), k_prev)


def telemetry_ref(traces: Dict[str, np.ndarray], k0,
                  valid: Optional[np.ndarray] = None
                  ) -> Dict[str, np.ndarray]:
    """Numpy float32 mirror of the device counters: replay the run's
    per-segment traces in time order with sequential float32
    accumulation. ``traces`` needs keys ``k``, ``dropped``,
    ``buffer_s``, ``on_s``, ``cl_s`` with (T,) (single-stream) or
    (V, T) (multi) leaves; ``k0`` is the initial ``k_cur`` (the
    switcher starts on the most qualitative config —
    ``argmin(rank_pos)``). Returns the counter dict the device
    telemetry must match BIT-EXACTLY."""
    k = np.asarray(traces["k"])
    single = k.ndim == 1
    def twod(x):
        a = np.asarray(x)
        return a[None] if single else a
    k = twod(traces["k"])
    dropped = twod(traces["dropped"])
    buf = twod(traces["buffer_s"]).astype(np.float32)
    on = twod(traces["on_s"]).astype(np.float32)
    cl = twod(traces["cl_s"]).astype(np.float32)
    V, T = k.shape
    if valid is None:
        vmask = np.ones((V, T), bool)
    else:
        vmask = twod(valid).astype(bool)
    counters = {key: np.zeros((V,), np.float32) for key in TEL_KEYS}
    k_prev = np.broadcast_to(np.asarray(k0, np.int64), (V,)).copy()
    for t in range(T):
        k_prev = _accumulate(counters, k_prev, k[:, t], dropped[:, t],
                             buf[:, t], on[:, t], cl[:, t], vmask[:, t])
    if single:
        counters = {key: v[0] for key, v in counters.items()}
    return counters


class HostTelemetry:
    """The serving pool's host counters and flight recorder.

    The integer tallies count whether or not the recorder is on:
    ``ticks``, ``replans``, ``host_pulls`` (blocking device→host reads
    of the tick path) and ``uploads`` (host→device puts), counted where
    the pool pulls and puts (``pull`` / ``put``).

    With ``record=True`` it is also a sequential float32 accumulator
    over per-tick switch outs. Updates happen host-side from arrays the
    pool already materializes each tick, so telemetry adds ZERO device
    dispatches (its extra reads are transfers of already-computed
    outputs, not new programs, and count in ``host_pulls``)."""

    def __init__(self, n_streams: int, k0: int, record: bool = True):
        self.V = int(n_streams)
        self.k0 = int(k0)
        self.record = bool(record)
        self.counters = {k: np.zeros((self.V,), np.float32)
                         for k in TEL_KEYS} if record else {}
        self._k_prev = np.full((self.V,), int(k0), np.int64)
        self.ticks = 0
        self.replans = 0
        self.host_pulls = 0
        self.uploads = 0

    def pull(self, x) -> np.ndarray:
        """``np.asarray`` of a device value, counted as one host pull."""
        self.host_pulls += 1
        return np.asarray(x)

    def put(self, x, dtype=None) -> jnp.ndarray:
        """``jnp.asarray``, counted as one upload when ``x`` is on the
        host."""
        self.uploads += not isinstance(x, jax.Array)
        return jnp.asarray(x, dtype)

    def update(self, outs, valid=None) -> int:
        """One pool tick: ``outs`` is the ``switch_step_multi`` outs
        dict ((V,) leaves, device or host). ``valid`` (V,) bool masks
        slots that took no step this tick (the elastic pool's
        retired/empty slots) — their counters are untouched, matching
        the fused engines' masked-step no-op contract. Without
        ``record`` only the tick is counted. Returns the host pulls it
        made: ``on_s`` and ``cl_s`` (the pool has already pulled ``k``,
        ``dropped`` and ``buffer_s``, whose host copies are cached)."""
        self.ticks += 1
        if not self.record:
            return 0
        pulls = self.host_pulls
        self._k_prev = _accumulate(
            self.counters, self._k_prev, np.asarray(outs["k"]),
            np.asarray(outs["dropped"]), np.asarray(outs["buffer_s"]),
            self.pull(outs["on_s"]), self.pull(outs["cl_s"]),
            np.ones((self.V,), bool) if valid is None
            else np.asarray(valid, bool))
        return self.host_pulls - pulls

    def grow(self, n_streams: int) -> None:
        """Widen the stream axis to ``n_streams`` slots (elastic-pool
        bucket growth); existing counters are preserved, new slots
        start zeroed with ``k_prev = k0``."""
        n = int(n_streams)
        if n <= self.V:
            return
        pad = n - self.V
        self.counters = {k: np.concatenate(
            [v, np.zeros((pad,), np.float32)])
            for k, v in self.counters.items()}
        self._k_prev = np.concatenate(
            [self._k_prev, np.full((pad,), self.k0, np.int64)])
        self.V = n

    def reset_slot(self, v: int) -> None:
        """Zero one slot's counters (a retired slot being re-admitted
        for a different stream starts a fresh accumulation)."""
        for arr in self.counters.values():
            arr[v] = np.float32(0.0)
        self._k_prev[v] = self.k0

    def snapshot(self, select=None) -> Telemetry:
        """Counter snapshot; ``select`` (slot indices) restricts the
        stream axis (the elastic pool passes its active slots)."""
        if select is None:
            counters = {k: v.copy() for k, v in self.counters.items()}
        else:
            idx = np.asarray(select, np.int64)
            counters = {k: v[idx].copy()
                        for k, v in self.counters.items()}
        return Telemetry(
            counters=counters,
            extras={"ticks": float(self.ticks),
                    "replans": float(self.replans),
                    "host_pulls": float(self.host_pulls),
                    "uploads": float(self.uploads)})


# ---------------------------------------------------------------------------
# warehouse: ingest-to-queryable lag + shard balance (host metadata only)
# ---------------------------------------------------------------------------

@dataclass
class StoreTelemetry:
    """Warehouse-side observability, computed ENTIRELY from host
    metadata the store already tracks (per-shard row counts, batch
    shapes) — zero extra dispatches, zero device reads.

    Ingest-to-queryable lag is measured in ticks (segment slots): a row
    ingested as part of a T-segment fused batch became queryable when
    the batch landed, so a row with in-batch timeline offset ``t`` waited
    ``T - 1 - t`` ticks; per-tick ingest is lag 0. This is the Fluid-ETL
    freshness metric: fused whole-run loads trade T/2 mean lag for
    throughput, the serving pool's tick ingest is lag-free."""
    rows_by_shard: np.ndarray
    ingest_dispatches: int = 0
    query_dispatches: int = 0
    lag_rows: int = 0
    lag_sum_ticks: int = 0
    lag_max_ticks: int = 0
    spill_events: int = 0
    spilled_rows: int = 0
    dequantize_events: int = 0
    # standing-query registry (warehouse.standing): registered plans,
    # how many ingest dispatches also refreshed them (lag-0 freshness —
    # a refresh IS the ingest), and the alert subscriptions' activity
    standing_queries: int = 0
    standing_refreshes: int = 0
    alerts_checked: int = 0
    alerts_fired: int = 0
    # host<->device transfers of the tick ingest and the alert poll
    host_pulls: int = 0
    uploads: int = 0

    @property
    def n_rows(self) -> int:
        return int(np.sum(self.rows_by_shard))

    @property
    def imbalance(self) -> float:
        """max-shard rows / mean-shard rows (1.0 = perfectly balanced;
        n_shards = everything on one shard; 0 rows reports 1.0)."""
        total = int(np.sum(self.rows_by_shard))
        if total == 0:
            return 1.0
        mean = total / len(self.rows_by_shard)
        return float(np.max(self.rows_by_shard) / mean)

    @property
    def lag_mean_ticks(self) -> float:
        return self.lag_sum_ticks / max(self.lag_rows, 1)

    def summary(self) -> str:
        return (f"rows={self.n_rows} shards={len(self.rows_by_shard)} "
                f"imbalance={self.imbalance:.2f} "
                f"lag_mean={self.lag_mean_ticks:.1f}t "
                f"lag_max={self.lag_max_ticks}t "
                f"ingests={self.ingest_dispatches} "
                f"queries={self.query_dispatches} "
                f"spills={self.spill_events} "
                f"dequantizes={self.dequantize_events} "
                f"standing={self.standing_queries} "
                f"refreshes={self.standing_refreshes} "
                f"alerts={self.alerts_fired}/{self.alerts_checked}")


def store_obs_init() -> Dict[str, int]:
    """Fresh host-side counter dict for a store instance."""
    return {"ingest_dispatches": 0, "query_dispatches": 0,
            "lag_rows": 0, "lag_sum_ticks": 0, "lag_max_ticks": 0,
            "standing_queries": 0, "standing_refreshes": 0,
            "alerts_checked": 0, "alerts_fired": 0,
            "host_pulls": 0, "uploads": 0}


def store_put(obs: Dict[str, int], x, dtype=None) -> jnp.ndarray:
    """``jnp.asarray`` for a store kernel operand, counted in
    ``obs["uploads"]`` when ``x`` is on the host."""
    obs["uploads"] += not isinstance(x, jax.Array)
    return jnp.asarray(x, dtype)


def store_obs_batch(obs: Dict[str, int], n_streams: int, T: int) -> None:
    """Record one fused-batch ingest: ``n_streams`` streams of ``T``
    sequential segments became queryable together, so per stream the
    lag over its rows is 0..T-1 (sum T*(T-1)/2, max T-1)."""
    obs["ingest_dispatches"] += 1
    obs["lag_rows"] += n_streams * T
    obs["lag_sum_ticks"] += n_streams * (T * (T - 1) // 2)
    obs["lag_max_ticks"] = max(obs["lag_max_ticks"], T - 1)


def store_obs_tick(obs: Dict[str, int], n_rows: int) -> None:
    """Record one per-tick ingest: rows are queryable the tick they
    land — lag 0."""
    obs["ingest_dispatches"] += 1
    obs["lag_rows"] += n_rows
