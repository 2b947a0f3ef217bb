"""Knob switcher (paper §4.2) — reactive, jit-compiled, O(µs)/decision.

Per segment:
 1. classify current content from the running config's reported quality
    (Eq. 5 — one KMeans dimension);
 2. pick the config with the largest planned-minus-actual usage deficit
    (Eq. 6);
 3. pick the cheapest placement that cannot overflow the buffer,
    recursively degrading to less-qualitative configs if necessary
    (vectorized here as a masked argmin instead of a loop).

The throughput guarantee: the cheapest config's all-on-prem placement is
validated real-time at fit(); it is always feasible, so the buffer can
never overflow.

Batched multi-stream engine (paper App. D): ``SwitchTables`` is a JAX
pytree, so V streams' tables stack leaf-wise into one table with a
leading (V,) axis (``stack_tables``) and the whole structure passes
straight through ``jax.jit`` / ``jax.vmap`` without field-unpacking.
``run_window_multi`` vmaps the per-segment decision over the stream axis
and drives all V streams through a SINGLE fused ``lax.scan`` — one
dispatch per window instead of V. ``run_window`` accepts an optional
validity mask so tail windows can be padded to a fixed length (masked
steps are exact no-ops), which keeps every window the same shape and
eliminates per-window recompiles.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, fields
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

BIG = jnp.int32(10 ** 6)


@dataclass
class SwitchTables:
    """Device-resident lookup tables the online switcher steps against
    (quality centers, cost/placement tables, rank order, thresholds) —
    a pytree so multi-stream code can ``stack_tables`` a batch."""
    centers: jnp.ndarray      # (C, K) mean quality of config k on category c
    power: jnp.ndarray        # (K,)
    cost: jnp.ndarray         # (K,) all-on-prem core-s / segment
    place_rt: jnp.ndarray     # (K, P) wall seconds / segment
    place_on: jnp.ndarray     # (K, P) on-prem core-s
    place_cl: jnp.ndarray     # (K, P) cloud core-s
    place_valid: jnp.ndarray  # (K, P) bool
    rank_pos: jnp.ndarray     # (K,) 0 = most qualitative
    tau: float                # segment seconds
    buffer_cap_s: float       # buffer size in seconds of video
    cloud_budget: float       # total cloud core-s for the run

    @property
    def n_categories(self):
        return self.centers.shape[0]

    @property
    def n_configs(self):
        return self.centers.shape[1]


_TABLE_FIELDS = tuple(f.name for f in fields(SwitchTables))


def _tables_flatten(t: SwitchTables):
    return tuple(getattr(t, n) for n in _TABLE_FIELDS), None


def _tables_unflatten(_, children):
    return SwitchTables(*children)


# Every field is a leaf (tau/buffer_cap_s/cloud_budget included), so
# tables stack per-stream — heterogeneous budgets become (V,) leaves —
# and the whole dataclass is a valid jit/vmap/scan argument.
jax.tree_util.register_pytree_node(SwitchTables, _tables_flatten,
                                   _tables_unflatten)


def stack_tables(tables: List[SwitchTables]) -> SwitchTables:
    """Stack V streams' tables leaf-wise onto a leading (V,) axis.
    Python-float scalar fields (tau etc.) stack to STRONGLY-typed f32
    leaves so carried table stacks round-trip through jitted admission
    edits with stable avals (no weak->strong recompiles)."""
    def stk(*xs):
        out = jnp.stack([jnp.asarray(x) for x in xs])
        return out.astype(out.dtype) if out.weak_type else out
    return jax.tree.map(stk, *tables)


def init_state(tables: SwitchTables) -> Dict:
    """Fresh per-stream switcher state (usage stats, buffer, cloud
    spend, current config = most qualitative)."""
    C, K = tables.centers.shape
    return {
        "used": jnp.zeros((C, K), jnp.float32),
        "count": jnp.zeros((C,), jnp.float32),
        "buffer_s": jnp.float32(0.0),
        "cloud_spent": jnp.float32(0.0),
        "k_cur": jnp.int32(int(jnp.argmin(tables.rank_pos))),
        "qual_prev": jnp.float32(1.0),
    }


def init_state_multi(tables: List[SwitchTables]) -> Dict:
    """Batched state for V streams: each leaf gains a leading (V,) axis."""
    return jax.tree.map(lambda *xs: jnp.stack(xs),
                        *[init_state(t) for t in tables])


def _pick(x, *idx):
    """``x[idx]`` for traced in-bounds indices (argmin/argmax results) as
    an explicit fill-mode gather: a plain scalar index lowers to
    ``dynamic_slice``, whose batching rule under ``vmap`` emits a CLIP
    gather."""
    return x.at[idx].get(mode="fill", fill_value=0)


def _switch(state, qual_row, arrival, alpha, tables: SwitchTables):
    """One knob-switching decision (pure function of pytrees; vmappable
    over a leading stream axis on every argument)."""
    tau = jnp.asarray(tables.tau, jnp.float32)
    cap = jnp.asarray(tables.buffer_cap_s, jnp.float32)
    cloud_budget = jnp.asarray(tables.cloud_budget, jnp.float32)
    # 1. classify from previous segment's reported quality (Eq. 5)
    col = jnp.take(tables.centers, state["k_cur"], axis=1)
    c = jnp.argmin(jnp.abs(col - state["qual_prev"]))
    # 2. usage-deficit pick (Eq. 6)
    frac = (_pick(state["used"], c)
            / jnp.maximum(_pick(state["count"], c), 1.0))
    k_next = jnp.argmax(_pick(alpha, c) - frac)
    # 3. placement feasibility
    rt_eff = tables.place_rt * arrival
    headroom = tau + (cap - state["buffer_s"])
    feas = (tables.place_valid
            & (rt_eff <= headroom)
            & (state["cloud_spent"] + tables.place_cl * arrival
               <= cloud_budget))
    feas_k = feas.any(axis=1)
    cl_masked = jnp.where(feas, tables.place_cl, jnp.inf)
    p_best = jnp.argmin(cl_masked, axis=1)                       # (K,)
    eligible = tables.rank_pos >= _pick(tables.rank_pos, k_next)
    cand = feas_k & eligible
    pos1 = jnp.where(cand, tables.rank_pos, BIG)
    pos2 = jnp.where(feas_k, tables.rank_pos, BIG)
    k_sel = jnp.where(cand.any(), jnp.argmin(pos1), jnp.argmin(pos2))
    p_sel = _pick(p_best, k_sel)
    # overload shedding: if NO config/placement fits (arrival spike above
    # peak provisioning), drop the segment — Eq. 1 must hold universally
    # (the streaming-ETL load-shedding fallback; quality 0 for the drop)
    any_feas = feas_k.any()
    rt = jnp.where(any_feas, _pick(rt_eff, k_sel, p_sel), 0.0)
    on_s = jnp.where(any_feas,
                     _pick(tables.place_on, k_sel, p_sel) * arrival, 0.0)
    cl_s = jnp.where(any_feas,
                     _pick(tables.place_cl, k_sel, p_sel) * arrival, 0.0)
    qual = jnp.where(any_feas, _pick(qual_row, k_sel), 0.0)
    new_state = {
        "used": state["used"].at[c, k_sel].add(1.0),
        "count": state["count"].at[c].add(1.0),
        "buffer_s": jnp.maximum(state["buffer_s"] + rt - tau, 0.0),
        "cloud_spent": state["cloud_spent"] + cl_s,
        "k_cur": k_sel.astype(jnp.int32),
        "qual_prev": qual,
    }
    out = {"k": k_sel, "p": p_sel, "c": c, "qual": qual, "on_s": on_s,
           "cl_s": cl_s, "buffer_s": new_state["buffer_s"], "rt": rt,
           "dropped": ~any_feas}
    return new_state, out


def _masked_switch(state, qual_row, arrival, valid, alpha,
                   tables: SwitchTables):
    """_switch, but a ``valid=False`` step is an exact no-op: state is
    untouched and every output is zeroed (padding segments contribute
    nothing to quality, work, or buffer)."""
    new_state, out = _switch(state, qual_row, arrival, alpha, tables)
    keep = jnp.asarray(valid, bool)
    new_state = jax.tree.map(
        lambda new, old: jnp.where(keep, new, old), new_state, state)
    zero = {"k": jnp.int32(0), "p": jnp.int32(0), "c": jnp.int32(0),
            "qual": jnp.float32(0.0), "on_s": jnp.float32(0.0),
            "cl_s": jnp.float32(0.0), "buffer_s": state["buffer_s"],
            "rt": jnp.float32(0.0), "dropped": jnp.asarray(False)}
    out = jax.tree.map(lambda o, z: jnp.where(keep, o, z), out, zero)
    return new_state, out


_switch_jit = jax.jit(_switch)
_switch_multi_jit = jax.jit(jax.vmap(_switch))


def switch_step(state, qual_row, arrival, alpha, tables: SwitchTables):
    """One knob-switching decision. qual_row (K,) = measured qualities of
    this segment (only qual_row[k_sel] is observed by the system). The
    tables pytree is passed straight to jit — no field unpacking."""
    return _switch_jit(state, qual_row, arrival, alpha, tables)


def switch_step_multi(state, qual_rows, arrivals, alpha,
                      tables: SwitchTables):
    """One batched decision for V live streams in a single dispatch:
    state from ``init_state_multi``, qual_rows (V,K), arrivals (V,),
    alpha (V,C,K), tables stacked via ``stack_tables``."""
    return _switch_multi_jit(state, qual_rows, arrivals, alpha, tables)


def window_scan(state, quals, arrivals, valid, alpha, tables):
    """Pure (un-jitted) window body: the masked-switch ``lax.scan`` over
    one planning window. Reusable INSIDE an outer scan — the fused
    whole-run engine (``ingest.run_skyscraper_fused``) inlines this as
    its per-window step, so forecast→plan→switch lowers to one program.
    """
    def body(st, inp):
        q_row, arr, v = inp
        return _masked_switch(st, q_row, arr, v, alpha, tables)

    return jax.lax.scan(body, state, (quals, arrivals, valid))


_run_window = jax.jit(window_scan)


def run_window(state, quals, arrivals, alpha, tables: SwitchTables,
               valid: Optional[jnp.ndarray] = None):
    """lax.scan over a planning window. quals (T,K); arrivals (T,);
    valid (T,) bool — False marks padding segments (exact no-ops).

    Top-level jitted: repeated windows of the same length compile once.
    """
    if valid is None:
        valid = jnp.ones(quals.shape[:1], bool)
    return _run_window(state, quals, arrivals, valid, alpha, tables)


def pad_window(quals, arrivals, W: int):
    """Pad a (T,K)/(T,) window to length W, returning (quals, arrivals,
    valid). With a fixed W every window — including the short tail —
    lowers to the same jaxpr, so the scan compiles exactly once."""
    T = quals.shape[0]
    if T == W:
        return quals, arrivals, jnp.ones((W,), bool)
    pad = W - T
    quals = jnp.pad(quals, ((0, pad), (0, 0)))
    arrivals = jnp.pad(arrivals, (0, pad), constant_values=1.0)
    valid = jnp.arange(W) < T
    return quals, arrivals, valid


def pad_window_multi(quals, arrivals, W: int):
    """Batched pad_window: quals (V,T,K), arrivals (V,T) -> padded to W
    along the time axis with a (V,W) validity mask."""
    V, T = arrivals.shape
    valid = jnp.broadcast_to(jnp.arange(W) < T, (V, W))
    if T == W:
        return quals, arrivals, valid
    pad = W - T
    quals = jnp.pad(quals, ((0, 0), (0, pad), (0, 0)))
    arrivals = jnp.pad(arrivals, ((0, 0), (0, pad)), constant_values=1.0)
    return quals, arrivals, valid


def window_scan_multi(state, quals, arrivals, valid, alpha, tables):
    """Pure (un-jitted) batched window body — reusable inside an outer
    scan (the fused multi-stream engine). vmaps the decision over the
    leading stream axis of EVERY pytree — batched state {used:(V,C,K),
    buffer_s:(V,), ...}, (V,C,K) alpha stack, and stacked tables — then
    scans once over time."""
    vstep = jax.vmap(_masked_switch)

    def body(st, inp):
        q_row, arr, v = inp                         # (V,K), (V,), (V,)
        return vstep(st, q_row, arr, v, alpha, tables)

    # scan iterates the leading axis: feed time-major (T,V,...) slices
    xs = (jnp.swapaxes(quals, 0, 1), jnp.swapaxes(arrivals, 0, 1),
          jnp.swapaxes(valid, 0, 1))
    state, outs = jax.lax.scan(body, state, xs)
    outs = jax.tree.map(lambda x: jnp.swapaxes(x, 0, 1), outs)  # (V,T,...)
    return state, outs


_run_window_multi = jax.jit(window_scan_multi)


def run_window_multi(state, quals, arrivals, alpha,
                     tables: SwitchTables,
                     valid: Optional[jnp.ndarray] = None):
    """Batched multi-stream window: ONE fused lax.scan executes all V
    streams' switch decisions per time step.

    state: batched pytree from ``init_state_multi`` (leading (V,) axis);
    quals (V,T,K); arrivals (V,T); alpha (V,C,K); tables stacked via
    ``stack_tables``; valid (V,T) bool marks padding (exact no-ops).
    Returns (batched state, outs with (V,T) leaves).
    """
    if valid is None:
        valid = jnp.ones(arrivals.shape, bool)
    return _run_window_multi(state, quals, arrivals, valid, alpha, tables)


def compile_cache_size() -> Tuple[int, int]:
    """(single-window, multi-window) jit cache entries — lets tests and
    benchmarks assert zero recompiles after warmup."""
    return _run_window._cache_size(), _run_window_multi._cache_size()


# Engine modules (fused ingest, serving pool) register their jitted
# entry points here so one probe covers every compiled program that
# could silently retrace.
_CACHE_PROBES = {
    "run_window": lambda: _run_window._cache_size(),
    "run_window_multi": lambda: _run_window_multi._cache_size(),
    "switch_step": lambda: _switch_jit._cache_size(),
    "switch_step_multi": lambda: _switch_multi_jit._cache_size(),
}


def register_cache_probe(name: str, probe) -> None:
    """Register a zero-arg callable reporting an engine's jit cache
    entry count under ``name`` in ``compile_cache_sizes()``."""
    _CACHE_PROBES[name] = probe


def compile_cache_sizes() -> Dict[str, int]:
    """Per-engine jit cache entry counts (a superset of
    ``compile_cache_size``): stable values across ticks/windows prove
    zero recompiles after warmup."""
    return {name: int(probe()) for name, probe in _CACHE_PROBES.items()}


# ---- static-analysis registry (see repro.analysis) -------------------------
from repro.analysis.registry import example_builder, register_engine  # noqa: E402

register_engine("switch_step", example_builder("switch_step"),
                probe=_CACHE_PROBES["switch_step"],
                covers=("repro.core.switcher:_switch_jit",),
                probe_name="switch_step")
register_engine("switch_step_multi", example_builder("switch_step_multi"),
                probe=_CACHE_PROBES["switch_step_multi"],
                covers=("repro.core.switcher:_switch_multi_jit",),
                probe_name="switch_step_multi")
register_engine("run_window", example_builder("run_window"),
                probe=_CACHE_PROBES["run_window"],
                covers=("repro.core.switcher:_run_window",),
                probe_name="run_window")
register_engine("run_window_multi", example_builder("run_window_multi"),
                probe=_CACHE_PROBES["run_window_multi"],
                covers=("repro.core.switcher:_run_window_multi",),
                probe_name="run_window_multi")
