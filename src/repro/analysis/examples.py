"""Tiny deterministic example inputs for every registered engine.

Engine modules register lazy builders that call into here (the import
happens inside the builder, never at engine-module import time, so
there are no cycles and registering costs nothing until the auditor
runs). Shapes are deliberately small — each example traces and compiles
in well under a second on CPU — but structurally faithful: the same
static arguments, pytree layouts, and dtypes as production calls, so
the jaxpr/HLO the auditor sees is the real program at toy size.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.registry import EngineExample

K, C, P, V = 4, 3, 3, 2          # configs, categories, placements, streams
W, T, N_W = 6, 10, 2             # window len, run len, windows per run
N_SPLIT, INTERVAL = 2, 3         # forecaster history layout
OUT_DIM, CAP = 4, 64             # warehouse embedding width / capacity
N_SHARDS = 2


def demo_tables(seed: int = 0, tau: float = 2.0, cap: float = 30.0,
                cloud: float = 50.0, n_cores: int = 4):
    from repro.core.switcher import SwitchTables
    rng = np.random.default_rng(seed)
    power = np.sort(rng.random(K)).astype(np.float32)
    cost = np.sort(rng.random(K) * 20 + 0.5).astype(np.float32)
    cost[0] = min(cost[0], tau * n_cores * 0.9)
    centers = np.sort(rng.random((C, K)), axis=0).astype(np.float32)
    rt = np.stack([cost / n_cores, cost / n_cores * 0.6,
                   cost / n_cores * 0.3], 1)
    cl = np.stack([np.zeros(K), cost * 0.4, cost * 0.7], 1)
    on = np.stack([cost, cost * 0.6, cost * 0.3], 1)
    return SwitchTables(
        centers=jnp.asarray(centers), power=jnp.asarray(power),
        cost=jnp.asarray(cost), place_rt=jnp.asarray(rt, jnp.float32),
        place_on=jnp.asarray(on, jnp.float32),
        place_cl=jnp.asarray(cl, jnp.float32),
        place_valid=jnp.ones((K, P), bool),
        rank_pos=jnp.asarray(np.argsort(np.argsort(-power)), jnp.int32),
        tau=tau, buffer_cap_s=cap, cloud_budget=cloud)


def _alpha(rng):
    a = rng.random((C, K)).astype(np.float32)
    return jnp.asarray(a / a.sum(1, keepdims=True))


def _quals(rng, *shape):
    return jnp.asarray(rng.random(shape + (K,)), jnp.float32)


# ---- switcher --------------------------------------------------------------

def switch_step():
    from repro.core.switcher import _switch_jit, init_state
    rng = np.random.default_rng(0)
    t = demo_tables()
    return EngineExample(_switch_jit,
                         (init_state(t), _quals(rng), jnp.float32(1.2),
                          _alpha(rng), t), {})


def switch_step_multi():
    from repro.core.switcher import (_switch_multi_jit, init_state_multi,
                                     stack_tables)
    rng = np.random.default_rng(0)
    ts = [demo_tables(seed=s) for s in range(V)]
    alpha = jnp.stack([_alpha(rng) for _ in range(V)])
    return EngineExample(_switch_multi_jit,
                         (init_state_multi(ts), _quals(rng, V),
                          jnp.ones((V,), jnp.float32), alpha,
                          stack_tables(ts)), {})


def run_window():
    from repro.core.switcher import _run_window, init_state
    rng = np.random.default_rng(0)
    t = demo_tables()
    return EngineExample(_run_window,
                         (init_state(t), _quals(rng, W),
                          jnp.ones((W,), jnp.float32), jnp.ones((W,), bool),
                          _alpha(rng), t), {})


def run_window_multi():
    from repro.core.switcher import (_run_window_multi, init_state_multi,
                                     stack_tables)
    rng = np.random.default_rng(0)
    ts = [demo_tables(seed=s) for s in range(V)]
    alpha = jnp.stack([_alpha(rng) for _ in range(V)])
    return EngineExample(_run_window_multi,
                         (init_state_multi(ts), _quals(rng, V, W),
                          jnp.ones((V, W), jnp.float32),
                          jnp.ones((V, W), bool), alpha,
                          stack_tables(ts)), {})


# ---- fused ingestion engines ----------------------------------------------

def _windowed(rng):
    """(quals_w, arrs_w, valid_w, wts, fracs) for a T-segment run."""
    from repro.core.ingest import _window_layout
    n_w, pad, wts, fracs = _window_layout(T, W)
    quals = _quals(rng, T)
    quals_w = jnp.pad(quals, ((0, pad), (0, 0))).reshape(n_w, W, K)
    arrs_w = jnp.ones((n_w, W), jnp.float32)
    valid_w = (jnp.arange(n_w * W) < T).reshape(n_w, W)
    return quals_w, arrs_w, valid_w, jnp.asarray(wts), jnp.asarray(fracs)


def fused_single():
    from repro.core.forecaster import init_forecaster
    from repro.core.ingest import _fused_run
    from repro.core.switcher import init_state
    rng = np.random.default_rng(0)
    t = demo_tables()
    quals_w, arrs_w, valid_w, wts, fracs = _windowed(rng)
    params = init_forecaster(jax.random.PRNGKey(0), N_SPLIT, C)
    return EngineExample(
        _fused_run,
        (init_state(t), jnp.zeros((N_SPLIT * INTERVAL,), jnp.int32),
         quals_w, arrs_w, valid_w, wts, fracs, t, t.centers, t.cost,
         params, jnp.float32(8.0), jnp.float32(50.0)),
        {"mode": "model", "n_split": N_SPLIT, "interval": INTERVAL})


def fused_multi():
    from repro.core.ingest import _fused_run_multi
    from repro.core.switcher import init_state_multi, stack_tables
    rng = np.random.default_rng(0)
    ts = [demo_tables(seed=s) for s in range(V)]
    quals_w = jnp.asarray(rng.random((N_W, V, W, K)), jnp.float32)
    arrs_w = jnp.ones((N_W, V, W), jnp.float32)
    valid_w = jnp.broadcast_to((jnp.arange(N_W * W) < T).reshape(N_W, 1, W),
                               (N_W, V, W))
    wts = jnp.asarray(np.minimum(W, T - np.arange(N_W) * W), jnp.int32)
    return EngineExample(
        _fused_run_multi,
        (init_state_multi(ts), quals_w, arrs_w, valid_w, wts,
         stack_tables(ts), ts[0].cost, jnp.float32(16.0),
         jnp.float32(0.5)),
        {"with_traces": True})


def fused_single_telemetry():
    """``fused_single`` with the flight recorder threaded through the
    carry — the auditor proves the telemetry variant is still one
    executable with no host transfers."""
    ex = fused_single()
    return EngineExample(ex.fn, ex.args, dict(ex.kwargs, telemetry=True))


def fused_multi_telemetry():
    ex = fused_multi()
    return EngineExample(ex.fn, ex.args, dict(ex.kwargs, telemetry=True))


# ---- serving pool ----------------------------------------------------------

def pool_replan():
    from repro.core.api import _pool_replan
    rng = np.random.default_rng(0)
    from repro.core.forecaster import init_forecaster
    params = init_forecaster(jax.random.PRNGKey(0), N_SPLIT, C)
    bufs = jnp.asarray(rng.integers(0, C, (V, N_SPLIT * INTERVAL)),
                       jnp.int32)
    centers = jnp.asarray(np.sort(rng.random((C, K)), axis=0), jnp.float32)
    cost = jnp.asarray(np.sort(rng.random(K) * 10 + 0.5), jnp.float32)
    return EngineExample(
        _pool_replan,
        (params, bufs, centers, cost, jnp.float32(8.0),
         jnp.asarray(True)),
        {"n_split": N_SPLIT, "interval": INTERVAL})


def pool_shift():
    from repro.core.api import _pool_shift
    bufs = jnp.zeros((V, N_SPLIT * INTERVAL), jnp.int32)
    return EngineExample(_pool_shift,
                         (bufs, jnp.ones((V,), jnp.int32)), {})


def pool_replan_stacked():
    from repro.core.api import _pool_replan_stacked
    ex = pool_replan()
    params, bufs, centers, cost, budget, use_model = ex.args
    return EngineExample(
        _pool_replan_stacked,
        (params, bufs, centers, cost, budget, use_model,
         jnp.ones((V,), bool), jnp.ones((V,), jnp.float32)),
        dict(ex.kwargs))


def pool_tick():
    from repro.core.api import _pool_tick
    from repro.core.switcher import init_state_multi, stack_tables
    rng = np.random.default_rng(0)
    ts = [demo_tables(seed=s) for s in range(V)]
    alpha = jnp.stack([_alpha(rng) for _ in range(V)])
    return EngineExample(
        _pool_tick,
        (init_state_multi(ts), jnp.ones((V,), jnp.float32),
         jnp.ones((V,), bool), _quals(rng, V),
         jnp.ones((V,), jnp.float32), jnp.ones((V,), bool),
         jnp.ones((V,), jnp.float32), alpha, stack_tables(ts),
         jnp.float32(np.inf), jnp.float32(np.inf)), {})


def pool_admit():
    from repro.core.api import _pool_admit
    from repro.core.switcher import init_state_multi, stack_tables
    rng = np.random.default_rng(0)
    ts = [demo_tables(seed=s) for s in range(V)]
    alpha = jnp.stack([_alpha(rng) for _ in range(V)])
    bufs = jnp.zeros((V, N_SPLIT * INTERVAL), jnp.int32)
    return EngineExample(
        _pool_admit,
        (stack_tables(ts), init_state_multi(ts), bufs, alpha,
         jnp.zeros((V,), bool), jnp.zeros((V,), jnp.float32),
         jnp.int32(0), jnp.float32(1.0), ts[0], _alpha(rng)), {})


def pool_retire():
    from repro.core.api import _pool_retire
    return EngineExample(_pool_retire,
                         (jnp.ones((V,), bool), jnp.int32(0)), {})


# ---- forecaster / categories / planner -------------------------------------

def adam_step():
    from repro.core.forecaster import _adam_step, init_forecaster
    params = init_forecaster(jax.random.PRNGKey(0), N_SPLIT, C)
    opt = {"m": jax.tree.map(jnp.zeros_like, params),
           "v": jax.tree.map(jnp.zeros_like, params), "t": jnp.int32(0)}
    rng = np.random.default_rng(0)
    X = jnp.asarray(rng.random((8, N_SPLIT, C)), jnp.float32)
    Y = jnp.asarray(rng.random((8, C)), jnp.float32)
    return EngineExample(_adam_step, (params, opt, X, Y,
                                      jnp.float32(3e-3)), {})


def lloyd_step():
    from repro.core.categories import _lloyd_step
    rng = np.random.default_rng(0)
    centers = jnp.asarray(rng.random((C, K)), jnp.float32)
    Q = jnp.asarray(rng.random((20, K)), jnp.float32)
    return EngineExample(_lloyd_step, (centers, Q), {})


def classify_full():
    from repro.core.categories import classify_full as fn
    rng = np.random.default_rng(0)
    return EngineExample(fn, (jnp.asarray(rng.random(K), jnp.float32),
                              jnp.asarray(rng.random((C, K)),
                                          jnp.float32)), {})


def classify_1d():
    from repro.core.categories import classify_1d as fn
    rng = np.random.default_rng(0)
    return EngineExample(fn, (jnp.float32(0.5), jnp.int32(1),
                              jnp.asarray(rng.random((C, K)),
                                          jnp.float32)), {})


def lp_lagrangian():
    from repro.core.planner import solve_lp_lagrangian
    rng = np.random.default_rng(0)
    qual = jnp.asarray(np.sort(rng.random((C, K)), axis=0), jnp.float32)
    cost = jnp.asarray(np.sort(rng.random(K) * 10 + 0.5), jnp.float32)
    r = jnp.full((C,), 1.0 / C, jnp.float32)
    return EngineExample(solve_lp_lagrangian,
                         (qual, cost, r, jnp.float32(4.0)), {})


# ---- warehouse: query engines ----------------------------------------------

def _store_cols(stacked: bool = False):
    from repro.warehouse.store import _empty_columns
    cols = _empty_columns(CAP, OUT_DIM)
    if stacked:
        cols = {k: jnp.broadcast_to(v[None], (N_SHARDS,) + v.shape)
                for k, v in cols.items()}
    return cols


def _plan(kind: str):
    from repro.warehouse.query import (Filter, GroupBy, MultiGroupBy, TopK,
                                       WindowAgg)
    if kind == "filter_groupby":
        return (Filter("quality", "ge", 0.25),
                GroupBy("category", "quality", agg="mean", num_groups=C))
    if kind == "window_sum":
        return (WindowAgg(window=4, value="on_core_s", agg="sum",
                          num_windows=8),)
    if kind == "multi_topk":
        return (MultiGroupBy(keys=("t", "category"), value="quality",
                             agg="sum", nums=(8, C), windows=(4, 0)),
                TopK(5, "quality"))
    if kind == "topk":
        return (Filter("t", "lt", 48), TopK(5, "quality"))
    if kind == "group_max":
        # int-column filter + max agg: the fused kernel's in-register
        # int_pred path and ∓inf-sentinel accumulator path
        return (Filter("k", "gt", 0.5),
                GroupBy("category", "quality", agg="max", num_groups=C))
    raise ValueError(kind)


def query(kind: str):
    from repro.warehouse.query import _run_plan, normalize
    spec, fvals = normalize(_plan(kind))
    return EngineExample(_run_plan,
                         (_store_cols(), jnp.int32(50), fvals),
                         {"spec": spec})


def query_pallas(kind: str):
    """Same plans as ``query`` but through the fused Pallas
    filter+group+aggregate kernel (interpret mode on CPU) — the
    auditor's scatter census over these engines is the
    scatter-floor-broken proof (0 executed scatters)."""
    from repro.warehouse.query import _run_plan, normalize
    spec, fvals = normalize(_plan(kind))
    return EngineExample(_run_plan,
                         (_store_cols(), jnp.int32(50), fvals),
                         {"spec": spec, "use_pallas": True})


def query_sharded(kind: str, use_pallas: bool = False):
    from repro.launch.mesh import make_shard_mesh
    from repro.warehouse.query import _sharded_kernel, normalize
    spec, fvals = normalize(_plan(kind))
    kern = _sharded_kernel(make_shard_mesh(N_SHARDS), N_SHARDS)
    n_valid = jnp.asarray([50, 40], jnp.int32)
    return EngineExample(kern,
                         (_store_cols(stacked=True), n_valid, fvals,
                          jax.random.PRNGKey(0)),
                         {"spec": spec, "compressed": False,
                          "use_pallas": bool(use_pallas)})


# ---- warehouse: ingest engines ---------------------------------------------

def _traces(*lead):
    rng = np.random.default_rng(0)
    tr = {}
    for src, dt in (("c", jnp.int32), ("k", jnp.int32),
                    ("qual", jnp.float32), ("on_s", jnp.float32),
                    ("cl_s", jnp.float32), ("buffer_s", jnp.float32)):
        x = rng.integers(0, C, lead) if dt == jnp.int32 \
            else rng.random(lead)
        tr[src] = jnp.asarray(x, dt)
    return tr


def store_scatter():
    from repro.warehouse.store import OUT_COLUMN, SCALAR_COLUMNS, _scatter
    n = 5
    upd = {name: jnp.zeros((n,), dt) for name, dt in SCALAR_COLUMNS}
    upd[OUT_COLUMN] = jnp.zeros((n, OUT_DIM), jnp.float32)
    return EngineExample(_scatter, (_store_cols(), upd, jnp.int32(0)), {})


def store_ingest_fused():
    from repro.warehouse.store import _ingest_fused
    return EngineExample(
        _ingest_fused,
        (_store_cols(), _traces(N_W, W),
         jnp.zeros((T, OUT_DIM), jnp.float32), jnp.int32(0), jnp.int32(0),
         jnp.int32(0)), {"T": T})


def store_ingest_fused_multi():
    from repro.warehouse.store import _ingest_fused_multi
    return EngineExample(
        _ingest_fused_multi,
        (_store_cols(), _traces(N_W, V, W),
         jnp.zeros((V, T, OUT_DIM), jnp.float32), jnp.int32(0),
         jnp.int32(0), jnp.int32(0)), {"T": T})


def store_ingest_tick():
    from repro.warehouse.store import _ingest_tick
    return EngineExample(
        _ingest_tick,
        (_store_cols(), _traces(V), jnp.ones((V,), jnp.float32),
         jnp.zeros((V, OUT_DIM), jnp.float32), jnp.int32(0),
         jnp.int32(0)), {})


def store_ingest_tick_masked():
    from repro.warehouse.store import _ingest_tick_masked
    return EngineExample(
        _ingest_tick_masked,
        (_store_cols(), _traces(V), jnp.ones((V,), jnp.float32),
         jnp.zeros((V, OUT_DIM), jnp.float32), jnp.int32(0),
         jnp.int32(0), jnp.arange(V, dtype=jnp.int32),
         jnp.ones((V,), bool)), {})


def _sharded_append_args():
    n_rows = jnp.zeros((N_SHARDS,), jnp.int32)
    return _store_cols(stacked=True), n_rows


def store_sharded(kind: str):
    from repro.launch.mesh import make_shard_mesh
    from repro.warehouse.store import (OUT_COLUMN, SCALAR_COLUMNS,
                                       _shard_kernel)
    mesh = make_shard_mesh(N_SHARDS)
    kern = _shard_kernel(kind, mesh, N_SHARDS)
    cols, n_rows = _sharded_append_args()
    if kind == "append":
        n = 6
        upd = {name: jnp.zeros((n,), dt) for name, dt in SCALAR_COLUMNS}
        upd[OUT_COLUMN] = jnp.zeros((n, OUT_DIM), jnp.float32)
        return EngineExample(kern, (cols, n_rows, upd), {})
    if kind == "fused_multi":
        return EngineExample(
            kern, (cols, n_rows, _traces(N_W, V, W),
                   jnp.zeros((V, T, OUT_DIM), jnp.float32), jnp.int32(0),
                   jnp.int32(0)), {"T": T})
    if kind == "tick":
        return EngineExample(
            kern, (cols, n_rows, _traces(V), jnp.ones((V,), jnp.float32),
                   jnp.zeros((V, OUT_DIM), jnp.float32), jnp.int32(0)), {})
    if kind == "tick_ids":
        return EngineExample(
            kern, (cols, n_rows, _traces(V), jnp.ones((V,), jnp.float32),
                   jnp.zeros((V, OUT_DIM), jnp.float32), jnp.int32(0),
                   jnp.arange(V, dtype=jnp.int32), jnp.ones((V,), bool)),
            {})
    raise ValueError(kind)


def store_rebalance():
    from repro.launch.mesh import make_shard_mesh
    from repro.runtime.elastic import _rebalance_kernel
    mesh = make_shard_mesh(N_SHARDS)
    kern = _rebalance_kernel(mesh, N_SHARDS, N_SHARDS)
    cols, n_rows = _sharded_append_args()
    return EngineExample(kern, (cols, n_rows), {"cap_new": CAP})


# ---- warehouse: standing queries -------------------------------------------

_Q_STAND = 2                     # stacked query slots in the examples


def _standing_args(kind: str, q: int = _Q_STAND, sharded: bool = False):
    """(spec, stacked (Q, F) threshold operands, init state) for a
    standing-query group of ``q`` same-shape queries — the operand
    layout ``StandingQueries`` threads through the ingest kernels."""
    from repro.warehouse.query import normalize, split_plan
    from repro.warehouse.standing import _seed_state
    spec, fv = normalize(_plan(kind))
    fvq = tuple(jnp.broadcast_to(a[None], (q,) + a.shape) for a in fv)
    _pre, node, _post = split_plan(spec)
    lead = (N_SHARDS, q) if sharded else (q,)
    return spec, fvq, _seed_state(node, lead)


def standing_backfill(kind: str, use_pallas: bool = False):
    from repro.warehouse.standing import _backfill
    spec, fvq, state = _standing_args(kind)
    return EngineExample(_backfill,
                         (_store_cols(), jnp.int32(50), fvq, state),
                         {"sspec": (spec, bool(use_pallas))})


def standing_fold_sharded():
    from repro.launch.mesh import make_shard_mesh
    from repro.warehouse.standing import _sharded_fold_kernel
    spec, fvq, state = _standing_args("filter_groupby", sharded=True)
    kern = _sharded_fold_kernel(make_shard_mesh(N_SHARDS), N_SHARDS)
    return EngineExample(kern,
                         (_store_cols(stacked=True),
                          jnp.asarray([50, 40], jnp.int32), fvq, state),
                         {"sspec": (spec, False)})


def standing_answer(sharded: bool):
    from repro.warehouse.standing import _answer_kernel
    spec, fvq, state = _standing_args("filter_groupby",
                                      sharded=bool(sharded))
    return EngineExample(_answer_kernel, (state, fvq),
                         {"spec": spec, "sharded": bool(sharded)})


def store_scatter_standing():
    """``append_rows`` with a registered standing query: the scatter
    AND the incremental fold in the one jitted program."""
    from repro.warehouse.store import (OUT_COLUMN, SCALAR_COLUMNS,
                                      _scatter_fold)
    n = 5
    upd = {name: jnp.zeros((n,), dt) for name, dt in SCALAR_COLUMNS}
    upd[OUT_COLUMN] = jnp.zeros((n, OUT_DIM), jnp.float32)
    spec, fvq, state = _standing_args("filter_groupby")
    return EngineExample(_scatter_fold,
                         (_store_cols(), upd, jnp.int32(0), (state,),
                          (fvq,)),
                         {"sspecs": ((spec, False),)})


def store_ingest_tick_standing():
    from repro.warehouse.store import _ingest_tick
    spec, fvq, state = _standing_args("filter_groupby")
    return EngineExample(
        _ingest_tick,
        (_store_cols(), _traces(V), jnp.ones((V,), jnp.float32),
         jnp.zeros((V, OUT_DIM), jnp.float32), jnp.int32(0),
         jnp.int32(0), (state,), (fvq,)),
        {"sspecs": ((spec, False),)})


def store_sharded_standing():
    """Sharded tick ingest with a standing fold: one ``shard_map``
    dispatch writes the rows AND refreshes the per-shard partials."""
    from repro.launch.mesh import make_shard_mesh
    from repro.warehouse.store import _shard_kernel
    kern = _shard_kernel("tick", make_shard_mesh(N_SHARDS), N_SHARDS)
    cols, n_rows = _sharded_append_args()
    spec, fvq, state = _standing_args("filter_groupby", sharded=True)
    return EngineExample(
        kern,
        (cols, n_rows, _traces(V), jnp.ones((V,), jnp.float32),
         jnp.zeros((V, OUT_DIM), jnp.float32), jnp.int32(0),
         (state,), (fvq,)),
        {"sspecs": ((spec, False),)})


# ---- warehouse: tiers ------------------------------------------------------

_CHUNK, _N_SPILL = 4, 8


def tiers_quantize():
    from repro.warehouse.tiers import _quantize_chunks
    return EngineExample(_quantize_chunks,
                         (_store_cols(), jax.random.PRNGKey(0)),
                         {"n": _N_SPILL, "chunk": _CHUNK})


def tiers_compact():
    from repro.warehouse.tiers import _compact
    return EngineExample(_compact, (_store_cols(),),
                         {"n_spill": _N_SPILL})


def tiers_materialize():
    from repro.warehouse.tiers import _materialize, _quantize_chunks
    cols = _store_cols()
    q, scales, ints = _quantize_chunks(cols, jax.random.PRNGKey(0),
                                       n=_N_SPILL, chunk=_CHUNK)
    return EngineExample(_materialize, (q, scales, ints, cols),
                         {"chunk": _CHUNK})


def tiers_quantize_sharded():
    from repro.warehouse.tiers import _quantize_chunks_sharded
    return EngineExample(_quantize_chunks_sharded,
                         (_store_cols(stacked=True), jax.random.PRNGKey(0)),
                         {"n": _N_SPILL, "chunk": _CHUNK})


def tiers_cold_write():
    from repro.warehouse.tiers import _cold_write
    dst = {"x": jnp.zeros((N_SHARDS, 16, 3), jnp.float32)}
    src = {"x": jnp.ones((N_SHARDS, _N_SPILL, 3), jnp.float32)}
    return EngineExample(_cold_write,
                         (dst, src, jnp.zeros((N_SHARDS,), jnp.int32)), {})


def tiers_compact_ragged():
    from repro.warehouse.tiers import _compact_ragged
    cols = {"x": jnp.ones((N_SHARDS, 16, 3), jnp.float32)}
    return EngineExample(_compact_ragged,
                         (cols, jnp.asarray([4, 0], jnp.int32)), {})


def tiers_materialize_sharded():
    from repro.warehouse.tiers import (_materialize_sharded,
                                       _quantize_chunks_sharded)
    cols = _store_cols(stacked=True)
    q, scales, ints = _quantize_chunks_sharded(cols, jax.random.PRNGKey(0),
                                               n=_N_SPILL, chunk=_CHUNK)
    return EngineExample(
        _materialize_sharded,
        (q, scales, ints, cols, jnp.asarray([_N_SPILL, 0], jnp.int32)),
        {"chunk": _CHUNK})
