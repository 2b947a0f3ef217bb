"""Standing queries: registered plans kept fresh AT INGEST RATE.

``store.query(plan)`` rescans every stored row — an O(rows) floor that
grows without bound while ingestion runs. But the partial/merge split
(``warehouse.query``) already reduces any aggregating plan to
fixed-shape ``{"acc", "cnt"}`` accumulators, and those are exactly
incrementally-maintainable state: fold the NEW rows' contributions into
the stored accumulators at ingest time and the plan's answer is a pure
O(result) finalize — no rescan, ever.

``StandingQueries`` is that registry:

- ``register(plan)`` splits the plan at its aggregating reducer
  (GroupBy / WindowAgg / MultiGroupBy — pure row plans and row-level
  TopK have no fixed-size incremental state and are rejected), takes a
  one-time O(rows) *backfill* partial over whatever the store already
  holds, and from then on every ingest folds the new rows in.
- The fold runs INSIDE the store's ingest kernels — the same single
  dispatch as ``ShardedStore.ingest_fused[_multi]`` / ``ingest_tick`` /
  ``append_rows`` (and the trivial 1-shard ``SegmentStore`` paths): the
  ingest kernel takes the stacked standing state as extra operands and
  returns the updated state next to the new columns. No second
  dispatch, no extra executable per query.
- Queries of the SAME plan shape batch into one vmapped fold: their
  thresholds are stacked dynamic operands ``(Q, F)`` and their state
  carries a leading query axis, padded to power-of-two buckets — so
  registering thousands of queries costs O(log Q) recompiles total and
  ZERO warm recompiles per tick (changing thresholds never recompiles,
  matching the query engine's operand-hoisting contract).
- ``subscribe(plan, predicate)`` layers change-data alerts on top: each
  poll evaluates the predicate over the plan's fixed-shape answer table
  and returns a fired-alert mask per result row, surfaced through the
  store's flight-recorder counters (``standing_refreshes``,
  ``alerts_checked``, ``alerts_fired`` — see ``obs.telemetry``).

Exactness contract (pinned by tests/test_standing_properties.py): the
fold is ``query._seg_fold`` — the blocked segment sum SEEDED with the
stored closed-block sum and open-block partial, at the row positions
the state has counted — so each group's fp32 addition sequence
continues exactly where the previous fold stopped. A backfill plus any
interleaving of ingest folds is therefore bit-exact with one
``_seg_partial`` over all rows in ingest order: on the single-store
path standing answers equal ``execute_ref`` bit-exactly (including
float sums); per-shard accumulators equal the rescan's per-shard
partials bit-exactly, with only the final cross-shard float-sum merge
regrouping (counts / max / min / integer-valued sums stay exact), the
same contract ``execute_sharded`` itself has. Spills never change a
standing answer: every row's exact fp32 contribution was folded when
it was INGESTED, so demoting the row to the int8 cold tier later
cannot touch the accumulators (rescans, by contrast, drift by the
quantization error).

The Pallas fused filter+group+aggregate kernel can compute the
delta-partials (``use_pallas=True`` at registration, single-store path
only; sharded stores fold on the XLA path): zero-scatter folds whose
batch membership (every row of an append, the live slots of the
elastic pool's masked tick) enters the kernel as one more filter
column. Its float sums accumulate tile-wise, so that path trades the
bit-exact-sum contract for tolerance (max/min/count stay exact) — same
trade the ``use_pallas`` query path documents.
"""
from __future__ import annotations

import functools
import math
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.analysis.registry import example_builder, register_engine
from repro.core.switcher import register_cache_probe
from repro.kernels.warehouse_agg import CMP as _CMP
from repro.kernels.warehouse_agg import FusedAggSpec, fused_segment_agg
from repro.obs.spans import span
from repro.warehouse.query import (Filter, GroupBy, MultiGroupBy, TopK,
                                   WindowAgg, _apply_nodes, _FilterRef,
                                   _pallas_spec, _resolve_use_pallas,
                                   _seg_finalize, _seg_fold, _seg_table,
                                   normalize, split_plan, to_host)


def _num_groups(node) -> int:
    if isinstance(node, GroupBy):
        return node.num_groups
    if isinstance(node, WindowAgg):
        return node.num_windows
    return math.prod(node.nums)                      # MultiGroupBy


def _bucket(n: int) -> int:
    """Power-of-two query-slot buckets (1, 2, 4, ...): the stacked
    threshold operands and state rows only change shape at bucket
    crossings, so reaching Q registered queries costs O(log Q)
    recompiles of the ingest program — then zero, warm."""
    return 1 << (n - 1).bit_length()


# ---------------------------------------------------------------------------
# the fold: new rows -> stored partials, traced inside the ingest kernels
# ---------------------------------------------------------------------------

def _seed_state(node, lead, width=()):
    """Fresh standing state of ``lead`` (``([S,] Q)``) query slots: the
    closed-block sums ``acc`` (∓inf seeds for max/min), the open-block
    partials ``open``, the counts, and ``n``, the row positions folded
    in so far (``query._seg_fold``)."""
    num = _num_groups(node)
    fill = {"max": -jnp.inf, "min": jnp.inf}.get(node.agg, 0.0)
    return {"acc": jnp.full(lead + (num,) + width, fill, jnp.float32),
            "open": jnp.zeros(lead + (num,) + width, jnp.float32),
            "cnt": jnp.zeros(lead + (num,), jnp.float32),
            "n": jnp.zeros(lead, jnp.int32)}


_MEMBER = "__member"          # the Pallas delta's batch-membership column


def _fold_group(state, fvals, table, mask, *, spec, use_pallas):
    """Fold one plan-shape group's batch of new rows into its stacked
    per-query state, over the leading query axis of ``(state, fvals)``.
    ``table`` is the replicated new-rows column block, ``mask`` the
    rows that join this store, in order (every row of an append, the
    live slots of a masked pool tick, the rows a shard owns)."""
    pre, node, _post = split_plan(spec)

    def one(st, fv):
        if not use_pallas:
            tbl, m = _apply_nodes(table, mask, fv, pre)
            return _seg_fold(st, tbl, m, node, mask)
        # zero-scatter delta partial via the fused kernel, the batch
        # membership as one more filter (``member == 1``), then an
        # elementwise combiner fold (sum/max/min are the merge algebra
        # of _merge_partials)
        aspec = _pallas_spec(pre, node, table)
        aspec = FusedAggSpec(
            filters=aspec.filters + ((_MEMBER, "eq", fv[0].shape[0]),),
            keys=aspec.keys, value=aspec.value, agg=aspec.agg)
        vals, floors, isint, oob = (
            jnp.concatenate([a, jnp.full((1,), x, a.dtype)])
            for a, x in zip(fv, (1.0, 1, True, 0)))
        delta = fused_segment_agg(
            dict(table, **{_MEMBER: mask.astype(jnp.int32)}),
            jnp.int32(mask.shape[0]), (vals, floors, isint, oob),
            spec=aspec)
        if node.agg == "max":
            acc = jnp.maximum(st["acc"], delta["acc"])
        elif node.agg == "min":
            acc = jnp.minimum(st["acc"], delta["acc"])
        else:
            acc = st["acc"] + delta["acc"]
        return dict(st, acc=acc, cnt=st["cnt"] + delta["cnt"],
                    n=st["n"] + mask.sum(dtype=jnp.int32))

    if not use_pallas:
        return jax.vmap(one)(state, fvals)
    # one kernel launch per query slot: a batched pallas_call would
    # block its (Q, F) threshold operands as (1, F) tiles, which the
    # TPU lowering refuses (tiles must be (8, 128)-aligned)
    return jax.lax.map(lambda a: one(*a), (state, fvals))


def _fold_all(sstates, sfvals, table, mask, sspecs):
    """Every registered group's fold, in registration order — called
    INSIDE the store ingest kernels (see ``warehouse.store``), so the
    refresh shares their single dispatch. ``sspecs`` is the static
    tuple of ``(plan spec, use_pallas)`` pairs aligned with the
    ``sstates`` / ``sfvals`` operand tuples."""
    return tuple(
        _fold_group(st, fv, table, mask, spec=sp, use_pallas=up)
        for st, fv, (sp, up) in zip(sstates, sfvals, sspecs))


@functools.partial(jax.jit, static_argnames=("sspec",))
def _backfill(cols, n_rows, fvals, state, *, sspec):
    """One-time O(rows) registration scan on the single-store path:
    the same fold, seeded with the fresh init state, over the store's
    live prefix — after this, ingest folds keep the state current."""
    spec, use_pallas = sspec
    cap = next(iter(cols.values())).shape[0]
    return _fold_group(state, fvals, cols, jnp.arange(cap) < n_rows,
                       spec=spec, use_pallas=use_pallas)


# (mesh, n_shards) -> jitted sharded backfill kernel; plain dict so the
# cache probe can sum executable counts (same pattern as query.py)
_SHARDED_FOLD: Dict = {}


def _sharded_fold_kernel(mesh, n_shards: int):
    kern = _SHARDED_FOLD.get((mesh, n_shards))
    if kern is not None:
        return kern

    @functools.partial(jax.jit, static_argnames=("sspec",))
    def run(cols, n_valid, fvals, state, *, sspec):
        spec, _up = sspec        # Pallas deltas are single-store only
        if mesh is None:
            def one(c, n, st):
                cap = next(iter(c.values())).shape[0]
                return _fold_group(st, fvals, c, jnp.arange(cap) < n,
                                   spec=spec, use_pallas=False)
            return jax.vmap(one)(cols, n_valid, state)

        def body(c, n, fv, st):
            c0 = {k: v[0] for k, v in c.items()}
            cap = next(iter(c0.values())).shape[0]
            st2 = _fold_group(jax.tree.map(lambda x: x[0], st), fv, c0,
                              jnp.arange(cap) < n[0], spec=spec,
                              use_pallas=False)
            return jax.tree.map(lambda x: x[None], st2)

        return shard_map(body, mesh=mesh,
                         in_specs=(P("shard"), P("shard"), P(),
                                   P("shard")),
                         out_specs=P("shard"), check_vma=False)(
                             cols, n_valid, fvals, state)

    _SHARDED_FOLD[(mesh, n_shards)] = run
    return run


@functools.partial(jax.jit, static_argnames=("spec", "sharded"))
def _answer_kernel(state, fvals, *, spec, sharded):
    """O(result) snapshot of a whole group: merge the per-shard
    accumulators (sum / max / min over the stacked shard axis — the
    ``_merge_partials`` algebra), finalize, and run the post-reduction
    nodes, vmapped over the query axis. Input sizes are
    ``(S, Q, groups)`` — never the stored rows — and changing
    thresholds reuses the executable."""
    _pre, node, post = split_plan(spec)

    def one(st, fv):
        acc, cnt = st["acc"], st["cnt"]
        if node.agg not in ("max", "min"):
            acc = acc + st["open"]           # closed blocks + open block
        if sharded:
            if node.agg == "max":
                acc = acc.max(axis=0)
            elif node.agg == "min":
                acc = acc.min(axis=0)
            else:
                acc = acc.sum(axis=0)
            cnt = cnt.sum(axis=0)
        out, cnt = _seg_finalize(acc, cnt, node.agg)
        table, mask = _seg_table(node, out, cnt)
        return _apply_nodes(table, mask, fv, post)

    with jax.named_scope("sink.answer"):
        return jax.vmap(one, in_axes=(1, 0) if sharded else (0, 0))(
            state, fvals)


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

@dataclass
class Alert:
    """One subscription's poll result: ``fired`` is the fixed-shape
    per-result-row alert mask (predicate AND the row's validity), the
    same shape every tick; ``table`` the answer snapshot it was
    evaluated on (host numpy)."""
    sub: int
    name: str
    handle: int
    fired: np.ndarray
    table: Dict[str, np.ndarray]

    @property
    def n_fired(self) -> int:
        return int(self.fired.sum())


@dataclass
class _Sub:
    sid: int
    name: str
    handle: int
    predicate: Filter


@dataclass
class _Query:
    handle: int
    name: str
    plan: tuple
    spec: tuple                        # normalized plan shape (group key)
    fvals: Tuple[np.ndarray, ...]      # this query's (F,) operands
    slot: int                          # row in the group's stacked state


class _Group:
    """All registered queries of one plan SHAPE: one spec, stacked
    ``(Qb, F)`` threshold operands, stacked ``([S,] Qb, groups[, D])``
    accumulator state, one vmapped fold per ingest."""

    def __init__(self, reg: "StandingQueries", spec, use_pallas: bool):
        self.reg = reg
        self.spec = spec
        self.use_pallas = bool(use_pallas)
        _pre, self.node, _post = split_plan(spec)
        self.queries: List[_Query] = []
        self.qb = 0
        self.fvals_dev = None
        self.state = None

    @property
    def q(self) -> int:
        return len(self.queries)

    @property
    def sspec(self):
        return (self.spec, self.use_pallas)

    def _init_state(self, qb: Optional[int] = None):
        qb = self.qb if qb is None else qb
        reg, node = self.reg, self.node
        vcol = reg.host.columns[node.value]
        width = vcol.shape[(2 if reg.sharded else 1):]   # () or (D,)
        lead = (reg.host.n_shards, qb) if reg.sharded else (qb,)
        return reg._place(_seed_state(node, lead, width))

    def _restack_fvals(self) -> None:
        """(Qb, F) stacked dynamic threshold operands; padding slots
        replicate query 0 (their state rows are never read)."""
        rows = [q.fvals for q in self.queries]
        rows += [rows[0]] * (self.qb - len(rows))
        self.fvals_dev = tuple(
            jnp.asarray(np.stack([r[i] for r in rows]))
            for i in range(4))

    def add(self, query: _Query) -> None:
        self.queries.append(query)
        if self.q > self.qb:                 # bucket crossing: grow
            old, old_qb = self.state, self.qb
            self.qb = _bucket(self.q)
            grown = self._init_state()
            if old is not None:
                # folded history is irreplaceable state (a re-backfill
                # after a spill would see dequantized rows) — copy it
                if self.reg.sharded:
                    grown = jax.tree.map(
                        lambda g, o: g.at[:, :old_qb].set(o), grown, old)
                else:
                    grown = jax.tree.map(
                        lambda g, o: g.at[:old_qb].set(o), grown, old)
            self.state = self.reg._place(grown)
        self._restack_fvals()
        self._backfill_slot(query)

    def _backfill_slot(self, query: _Query) -> None:
        """Fold the store's EXISTING rows into the new query's slot —
        a single-slot (Q=1) kernel call, so every registration reuses
        one executable regardless of the group's bucket size."""
        reg = self.reg
        src = reg._source()
        if src is None:                      # empty store: init seed
            return
        cols, n_valid = src
        fv1 = tuple(jnp.asarray(a[None]) for a in query.fvals)
        st1 = self._init_state(qb=1)
        if reg.sharded:
            kern = _sharded_fold_kernel(reg.host.mesh, reg.host.n_shards)
            bf = kern(cols, n_valid, fv1, st1, sspec=self.sspec)
            self.state = reg._place(jax.tree.map(
                lambda st, b: st.at[:, query.slot].set(b[:, 0]),
                self.state, bf))
        else:
            bf = _backfill(cols, jnp.int32(n_valid), fv1, st1,
                           sspec=self.sspec)
            self.state = jax.tree.map(
                lambda st, b: st.at[query.slot].set(b[0]),
                self.state, bf)


class StandingQueries:
    """The store-attached registry. Attach once per store::

        reg = StandingQueries(store)          # any store/tiered variant
        h = reg.register((Filter(...), GroupBy(...)))
        store.append_rows(rows)               # fold happens IN the ingest
        table, mask = reg.answer(h)           # O(result), no rescan

    Works over ``SegmentStore`` / ``ShardedStore`` and their tiered
    wrappers (``TieredStore`` / ``ShardedTieredStore`` — registration
    attaches to the hot store, whose ingest kernels do the folding;
    backfill scans the two-tier view, so registering AFTER a spill
    snapshots the cold rows at their dequantized values)."""

    def __init__(self, store):
        self.store = store
        self.host = getattr(store, "hot", store)
        assert getattr(self.host, "standing", None) is None, \
            "store already has a StandingQueries registry attached"
        self.host.standing = self
        self.sharded = hasattr(self.host, "n_shards")
        self._groups: Dict[tuple, _Group] = {}
        self._queries: Dict[int, _Query] = {}
        self._subs: Dict[int, _Sub] = {}
        self._active: List[_Group] = []
        self._next = 0

    def __len__(self) -> int:
        return len(self._queries)

    @property
    def has_subscriptions(self) -> bool:
        return bool(self._subs)

    # -- registration --------------------------------------------------
    def _validate(self, spec) -> None:
        pre, node, _post = split_plan(spec)
        if node is None or isinstance(node, TopK):
            raise ValueError(
                "standing queries need an aggregating reducer (GroupBy/"
                "WindowAgg/MultiGroupBy): pure row plans and row-level "
                "TopK have no fixed-size incremental state")
        avail = set(self.host.columns)
        for nd in pre:
            if isinstance(nd, _FilterRef):
                if nd.column not in avail:
                    raise ValueError(f"unknown column {nd.column!r}")
            else:                                        # Project
                if not set(nd.columns) <= avail:
                    raise ValueError(
                        f"unknown columns {set(nd.columns) - avail}")
                avail = set(nd.columns)
        if isinstance(node, GroupBy):
            keys = {node.key}
        elif isinstance(node, WindowAgg):
            keys = {"t"}
        else:
            keys = set(node.keys)
        missing = (keys | {node.value}) - avail
        if missing:
            raise ValueError(f"plan references unknown columns {missing}")

    def _resolve_pallas(self, flag, spec) -> bool:
        if self.sharded:                 # sharded stores fold on XLA
            return False
        pre, node, _post = split_plan(spec)
        cols = {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
                for k, v in self.host.columns.items()}
        return _resolve_use_pallas(flag, pre, node, cols)

    def register(self, plan, *, name: Optional[str] = None,
                 use_pallas=None) -> int:
        """Register ``plan`` as a standing query; returns its handle.
        One-time cost: an O(rows) backfill partial over the current
        store. Thereafter the plan's partial is maintained inside every
        ingest dispatch and ``answer(handle)`` is O(result)."""
        spec, fv_dev = normalize(plan)
        self._validate(spec)
        g = self._groups.get(spec)
        if g is None:
            g = _Group(self, spec, self._resolve_pallas(use_pallas, spec))
            self._groups[spec] = g
        handle = self._next
        self._next += 1
        q = _Query(handle, name or f"q{handle}", tuple(plan), spec,
                   tuple(np.asarray(a) for a in fv_dev), g.q)
        g.add(q)
        self._queries[handle] = q
        self.host.obs["standing_queries"] = len(self._queries)
        return handle

    def subscribe(self, plan, predicate: Filter, *,
                  name: Optional[str] = None, use_pallas=None) -> int:
        """Register ``plan`` AND a threshold alert over its answer
        table: ``predicate`` is a ``Filter`` on a result column (the
        agg value, ``count``, or a group-key column). Every ``poll()``
        evaluates it over the fixed-shape answer and returns the fired
        mask — change-data capture at O(result) per tick."""
        assert isinstance(predicate, Filter), \
            "predicate must be a Filter(...) over the answer table"
        handle = self.register(plan, name=name, use_pallas=use_pallas)
        sid = self._next
        self._next += 1
        self._subs[sid] = _Sub(sid, name or f"alert{sid}", handle,
                               predicate)
        return sid

    # -- ingest-side hooks (called by the stores) ----------------------
    def kernel_args(self):
        """(sstates, sfvals, sspecs) operand/static tuples the ingest
        kernels thread through their single dispatch."""
        self._active = [g for g in self._groups.values() if g.q]
        return (tuple(g.state for g in self._active),
                tuple(g.fvals_dev for g in self._active),
                tuple(g.sspec for g in self._active))

    def absorb(self, new_states) -> None:
        """Store the folded state an ingest kernel returned."""
        for g, st in zip(self._active, new_states):
            g.state = st
        self.host.obs["standing_refreshes"] += 1

    def _place(self, tree):
        put = getattr(self.host, "_put", None)
        return put(tree) if put is not None else tree

    def _source(self):
        """(columns, valid counts) for backfill — the store's combined
        two-tier view — or None when there is nothing to scan."""
        if self.store.n_rows == 0:
            return None
        if self.sharded:
            return self.store.shard_source()
        from repro.warehouse.query import _source as q_source
        return q_source(self.store)

    # -- answers -------------------------------------------------------
    def group_answers(self, group: _Group):
        """Stacked (Q, ...) answer tables of one whole group — ONE
        O(result) dispatch shared by every query of the shape."""
        return _answer_kernel(group.state, group.fvals_dev,
                              spec=group.spec, sharded=self.sharded)

    def answer(self, handle: int):
        """(table, mask) of one standing query — device arrays, no
        rescan (accumulator finalize + post nodes only)."""
        q = self._queries[handle]
        table, mask = self.group_answers(self._group_of(q))
        return ({k: v[q.slot] for k, v in table.items()}, mask[q.slot])

    def _group_of(self, q: _Query) -> _Group:
        return self._groups[q.spec]

    def answer_host(self, handle: int) -> Dict[str, np.ndarray]:
        """``answer`` compacted to host numpy (masked rows dropped)."""
        table, mask = self.answer(handle)
        return to_host(table, mask)

    # -- alerts --------------------------------------------------------
    def poll(self) -> List[Alert]:
        """Evaluate every subscription against its plan's CURRENT
        standing answer: one answer dispatch per plan shape, then the
        predicates host-side over the fixed-shape tables. Updates the
        flight-recorder counters (``alerts_checked``/``alerts_fired``,
        and ``host_pulls`` for the answer columns read), inside a
        ``sink.alert_poll`` span."""
        alerts: List[Alert] = []
        cache: Dict[int, tuple] = {}
        obs = self.host.obs
        pulls, fired_n = obs["host_pulls"], obs["alerts_fired"]
        with span("sink.alert_poll",
                  subscriptions=len(self._subs)) as sp:
            for sub in self._subs.values():
                q = self._queries[sub.handle]
                g = self._group_of(q)
                if id(g) not in cache:
                    cache[id(g)] = self.group_answers(g)
                table, mask = cache[id(g)]
                row = {k: np.asarray(v[q.slot]) for k, v in table.items()}
                valid = np.asarray(mask[q.slot])
                obs["host_pulls"] += len(row) + 1
                col = row[sub.predicate.column]
                dt = np.float64 if np.issubdtype(col.dtype, np.integer) \
                    else np.float32
                pred = np.asarray(_CMP[sub.predicate.op](
                    col.astype(dt), dt(sub.predicate.value)))
                fired = valid & pred
                obs["alerts_checked"] += 1
                obs["alerts_fired"] += int(fired.sum())
                alerts.append(Alert(sub.sid, sub.name, sub.handle, fired,
                                    row))
            sp.set_metadata(pulls=obs["host_pulls"] - pulls,
                            fired=obs["alerts_fired"] - fired_n)
        return alerts


# ---- cache probes + static-analysis registry -------------------------------

register_cache_probe(
    "warehouse_standing",
    lambda: (_backfill._cache_size() + _answer_kernel._cache_size()
             + sum(k._cache_size() for k in _SHARDED_FOLD.values())))

register_engine("standing_backfill",
                example_builder("standing_backfill", "filter_groupby"),
                probe=lambda: _backfill._cache_size(),
                covers=("repro.warehouse.standing:_backfill",),
                probe_name="warehouse_standing")
# "_pallas" in the name keys this engine into the aggregated
# scatter_ops.query_pallas=0 bench ceiling: the fused delta path must
# stay scatter-free
register_engine("standing_backfill_pallas",
                example_builder("standing_backfill", "group_max", True),
                probe=lambda: _backfill._cache_size(),
                probe_name="warehouse_standing")
register_engine("standing_fold_sharded",
                example_builder("standing_fold_sharded"),
                probe=lambda: sum(k._cache_size()
                                  for k in _SHARDED_FOLD.values()),
                probe_name="warehouse_standing")
register_engine("standing_answer",
                example_builder("standing_answer", False),
                probe=lambda: _answer_kernel._cache_size(),
                covers=("repro.warehouse.standing:_answer_kernel",),
                probe_name="warehouse_standing")
register_engine("standing_answer_sharded",
                example_builder("standing_answer", True),
                probe=lambda: _answer_kernel._cache_size(),
                probe_name="warehouse_standing")
