"""Compiled queries over the warehouse (the paper's "easy to query").

A query is a tuple of plan nodes applied left to right:

    Filter(column, op, value)   row predicate; ANDed into the row mask
    Project(columns)            keep only the named columns
    GroupBy(key, value, agg)    segment_sum/-max aggregation per key id
    WindowAgg(window, value)    same, keyed by time window t // window
    MultiGroupBy(keys, value)   multi-key aggregation (e.g. window x
                                category) via fused key encoding into
                                ONE segment_sum pass
    TopK(k, by)                 lax.top_k over a (possibly aggregated)
                                column; gathers every surviving column

Execution model: every plan is a two-phase **partial / merge** program.
The *partial* phase runs row-local work (filter masks, projections) and
reduces its rows to a fixed-shape, mergeable partial — masked
segment_sum accumulators for aggregations, a local top-k candidate
block for TopK, the masked rows themselves for pure row plans. The
*merge* phase combines partials (sum / max / concat), finalizes
(mean division, empty-group replacement), and runs any post-reduction
nodes. The single-device engine is the trivial 1-shard case of this
model — partial + identity merge, bit-exact with the pre-refactor
kernel — and the SAME partial/merge functions execute sharded:
``execute_sharded`` runs ONE ``shard_map`` dispatch over a
``ShardedStore``'s device mesh (psum/pmax/all_gather merge; optionally
int8-compressed partial sums for wide embedding columns, reusing
``distribution.compression``), or, below the device count, the same
kernels vmapped over a stacked shard axis on one device.

The whole plan compiles to ONE jitted kernel per *plan shape*: filter
predicates are vmapped masks whose threshold VALUES are dynamic
operands (re-querying with a new threshold, or after more rows arrive
within the same chunk capacity, reuses the executable — assert it via
``compile_cache_size()`` / the registered ``warehouse_query`` probes).
Aggregations scatter into static group counts (float sums in two
levels, ``_blocked_sum``), so no data-dependent shapes ever
materialize; filtered-out and padding rows participate as exact no-ops
(weight 0 / -inf).

Aggregation partials have TWO interchangeable kernels behind
``use_pallas`` (see ``execute``): the XLA ``segment_sum`` path above,
and the fused Pallas filter+group+aggregate kernel
(``repro.kernels.warehouse_agg``) that evaluates the predicate mask
in-register and accumulates into an on-chip ``(n_groups[, lanes])``
accumulator with ZERO scatters — the auditor's scatter census is 0 on
that path (the XLA path pins one executed scatter per groupby-style
plan). Both produce the identical ``{"acc", "cnt"}`` partial, share
``_seg_finalize`` and the merge combiners, and ``execute_sharded``
runs the fused kernel per shard inside its single shard_map dispatch.

``execute`` returns ``(table, mask)``: a dict of device columns plus a
validity mask over its rows (top-k slots beyond the number of matching
groups are masked off). ``execute_ref`` is the plain-numpy reference
implementation used by tests and the benchmark baseline; it replicates
the XLA path's blocked summation (``_blocked_sum``) so fp32 results
match exactly on a single shard (multi-shard float sums regroup the
addition and match to tolerance; counts and integer-valued sums stay
exact).
"""
from __future__ import annotations

import functools
import math
from dataclasses import dataclass
from typing import Dict, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.analysis.registry import example_builder, register_engine
from repro.core.switcher import register_cache_probe
from repro.distribution.compression import compressed_psum, quantize_int8
from repro.kernels.warehouse_agg import (CMP as _CMP, FusedAggSpec,
                                         fused_segment_agg, int_pred,
                                         pallas_auto)


@dataclass(frozen=True)
class Filter:
    """Row predicate plan node: keep rows where ``column <op> value``
    (also reused as the standing-alert predicate over answer tables)."""
    column: str
    op: str              # eq | ne | lt | le | gt | ge
    value: float         # dynamic operand: changing it never recompiles


@dataclass(frozen=True)
class Project:
    """Column-selection plan node: restrict downstream nodes to
    ``columns`` (trace-time slicing; no device work of its own)."""
    columns: Tuple[str, ...]


@dataclass(frozen=True)
class GroupBy:
    """Grouped aggregation plan node: one ``segment_sum``-style pass
    over an integer key column, fixed ``num_groups`` output shape."""
    key: str             # integer column holding the group id
    value: str           # column to aggregate
    agg: str = "sum"     # sum | mean | count | max | min
    num_groups: int = 8  # static: group ids clip into [0, num_groups)


@dataclass(frozen=True)
class WindowAgg:
    """Time-window aggregation plan node: group rows by
    ``t // window`` into ``num_windows`` fixed slots."""
    window: int          # segments per time window (ids = t // window)
    value: str
    agg: str = "sum"
    num_windows: int = 64


@dataclass(frozen=True)
class MultiGroupBy:
    """Aggregate by SEVERAL integer keys at once (e.g. time window x
    content category) with the key tuple fused into one flat id, so the
    whole multi-key aggregation is still ONE segment_sum pass.

    ``nums[i]`` is the static id count of ``keys[i]`` (ids clip into
    [0, nums[i]) after windowing); ``windows[i] > 1`` divides that key's
    column first (``keys[i] == "t", windows[i] == W`` reproduces
    WindowAgg's time windows). The result table has one decoded id
    column per key plus the aggregated value and ``count``."""
    keys: Tuple[str, ...]
    value: str
    agg: str = "sum"
    nums: Tuple[int, ...] = ()
    windows: Tuple[int, ...] = ()    # optional, same length as keys


@dataclass(frozen=True)
class TopK:
    """Row-level top-k plan node: the ``k`` rows extremal in ``by``
    (a post node — no fixed-size mergeable partial, so not standing)."""
    k: int
    by: str
    largest: bool = True


PlanNode = Union[Filter, Project, GroupBy, WindowAgg, MultiGroupBy, TopK]

# nodes that reduce rows to a fixed-shape mergeable partial — a sharded
# plan splits at the FIRST of these
_REDUCERS = (GroupBy, WindowAgg, MultiGroupBy, TopK)


@dataclass(frozen=True)
class _FilterRef:
    """Filter with its value hoisted into the dynamic operand vector, so
    the jitted plan is value-independent."""
    column: str
    op: str
    idx: int


def normalize(plan):
    """Split a plan into its static shape (hashable spec) and the
    dynamic filter-value operands: the f32 thresholds (float columns)
    plus each threshold's float64-computed floor, integrality, and
    out-of-int32-range flag (integer columns — f32 can't hold ints
    past 2^24, so those are hoisted host-side at full precision).
    ``int_pred``'s rewrites are closed-form in the floor (no ±1
    arithmetic), so every threshold with a representable int32 floor —
    including the ±2^31 edges — compares exactly; ``oob`` (-1/0/+1)
    marks thresholds outside int32 entirely (incl. ∓inf), where the
    comparison is a constant for every possible column value."""
    spec, vals, floors, isint, oob = [], [], [], [], []
    for node in plan:
        if isinstance(node, Filter):
            assert node.op in _CMP, f"unknown filter op {node.op!r}"
            spec.append(_FilterRef(node.column, node.op, len(vals)))
            v = float(node.value)
            assert not math.isnan(v), "NaN filter threshold"
            vals.append(np.float32(v))
            if v >= 2.0 ** 31:                 # incl. +inf
                ob, fl, ii = 1, 0, False
            elif v < -2.0 ** 31:               # incl. -inf
                ob, fl, ii = -1, 0, False
            else:
                ob, fl = 0, math.floor(v)      # in [-2^31, 2^31 - 1]
                ii = v == fl
            floors.append(np.int32(fl))
            isint.append(ii)
            oob.append(np.int32(ob))
        else:
            if isinstance(node, MultiGroupBy):
                assert len(node.keys) >= 1 and \
                    len(node.nums) == len(node.keys), \
                    "MultiGroupBy needs one static id count per key"
                assert not node.windows or \
                    len(node.windows) == len(node.keys), \
                    "MultiGroupBy windows must match keys"
            spec.append(node)
    return tuple(spec), (jnp.asarray(np.asarray(vals, np.float32)),
                         jnp.asarray(np.asarray(floors, np.int32)),
                         jnp.asarray(np.asarray(isint, bool)),
                         jnp.asarray(np.asarray(oob, np.int32)))


# ---------------------------------------------------------------------------
# segment aggregation as partial -> finalize (the mergeable core)
# ---------------------------------------------------------------------------

def _seg_ids(table, node):
    """Clipped int32 group ids + static group count for an agg node."""
    if isinstance(node, GroupBy):
        ids, num = table[node.key], node.num_groups
    elif isinstance(node, WindowAgg):
        ids, num = table["t"] // node.window, node.num_windows
    else:                                            # MultiGroupBy
        wins = node.windows or (0,) * len(node.keys)
        fused = None
        for key, n, w in zip(node.keys, node.nums, wins):
            ids = table[key].astype(jnp.int32)
            if w and w > 1:
                ids = ids // w
            ids = jnp.clip(ids, 0, n - 1)
            # fused encoding: ONE scatter pass covers the key tuple
            fused = ids if fused is None else fused * n + ids
        return fused, math.prod(node.nums)
    return jnp.clip(ids.astype(jnp.int32), 0, num - 1), num


# rows per first-level block of every fp32 group sum (a power of two;
# see _blocked_sum)
SUM_BLOCK_ROWS = 1 << 14


def _blocked_sum(closed, open_, v, ids, mask, rel, n_closed, nb):
    """Two-level fp32 sum of one lane ``v``'s unmasked rows into their
    groups ``ids`` — the addition order of every float group sum.

    Rows fall in blocks of ``SUM_BLOCK_ROWS`` consecutive row positions
    (``rel`` is each row's block, counted from the block the batch
    starts in). Within a block each group adds its rows in row order
    (one scatter into ``(nb, num)`` cells, block 0 seeded with
    ``open_``, the partial of the block the previous batch left open);
    the first ``n_closed`` block partials then add into ``closed`` in
    block order and the next one is the new open partial. A row-order
    sum over a million-row group drifts by percents once the running
    sum's ulp nears the addends (3% at 8.4M rows on a v5e); here no
    running sum spans more than one block's rows or one partial per
    block. Masked rows route past the last cell and drop, so no masked
    copy of ``v`` is made. Returns ``(closed, open)``."""
    num = closed.shape[0]
    cells = jnp.concatenate([open_, jnp.zeros(((nb - 1) * num,),
                                              jnp.float32)])
    cells = cells.at[jnp.where(mask, rel * num + ids, nb * num)].add(
        v, mode="drop").reshape(nb, num)
    blk = jax.lax.broadcasted_iota(jnp.int32, (nb, num), 0)
    grp = jax.lax.broadcasted_iota(jnp.int32, (nb, num), 1)
    closed = closed.at[jnp.where(blk < n_closed, grp, num).reshape(-1)] \
        .add(cells.reshape(-1), mode="drop")
    # block n_closed's cells, picked by a sum with exact zeros
    return closed, jnp.where(blk == n_closed, cells, 0.0).sum(axis=0)


def _fold_sum(closed, open_, n, v, ids, mask, member=None):
    """Fold a batch into a ``(closed, open)`` blocked sum whose first
    ``n`` row positions are already in. ``member`` marks the batch rows
    that take the next positions (in order; ``None``: every row);
    ``mask`` the member rows that add. A ``(rows, D)`` value column
    sums one lane at a time from its ``(D, rows)`` view (how the store
    lays it out): a (rows, D) scatter pads D to 128 lanes on TPU,
    32 GiB for a 2^26-row, 9-wide column."""
    shift = SUM_BLOCK_ROWS.bit_length() - 1
    rows = v.shape[0]
    if member is None:
        pos, count = n + jnp.arange(rows, dtype=jnp.int32), rows
    else:
        pos = n + jnp.cumsum(member.astype(jnp.int32)) - 1
        count = member.sum(dtype=jnp.int32)
    rel = (pos >> shift) - (n >> shift)
    n_closed = ((n + count) >> shift) - (n >> shift)
    nb = (rows >> shift) + 2
    if v.ndim == 1:
        return _blocked_sum(closed, open_, v, ids, mask, rel, n_closed, nb)
    closed, open_ = jax.lax.map(
        lambda a: _blocked_sum(a[0], a[1], a[2], ids, mask, rel, n_closed,
                               nb), (closed.T, open_.T, v.T))
    return closed.T, open_.T


def _seg_partial(table, mask, node):
    """Masked segment accumulators — the per-shard PARTIAL of an agg
    node: {"acc", "cnt"}, fixed (num_groups,[D]) shapes, mergeable by
    sum (sum/mean/count) or max/min. Filtered rows are exact no-ops.
    Sums are ``_blocked_sum``s over the table's row positions."""
    ids, num = _seg_ids(table, node)
    v = table[node.value].astype(jnp.float32)
    cnt = jax.ops.segment_sum(mask.astype(jnp.float32), ids,
                              num_segments=num)
    if node.agg in ("sum", "mean", "count"):
        zero = jnp.zeros((num,) + v.shape[1:], jnp.float32)
        closed, open_ = _fold_sum(zero, zero, jnp.int32(0), v, ids, mask)
        return {"acc": closed + open_, "cnt": cnt}
    assert v.ndim == 1, f"agg {node.agg!r} needs a scalar column"
    if node.agg == "max":
        acc = jax.ops.segment_max(jnp.where(mask, v, -jnp.inf), ids,
                                  num_segments=num)
    elif node.agg == "min":
        acc = jax.ops.segment_min(jnp.where(mask, v, jnp.inf), ids,
                                  num_segments=num)
    else:
        raise ValueError(f"unknown agg {node.agg!r}")
    return {"acc": acc, "cnt": cnt}


def _seg_fold(part, table, mask, node, member):
    """Fold a batch of NEW rows into a standing partial ``{"acc",
    "open", "cnt", "n"}``: ``n`` row positions are already in, ``acc``
    holds the closed blocks' sum and ``open`` the partial of the block
    position ``n`` falls in (``_fold_sum``). ``member`` marks the batch
    rows that take the next positions, ``mask`` those that the plan's
    filters keep. Each group's sum continues exactly where the stored
    partial left off, so a backfill followed by any number of folds is
    BIT-EXACT with one ``_seg_partial`` over the concatenated rows
    (``acc + open`` there) — the standing-query engine's exactness
    contract (see ``warehouse.standing``). max/min/count folds are
    order-independent and exact regardless."""
    ids, num = _seg_ids(table, node)
    v = table[node.value].astype(jnp.float32)
    out = dict(part,
               cnt=part["cnt"].at[ids].add(mask.astype(jnp.float32),
                                           mode="drop"),
               n=part["n"] + member.sum(dtype=jnp.int32))
    if node.agg in ("sum", "mean", "count"):
        out["acc"], out["open"] = _fold_sum(part["acc"], part["open"],
                                            part["n"], v, ids, mask,
                                            member)
        return out
    assert v.ndim == 1, f"agg {node.agg!r} needs a scalar column"
    if node.agg == "max":
        out["acc"] = part["acc"].at[ids].max(jnp.where(mask, v, -jnp.inf),
                                             mode="drop")
    elif node.agg == "min":
        out["acc"] = part["acc"].at[ids].min(jnp.where(mask, v, jnp.inf),
                                             mode="drop")
    else:
        raise ValueError(f"unknown agg {node.agg!r}")
    return out


def _seg_finalize(acc, cnt, agg):
    """Merged accumulators -> the agg's answer (pure; shared verbatim
    by the 1-shard, sharded, and Pallas paths, so they cannot drift).

    Empty-group contract: a group with NO surviving rows (filtered out
    or never present) answers 0.0 with ``count == 0`` and a masked-off
    result row, for EVERY agg — the ``∓inf`` sentinels that seed
    ``max``/``min`` accumulators (and survive pmax/pmin merges of
    all-empty shards) must never leak into a result table.
    ``execute_ref`` defines the same contract and the regression tests
    in tests/test_warehouse_agg_pallas.py pin it on all three paths."""
    if agg == "mean":
        c = jnp.maximum(cnt, 1.0)
        out = acc / (c if acc.ndim == cnt.ndim else c[:, None])
    elif agg == "count":
        out = cnt
    elif agg in ("max", "min"):
        out = jnp.where(cnt > 0, acc, 0.0)
    else:
        out = acc
    return out, cnt


def _seg_table(node, out, cnt):
    """Result table + mask for a finalized aggregation."""
    if isinstance(node, GroupBy):
        table = {node.key: jnp.arange(node.num_groups, dtype=jnp.int32)}
    elif isinstance(node, WindowAgg):
        table = {"window": jnp.arange(node.num_windows, dtype=jnp.int32)}
    else:                                            # MultiGroupBy
        num = math.prod(node.nums)
        rem = jnp.arange(num, dtype=jnp.int32)
        decoded = {}
        for key, n in zip(reversed(node.keys), reversed(node.nums)):
            decoded[key] = rem % n
            rem = rem // n
        table = {k: decoded[k] for k in node.keys}
    table[node.value] = out
    table["count"] = cnt
    return table, cnt > 0


def _apply_nodes(table, mask, fvals, spec):
    """Run plan nodes left-to-right on a (replicated) table — row-local
    nodes plus full (partial + trivially-merged) reductions. This IS the
    single-device engine, and the sharded engine reuses it for the
    pre-reduction and post-merge phases."""
    for node in spec:
        if isinstance(node, _FilterRef):
            vals, floors, isint, oob = fvals
            col = table[node.column]
            if jnp.issubdtype(col.dtype, jnp.integer):
                i, ii, ob = floors[node.idx], isint[node.idx], \
                    oob[node.idx]
                pred = jax.vmap(
                    lambda x: int_pred(x, node.op, i, ii, ob))(col)
            else:
                v = vals[node.idx]
                pred = jax.vmap(
                    lambda x: _CMP[node.op](x.astype(jnp.float32), v))(col)
            mask = mask & pred
        elif isinstance(node, Project):
            table = {c: table[c] for c in node.columns}
        elif isinstance(node, (GroupBy, WindowAgg, MultiGroupBy)):
            part = _seg_partial(table, mask, node)
            out, cnt = _seg_finalize(part["acc"], part["cnt"], node.agg)
            table, mask = _seg_table(node, out, cnt)
        elif isinstance(node, TopK):
            score = jnp.where(mask, table[node.by].astype(jnp.float32),
                              -jnp.inf)
            score = score if node.largest else jnp.where(
                jnp.isfinite(score), -score, score)
            kk = min(node.k, int(score.shape[0]))
            top, idx = jax.lax.top_k(score, kk)
            table = {c: jnp.take(table[c], idx, axis=0) for c in table}
            table["index"] = idx
            mask = jnp.isfinite(top)
        else:
            raise TypeError(f"unknown plan node {node!r}")
    return table, mask


def _pallas_spec(pre, node, cols):
    """``FusedAggSpec`` for a plan's partial phase, or None when the
    fused Pallas kernel cannot run it: no reducer / TopK reducer /
    wide-column max-min, or a pre-node referencing columns the XLA
    path would reject (Project order is honored, so forced-Pallas
    never silently answers a plan the fallback path errors on).
    ``cols`` may be real arrays or per-shard ShapeDtypeStructs."""
    if node is None or isinstance(node, TopK):
        return None
    avail = set(cols)
    filters = []
    for nd in pre:
        if isinstance(nd, _FilterRef):
            if nd.column not in avail:
                return None
            filters.append((nd.column, nd.op, nd.idx))
        elif isinstance(nd, Project):
            if not set(nd.columns) <= avail:
                return None
            avail = set(nd.columns)
        else:
            return None
    if isinstance(node, GroupBy):
        keys = ((node.key, node.num_groups, 0),)
    elif isinstance(node, WindowAgg):
        keys = (("t", node.num_windows, node.window),)
    else:                                            # MultiGroupBy
        wins = node.windows or (0,) * len(node.keys)
        keys = tuple(zip(node.keys, node.nums, wins))
    if not {k for k, _, _ in keys} | {node.value} <= avail:
        return None
    if len(cols[node.value].shape) == 2 and node.agg in ("max", "min"):
        return None                  # the XLA path asserts scalar too
    return FusedAggSpec(filters=tuple(filters), keys=keys,
                        value=node.value, agg=node.agg)


def _resolve_use_pallas(flag, pre, node, cols) -> bool:
    """Host-side dispatch: ``False`` forces XLA; ``True`` requests the
    fused kernel (falling back to XLA when the plan shape doesn't fit
    it — e.g. TopK reducers); ``None`` is the cost-based auto policy
    (``pallas_auto``): Pallas on TPU for on-chip-sized accumulators,
    XLA elsewhere (CPU interpret mode is a correctness path only)."""
    if flag is not None and not flag:
        return False
    aspec = _pallas_spec(pre, node, cols)
    if aspec is None:
        return False
    if flag:
        return True
    width = cols[aspec.value].shape[1] \
        if len(cols[aspec.value].shape) == 2 else 1
    return pallas_auto(aspec, width)


@functools.partial(jax.jit, static_argnames=("spec", "use_pallas"))
def _run_plan(cols, n_rows, fvals, *, spec, use_pallas=False):
    if use_pallas:
        # fused Pallas partial (no scatter, mask in-register) + the
        # SAME finalize/post nodes as the XLA path
        pre, node, post = split_plan(spec)
        aspec = _pallas_spec(pre, node, cols)
        assert aspec is not None, "unsupported plan for the fused kernel"
        part = fused_segment_agg(cols, n_rows, fvals, spec=aspec)
        out, cnt = _seg_finalize(part["acc"], part["cnt"], node.agg)
        table, mask = _seg_table(node, out, cnt)
        return _apply_nodes(table, mask, fvals, post)
    cap = cols["t"].shape[0] if "t" in cols else \
        next(iter(cols.values())).shape[0]
    mask = jnp.arange(cap) < n_rows
    return _apply_nodes(cols, mask, fvals, spec)


register_cache_probe("warehouse_query", lambda: _run_plan._cache_size())
register_engine("warehouse_query_filter_groupby",
                example_builder("query", "filter_groupby"),
                probe=lambda: _run_plan._cache_size(),
                covers=("repro.warehouse.query:_run_plan",),
                probe_name="warehouse_query")
register_engine("warehouse_query_window",
                example_builder("query", "window_sum"),
                probe=lambda: _run_plan._cache_size(),
                probe_name="warehouse_query")
register_engine("warehouse_query_multi_topk",
                example_builder("query", "multi_topk"),
                probe=lambda: _run_plan._cache_size(),
                probe_name="warehouse_query")
# the fused Pallas path (use_pallas=True) — the "_pallas" suffix keys
# the per-engine scatter_ops.* ceilings AND the aggregated
# scatter_ops.query_pallas=0 metric in benchmarks/run.py: the audit
# fails the bench --compare if a scatter ever creeps back in
register_engine("warehouse_query_pallas_groupby",
                example_builder("query_pallas", "filter_groupby"),
                probe=lambda: _run_plan._cache_size(),
                probe_name="warehouse_query")
register_engine("warehouse_query_pallas_window",
                example_builder("query_pallas", "window_sum"),
                probe=lambda: _run_plan._cache_size(),
                probe_name="warehouse_query")
register_engine("warehouse_query_pallas_groupmax",
                example_builder("query_pallas", "group_max"),
                probe=lambda: _run_plan._cache_size(),
                probe_name="warehouse_query")
register_engine("warehouse_query_pallas_multi",
                example_builder("query_pallas", "multi_topk"),
                probe=lambda: _run_plan._cache_size(),
                probe_name="warehouse_query")


def compile_cache_size() -> int:
    """jit cache entries of the single-device query kernel: one per
    distinct plan shape x store capacity — stable across repeated
    queries (changed filter values, appended rows within the same chunk
    capacity)."""
    return _run_plan._cache_size()


# ---------------------------------------------------------------------------
# sharded execution: per-shard partial kernel + merge combiner
# ---------------------------------------------------------------------------

def split_plan(spec):
    """(pre, reduce_node, post): the partial phase runs ``pre`` (row-
    local Filter/Project) plus the first reducing node's accumulators;
    the merge phase combines partials and runs ``post`` on the merged,
    replicated table."""
    for i, node in enumerate(spec):
        if isinstance(node, _REDUCERS):
            return spec[:i], node, spec[i + 1:]
    return spec, None, ()


class _CollectiveCombine:
    """Merge primitives inside shard_map: collectives over the mesh's
    'shard' axis."""
    collective = True

    def __init__(self, axis: str, n: int):
        self.axis, self.n = axis, n

    def sum(self, x):
        return jax.lax.psum(x, self.axis)

    def max(self, x):
        return jax.lax.pmax(x, self.axis)

    def min(self, x):
        return jax.lax.pmin(x, self.axis)

    def concat(self, x):
        if x.shape[0] == 0:       # an empty store: nothing to gather
            return x              # (a zero-size all_gather fails to lower)
        return jax.lax.all_gather(x, self.axis, axis=0, tiled=True)


class _StackedCombine:
    """Merge primitives for the single-device fallback: partial leaves
    carry a leading (n_shards,) axis (vmapped partial kernel) and merge
    by axis-0 reduction — the same algebra, no collectives."""
    collective = False

    def __init__(self, n: int):
        self.n = n

    def sum(self, x):
        return x.sum(axis=0)

    def max(self, x):
        return x.max(axis=0)

    def min(self, x):
        return x.min(axis=0)

    def concat(self, x):
        return x.reshape((-1,) + x.shape[2:])


def _compressed_sum(acc, combine, key):
    """Merge float partial sums through int8 quantization (per-shard
    scale + stochastic rounding) — 4x fewer bytes on the cross-shard
    hop, for wide embedding-column accumulators. The collective path
    reuses ``distribution.compression.compressed_psum`` (x n to undo its
    mean); the stacked path mirrors its math (sum of int8 codes times
    the mean scale) so both modes share semantics."""
    if combine.collective:
        k = jax.random.fold_in(key, jax.lax.axis_index(combine.axis))
        mean, _ = compressed_psum(acc, combine.axis, k,
                                  jnp.zeros_like(acc))
        return mean * combine.n
    keys = jax.random.split(key, acc.shape[0])
    q, scale = jax.vmap(quantize_int8)(acc, keys)
    total = q.astype(jnp.int32).sum(axis=0).astype(jnp.float32)
    return total * (scale.sum() / combine.n)


def _shard_partial_pallas(cols, n_valid, fvals, shard_id, *, pre, node):
    """``_shard_partial`` with the whole filter+group+aggregate partial
    as ONE fused Pallas kernel pass — the identical ``{"acc", "cnt"}``
    convention, so the merge combiners and finalize are untouched
    (selected per-plan by ``execute_sharded``'s ``use_pallas``)."""
    aspec = _pallas_spec(pre, node, cols)
    assert aspec is not None, "unsupported plan for the fused kernel"
    return fused_segment_agg(cols, n_valid, fvals, spec=aspec)


def _shard_partial(cols, n_valid, fvals, shard_id, *, pre, node):
    """ONE shard's partial: row-local pre nodes, then the reduce node's
    fixed-shape mergeable accumulators (or the masked rows themselves
    for pure row plans)."""
    cap = next(iter(cols.values())).shape[0]
    mask = jnp.arange(cap) < n_valid
    table, mask = _apply_nodes(cols, mask, fvals, pre)
    if node is None:
        return {"table": table, "mask": mask}
    if isinstance(node, TopK):
        # local candidates: the global top-k is a subset of the union of
        # per-shard top-k blocks, so k survivors per shard suffice
        score = jnp.where(mask, table[node.by].astype(jnp.float32),
                          -jnp.inf)
        if not node.largest:
            score = jnp.where(jnp.isfinite(score), -score, score)
        kk = min(node.k, int(score.shape[0]))
        top, idx = jax.lax.top_k(score, kk)
        cand = {c: jnp.take(table[c], idx, axis=0) for c in table}
        cand["index"] = idx + shard_id * cap       # global row id
        return {"table": cand, "score": top}
    return _seg_partial(table, mask, node)


def _merge_partials(part, node, post, fvals, combine, key, compressed):
    """Pure merge combiner: cross-shard reduction of the partial, agg
    finalization, then the post-reduction plan nodes on the (now
    replicated) merged table."""
    if node is None:                                  # pure row plan
        table = {k: combine.concat(v) for k, v in part["table"].items()}
        return table, combine.concat(part["mask"])
    if isinstance(node, TopK):
        score = combine.concat(part["score"])
        cand = {c: combine.concat(v) for c, v in part["table"].items()}
        kk = min(node.k, int(score.shape[0]))
        top, idx = jax.lax.top_k(score, kk)
        table = {c: jnp.take(v, idx, axis=0) for c, v in cand.items()}
        mask = jnp.isfinite(top)
    else:
        acc, cnt = part["acc"], part["cnt"]
        if node.agg == "max":
            acc = combine.max(acc)
        elif node.agg == "min":
            acc = combine.min(acc)
        elif compressed and acc.dtype == jnp.float32:
            acc = _compressed_sum(acc, combine, key)
        else:
            acc = combine.sum(acc)
        cnt = combine.sum(cnt)                        # counts stay exact
        out, cnt = _seg_finalize(acc, cnt, node.agg)
        table, mask = _seg_table(node, out, cnt)
    return _apply_nodes(table, mask, fvals, post)


# (mesh, n_shards) -> jitted sharded kernel; a plain dict (not
# lru_cache) so the cache probe can sum executable counts across them
_SHARDED_KERNELS: Dict = {}


def _sharded_kernel(mesh, n_shards: int):
    kern = _SHARDED_KERNELS.get((mesh, n_shards))
    if kern is not None:
        return kern

    @functools.partial(jax.jit,
                       static_argnames=("spec", "compressed",
                                        "use_pallas"))
    def run(cols, n_valid, fvals, key, *, spec, compressed,
            use_pallas=False):
        pre, node, post = split_plan(spec)
        part_fn = _shard_partial_pallas if use_pallas else _shard_partial
        if mesh is None:
            # single-device fallback: vmap the SAME partial kernel over
            # the stacked shard axis, merge by axis-0 reduction
            sids = jnp.arange(n_shards, dtype=jnp.int32)
            part = jax.vmap(lambda c, n, s: part_fn(
                c, n, fvals, s, pre=pre, node=node))(cols, n_valid, sids)
            return _merge_partials(part, node, post, fvals,
                                   _StackedCombine(n_shards), key,
                                   compressed)

        def body(c, n, fv, k):
            sid = jax.lax.axis_index("shard")
            part = part_fn({name: v[0] for name, v in c.items()},
                           n[0], fv, sid, pre=pre, node=node)
            return _merge_partials(part, node, post, fv,
                                   _CollectiveCombine("shard", n_shards),
                                   k, compressed)

        return shard_map(body, mesh=mesh,
                         in_specs=(P("shard"), P("shard"), P(), P()),
                         out_specs=P(), check_vma=False)(
                             cols, n_valid, fvals, key)

    _SHARDED_KERNELS[(mesh, n_shards)] = run
    return run


def sharded_compile_cache_size() -> int:
    """jit cache entries across every sharded query kernel: one per
    (plan shape x shard capacity) per (mesh, shard count) — stable
    across repeated queries at a fixed shard count."""
    return sum(k._cache_size() for k in _SHARDED_KERNELS.values())


register_cache_probe("warehouse_query_sharded", sharded_compile_cache_size)
register_engine("warehouse_query_sharded_groupby",
                example_builder("query_sharded", "filter_groupby"),
                probe=sharded_compile_cache_size,
                probe_name="warehouse_query_sharded")
register_engine("warehouse_query_sharded_topk",
                example_builder("query_sharded", "topk"),
                probe=sharded_compile_cache_size,
                probe_name="warehouse_query_sharded")
register_engine("warehouse_query_pallas_sharded",
                example_builder("query_sharded", "filter_groupby", True),
                probe=sharded_compile_cache_size,
                probe_name="warehouse_query_sharded")


def execute_sharded(store, plan, *, compressed: bool = False, key=None,
                    use_pallas=None):
    """Run ``plan`` over a sharded store as ONE dispatch: the per-shard
    partial kernel through ``shard_map`` on the store's device mesh
    followed by the pure merge combiner (psum / pmax / all-gather), or
    the vmapped stacked equivalent when the host lacks the devices.
    ``compressed=True`` merges float partial sums through int8
    quantization (see ``_compressed_sum``) — exact counts, lossy sums.
    ``use_pallas`` picks the per-shard partial kernel exactly like
    ``execute`` (None = cost-based auto; True = fused Pallas partials
    inside the same shard_map dispatch, when the plan shape fits).
    Returns ``(table, mask)`` of replicated device arrays."""
    cols, n_valid = store.shard_source()
    spec, fvals = normalize(plan)
    if key is None:
        key = jax.random.PRNGKey(0)
    pre, node, _post = split_plan(spec)
    shard_cols = {k: jax.ShapeDtypeStruct(v.shape[1:], v.dtype)
                  for k, v in cols.items()}
    up = _resolve_use_pallas(use_pallas, pre, node, shard_cols)
    kern = _sharded_kernel(store.mesh, store.n_shards)
    return kern(cols, n_valid, fvals, key, spec=spec,
                compressed=bool(compressed), use_pallas=up)


def _source(store):
    """(columns, n_rows) from a SegmentStore, a TieredStore (which
    materializes its cold tier on device), or a raw (columns, n) pair."""
    if hasattr(store, "materialize"):
        return store.materialize()
    if hasattr(store, "columns") and hasattr(store, "n_rows"):
        return store.columns, store.n_rows
    cols, n = store
    return cols, n


def execute(store, plan, *, use_pallas=None):
    """Run ``plan`` over ``store`` as one compiled dispatch; returns
    ``(table, mask)`` of device arrays. Sharded stores route to
    ``execute_sharded``. ``use_pallas=None`` picks the backend-aware
    cost-based dispatch (fused Pallas kernel on TPU for on-chip-sized
    accumulators, XLA ``segment_sum`` elsewhere); ``True`` forces the
    fused kernel for plan shapes it supports — on CPU it runs in
    interpret mode, a correctness path, not a fast one — and ``False``
    forces the XLA path."""
    if hasattr(store, "shard_source"):
        return execute_sharded(store, plan, use_pallas=use_pallas)
    cols, n_rows = _source(store)
    spec, fvals = normalize(plan)
    pre, node, _post = split_plan(spec)
    up = _resolve_use_pallas(use_pallas, pre, node, cols)
    return _run_plan(cols, jnp.int32(n_rows), fvals, spec=spec,
                     use_pallas=up)


def windows_for(store, window: int) -> int:
    """Static window count covering every stored timestamp."""
    t_max = store.t_max if hasattr(store, "t_max") else store.hot.t_max
    return max(1, int(t_max) // int(window) + 1)


def to_host(table, mask) -> Dict[str, np.ndarray]:
    """Compact a query result to host numpy, dropping masked-off rows."""
    m = np.asarray(mask)
    return {k: np.asarray(v)[m] for k, v in table.items()}


# ---------------------------------------------------------------------------
# numpy reference (tests + benchmark correctness baseline)
# ---------------------------------------------------------------------------

def _np_seg_ids(table, node):
    if isinstance(node, GroupBy):
        ids, num = table[node.key], node.num_groups
    elif isinstance(node, WindowAgg):
        ids, num = table["t"] // node.window, node.num_windows
    else:                                            # MultiGroupBy
        wins = node.windows or (0,) * len(node.keys)
        fused = None
        for key, n, w in zip(node.keys, node.nums, wins):
            ids = np.asarray(table[key], np.int64)
            if w and w > 1:
                ids = ids // w
            ids = np.clip(ids, 0, n - 1)
            fused = ids if fused is None else fused * n + ids
        return fused, math.prod(node.nums)
    return np.clip(np.asarray(ids, np.int64), 0, num - 1), num


def _np_aggregate(table, mask, node, dtype=np.float32):
    ids, num = _np_seg_ids(table, node)
    v = np.asarray(table[node.value], dtype)
    agg = node.agg
    cnt = np.zeros(num, dtype)
    np.add.at(cnt, ids[mask], dtype(1.0))
    if agg == "count":
        out = cnt
    elif agg in ("sum", "mean"):
        # the XLA path's _blocked_sum, in the same order: each (block,
        # group) cell adds its rows in row order, then each group adds
        # its cells in block order (np.add.at is sequential), so
        # single-shard fp32 sums match bit-exact
        B, rows = SUM_BLOCK_ROWS, len(ids)
        nb = rows // B + 2
        cells = np.zeros((nb * num,) + v.shape[1:], dtype)
        cell = (np.arange(rows) // B) * num + ids
        np.add.at(cells, cell[mask], v[mask])
        out = np.zeros((num,) + v.shape[1:], dtype)
        np.add.at(out, np.tile(np.arange(num), nb), cells)
        if agg == "mean":
            c = np.maximum(cnt, 1.0)
            out = out / (c if out.ndim == 1 else c[:, None])
    elif agg == "max":
        assert v.ndim == 1, "max needs a scalar column"
        out = np.full(num, -np.inf, dtype)
        np.maximum.at(out, ids[mask], v[mask])
        out = np.where(cnt > 0, out, 0.0).astype(dtype)
    elif agg == "min":
        assert v.ndim == 1, "min needs a scalar column"
        out = np.full(num, np.inf, dtype)
        np.minimum.at(out, ids[mask], v[mask])
        out = np.where(cnt > 0, out, 0.0).astype(dtype)
    else:
        raise ValueError(agg)
    return out, cnt


def _np_seg_table(node, out, cnt):
    if isinstance(node, GroupBy):
        table = {node.key: np.arange(node.num_groups, dtype=np.int32)}
    elif isinstance(node, WindowAgg):
        table = {"window": np.arange(node.num_windows, dtype=np.int32)}
    else:
        num = math.prod(node.nums)
        rem = np.arange(num, dtype=np.int64)
        decoded = {}
        for key, n in zip(reversed(node.keys), reversed(node.nums)):
            decoded[key] = (rem % n).astype(np.int32)
            rem = rem // n
        table = {k: decoded[k] for k in node.keys}
    table[node.value] = out
    table["count"] = cnt
    return table, cnt > 0


def _np_topk_idx(score, kk: int) -> np.ndarray:
    """Mirror ``lax.top_k``'s ordering exactly: descending IEEE-754
    TOTAL order — so ``+0.0`` outranks ``-0.0``, which a plain
    ``np.argsort(-score)`` treats as equal and orders by index —
    with ties at identical bit patterns broken by ascending row index
    (both are stable). The total order comes from the classic
    sign-magnitude bit flip: non-negative floats set the sign bit,
    negative floats invert all bits, and the uint32 keys then sort in
    float total order."""
    bits = np.ascontiguousarray(np.asarray(score, np.float32)) \
        .view(np.uint32)
    key = np.where(bits & np.uint32(0x80000000), ~bits,
                   bits | np.uint32(0x80000000))
    return np.argsort(~key, kind="stable")[:kk].astype(np.int32)


def execute_ref(cols: Dict[str, np.ndarray], n_rows: int, plan, *,
                dtype=np.float32):
    """Plain-numpy mirror of ``execute`` (same clipping, masking, and
    summation-order semantics — including ``_seg_finalize``'s
    empty-group contract: 0.0 / count 0 / masked row for every agg,
    and ``lax.top_k``'s total-order tie-break). Returns ``(table,
    mask)`` in numpy.

    ``dtype`` is the accumulation dtype of the aggregates. ``float32``
    (the default) mirrors the XLA path's addition sequence; ``float64``
    gives the near-exact sums that every path is held to within a
    count-dependent tolerance at million-row groups."""
    cap = len(next(iter(cols.values())))
    mask = np.arange(cap) < n_rows
    table = {k: np.asarray(v) for k, v in cols.items()}
    for node in plan:
        if isinstance(node, Filter):
            x = table[node.column]
            if np.issubdtype(x.dtype, np.integer):
                # exact: int32 values and the host-side threshold both
                # embed in float64 (mirrors the kernel's _int_pred)
                mask = mask & _CMP[node.op](x.astype(np.float64),
                                            np.float64(node.value))
            else:
                mask = mask & _CMP[node.op](x.astype(np.float32),
                                            np.float32(node.value))
        elif isinstance(node, Project):
            table = {c: table[c] for c in node.columns}
        elif isinstance(node, (GroupBy, WindowAgg, MultiGroupBy)):
            out, cnt = _np_aggregate(table, mask, node, dtype)
            table, mask = _np_seg_table(node, out, cnt)
        elif isinstance(node, TopK):
            score = np.where(mask, table[node.by].astype(np.float32),
                             -np.inf)
            if not node.largest:
                score = np.where(np.isfinite(score), -score, score)
            kk = min(node.k, len(score))
            idx = _np_topk_idx(score, kk)
            top = score[idx]
            table = {c: np.take(table[c], idx, axis=0) for c in table}
            table["index"] = idx
            mask = np.isfinite(top)
        else:
            raise TypeError(f"unknown plan node {node!r}")
    return table, mask
