"""V-ETL *Load*: a device-resident columnar segment store.

The paper frames video analytics as data warehousing: Extract decodes,
Transform runs the content-adaptive UDFs, and **Load** lands every
segment's results "in an application-specific format that is easy to
query". Before this module the fused engines reduced a run to a
``RunResult`` summary and threw the per-segment outputs away.

``SegmentStore`` is append-only, chunked, and columnar: one device
array per column, grown in ``chunk_rows`` multiples so the set of array
shapes (and therefore jit executables) stays small. Columns:

    stream_id     int32   which camera/stream produced the segment
    t             int32   segment index on that stream's timeline
    category      int32   content category the switcher classified
    k             int32   knob configuration the switcher chose
    quality       f32     measured quality of the chosen config
    on_core_s     f32     on-prem work spent (core-seconds)
    cloud_core_s  f32     cloud work spent (core-seconds)
    buffer_s      f32     buffer fill after the segment (seconds)
    out           f32     fixed-width application output / embedding (D,)

Ingestion is batched and device-side: ``ingest_fused`` takes the fused
whole-run engine's *stacked* traces (``(n_w, W)`` leaves, still on
device) and writes all columns in ONE jitted dispatch — flattening,
tail-slicing, column synthesis (stream_id/t) and the scatter all live
in the same program, so nothing round-trips through the host per
segment. ``ingest_fused_multi`` does the same for the (n_w, V, W)
multi-stream traces and ``ingest_tick`` lands one row per live stream
from a serving-pool tick.

The store is a registered JAX pytree (columns are leaves; row count and
chunking are static aux), so it passes through jit/vmap and flattens
for checkpointing (see ``warehouse.tiers``).

``ShardedStore`` is the horizontal scale-out of the same layout: rows
partition by ``stream_id % n_shards`` onto a 1-D ``('shard',)`` device
mesh, columns are stacked ``(n_shards, cap, ...)`` arrays whose leading
axis is split across devices, and every ingest runs as ONE ``shard_map``
dispatch — each shard scatters exactly the rows it owns (a masked
cumulative-rank scatter; non-owned rows land out of bounds and are
dropped), so routing never gathers through the host. Queries execute
through the partial/merge engine (``warehouse.query.execute_sharded``).
With fewer devices than shards the same kernels run vmapped over the
stacked axis on one device, so all sharding semantics stay testable
anywhere.
"""
from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.analysis.registry import example_builder, register_engine
from repro.core.switcher import register_cache_probe
from repro.distribution.sharding import put_row_sharded
from repro.launch.mesh import make_shard_mesh
from repro.obs.spans import span
from repro.obs.telemetry import (StoreTelemetry, store_obs_batch,
                                 store_obs_init, store_obs_tick, store_put)

# repro.warehouse.standing's fold — imported lazily (inside the ingest
# kernels, only on the sspecs != () trace path) because standing.py
# imports query.py, which transitively imports this module: by the time
# a StandingQueries registry can hand a store non-empty sspecs, the
# standing module is fully initialized.


def _fold_all(*args):
    from repro.warehouse.standing import _fold_all as fold
    return fold(*args)

SCALAR_COLUMNS = (
    ("stream_id", jnp.int32),
    ("t", jnp.int32),
    ("category", jnp.int32),
    ("k", jnp.int32),
    ("quality", jnp.float32),
    ("on_core_s", jnp.float32),
    ("cloud_core_s", jnp.float32),
    ("buffer_s", jnp.float32),
)
OUT_COLUMN = "out"

# fused-run trace key -> store column
_RUN_KEYS = (("c", "category"), ("k", "k"), ("qual", "quality"),
             ("on_s", "on_core_s"), ("cl_s", "cloud_core_s"),
             ("buffer_s", "buffer_s"))


def _empty_columns(cap: int, out_dim: int) -> Dict[str, jnp.ndarray]:
    cols = {n: jnp.zeros((cap,), dt) for n, dt in SCALAR_COLUMNS}
    cols[OUT_COLUMN] = jnp.zeros((cap, out_dim), jnp.float32)
    return cols


def _bucket_cap(need: int, chunk: int) -> int:
    """Smallest capacity from the fixed ladder ``{chunk * 2**j}`` that
    fits ``need`` rows. Growing to ladder rungs (instead of the exact
    chunk-aligned need) means EVERY store with the same chunk size
    draws its capacities from one small global set, so the kernels
    specialized on capacity (append / ingest / query) compile O(log
    rows) times over a store's whole lifetime and a warm capacity is
    never re-traced — the recompile-per-growth fix pinned by
    tests/test_standing.py."""
    units = max(1, -(-need // chunk))
    return chunk * (1 << (units - 1).bit_length())


def _standing_args(store):
    """The attached ``StandingQueries`` registry's ingest operands
    ``(sstates, sfvals, sspecs)`` — empty tuples (the kernels' no-op
    defaults, tracing the exact pre-standing programs) when no registry
    or no registered queries."""
    reg = store.standing
    if reg is None or not len(reg):
        return (), (), ()
    return reg.kernel_args()


def _put_all(cols, upd, offset):
    """Write every column's update block at row ``offset`` (dynamic)."""
    def put(dst, src):
        idx = (offset,) + (0,) * (src.ndim - 1)
        return jax.lax.dynamic_update_slice(dst, src.astype(dst.dtype), idx)
    return {k: put(cols[k], upd[k]) for k in cols}


_scatter = jax.jit(_put_all)


def _write_and_fold(cols, upd, offset, sstates, sfvals, sspecs):
    """Scatter the update block AND fold it into the standing-query
    accumulators — the shared tail of every single-store ingest kernel,
    so registered answers refresh inside the SAME dispatch that lands
    the rows (see ``warehouse.standing``). With no registered queries
    (``sspecs=()``, the static default) this traces the exact
    pre-standing program and keeps the old single-value return."""
    new = _put_all(cols, upd, offset)
    if not sspecs:
        return new
    # fold what a rescan would READ: the update block cast to the
    # stored column dtypes (the standing exactness contract)
    cast = {k: v.astype(cols[k].dtype) for k, v in upd.items()}
    n = upd["t"].shape[0]
    states = _fold_all(sstates, sfvals, cast, jnp.ones((n,), bool), sspecs)
    return new, states


@functools.partial(jax.jit, static_argnames=("sspecs",))
def _scatter_fold(cols, upd, offset, sstates, sfvals, *, sspecs):
    """``append_rows`` + standing refresh in one dispatch (the plain
    ``_scatter`` stays the no-registry fast path)."""
    return _write_and_fold(cols, upd, offset, sstates, sfvals, sspecs)


@functools.partial(jax.jit, static_argnames=("T", "sspecs"))
def _ingest_fused(cols, traces, out_vecs, stream_id, t0, offset,
                  sstates=(), sfvals=(), *, T, sspecs=()):
    """One device op: flatten the fused engine's stacked (n_w, W) traces,
    drop the tail padding, synthesize stream_id/t, scatter all columns
    (folding standing-query partials in the same program)."""
    upd = {dst: traces[src].reshape(-1)[:T] for src, dst in _RUN_KEYS}
    upd["stream_id"] = jnp.full((T,), stream_id, jnp.int32)
    upd["t"] = t0 + jnp.arange(T, dtype=jnp.int32)
    upd[OUT_COLUMN] = out_vecs
    return _write_and_fold(cols, upd, offset, sstates, sfvals, sspecs)


@functools.partial(jax.jit, static_argnames=("T", "sspecs"))
def _ingest_fused_multi(cols, traces, out_vecs, stream_base, t0, offset,
                        sstates=(), sfvals=(), *, T, sspecs=()):
    """Multi-stream ingest: traces have (n_w, V, W) leaves; rows land
    stream-major ((stream 0 t=0..T-1), (stream 1 ...), ...)."""
    V = out_vecs.shape[0]

    def flat(x):                                  # (n_w, V, W) -> (V*T,)
        return jnp.swapaxes(x, 0, 1).reshape(V, -1)[:, :T].reshape(-1)

    upd = {dst: flat(traces[src]) for src, dst in _RUN_KEYS}
    upd["stream_id"] = (stream_base
                        + jnp.repeat(jnp.arange(V, dtype=jnp.int32), T))
    upd["t"] = t0 + jnp.tile(jnp.arange(T, dtype=jnp.int32), V)
    upd[OUT_COLUMN] = out_vecs.reshape(V * T, -1)
    return _write_and_fold(cols, upd, offset, sstates, sfvals, sspecs)


@functools.partial(jax.jit, static_argnames=("sspecs",))
def _ingest_tick(cols, traces, quality, out_vecs, t, offset,
                 sstates=(), sfvals=(), *, sspecs=()):
    """One serving-pool tick: V rows (one per live stream)."""
    V = quality.shape[0]
    upd = {dst: traces[src] for src, dst in _RUN_KEYS}
    upd["quality"] = quality          # measured by the user's Transform
    upd["stream_id"] = jnp.arange(V, dtype=jnp.int32)
    upd["t"] = jnp.full((V,), t, jnp.int32)
    upd[OUT_COLUMN] = out_vecs
    return _write_and_fold(cols, upd, offset, sstates, sfvals, sspecs)


@functools.partial(jax.jit, static_argnames=("sspecs",))
def _ingest_tick_masked(cols, traces, quality, out_vecs, t, offset,
                        stream_ids, valid, sstates=(), sfvals=(), *,
                        sspecs=()):
    """Elastic-pool tick: the slot axis carries REAL stream ids and an
    ``active`` mask (retired/empty slots). Active rows compact to
    consecutive positions at ``offset`` via the same masked-rank
    scatter the sharded router uses (inactive rows index past the
    capacity and drop), and only active rows fold into the standing
    accumulators — all fixed-shape, one executable per capacity."""
    V = quality.shape[0]
    upd = {dst: traces[src] for src, dst in _RUN_KEYS}
    upd["quality"] = quality
    upd["stream_id"] = stream_ids.astype(jnp.int32)
    upd["t"] = jnp.full((V,), t, jnp.int32)
    upd[OUT_COLUMN] = out_vecs
    keep = jnp.asarray(valid, bool)
    cap = next(iter(cols.values())).shape[0]
    with jax.named_scope("sink.write"):
        rank = jnp.cumsum(keep.astype(jnp.int32)) - 1
        idx = jnp.where(keep, offset + rank, cap)
        new = {k: cols[k].at[idx].set(upd[k].astype(cols[k].dtype),
                                      mode="drop") for k in cols}
    if not sspecs:
        return new
    with jax.named_scope("sink.fold"):
        cast = {k: v.astype(cols[k].dtype) for k, v in upd.items()}
        states = _fold_all(sstates, sfvals, cast, keep, sspecs)
    return new, states


class SegmentStore:
    """Append-only columnar store for per-segment V-ETL results."""

    def __init__(self, out_dim: int, chunk_rows: int = 8192):
        assert out_dim >= 1 and chunk_rows >= 1
        self.out_dim = int(out_dim)
        self.chunk_rows = int(chunk_rows)
        self.n_rows = 0
        self.t_max = -1
        self.columns = _empty_columns(0, out_dim)
        # host-side observability counters (see ``telemetry()``) —
        # deliberately NOT pytree aux: they vary per instance, and
        # hashable aux must stay stable or every jit call recompiles
        self.obs = store_obs_init()
        # StandingQueries registry (attached by its constructor)
        self.standing = None

    # -- capacity ------------------------------------------------------
    @property
    def capacity(self) -> int:
        return self.columns["t"].shape[0]

    def _reserve(self, n_new: int) -> None:
        need = self.n_rows + n_new
        if need <= self.capacity:
            return
        cap = _bucket_cap(need, self.chunk_rows)
        # pad in place of zeros + update: a grown 2^26-row store is
        # 6 GiB on a v5e, and old + zeros + grown would not fit in HBM
        with span("sink.grow", capacity=cap):
            self.columns = {
                k: jnp.pad(v, ((0, cap - v.shape[0]),)
                           + ((0, 0),) * (v.ndim - 1))
                for k, v in self.columns.items()}

    # -- ingestion -----------------------------------------------------
    def ingest_fused(self, traces, out_vecs, *, stream_id: int = 0,
                     t0: int = 0) -> int:
        """Land a full ``run_skyscraper_fused`` run: ``traces`` is the
        engine's stacked outs dict ((n_w, W) device leaves), ``out_vecs``
        the (T, D) per-segment output/embedding block (e.g. the measured
        quality vectors). Returns the number of rows appended."""
        T = int(out_vecs.shape[0])
        assert out_vecs.ndim == 2 and out_vecs.shape[1] == self.out_dim, \
            f"out_vecs must be (T, {self.out_dim})"
        self._reserve(T)
        sub = {src: traces[src] for src, _ in _RUN_KEYS}
        sstates, sfvals, sspecs = _standing_args(self)
        res = _ingest_fused(
            self.columns, sub, jnp.asarray(out_vecs, jnp.float32),
            jnp.int32(stream_id), jnp.int32(t0), jnp.int32(self.n_rows),
            sstates, sfvals, T=T, sspecs=sspecs)
        if sspecs:
            self.columns, states = res
            self.standing.absorb(states)
        else:
            self.columns = res
        self.n_rows += T
        self.t_max = max(self.t_max, t0 + T - 1)
        store_obs_batch(self.obs, 1, T)
        return T

    def ingest_fused_multi(self, traces, out_vecs, *, stream_base: int = 0,
                           t0: int = 0) -> int:
        """Land a full ``run_skyscraper_multi`` run: traces have
        (n_w, V, W) device leaves, ``out_vecs`` is (V, T, D)."""
        V, T = int(out_vecs.shape[0]), int(out_vecs.shape[1])
        assert out_vecs.ndim == 3 and out_vecs.shape[2] == self.out_dim
        self._reserve(V * T)
        sub = {src: traces[src] for src, _ in _RUN_KEYS}
        sstates, sfvals, sspecs = _standing_args(self)
        res = _ingest_fused_multi(
            self.columns, sub, jnp.asarray(out_vecs, jnp.float32),
            jnp.int32(stream_base), jnp.int32(t0), jnp.int32(self.n_rows),
            sstates, sfvals, T=T, sspecs=sspecs)
        if sspecs:
            self.columns, states = res
            self.standing.absorb(states)
        else:
            self.columns = res
        self.n_rows += V * T
        self.t_max = max(self.t_max, t0 + T - 1)
        store_obs_batch(self.obs, V, T)
        return V * T

    def ingest_tick(self, traces, *, quality, out_vecs, t: int,
                    stream_ids=None, valid=None) -> int:
        """Land one serving-pool tick: traces have (V,) device leaves
        (a ``switch_step_multi`` outs dict); ``quality`` (V,) is the
        measured quality reported by the user's Transform.

        The elastic pool passes ``stream_ids`` (V,) — the REAL stream
        id behind each slot — and ``valid`` (V,) host bool: inactive
        slots land no row (the masked kernel compacts active rows to
        consecutive positions). Defaults keep the fixed-pool contract:
        slot v IS stream v, every slot lands."""
        V = int(out_vecs.shape[0])
        assert out_vecs.ndim == 2 and out_vecs.shape[1] == self.out_dim
        keep = None if valid is None else np.asarray(valid, bool)
        n_new = V if keep is None else int(keep.sum())
        with span("sink.ingest", t=t, rows=n_new):
            self._reserve(n_new)
            sub = {src: traces[src] for src, _ in _RUN_KEYS}
            sstates, sfvals, sspecs = _standing_args(self)
            obs = self.obs
            args = (self.columns, sub,
                    store_put(obs, quality, jnp.float32),
                    store_put(obs, out_vecs, jnp.float32),
                    store_put(obs, t, jnp.int32),
                    store_put(obs, self.n_rows, jnp.int32))
            if stream_ids is None and keep is None:
                res = _ingest_tick(*args, sstates, sfvals, sspecs=sspecs)
            else:
                ids = (np.arange(V) if stream_ids is None
                       else np.asarray(stream_ids))
                res = _ingest_tick_masked(
                    *args, store_put(obs, ids, jnp.int32),
                    store_put(obs, np.ones(V, bool) if keep is None
                              else keep),
                    sstates, sfvals, sspecs=sspecs)
            if sspecs:
                self.columns, states = res
                self.standing.absorb(states)
            else:
                self.columns = res
        self.n_rows += n_new
        if n_new:
            self.t_max = max(self.t_max, t)
        store_obs_tick(self.obs, n_new)
        return n_new

    def append_rows(self, rows: Dict[str, jnp.ndarray]) -> int:
        """Generic batched append: ``rows`` maps every column name to an
        (n,) array (``out`` to (n, D)). Host-facing convenience for
        tests and manual loads."""
        n = int(np.shape(rows["t"])[0])
        assert set(rows) == set(self.columns), \
            f"need exactly columns {sorted(self.columns)}"
        self._reserve(n)
        upd = {k: jnp.asarray(v) for k, v in rows.items()}
        sstates, sfvals, sspecs = _standing_args(self)
        if sspecs:
            self.columns, states = _scatter_fold(
                self.columns, upd, jnp.int32(self.n_rows), sstates,
                sfvals, sspecs=sspecs)
            self.standing.absorb(states)
        else:
            self.columns = _scatter(self.columns, upd,
                                    jnp.int32(self.n_rows))
        self.n_rows += n
        self.t_max = max(self.t_max, int(np.max(np.asarray(rows["t"]))))
        store_obs_tick(self.obs, n)
        return n

    # -- reading -------------------------------------------------------
    def query(self, plan, **kw):
        """Run a compiled query plan over the live rows (see
        ``warehouse.query``; ``use_pallas=`` selects the aggregation
        kernel)."""
        from repro.warehouse import query as Q
        self.obs["query_dispatches"] += 1
        return Q.execute(self, plan, **kw)

    def telemetry(self) -> StoreTelemetry:
        """Warehouse flight recorder: row counts, ingest/query dispatch
        counts, and ingest-to-queryable lag — all from host metadata,
        zero device reads. Counters are per live instance (a store
        rebuilt through pytree unflatten starts fresh)."""
        return StoreTelemetry(rows_by_shard=np.asarray([self.n_rows]),
                              **self.obs)

    def host_rows(self) -> Dict[str, np.ndarray]:
        """All live rows as host numpy (an explicit full transfer — for
        tests, references, and exports; the query path never needs it).
        """
        return {k: np.asarray(v)[: self.n_rows]
                for k, v in self.columns.items()}

    def __len__(self) -> int:
        return self.n_rows

    def __repr__(self) -> str:
        return (f"SegmentStore(rows={self.n_rows}, cap={self.capacity}, "
                f"out_dim={self.out_dim}, chunk={self.chunk_rows})")


def _store_flatten(s: SegmentStore):
    keys = tuple(sorted(s.columns))
    return (tuple(s.columns[k] for k in keys),
            (keys, s.out_dim, s.chunk_rows, s.n_rows, s.t_max))


def _store_unflatten(aux, children) -> SegmentStore:
    keys, out_dim, chunk_rows, n_rows, t_max = aux
    s = SegmentStore.__new__(SegmentStore)
    s.out_dim, s.chunk_rows = out_dim, chunk_rows
    s.n_rows, s.t_max = n_rows, t_max
    s.columns = dict(zip(keys, children))
    # fresh counters: mutable host state can't ride through aux (it
    # must stay hashable and stable), so telemetry isn't checkpointed;
    # same for standing registries (re-register after a reload)
    s.obs = store_obs_init()
    s.standing = None
    return s


jax.tree_util.register_pytree_node(SegmentStore, _store_flatten,
                                   _store_unflatten)

register_cache_probe(
    "warehouse_append",
    lambda: (_scatter._cache_size() + _scatter_fold._cache_size()
             + _ingest_fused._cache_size()
             + _ingest_fused_multi._cache_size()
             + _ingest_tick._cache_size()))
register_engine("warehouse_scatter", example_builder("store_scatter"),
                probe=lambda: _scatter._cache_size(),
                covers=("repro.warehouse.store:_scatter",),
                probe_name="warehouse_append")
# ingest + standing-query refresh fused into ONE executable: the same
# append/tick kernels with the stacked standing state threaded through
register_engine("warehouse_scatter_standing",
                example_builder("store_scatter_standing"),
                probe=lambda: _scatter_fold._cache_size(),
                covers=("repro.warehouse.store:_scatter_fold",),
                probe_name="warehouse_append")
register_engine("warehouse_ingest_tick_standing",
                example_builder("store_ingest_tick_standing"),
                probe=lambda: _ingest_tick._cache_size(),
                probe_name="warehouse_append")
register_engine("warehouse_ingest_fused",
                example_builder("store_ingest_fused"),
                probe=lambda: _ingest_fused._cache_size(),
                covers=("repro.warehouse.store:_ingest_fused",),
                probe_name="warehouse_append")
register_engine("warehouse_ingest_fused_multi",
                example_builder("store_ingest_fused_multi"),
                probe=lambda: _ingest_fused_multi._cache_size(),
                covers=("repro.warehouse.store:_ingest_fused_multi",),
                probe_name="warehouse_append")
register_engine("warehouse_ingest_tick",
                example_builder("store_ingest_tick"),
                probe=lambda: _ingest_tick._cache_size(),
                covers=("repro.warehouse.store:_ingest_tick",),
                probe_name="warehouse_append")
register_cache_probe("warehouse_tick_masked",
                     lambda: _ingest_tick_masked._cache_size())
register_engine("warehouse_ingest_tick_masked",
                example_builder("store_ingest_tick_masked"),
                probe=lambda: _ingest_tick_masked._cache_size(),
                covers=("repro.warehouse.store:_ingest_tick_masked",),
                probe_name="warehouse_tick_masked")


# ---------------------------------------------------------------------------
# sharded store: stream-hash partitioned rows across a device mesh
# ---------------------------------------------------------------------------

def _route_write(cols, n_rows, upd, owner, shard_id):
    """ONE shard's slice of a routed append. Rows whose ``owner`` equals
    ``shard_id`` scatter at consecutive positions starting at this
    shard's ``n_rows`` offset (rank = exclusive cumsum of the ownership
    mask); every other row's index points past the capacity and the
    scatter drops it — so all shards run the identical fixed-shape
    program on the identical replicated update block, and each keeps
    exactly its own rows. No host gathers, no data-dependent shapes."""
    cap = next(iter(cols.values())).shape[0]
    own = owner == shard_id
    rank = jnp.cumsum(own.astype(jnp.int32)) - 1
    idx = jnp.where(own, n_rows + rank, cap)
    new = {k: cols[k].at[idx].set(upd[k].astype(cols[k].dtype),
                                  mode="drop") for k in cols}
    return new, n_rows + own.sum(dtype=jnp.int32)


def _append_traced(cols, n_rows, upd, mesh, n_shards, sstates=(),
                   sfvals=(), sspecs=(), valid=None):
    """Routed append over all shards: shard_map on the mesh (one
    collective-free dispatch, each device writes its own block) or the
    vmapped stacked fallback. ``upd`` maps every column to an (n, ...)
    replicated update block; ownership is ``stream_id % n_shards``.

    With standing queries registered (``sspecs`` non-empty) each shard
    ALSO folds the rows it owns into its slice of the stacked standing
    state — the ownership mask doubles as the fold mask, so a row's
    contribution lands exactly once, on the shard that stores the row,
    inside this same dispatch. The return grows a third element (the
    folded state tuple); the empty-``sspecs`` trace is unchanged.

    ``valid`` (n,) bool, when given, marks rows that must NOT land
    anywhere (the elastic pool's retired/empty slots): their owner is
    forced past the last shard id, so the routed scatter drops them and
    the standing folds never see them — the default ``None`` traces the
    exact pre-elastic program."""
    owner = upd["stream_id"].astype(jnp.int32) % n_shards
    if valid is not None:
        owner = jnp.where(jnp.asarray(valid, bool), owner,
                          jnp.int32(n_shards))
    if mesh is None:
        sids = jnp.arange(n_shards, dtype=jnp.int32)
        if not sspecs:
            with jax.named_scope("sink.write"):
                return jax.vmap(lambda c, nr, s: _route_write(
                    c, nr, upd, owner, s))(cols, n_rows, sids)

        def one(c, nr, s, sts):
            with jax.named_scope("sink.write"):
                new, nn = _route_write(c, nr, upd, owner, s)
            with jax.named_scope("sink.fold"):
                cast = {k: upd[k].astype(c[k].dtype) for k in upd}
                states = _fold_all(sts, sfvals, cast, owner == s, sspecs)
            return new, nn, states

        return jax.vmap(one)(cols, n_rows, sids, sstates)

    def body(c, nr, u, ow, sts, fvs):
        c0 = {k: v[0] for k, v in c.items()}
        sid = jax.lax.axis_index("shard")
        with jax.named_scope("sink.write"):
            new, n2 = _route_write(c0, nr[0], u, ow, sid)
        stacked = {k: v[None] for k, v in new.items()}
        if not sspecs:
            return stacked, n2[None]
        with jax.named_scope("sink.fold"):
            cast = {k: u[k].astype(c0[k].dtype) for k in u}
            states = _fold_all(jax.tree.map(lambda x: x[0], sts), fvs,
                               cast, ow == sid, sspecs)
        return stacked, n2[None], jax.tree.map(lambda x: x[None], states)

    out_specs = (P("shard"), P("shard")) if not sspecs \
        else (P("shard"), P("shard"), P("shard"))
    return shard_map(body, mesh=mesh,
                     in_specs=(P("shard"), P("shard"), P(), P(),
                               P("shard"), P()),
                     out_specs=out_specs,
                     check_vma=False)(cols, n_rows, upd, owner, sstates,
                                      sfvals)


# (kind, mesh, n_shards) -> jitted kernel; plain dict so the cache probe
# can sum executable counts
_SHARD_KERNELS: Dict = {}


def _shard_kernel(kind: str, mesh, n_shards: int):
    key = (kind, mesh, n_shards)
    kern = _SHARD_KERNELS.get(key)
    if kern is not None:
        return kern
    if kind == "append":
        @functools.partial(jax.jit, static_argnames=("sspecs",))
        def kern(cols, n_rows, upd, sstates=(), sfvals=(), *, sspecs=()):
            return _append_traced(cols, n_rows, upd, mesh, n_shards,
                                  sstates, sfvals, sspecs)
    elif kind == "fused_multi":
        @functools.partial(jax.jit, static_argnames=("T", "sspecs"))
        def kern(cols, n_rows, traces, out_vecs, stream_base, t0,
                 sstates=(), sfvals=(), *, T, sspecs=()):
            V = out_vecs.shape[0]

            def flat(x):                      # (n_w, V, W) -> (V*T,)
                return jnp.swapaxes(x, 0, 1).reshape(V, -1)[:, :T] \
                    .reshape(-1)

            upd = {dst: flat(traces[src]) for src, dst in _RUN_KEYS}
            upd["stream_id"] = (stream_base
                                + jnp.repeat(jnp.arange(V, dtype=jnp.int32),
                                             T))
            upd["t"] = t0 + jnp.tile(jnp.arange(T, dtype=jnp.int32), V)
            upd[OUT_COLUMN] = out_vecs.reshape(V * T, -1)
            return _append_traced(cols, n_rows, upd, mesh, n_shards,
                                  sstates, sfvals, sspecs)
    elif kind == "tick":
        @functools.partial(jax.jit, static_argnames=("sspecs",))
        def kern(cols, n_rows, traces, quality, out_vecs, t,
                 sstates=(), sfvals=(), *, sspecs=()):
            V = quality.shape[0]
            upd = {dst: traces[src] for src, dst in _RUN_KEYS}
            upd["quality"] = quality
            upd["stream_id"] = jnp.arange(V, dtype=jnp.int32)
            upd["t"] = jnp.full((V,), t, jnp.int32)
            upd[OUT_COLUMN] = out_vecs
            return _append_traced(cols, n_rows, upd, mesh, n_shards,
                                  sstates, sfvals, sspecs)
    elif kind == "tick_ids":
        @functools.partial(jax.jit, static_argnames=("sspecs",))
        def kern(cols, n_rows, traces, quality, out_vecs, t, stream_ids,
                 valid, sstates=(), sfvals=(), *, sspecs=()):
            V = quality.shape[0]
            upd = {dst: traces[src] for src, dst in _RUN_KEYS}
            upd["quality"] = quality
            upd["stream_id"] = stream_ids.astype(jnp.int32)
            upd["t"] = jnp.full((V,), t, jnp.int32)
            upd[OUT_COLUMN] = out_vecs
            return _append_traced(cols, n_rows, upd, mesh, n_shards,
                                  sstates, sfvals, sspecs, valid=valid)
    else:
        raise ValueError(kind)
    _SHARD_KERNELS[key] = kern
    return kern


def _sharded_append_cache_size():
    return sum(k._cache_size() for k in _SHARD_KERNELS.values())


register_cache_probe("warehouse_append_sharded", _sharded_append_cache_size)
register_engine("warehouse_append_sharded",
                example_builder("store_sharded", "append"),
                probe=_sharded_append_cache_size,
                probe_name="warehouse_append_sharded")
register_engine("warehouse_ingest_sharded_fused",
                example_builder("store_sharded", "fused_multi"),
                probe=_sharded_append_cache_size,
                probe_name="warehouse_append_sharded")
register_engine("warehouse_ingest_sharded_tick",
                example_builder("store_sharded", "tick"),
                probe=_sharded_append_cache_size,
                probe_name="warehouse_append_sharded")
register_engine("warehouse_ingest_sharded_standing",
                example_builder("store_sharded_standing"),
                probe=_sharded_append_cache_size,
                probe_name="warehouse_append_sharded")
register_engine("warehouse_ingest_sharded_tick_ids",
                example_builder("store_sharded", "tick_ids"),
                probe=_sharded_append_cache_size,
                probe_name="warehouse_append_sharded")


class ShardedStore:
    """Stream-hash partitioned ``SegmentStore`` across a device mesh.

    Columns are stacked ``(n_shards, cap, ...)`` device arrays with the
    leading axis split over a 1-D ``'shard'`` mesh (one shard per
    device, see ``launch.mesh.make_shard_mesh``); row ``r`` of stream
    ``s`` lives on shard ``s % n_shards``. Every ingest path
    (``ingest_fused`` / ``ingest_fused_multi`` / ``ingest_tick`` /
    ``append_rows``) is ONE jitted shard_map dispatch that routes each
    row to its owning shard device-side, and ``query`` executes plans
    through the partial/merge engine as ONE shard_map dispatch of the
    per-shard partial kernel plus a collective merge. On hosts with
    fewer devices than shards the identical kernels run vmapped over
    the stacked axis (``mesh is None``) — same semantics, one device.

    Host-side bookkeeping (per-shard row counts, ``t_max``) is computed
    from ingest METADATA (stream ids and row counts the caller already
    knows) — the data itself never round-trips."""

    def __init__(self, out_dim: int, n_shards: int,
                 chunk_rows: int = 8192, mesh="auto"):
        assert out_dim >= 1 and n_shards >= 1 and chunk_rows >= 1
        self.out_dim = int(out_dim)
        self.n_shards = int(n_shards)
        self.chunk_rows = int(chunk_rows)
        self.mesh = make_shard_mesh(n_shards) if mesh == "auto" else mesh
        self.t_max = -1
        self.n_rows_by_shard = np.zeros(self.n_shards, np.int64)
        self.columns = self._put(self._empty(0))
        self.n_rows_dev = self._put(jnp.zeros((self.n_shards,), jnp.int32))
        self.obs = store_obs_init()
        self.standing = None

    def _put(self, tree):
        return put_row_sharded(tree, self.mesh) if self.mesh is not None \
            else tree

    @classmethod
    def _from_parts(cls, *, out_dim, n_shards, chunk_rows, mesh, columns,
                    n_rows_dev, n_rows_by_shard, t_max):
        """Adopt already-partitioned device columns without an ingest
        pass — the constructor ``runtime.elastic.rebalance`` uses to
        wrap its one-dispatch repartition output. Host bookkeeping
        (per-shard counts) comes from the caller; obs counters and the
        standing registry start fresh (rebalance re-registers)."""
        self = cls.__new__(cls)
        self.out_dim = int(out_dim)
        self.n_shards = int(n_shards)
        self.chunk_rows = int(chunk_rows)
        self.mesh = mesh
        self.t_max = int(t_max)
        self.n_rows_by_shard = np.asarray(n_rows_by_shard,
                                          np.int64).copy()
        self.columns = columns
        self.n_rows_dev = n_rows_dev
        self.obs = store_obs_init()
        self.standing = None
        return self

    def _empty(self, cap: int) -> Dict[str, jnp.ndarray]:
        cols = {n: jnp.zeros((self.n_shards, cap), dt)
                for n, dt in SCALAR_COLUMNS}
        cols[OUT_COLUMN] = jnp.zeros((self.n_shards, cap, self.out_dim),
                                     jnp.float32)
        return cols

    # -- capacity ------------------------------------------------------
    @property
    def capacity(self) -> int:
        """Per-shard row capacity."""
        return self.columns["t"].shape[1]

    @property
    def n_rows(self) -> int:
        return int(self.n_rows_by_shard.sum())

    def _reserve(self, incoming_by_shard: np.ndarray) -> None:
        """Grow every shard's capacity (uniformly, chunk-aligned,
        geometric) to fit the incoming per-shard row counts."""
        need = int((self.n_rows_by_shard + incoming_by_shard).max())
        if need <= self.capacity:
            return
        cap = _bucket_cap(need, self.chunk_rows)
        pad = cap - self.capacity
        with span("sink.grow", capacity=cap):
            grown = {k: jnp.pad(v, ((0, 0), (0, pad))
                                + ((0, 0),) * (v.ndim - 2))
                     for k, v in self.columns.items()}
            self.columns = self._put(grown)

    # -- ingestion -----------------------------------------------------
    def _owner_counts(self, stream_ids) -> np.ndarray:
        return np.bincount(np.asarray(stream_ids, np.int64)
                           % self.n_shards, minlength=self.n_shards)

    def ingest_fused(self, traces, out_vecs, *, stream_id: int = 0,
                     t0: int = 0) -> int:
        """Land a full single-stream fused run (``(n_w, W)`` trace
        leaves): all T rows route to shard ``stream_id % n_shards``."""
        T = int(out_vecs.shape[0])
        assert out_vecs.ndim == 2 and out_vecs.shape[1] == self.out_dim
        # (n_w, W) -> (n_w, 1, W): the multi kernel with V=1
        sub = {src: traces[src][:, None] for src, _ in _RUN_KEYS}
        return self._ingest_multi(sub, jnp.asarray(out_vecs,
                                                   jnp.float32)[None],
                                  stream_base=stream_id, t0=t0)

    def ingest_fused_multi(self, traces, out_vecs, *,
                           stream_base: int = 0, t0: int = 0) -> int:
        """Land a full multi-stream fused run (``(n_w, V, W)`` leaves):
        stream ``v``'s trace routes to shard
        ``(stream_base + v) % n_shards`` — ONE shard_map dispatch, no
        host gathers."""
        assert out_vecs.ndim == 3 and out_vecs.shape[2] == self.out_dim
        sub = {src: traces[src] for src, _ in _RUN_KEYS}
        return self._ingest_multi(sub, jnp.asarray(out_vecs, jnp.float32),
                                  stream_base=stream_base, t0=t0)

    def _ingest_multi(self, sub, out_vecs, *, stream_base, t0) -> int:
        V, T = int(out_vecs.shape[0]), int(out_vecs.shape[1])
        counts = self._owner_counts(stream_base + np.arange(V)) * T
        self._reserve(counts)
        kern = _shard_kernel("fused_multi", self.mesh, self.n_shards)
        sstates, sfvals, sspecs = _standing_args(self)
        res = kern(self.columns, self.n_rows_dev, sub, out_vecs,
                   jnp.int32(stream_base), jnp.int32(t0), sstates,
                   sfvals, T=T, sspecs=sspecs)
        if sspecs:
            self.columns, self.n_rows_dev, states = res
            self.standing.absorb(states)
        else:
            self.columns, self.n_rows_dev = res
        self.n_rows_by_shard += counts
        self.t_max = max(self.t_max, t0 + T - 1)
        store_obs_batch(self.obs, V, T)
        return V * T

    def ingest_tick(self, traces, *, quality, out_vecs, t: int,
                    stream_ids=None, valid=None) -> int:
        """Land one serving-pool tick (V rows, stream v -> shard
        ``v % n_shards``). ``stream_ids`` / ``valid`` route the elastic
        pool's slot axis: each active slot's row goes to the shard
        owning its REAL stream id, inactive slots land nothing — same
        single routed dispatch (see ``SegmentStore.ingest_tick``)."""
        V = int(out_vecs.shape[0])
        assert out_vecs.ndim == 2 and out_vecs.shape[1] == self.out_dim
        if stream_ids is None and valid is None:
            ids = keep = None
            counts = self._owner_counts(np.arange(V))
        else:
            ids = (np.arange(V) if stream_ids is None
                   else np.asarray(stream_ids))
            keep = (np.ones(V, bool) if valid is None
                    else np.asarray(valid, bool))
            counts = np.bincount(ids[keep].astype(np.int64)
                                 % self.n_shards,
                                 minlength=self.n_shards)
        n_new = int(counts.sum())
        with span("sink.ingest", t=t, rows=n_new):
            self._reserve(counts)
            sub = {src: traces[src] for src, _ in _RUN_KEYS}
            sstates, sfvals, sspecs = _standing_args(self)
            obs = self.obs
            args = (self.columns, self.n_rows_dev, sub,
                    store_put(obs, quality, jnp.float32),
                    store_put(obs, out_vecs, jnp.float32),
                    store_put(obs, t, jnp.int32))
            if ids is None:
                kern = _shard_kernel("tick", self.mesh, self.n_shards)
                res = kern(*args, sstates, sfvals, sspecs=sspecs)
            else:
                kern = _shard_kernel("tick_ids", self.mesh, self.n_shards)
                res = kern(*args, store_put(obs, ids, jnp.int32),
                           store_put(obs, keep), sstates, sfvals,
                           sspecs=sspecs)
            if sspecs:
                self.columns, self.n_rows_dev, states = res
                self.standing.absorb(states)
            else:
                self.columns, self.n_rows_dev = res
        self.n_rows_by_shard += counts
        if n_new:
            self.t_max = max(self.t_max, t)
        store_obs_tick(self.obs, n_new)
        return n_new

    def append_rows(self, rows: Dict[str, jnp.ndarray]) -> int:
        """Generic batched append, routed by the rows' own stream ids."""
        n = int(np.shape(rows["t"])[0])
        assert set(rows) == {c for c, _ in SCALAR_COLUMNS} | {OUT_COLUMN}, \
            "need exactly the store's columns"
        counts = self._owner_counts(rows["stream_id"])
        self._reserve(counts)
        upd = {k: jnp.asarray(v) for k, v in rows.items()}
        kern = _shard_kernel("append", self.mesh, self.n_shards)
        sstates, sfvals, sspecs = _standing_args(self)
        res = kern(self.columns, self.n_rows_dev, upd, sstates, sfvals,
                   sspecs=sspecs)
        if sspecs:
            self.columns, self.n_rows_dev, states = res
            self.standing.absorb(states)
        else:
            self.columns, self.n_rows_dev = res
        self.n_rows_by_shard += counts
        if n:
            self.t_max = max(self.t_max,
                             int(np.max(np.asarray(rows["t"]))))
        store_obs_tick(self.obs, n)
        return n

    # -- reading -------------------------------------------------------
    def shard_source(self):
        """(stacked columns, per-shard valid row counts) — what the
        sharded query kernel consumes."""
        return self.columns, self.n_rows_dev

    def query(self, plan, **kw):
        """ONE shard_map dispatch: per-shard partial kernel + merge
        combiner (see ``warehouse.query.execute_sharded``)."""
        from repro.warehouse import query as Q
        self.obs["query_dispatches"] += 1
        return Q.execute_sharded(self, plan, **kw)

    def telemetry(self) -> StoreTelemetry:
        """Warehouse flight recorder incl. per-shard balance: the
        imbalance factor (max/mean shard rows) comes straight off the
        ``n_rows_by_shard`` host metadata — zero device reads."""
        return StoreTelemetry(
            rows_by_shard=self.n_rows_by_shard.copy(), **self.obs)

    def host_rows(self) -> Dict[str, np.ndarray]:
        """All live rows as host numpy, shard-major (an explicit full
        transfer — tests/exports only; the query path never needs it)."""
        out = {}
        for k, v in self.columns.items():
            h = np.asarray(v)
            out[k] = np.concatenate(
                [h[s, : self.n_rows_by_shard[s]]
                 for s in range(self.n_shards)])
        return out

    def __len__(self) -> int:
        return self.n_rows

    def __repr__(self) -> str:
        dev = "mesh" if self.mesh is not None else "stacked"
        return (f"ShardedStore(shards={self.n_shards}[{dev}], "
                f"rows={self.n_rows_by_shard.tolist()}, "
                f"cap={self.capacity}, out_dim={self.out_dim}, "
                f"chunk={self.chunk_rows})")
