"""Warehouse tiering + persistence.

Hot tier: the fp32 ``SegmentStore`` chunks that queries touch most.
Cold tier: older chunks spilled to int8 with one quantization scale per
chunk (reusing ``distribution.compression.quantize_int8``, so the cold
tier inherits its stochastic-rounding error bound: per-element error is
at most the chunk's scale = max|x|/127). Integer columns spill
losslessly. ``spill`` moves whole chunks so every tier keeps
chunk-aligned shapes and the jit executables stay shared.

Queries run over BOTH tiers: ``materialize`` dequantizes the cold
chunks and concatenates them in front of the hot columns in one jitted
device op, and the compiled query kernel scans the combined table —
fp32-exact on the hot rows, within quantization tolerance on cold ones.

``save_warehouse``/``load_warehouse`` persist the whole thing through
``checkpoint/ckpt.py`` (atomic, mesh-agnostic, host-count independent),
so a warehouse survives process restart onto any topology: the hot tier
round-trips bit-exact (raw fp32 bytes), the cold tier's int8 codes and
scales likewise.
"""
from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import ckpt
from repro.distribution.compression import dequantize, quantize_int8
from repro.obs.telemetry import StoreTelemetry
from repro.warehouse.store import SegmentStore, ShardedStore, _bucket_cap


def _tier_obs_init():
    """Host-side tier counters (see ``telemetry()``): chunk spills and
    cold-tier dequantize (materialize cache-miss) events."""
    return {"spill_events": 0, "spilled_rows": 0, "dequantize_events": 0}


@functools.partial(jax.jit, static_argnames=("n", "chunk"))
def _quantize_chunks(cols, key, *, n: int, chunk: int):
    """Quantize the first ``n`` rows (a whole number of chunks) of every
    float column to int8 with a per-chunk scale; integer columns pass
    through. Output/embedding rows quantize with their chunk flattened
    so the (chunk, D) block shares one scale."""
    n_chunks = n // chunk
    keys = jax.random.split(key, n_chunks)
    q, scales, ints = {}, {}, {}
    for name, col in cols.items():
        block = col[:n]
        if col.dtype == jnp.float32:
            flat = block.reshape(n_chunks, -1)
            qq, ss = jax.vmap(quantize_int8)(flat, keys)
            q[name] = qq.reshape(block.shape)
            scales[name] = ss
        else:
            ints[name] = block
    return q, scales, ints


@functools.partial(jax.jit, static_argnames=("n_spill",))
def _compact(cols, *, n_spill: int):
    """Drop the spilled prefix from the hot tier: shift the survivors to
    row 0 and zero the tail (capacity unchanged)."""
    return {k: jnp.concatenate(
        [v[n_spill:], jnp.zeros((n_spill,) + v.shape[1:], v.dtype)])
        for k, v in cols.items()}


@functools.partial(jax.jit, static_argnames=("chunk",))
def _materialize(cold_q, cold_scales, cold_int, hot_cols, *, chunk: int):
    """Combined view for the query kernel: dequantized cold rows
    followed by the hot columns, one device op."""
    out = {}
    for name, hot in hot_cols.items():
        if name in cold_q:
            qq = cold_q[name]
            n_chunks = qq.shape[0] // chunk
            deq = jax.vmap(dequantize)(qq.reshape(n_chunks, -1),
                                       cold_scales[name])
            cold = deq.reshape(qq.shape).astype(hot.dtype)
        else:
            cold = cold_int[name]
        out[name] = jnp.concatenate([cold, hot])
    return out


class TieredStore:
    """A ``SegmentStore`` hot tier plus an int8 cold tier it spills to."""

    def __init__(self, hot: SegmentStore, seed: int = 0):
        self.hot = hot
        self.seed = int(seed)
        self.n_cold = 0
        self.cold_q: Dict[str, jnp.ndarray] = {}
        self.cold_scales: Dict[str, jnp.ndarray] = {}
        self.cold_int: Dict[str, jnp.ndarray] = {}
        # memoized combined view; keyed on the hot columns object (every
        # append/spill replaces that dict) + the cold row count. A hot
        # write consumes the arrays of the dict kept here (the store's
        # kernels donate them), so the key is compared by identity and
        # never read; the view itself is a fresh copy and stays valid
        self._mat_cache = None
        self.tier_obs = _tier_obs_init()

    @property
    def n_rows(self) -> int:
        return self.n_cold + self.hot.n_rows

    @property
    def t_max(self) -> int:
        return self.hot.t_max

    def spill(self, keep_hot: int) -> int:
        """Move the oldest whole chunks to the cold tier until at most
        ``keep_hot`` rows (rounded up to a chunk) stay hot. Returns the
        number of rows spilled.

        Standing queries (``warehouse.standing``) are spill-invariant:
        every row's exact fp32 contribution folded into the stored
        partials when the row was INGESTED, so demoting rows to int8
        afterwards cannot touch a registered answer — only rescans (and
        backfills of queries registered after the spill) see the
        quantized values."""
        # keep_hot >= 0 keeps n_spill <= n_rows: capacity padding can
        # never enter the cold tier as phantom data
        assert keep_hot >= 0, keep_hot
        chunk = self.hot.chunk_rows
        n_spill = ((self.hot.n_rows - keep_hot) // chunk) * chunk
        if n_spill <= 0:
            return 0
        key = jax.random.fold_in(jax.random.PRNGKey(self.seed), self.n_cold)
        q, scales, ints = _quantize_chunks(self.hot.columns, key,
                                           n=n_spill, chunk=chunk)
        if self.n_cold:
            q = {k: jnp.concatenate([self.cold_q[k], v])
                 for k, v in q.items()}
            scales = {k: jnp.concatenate([self.cold_scales[k], v])
                      for k, v in scales.items()}
            ints = {k: jnp.concatenate([self.cold_int[k], v])
                    for k, v in ints.items()}
        self.cold_q, self.cold_scales, self.cold_int = q, scales, ints
        self.n_cold += n_spill
        self.hot.columns = _compact(self.hot.columns, n_spill=n_spill)
        self.hot.n_rows -= n_spill
        self.tier_obs["spill_events"] += 1
        self.tier_obs["spilled_rows"] += n_spill
        return n_spill

    def materialize(self) -> Tuple[Dict[str, jnp.ndarray], int]:
        """(columns, n_rows) spanning both tiers — what the compiled
        query kernel scans. Valid rows stay a prefix: cold rows are
        oldest-first, hot live rows are a prefix of the hot arrays.
        Memoized: repeat queries between appends/spills reuse the
        combined view instead of re-dequantizing the cold tier."""
        if self.n_cold == 0:
            return self.hot.columns, self.hot.n_rows
        c = self._mat_cache
        if c is not None and c[0] is self.hot.columns \
                and c[1] == self.n_cold:
            return c[2], self.n_rows
        cols = _materialize(self.cold_q, self.cold_scales, self.cold_int,
                            self.hot.columns, chunk=self.hot.chunk_rows)
        self._mat_cache = (self.hot.columns, self.n_cold, cols)
        self.tier_obs["dequantize_events"] += 1
        return cols, self.n_rows

    @property
    def standing(self):
        """The hot store's ``StandingQueries`` registry (None until one
        is attached — ``StandingQueries(tiered_store)`` attaches to the
        hot tier, whose ingest kernels do the folding, while backfills
        scan this wrapper's two-tier view)."""
        return self.hot.standing

    def query(self, plan, **kw):
        from repro.warehouse import query as Q
        self.hot.obs["query_dispatches"] += 1
        return Q.execute(self, plan, **kw)

    def telemetry(self) -> StoreTelemetry:
        """Hot-tier flight recorder merged with the tier counters:
        total rows span both tiers; spills/dequantizes count cold-tier
        movement (a dequantize event = a materialize cache miss)."""
        import dataclasses
        return dataclasses.replace(
            self.hot.telemetry(),
            rows_by_shard=np.asarray([self.n_rows]), **self.tier_obs)

    def max_cold_scale(self) -> float:
        """Largest per-chunk quantization scale across the cold tier —
        the per-element error bound of cold-row values."""
        if not self.cold_scales:
            return 0.0
        return max(float(jnp.max(s)) for s in self.cold_scales.values())

    def __repr__(self) -> str:
        return (f"TieredStore(hot={self.hot.n_rows}, cold={self.n_cold}, "
                f"chunk={self.hot.chunk_rows})")


# ---------------------------------------------------------------------------
# sharded tiering: every shard spills its own oldest chunks
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("n", "chunk"))
def _quantize_chunks_sharded(cols, key, *, n: int, chunk: int):
    """Per-shard ``_quantize_chunks``: quantize the first ``n`` rows of
    every shard's block with one scale per (shard, chunk)."""
    n_shards = next(iter(cols.values())).shape[0]
    keys = jax.random.split(key, n_shards)
    return jax.vmap(lambda c, k: _quantize_chunks(c, k, n=n,
                                                  chunk=chunk))(cols, keys)


@jax.jit
def _cold_write(dst, src, off):
    """Append each shard's spill block at that shard's own cold offset
    (``dst``/``src`` are dicts of (S, cap, ...) / (S, n, ...) arrays;
    ``off`` is (S,) int32). Rows past a shard's real spill depth are
    junk until a later spill overwrites them — they sit beyond the
    shard's valid cold count, so queries never see them."""
    def one(d, s, o):
        idx = (o,) + (0,) * (s.ndim - 1)
        return jax.lax.dynamic_update_slice(d, s.astype(d.dtype), idx)

    return {k: jax.vmap(one)(dst[k], src[k], off) for k in dst}


@jax.jit
def _compact_ragged(cols, d):
    """Drop the first ``d_s`` rows of every shard's hot block (per-shard
    dynamic depth), shifting survivors to row 0 and zero-filling the
    tail (capacity unchanged)."""
    def one(cols_s, d_s):
        def shift(v):
            idx = jnp.arange(v.shape[0]) + d_s
            return jnp.take(v, idx, axis=0, mode="fill", fill_value=0)

        return {k: shift(v) for k, v in cols_s.items()}

    return jax.vmap(one)(cols, d)


@functools.partial(jax.jit, static_argnames=("chunk",))
def _materialize_sharded(cold_q, cold_scales, cold_int, hot_cols, c, *,
                         chunk: int):
    """Combined two-tier view with per-shard cold depths: dequantize
    every shard's cold block, then land the hot block at that shard's
    own cold-valid offset ``c_s`` — so each shard's valid rows stay a
    prefix (c_s cold rows, then its hot rows) whatever the imbalance."""
    def one(q, s, i, h, c_s):
        out = {}
        for name, hot in h.items():
            if name in q:
                qq = q[name]
                n_chunks = qq.shape[0] // chunk
                deq = jax.vmap(dequantize)(qq.reshape(n_chunks, -1),
                                           s[name])
                cold = deq.reshape(qq.shape).astype(hot.dtype)
            else:
                cold = i[name]
            dst = jnp.concatenate([cold, jnp.zeros_like(hot)])
            idx = (c_s,) + (0,) * (hot.ndim - 1)
            out[name] = jax.lax.dynamic_update_slice(dst, hot, idx)
        return out

    return jax.vmap(one)(cold_q, cold_scales, cold_int, hot_cols, c)


class ShardedTieredStore:
    """Hot/cold tiering over a ``ShardedStore``: the spill is PER SHARD
    and RAGGED — each shard quantizes however many of its own oldest
    whole chunks exceed ``keep_hot`` (its own scales, one vmapped
    dispatch over the stacked shard axis), so an imbalanced or even
    permanently-empty shard never blocks the others from spilling.
    Cold blocks live in one capacity-padded stacked array with a
    per-shard valid depth; each shard's materialized rows are its valid
    cold rows followed by its hot rows (a per-shard-offset
    ``dynamic_update_slice``), keeping validity a prefix, and queries
    span both tiers through the same ONE-dispatch sharded partial/merge
    engine."""

    def __init__(self, hot: ShardedStore, seed: int = 0):
        self.hot = hot
        self.seed = int(seed)
        self._spills = 0
        self.n_cold_by_shard = np.zeros(hot.n_shards, np.int64)
        self.cold_q: Dict[str, jnp.ndarray] = {}
        self.cold_scales: Dict[str, jnp.ndarray] = {}
        self.cold_int: Dict[str, jnp.ndarray] = {}
        self._mat_cache = None
        self.tier_obs = _tier_obs_init()

    @property
    def n_shards(self) -> int:
        return self.hot.n_shards

    @property
    def mesh(self):
        return self.hot.mesh

    @property
    def n_rows(self) -> int:
        return int(self.n_cold_by_shard.sum()) + self.hot.n_rows

    @property
    def t_max(self) -> int:
        return self.hot.t_max

    @property
    def cold_capacity(self) -> int:
        return self.cold_q["quality"].shape[1] if self.cold_q else 0

    def _cold_reserve(self, need: int) -> None:
        """Grow the stacked cold arrays to fit the deepest shard's cold
        depth — on the same bucketed capacity ladder as the stores
        (``_bucket_cap``), so cold-tier growth never mints a new shape
        for the spill/materialize kernels either."""
        cap = self.cold_capacity
        if need <= cap:
            return
        chunk = self.hot.chunk_rows
        new_cap = _bucket_cap(need, chunk)

        def grow(tree, cap_units, unit):
            pad = (new_cap // unit) - cap_units
            return {k: jnp.pad(v, ((0, 0), (0, pad))
                               + ((0, 0),) * (v.ndim - 2))
                    for k, v in tree.items()}

        if not self.cold_q:     # first spill: build from the hot schema
            S = self.n_shards
            for name, col in self.hot.columns.items():
                tail = col.shape[2:]
                if col.dtype == jnp.float32:
                    self.cold_q[name] = jnp.zeros((S, new_cap) + tail,
                                                  jnp.int8)
                    self.cold_scales[name] = jnp.zeros(
                        (S, new_cap // chunk), jnp.float32)
                else:
                    self.cold_int[name] = jnp.zeros((S, new_cap) + tail,
                                                    col.dtype)
            return
        self.cold_q = grow(self.cold_q, cap, 1)
        self.cold_int = grow(self.cold_int, cap, 1)
        self.cold_scales = grow(self.cold_scales, cap // chunk, chunk)

    def spill(self, keep_hot: int) -> int:
        """Move each shard's oldest whole chunks to its cold tier until
        at most ``keep_hot`` rows (rounded up to a chunk) stay hot on
        that shard — depths are ragged across shards, so imbalanced or
        empty shards never block the rest. Returns total rows spilled.

        Spill-invariant for standing queries, exactly as on
        ``TieredStore.spill``: contributions folded at ingest, so the
        stored partials never see the quantization."""
        # keep_hot >= 0 keeps every depth <= that shard's live rows:
        # capacity padding can never enter the cold tier as phantom data
        assert keep_hot >= 0, keep_hot
        chunk = self.hot.chunk_rows
        d = np.maximum(
            ((self.hot.n_rows_by_shard - keep_hot) // chunk) * chunk, 0)
        d_max = int(d.max())
        if d_max <= 0:
            return 0
        # reserve the full d_max write window past EVERY shard's offset
        # (not just its own depth d_s): _cold_write lands a d_max-row
        # block at each shard's offset, and dynamic_update_slice CLAMPS
        # an out-of-range start backward — an unreserved junk tail would
        # silently overwrite the deepest shard's valid cold rows
        self._cold_reserve(int((self.n_cold_by_shard + d_max).max()))
        key = jax.random.fold_in(jax.random.PRNGKey(self.seed),
                                 self._spills)
        self._spills += 1
        # quantize the deepest depth on EVERY shard (static shape); a
        # shard whose own depth is smaller writes the extra rows as
        # junk past its valid cold count, where later spills overwrite
        # them — they are never queried and its hot copy stays live
        q, scales, ints = _quantize_chunks_sharded(
            self.hot.columns, key, n=d_max, chunk=chunk)
        off = jnp.asarray(self.n_cold_by_shard, jnp.int32)
        self.cold_q = _cold_write(self.cold_q, q, off)
        self.cold_int = _cold_write(self.cold_int, ints, off)
        self.cold_scales = _cold_write(self.cold_scales, scales,
                                       off // chunk)
        d_dev = jnp.asarray(d, jnp.int32)
        self.hot.columns = _compact_ragged(self.hot.columns, d_dev)
        self.hot.n_rows_by_shard = self.hot.n_rows_by_shard - d
        self.hot.n_rows_dev = self.hot.n_rows_dev - d_dev
        self.n_cold_by_shard += d
        self.tier_obs["spill_events"] += 1
        self.tier_obs["spilled_rows"] += int(d.sum())
        return int(d.sum())

    def shard_source(self):
        """(stacked columns spanning both tiers, per-shard valid counts):
        each shard's rows are its valid cold rows followed by its hot
        rows, so valid rows stay a per-shard prefix. Memoized like
        ``TieredStore.materialize``."""
        if not self.n_cold_by_shard.any():
            return self.hot.shard_source()
        cold_key = tuple(self.n_cold_by_shard)
        c = self._mat_cache
        off = jnp.asarray(self.n_cold_by_shard, jnp.int32)
        if c is not None and c[0] is self.hot.columns \
                and c[1] == cold_key:
            return c[2], off + self.hot.n_rows_dev
        cols = _materialize_sharded(self.cold_q, self.cold_scales,
                                    self.cold_int, self.hot.columns,
                                    off, chunk=self.hot.chunk_rows)
        self._mat_cache = (self.hot.columns, cold_key, cols)
        self.tier_obs["dequantize_events"] += 1
        return cols, off + self.hot.n_rows_dev

    @property
    def standing(self):
        """The hot store's ``StandingQueries`` registry (see
        ``TieredStore.standing``)."""
        return self.hot.standing

    def query(self, plan, **kw):
        from repro.warehouse import query as Q
        self.hot.obs["query_dispatches"] += 1
        return Q.execute_sharded(self, plan, **kw)

    def telemetry(self) -> StoreTelemetry:
        """Per-shard balance spans BOTH tiers (hot + that shard's cold
        depth), so the imbalance factor reflects where rows actually
        live, not just the hot residue after spills."""
        import dataclasses
        return dataclasses.replace(
            self.hot.telemetry(),
            rows_by_shard=(self.hot.n_rows_by_shard
                           + self.n_cold_by_shard),
            **self.tier_obs)

    def max_cold_scale(self) -> float:
        """Largest per-(shard, chunk) quantization scale across the cold
        tier — the per-element error bound of cold-row values."""
        if not self.cold_scales:
            return 0.0
        return max(float(jnp.max(s)) for s in self.cold_scales.values())

    def __repr__(self) -> str:
        return (f"ShardedTieredStore(shards={self.n_shards}, "
                f"hot={self.hot.n_rows_by_shard.tolist()}, "
                f"cold={self.n_cold_by_shard.tolist()}, "
                f"chunk={self.hot.chunk_rows})")


# ---------------------------------------------------------------------------
# persistence (through checkpoint/ckpt.py)
# ---------------------------------------------------------------------------

def save_warehouse(path: str, ts: TieredStore) -> str:
    """Atomic save of both tiers; restores onto any host/topology."""
    tree = {"hot": ts.hot.columns}
    if ts.n_cold:
        tree["cold"] = {"q": ts.cold_q, "scales": ts.cold_scales,
                        "ints": ts.cold_int}
    meta = {"n_rows": ts.hot.n_rows, "t_max": ts.hot.t_max,
            "out_dim": ts.hot.out_dim, "chunk_rows": ts.hot.chunk_rows,
            "n_cold": ts.n_cold, "seed": ts.seed}
    return ckpt.save(path, tree, meta=meta)


def load_warehouse(path: str) -> TieredStore:
    """Restore a ``save_warehouse`` checkpoint into a fresh hot
    ``SegmentStore`` wrapped in a ``TieredStore`` (cold tier re-attached
    from the saved metadata)."""
    tree, meta = ckpt.restore(path, return_meta=True)
    assert meta is not None, f"{path} is not a warehouse checkpoint"
    hot = SegmentStore(meta["out_dim"], chunk_rows=meta["chunk_rows"])
    hot.columns = tree["hot"]
    hot.n_rows = meta["n_rows"]
    hot.t_max = meta["t_max"]
    ts = TieredStore(hot, seed=meta["seed"])
    ts.n_cold = meta["n_cold"]
    if ts.n_cold:
        ts.cold_q = tree["cold"]["q"]
        ts.cold_scales = tree["cold"]["scales"]
        ts.cold_int = tree["cold"]["ints"]
    return ts


# ---- cache probes + static-analysis registry -------------------------------
from repro.analysis.registry import example_builder, register_engine  # noqa: E402
from repro.core.switcher import register_cache_probe  # noqa: E402

register_cache_probe(
    "warehouse_tiers",
    lambda: (_quantize_chunks._cache_size() + _compact._cache_size()
             + _materialize._cache_size()))
register_cache_probe(
    "warehouse_tiers_sharded",
    lambda: (_quantize_chunks_sharded._cache_size()
             + _cold_write._cache_size() + _compact_ragged._cache_size()
             + _materialize_sharded._cache_size()))

register_engine("tiers_quantize", example_builder("tiers_quantize"),
                probe=lambda: _quantize_chunks._cache_size(),
                covers=("repro.warehouse.tiers:_quantize_chunks",),
                probe_name="warehouse_tiers")
register_engine("tiers_compact", example_builder("tiers_compact"),
                probe=lambda: _compact._cache_size(),
                covers=("repro.warehouse.tiers:_compact",),
                probe_name="warehouse_tiers")
register_engine("tiers_materialize", example_builder("tiers_materialize"),
                probe=lambda: _materialize._cache_size(),
                covers=("repro.warehouse.tiers:_materialize",),
                probe_name="warehouse_tiers")
register_engine("tiers_quantize_sharded",
                example_builder("tiers_quantize_sharded"),
                probe=lambda: _quantize_chunks_sharded._cache_size(),
                covers=("repro.warehouse.tiers:_quantize_chunks_sharded",),
                probe_name="warehouse_tiers_sharded")
# the CLIP scatters in _cold_write / _materialize_sharded are vmapped
# dynamic_update_slice — start-index clamping is that op's documented
# semantics (offsets are cumulative cold depths, in range by
# construction), not an out-of-bounds footgun, so the clip ban is
# waived for exactly these two engines.
register_engine("tiers_cold_write", example_builder("tiers_cold_write"),
                invariants={"no_clip_scatter": False},
                probe=lambda: _cold_write._cache_size(),
                covers=("repro.warehouse.tiers:_cold_write",),
                probe_name="warehouse_tiers_sharded")
register_engine("tiers_compact_ragged",
                example_builder("tiers_compact_ragged"),
                probe=lambda: _compact_ragged._cache_size(),
                covers=("repro.warehouse.tiers:_compact_ragged",),
                probe_name="warehouse_tiers_sharded")
register_engine("tiers_materialize_sharded",
                example_builder("tiers_materialize_sharded"),
                invariants={"no_clip_scatter": False},
                probe=lambda: _materialize_sharded._cache_size(),
                covers=("repro.warehouse.tiers:_materialize_sharded",),
                probe_name="warehouse_tiers_sharded")
