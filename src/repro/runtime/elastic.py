"""Elastic scaling + failure handling.

Checkpoints are mesh-agnostic (host numpy), so recovery after losing
devices is: build a new mesh from the surviving devices, derive fresh
shardings from the SAME logical rules, and restore. ``shrink_mesh``
picks the largest (data' x model) grid that fits the survivors while
keeping the model axis intact (TP degree is a property of the lowered
program; DP/FSDP degree is elastic).

``rebalance`` is the warehouse's elastic move: re-partition a
``ShardedStore``'s rows onto a different shard count in ONE collective
dispatch (the same routed-scatter program every ingest uses, pointed at
the full row set), preserving the ``stream_id % n_shards`` ownership
rule and the 1-shard==N-shard bit-exactness contract.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from repro.analysis.registry import example_builder, register_engine
from repro.checkpoint import ckpt as CK
from repro.core.switcher import register_cache_probe
from repro.launch.mesh import make_shard_mesh
from repro.runtime.steps import train_state_shardings


def make_mesh_from(devices: Sequence, model_axis: int,
                   pod_axis: int = 1) -> Mesh:
    n = len(devices)
    if n % model_axis:
        raise ValueError(f"{n} devices not divisible by model={model_axis}")
    data_axis = n // (model_axis * pod_axis)
    shape = ((pod_axis, data_axis, model_axis) if pod_axis > 1
             else (data_axis, model_axis))
    names = (("pod", "data", "model") if pod_axis > 1 else ("data", "model"))
    devs = np.asarray(devices[:pod_axis * data_axis * model_axis]).reshape(shape)
    return Mesh(devs, names)


def shrink_mesh(old_mesh: Mesh, surviving: Sequence) -> Mesh:
    """Largest elastic mesh on the survivors with the same model degree."""
    model_axis = old_mesh.shape.get("model", 1)
    usable = (len(surviving) // model_axis) * model_axis
    if usable == 0:
        raise RuntimeError("not enough devices for one model shard")
    return make_mesh_from(list(surviving)[:usable], model_axis)


def restore_elastic(ckpt_dir: str, model, mesh: Mesh, step=None):
    """Restore the latest checkpoint resharded onto ``mesh``."""
    step = CK.latest_step(ckpt_dir) if step is None else step
    if step is None:
        return None, None
    shardings = train_state_shardings(model, mesh)
    state = CK.restore(ckpt_dir, step, mesh=mesh, shardings=shardings)
    return state, step


# ---------------------------------------------------------------------------
# warehouse shard rebalancing: ShardedStore rows -> a new shard count
# ---------------------------------------------------------------------------

# (mesh_new, s_old, s_new) -> jitted repartition kernel; plain dict so
# the cache probe can sum executable counts (same idiom as the store's
# _SHARD_KERNELS)
_REBALANCE_KERNELS: Dict = {}


def _rebalance_kernel(mesh_new, s_old: int, s_new: int):
    """The one-dispatch repartition program: flatten the old stacked
    columns to a single shard-major row block, mask rows past each old
    shard's valid count, re-derive ownership as ``stream_id % s_new``,
    and run the store's routed scatter (``_route_write``) into fresh
    columns — shard_map on the new mesh (each device keeps exactly its
    rows) or the vmapped stacked fallback. The scatter's drop semantics
    do all the masking: invalid rows' owner points past the last shard,
    so they land nowhere."""
    key = (mesh_new, s_old, s_new)
    kern = _REBALANCE_KERNELS.get(key)
    if kern is not None:
        return kern
    from repro.warehouse.store import _route_write

    @functools.partial(jax.jit, static_argnames=("cap_new",))
    def kern(cols, n_rows_dev, *, cap_new):
        cap_old = cols["t"].shape[1]
        flat = {k: v.reshape((s_old * cap_old,) + v.shape[2:])
                for k, v in cols.items()}
        valid = (jnp.arange(cap_old)[None, :]
                 < n_rows_dev[:, None]).reshape(-1)
        owner = jnp.where(valid,
                          flat["stream_id"].astype(jnp.int32) % s_new,
                          jnp.int32(s_new))

        def empty_like(u):
            return {k: jnp.zeros((cap_new,) + v.shape[1:], v.dtype)
                    for k, v in u.items()}

        if mesh_new is None:
            def one(sid):
                return _route_write(empty_like(flat), jnp.int32(0),
                                    flat, owner, sid)

            return jax.vmap(one)(jnp.arange(s_new, dtype=jnp.int32))

        def body(u, ow):
            sid = jax.lax.axis_index("shard")
            new, nn = _route_write(empty_like(u), jnp.int32(0), u, ow,
                                   sid)
            return jax.tree.map(lambda x: x[None], new), nn[None]

        return shard_map(body, mesh=mesh_new, in_specs=(P(), P()),
                         out_specs=(P("shard"), P("shard")),
                         check_vma=False)(flat, owner)

    _REBALANCE_KERNELS[key] = kern
    return kern


def _rebalance_cache_size():
    return sum(k._cache_size() for k in _REBALANCE_KERNELS.values())


register_cache_probe("store_rebalance", _rebalance_cache_size)
register_engine("store_rebalance", example_builder("store_rebalance"),
                probe=_rebalance_cache_size,
                probe_name="store_rebalance")


def rebalance(store, new_shards: int, mesh="auto"):
    """Re-partition a ``ShardedStore`` onto ``new_shards`` shards in ONE
    collective dispatch; returns a NEW store (the input is untouched).

    The elastic pool's ownership rule is ``stream_id % n_shards``, so
    admitting/retiring streams — or resizing the serving fleet — skews
    the row distribution the rule originally balanced. ``rebalance``
    re-derives every row's owner under the new shard count and routes it
    there with the exact scatter program the ingest paths use, on
    device: no host gathers, no per-row loops, one dispatch regardless
    of row count. Row payloads move bit-identically, so the result obeys
    the 1-shard == N-shard property contract: row sets, counts, and
    masks are exact; float aggregates match to the suite's partial-sum
    ordering tolerance (a different shard count is a different but
    equally valid reduction tree).

    Standing queries registered on ``store`` are re-registered on the
    new store IN HANDLE ORDER (alert subscriptions included), so
    existing handles remain valid against ``new_store.standing``; their
    state is rebuilt by the registration backfill over the repartitioned
    rows.

    ``mesh``: "auto" builds a mesh over the first ``new_shards`` devices
    (stacked fallback when the host has fewer), or pass an explicit mesh
    / None."""
    assert new_shards >= 1
    from repro.warehouse.store import ShardedStore, _bucket_cap
    assert isinstance(store, ShardedStore), "rebalance takes a ShardedStore"
    mesh_new = make_shard_mesh(new_shards) if mesh == "auto" else mesh
    # one shard could own every row; sizing for the total keeps the
    # repartition a single fixed-shape dispatch with no host read of ids
    cap_new = _bucket_cap(max(store.n_rows, 1), store.chunk_rows)
    kern = _rebalance_kernel(mesh_new, store.n_shards, new_shards)
    # the source columns are committed to the OLD mesh's devices; move
    # them onto the new placement (replicated over the new mesh, or the
    # default device for the stacked fallback) so the repartition
    # dispatch sees one coherent device set
    if mesh_new is not None:
        target = jax.sharding.NamedSharding(mesh_new, P())
    else:
        target = jax.devices()[0]
    cols_in = jax.device_put(store.columns, target)
    nrd_in = jax.device_put(store.n_rows_dev, target)
    cols, n_rows_dev = kern(cols_in, nrd_in, cap_new=cap_new)
    counts = np.asarray(n_rows_dev, np.int64)   # (new_shards,) host pull
    new = ShardedStore._from_parts(
        out_dim=store.out_dim, n_shards=new_shards,
        chunk_rows=store.chunk_rows, mesh=mesh_new, columns=cols,
        n_rows_dev=n_rows_dev, n_rows_by_shard=counts, t_max=store.t_max)
    old_reg = getattr(store, "standing", None)
    if old_reg is not None and len(old_reg._queries):
        from repro.warehouse.standing import StandingQueries
        reg = StandingQueries(new)
        subs_by_handle = {s.handle: s for s in old_reg._subs.values()}
        for h in sorted(old_reg._queries):
            q = old_reg._queries[h]
            sub = subs_by_handle.get(h)
            if sub is not None:
                reg.subscribe(list(q.plan), sub.predicate, name=sub.name)
            else:
                reg.register(list(q.plan), name=q.name)
    return new
