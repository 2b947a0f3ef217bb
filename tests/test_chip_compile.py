"""Compile rehearsals for a TPU v5e that is described, not attached.

The served path's device programs are compiled at real widths by the
TPU compiler for a ``v5e:2x2`` topology description: the fused Pallas
aggregation kernel (``interpret=False``) at 2^24 rows for the warehouse
plan shapes, the elastic pool tick at V=4096, and the sharded tick
ingest and sharded query on a 4-device mesh. Nothing runs; a compile
the chip's compiler refuses (an unaligned block, too much fast memory,
an unpartitionable kernel) fails here instead of on the chip.

The topology is described inside a module fixture, never at import:
only one process may hold the TPU library, and every test worker
imports this module. The persistent compilation cache is off around
these compiles (an entry written for a described chip cannot be read
back without one).
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from repro.core.api import _pool_tick
from repro.core.switcher import SwitchTables
from repro.kernels.warehouse_agg import fused_segment_agg
from repro.warehouse import query as Q
from repro.warehouse.query import (Filter, GroupBy, MultiGroupBy, TopK,
                                   WindowAgg)
from repro.warehouse.store import (OUT_COLUMN, SCALAR_COLUMNS,
                                   _shard_kernel)

ROWS = 1 << 24          # rows the fused kernel scans
V = 4096                # pool slots
C, K, NW = 4, 9, 4      # COVID categories, configs, 2048-segment windows


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _columns(lead, sharding):
    cols = {n: _sds(lead, dt, sharding) for n, dt in SCALAR_COLUMNS}
    cols[OUT_COLUMN] = _sds(lead + (K,), jnp.float32, sharding)
    return cols


def _like(tree, sharding):
    return jax.tree.map(
        lambda a: _sds(np.shape(a), jnp.asarray(a).dtype, sharding), tree)


PLANS = {
    "filter_category_mean": (Filter("quality", "ge", 0.6),
                             GroupBy("category", "quality", agg="mean",
                                     num_groups=C)),
    "window2048_sum": (WindowAgg(window=2048, value="quality", agg="sum",
                                 num_windows=NW),),
    "wide_out_sum": (MultiGroupBy(("t", "k"), "out", agg="sum",
                                  nums=(NW, K), windows=(2048, 0)),),
    "stream_id_max": (Filter("quality", "lt", 0.5),
                      WindowAgg(window=2048, value="stream_id", agg="max",
                                num_windows=NW)),
    "filter_window_category_mean": (
        Filter("quality", "ge", 0.6),
        MultiGroupBy(("t", "category"), "quality", agg="mean",
                     nums=(NW, C), windows=(2048, 0))),
}


@pytest.mark.parametrize("name", sorted(PLANS))
def test_fused_segment_agg_compiles_for_v5e(name, one_chip):
    cols = _columns((ROWS,), one_chip)
    spec, fvals = Q.normalize(PLANS[name])
    pre, node, _ = Q.split_plan(spec)
    aspec = Q._pallas_spec(pre, node, cols)
    assert aspec is not None

    def partial(c, n, fv):
        return fused_segment_agg(c, n, fv, spec=aspec, interpret=False)

    compiled = jax.jit(partial).lower(
        cols, _sds((), jnp.int32, one_chip),
        _like(fvals, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_pool_tick_compiles_for_v5e(one_chip):
    f32, i32 = jnp.float32, jnp.int32
    s = lambda shape, dt=f32: _sds(shape, dt, one_chip)  # noqa: E731
    tables = SwitchTables(
        centers=s((V, C, K)), power=s((V, K)), cost=s((V, K)),
        place_rt=s((V, K, 1)), place_on=s((V, K, 1)),
        place_cl=s((V, K, 1)), place_valid=s((V, K, 1), jnp.bool_),
        rank_pos=s((V, K), i32), tau=s((V,)), buffer_cap_s=s((V,)),
        cloud_budget=s((V,)))
    state = {"used": s((V, C, K)), "count": s((V, C)),
             "buffer_s": s((V,)), "cloud_spent": s((V,)),
             "k_cur": s((V,), i32), "qual_prev": s((V,))}
    compiled = _pool_tick.lower(
        state, s((V,)), s((V,), jnp.bool_), s((V, K)), s((V,)),
        s((V,), jnp.bool_), s((V,)), s((V, C, K)), tables, s(()),
        s(())).compile()
    assert compiled.memory_analysis() is not None


@pytest.fixture(scope="module")
def shard_mesh(topo):
    return Mesh(np.asarray(topo.devices[:4]), ("shard",))


def test_sharded_tick_ingest_compiles_for_v5e(shard_mesh):
    rows = NamedSharding(shard_mesh, P("shard"))
    rep = NamedSharding(shard_mesh, P())
    cap = 1 << 22                                   # rows per shard
    kern = _shard_kernel("tick_ids", shard_mesh, 4)
    traces = {"c": jnp.int32, "k": jnp.int32, "qual": jnp.float32,
              "on_s": jnp.float32, "cl_s": jnp.float32,
              "buffer_s": jnp.float32}
    compiled = kern.lower(
        _columns((4, cap), rows), _sds((4,), jnp.int32, rows),
        {k: _sds((V,), dt, rep) for k, dt in traces.items()},
        _sds((V,), jnp.float32, rep), _sds((V, K), jnp.float32, rep),
        _sds((), jnp.int32, rep), _sds((V,), jnp.int32, rep),
        _sds((V,), jnp.bool_, rep)).compile()
    assert compiled.memory_analysis() is not None


@pytest.mark.parametrize("use_pallas", [True, False])
def test_sharded_query_compiles_for_v5e(shard_mesh, use_pallas,
                                        monkeypatch):
    # the kernel picks interpret mode from the process's backend, which
    # is the CPU here: compile the chip's branch
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    rows = NamedSharding(shard_mesh, P("shard"))
    rep = NamedSharding(shard_mesh, P())
    cap = 1 << 22
    plan = PLANS["window2048_sum"] + (TopK(4, by="quality"),)
    spec, fvals = Q.normalize(plan)
    kern = Q._sharded_kernel(shard_mesh, 4)
    compiled = kern.lower(
        _columns((4, cap), rows), _sds((4,), jnp.int32, rows),
        _like(fvals, rep), _sds((2,), jnp.uint32, rep), spec=spec,
        compressed=False, use_pallas=use_pallas).compile()
    hlo = compiled.as_text()
    assert ("tpu_custom_call" in hlo) == use_pallas
    assert "all-reduce" in hlo


def _capacity_copies(hlo, cap):
    """HLO lines that copy a buffer with ``cap`` rows."""
    pat = re.compile(r"= \w+\[%d[,\]].*\scopy\(" % cap)
    return [line.strip() for line in hlo.splitlines() if pat.search(line)]


@pytest.mark.parametrize("standing", [True, False],
                         ids=["standing", "plain"])
def test_masked_tick_pallas_standing_fold_compiles_for_v5e(one_chip,
                                                           monkeypatch,
                                                           standing):
    """The elastic pool's masked tick, with 64 standing queries whose
    folds take the Pallas delta (the live-slot mask is one more filter
    operand of the fused kernel) and without: either way the store's
    columns are donated, so every column aliases an output and no
    capacity-sized buffer is copied."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    from repro.warehouse.standing import _seed_state
    from repro.warehouse.store import _ingest_tick_masked
    q, cap = 64, 1 << 22
    traces = {"c": jnp.int32, "k": jnp.int32, "qual": jnp.float32,
              "on_s": jnp.float32, "cl_s": jnp.float32,
              "buffer_s": jnp.float32}
    cols = _columns((cap,), one_chip)
    kw, fold = {}, ()
    if standing:
        plan = (Filter("stream_id", "eq", 7.0),
                WindowAgg(window=1024, value="quality", agg="mean",
                          num_windows=9))
        spec, fvals = Q.normalize(plan)
        _pre, node, _ = Q.split_plan(spec)
        fvq = tuple(_sds((q,) + np.shape(a), jnp.asarray(a).dtype,
                         one_chip) for a in fvals)
        fold = ((_like(_seed_state(node, (q,)), one_chip),), (fvq,))
        kw = {"sspecs": ((spec, True),)}
    compiled = _ingest_tick_masked.lower(
        cols, {k: _sds((V,), dt, one_chip) for k, dt in traces.items()},
        _sds((V,), jnp.float32, one_chip), _sds((V, K), jnp.float32,
                                                one_chip),
        _sds((), jnp.int32, one_chip), _sds((), jnp.int32, one_chip),
        _sds((V,), jnp.int32, one_chip), _sds((V,), jnp.bool_, one_chip),
        *fold, **kw).compile()
    hlo = compiled.as_text()
    assert ("tpu_custom_call" in hlo) == standing
    # the columns' bytes in the chip's layout: the arguments of a
    # program that takes nothing else
    col_bytes = jax.jit(lambda c: c["t"][0], keep_unused=True).lower(
        cols).compile().memory_analysis().argument_size_in_bytes
    assert col_bytes >= 68 * cap                 # 8 scalars + 9 floats
    assert compiled.memory_analysis().alias_size_in_bytes == col_bytes
    assert "input_output_alias" in hlo
    assert _capacity_copies(hlo, cap) == []


def test_alert_poll_compiles_for_v5e(one_chip):
    """The alert poll's one program: a buffer high-water alert over 8192
    stream ids and a subscribed slot of a 64-query window group, both
    answered, gathered and packed into one int32 vector."""
    from repro.warehouse.standing import _poll_kernel, _seed_state
    plans = ((GroupBy("stream_id", "buffer_s", "max", num_groups=8192),),
             (Filter("stream_id", "eq", 7.0),
              WindowAgg(window=1024, value="quality", agg="mean",
                        num_windows=9)))
    states, fvals, specs = [], [], []
    for plan, q in zip(plans, (1, 64)):
        spec, fv = Q.normalize(plan)
        _pre, node, _ = Q.split_plan(spec)
        states.append(_like(_seed_state(node, (q,)), one_chip))
        fvals.append(tuple(_sds((q,) + np.shape(a), jnp.asarray(a).dtype,
                                one_chip) for a in fv))
        specs.append(spec)
    compiled = _poll_kernel.lower(
        tuple(states), tuple(fvals), specs=tuple(specs),
        slots=((0,), (5,)), sharded=False).compile()
    out, = jax.tree.leaves(compiled.out_info)
    # per group: its key column, value, count and mask, one row each
    assert out.dtype == jnp.int32 and out.shape == (4 * 8192 + 4 * 9,)


@pytest.mark.parametrize("name", ["window2048_sum", "wide_out_sum"])
def test_blocked_sum_query_compiles_for_v5e(name, one_chip):
    """The XLA path's two-level float sums over a 2^26-row store (the
    smoke's store after growth) fit in a v5e's 16 GiB beside it."""
    cols = _columns((1 << 26,), one_chip)
    spec, fvals = Q.normalize(PLANS[name])
    compiled = Q._run_plan.lower(
        cols, _sds((), jnp.int32, one_chip), _like(fvals, one_chip),
        spec=spec, use_pallas=False).compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 2 << 30, mem
