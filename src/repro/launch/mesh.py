"""Production meshes. Functions (not module constants) so importing never
touches jax device state."""
from __future__ import annotations

import jax
import numpy as np


def auto_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``AxisType.Auto``."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_shard_mesh(n_shards: int):
    """1-D ``('shard',)`` mesh over the first ``n_shards`` devices — the
    warehouse's row-partitioning axis (`warehouse.ShardedStore`). Returns
    ``None`` when the host has fewer devices, and callers fall back to a
    stacked single-device layout with identical semantics (so sharded
    code paths stay testable on a 1-device CPU; CI forces 8 host devices
    via ``--xla_force_host_platform_device_count`` for the real thing)."""
    devs = jax.devices()
    if n_shards > len(devs):
        return None
    if n_shards == len(devs):
        return auto_mesh((n_shards,), ("shard",))
    # a strict subset of the host's devices: build the Mesh directly
    # (jax.make_mesh insists on consuming every device)
    return jax.sharding.Mesh(np.asarray(devs[:n_shards]), ("shard",))


def make_host_mesh(model_axis: int = 1):
    """Small mesh over whatever devices exist (tests / examples)."""
    n = len(jax.devices())
    model_axis = min(model_axis, n)
    data_axis = n // model_axis
    return auto_mesh((data_axis, model_axis), ("data", "model"))
