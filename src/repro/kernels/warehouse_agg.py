"""Pallas fused filter+group+aggregate kernel for the warehouse query
engine — the "break the scatter floor" primitive (ROADMAP).

The XLA query path bottoms out on scatter-based ``segment_sum``: one
executed scatter per groupby-style plan (the static auditor's census
pins it — ``scatter_ops.*`` in ANALYSIS.json / the bench snapshots).
This kernel removes the scatter entirely: ONE pass over chunk-tiled
columns per grid step, the plan's predicate mask evaluated in-register
(never materialized to memory), and the segment aggregation expressed
as a one-hot ``(n_groups, block_rows)`` contraction accumulated
directly into a ``(n_groups[, lanes])`` on-chip accumulator that every
grid step revisits. Accumulators follow the engine's partial
convention exactly — ``{"acc", "cnt"}``, with ``∓inf`` sentinels for
``max``/``min`` — so the caller reuses ``_seg_finalize`` verbatim and
the fused partial is mergeable by the same sharded combiners
(psum/pmax) as the XLA partial.

The sequential-grid accumulation pattern (output block index map
pinned to 0, ``pl.when(step == 0)`` init) relies on Pallas' in-order
grid execution, and runs in interpret mode on CPU — that is the
tier-1-testable path in this container; on TPU the same kernel
compiles with the one-hot contraction as an MXU ``dot_general``.

fp32 exactness contract: ``count``/``max``/``min`` and integer-valued
sums are exact vs the XLA path and the numpy mirror; float ``sum`` /
``mean`` regroup the addition order across row tiles (tile-level
partial sums) and match to the same tolerance as multi-shard merges.

This module is import-light on purpose: ``repro.warehouse.query``
imports the kernel AND the predicate helpers (``CMP``/``int_pred``)
from here, never the other way around.
"""
from __future__ import annotations

import functools
import math
from dataclasses import dataclass
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

CMP = {
    "eq": lambda a, b: a == b,
    "ne": lambda a, b: a != b,
    "lt": lambda a, b: a < b,
    "le": lambda a, b: a <= b,
    "gt": lambda a, b: a > b,
    "ge": lambda a, b: a >= b,
}


def int_pred(x, op, i, is_int, oob):
    """Exact real-number comparison of an INTEGER column ``x`` against a
    threshold hoisted host-side as ``(floor(v), integral?, oob)`` — the
    float64 host computation means neither side ever rounds through f32
    (which collapses ints past 2^24; the append-only ``t`` column
    crosses that after ~388 days of 2 s segments). All three operands
    are dynamic: changing the threshold never recompiles.

    Every rewrite is closed-form in ``floor(v)`` with NO ``±1``
    arithmetic (the old ``x >= i + 1`` form both truncation-vs-floor
    mis-bucketed negative non-integral thresholds and overflowed at the
    int32 clamp edge):

        x >= v  <=>  x >= floor(v)  when v integral, else x > floor(v)
        x >  v  <=>  x > floor(v)          (integral or not)
        x <= v  <=>  x <= floor(v)         (integral or not)
        x <  v  <=>  x < floor(v)   when v integral, else x <= floor(v)

    ``oob`` (int32: -1/0/+1) marks thresholds outside int32 entirely
    (incl. ∓inf), where the comparison is constant for every possible
    x: below-range makes ge/gt/ne all-true, above-range makes le/lt/ne
    all-true."""
    i = i.astype(x.dtype)
    if op == "eq":
        return is_int & (x == i) & (oob == 0)
    if op == "ne":
        return ~is_int | (x != i) | (oob != 0)
    if op == "ge":
        p = jnp.where(is_int, x >= i, x > i)
        return jnp.where(oob == 0, p, oob < 0)
    if op == "gt":
        return jnp.where(oob == 0, x > i, oob < 0)
    if op == "le":
        return jnp.where(oob == 0, x <= i, oob > 0)
    if op == "lt":
        p = jnp.where(is_int, x < i, x <= i)
        return jnp.where(oob == 0, p, oob > 0)
    raise ValueError(f"unknown filter op {op!r}")


@dataclass(frozen=True)
class FusedAggSpec:
    """Static (hashable) shape of one fused filter+group+aggregate
    pass — the partial phase of a plan up to and including its first
    segment-reducing node.

    ``filters[j] = (column, op, idx)`` with ``idx`` indexing the
    dynamic operand vectors; ``keys[j] = (column, num_ids, window)``
    is the fused multi-key encoding (``window > 1`` divides the key
    column first; ids clip into ``[0, num_ids)``), identical to the
    engine's ``_seg_ids``."""
    filters: Tuple[Tuple[str, str, int], ...]
    keys: Tuple[Tuple[str, int, int], ...]
    value: str
    agg: str  # sum | mean | count | max | min

    @property
    def num_groups(self) -> int:
        return math.prod(n for _, n, _ in self.keys)


def _agg_kernel(*refs, filters, keys, num, bn, wide, agg):
    """One grid step: rows ``[step*bn, step*bn+bn)`` of every operand
    column -> mask in-register -> one-hot contraction into the
    revisited ``(num[, D])`` accumulators. ``filters``/``keys`` carry
    positions into ``col_refs`` (baked static, loops fully unrolled)."""
    n_ref, vals_ref, floors_ref, isint_ref, oob_ref = refs[:5]
    col_refs = refs[5:-2]
    acc_ref, cnt_ref = refs[-2], refs[-1]
    step = pl.program_id(0)

    @pl.when(step == 0)
    def _init():
        cnt_ref[...] = jnp.zeros_like(cnt_ref)
        if agg == "max":
            acc_ref[...] = jnp.full_like(acc_ref, -jnp.inf)
        elif agg == "min":
            acc_ref[...] = jnp.full_like(acc_ref, jnp.inf)
        else:
            acc_ref[...] = jnp.zeros_like(acc_ref)

    # validity mask, (1, bn), never materialized outside registers
    rows = step * bn + jax.lax.broadcasted_iota(jnp.int32, (1, bn), 1)
    mask = rows < n_ref[0]
    for pos, op, fidx in filters:
        x = col_refs[pos][...]
        if jnp.issubdtype(x.dtype, jnp.integer):
            p = int_pred(x, op, floors_ref[fidx], isint_ref[fidx] != 0,
                         oob_ref[fidx])
        else:
            p = CMP[op](x.astype(jnp.float32), vals_ref[fidx])
        mask = mask & p[None, :]

    # fused multi-key group ids, (1, bn) — same clip/encode as _seg_ids
    gid = None
    for pos, n_ids, window in keys:
        ids = col_refs[pos][...].astype(jnp.int32)
        if window > 1:
            ids = ids // window
        ids = jnp.clip(ids, 0, n_ids - 1)
        gid = ids if gid is None else gid * n_ids + ids
    gid = gid[None, :]

    # one-hot (num, bn): the scatter-free segment reduction
    oh = (jax.lax.broadcasted_iota(jnp.int32, (num, bn), 0) == gid) & mask
    cnt_ref[...] += jnp.sum(oh.astype(jnp.float32), axis=1)
    v = col_refs[-1][...].astype(jnp.float32)
    if agg in ("sum", "mean", "count"):
        if wide:                                 # (D, bn) value block
            # HIGHEST: the MXU's default f32 pass rounds v to bf16-class
            # precision (1e-4 relative on a v5e at 2^25 rows)
            acc_ref[...] += jax.lax.dot_general(
                oh.astype(jnp.float32), v, (((1,), (1,)), ((), ())),
                precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32)
        else:
            acc_ref[...] += jnp.sum(jnp.where(oh, v[None, :], 0.0), axis=1)
    elif agg == "max":
        acc_ref[...] = jnp.maximum(
            acc_ref[...], jnp.max(jnp.where(oh, v[None, :], -jnp.inf),
                                  axis=1))
    else:                                        # min
        acc_ref[...] = jnp.minimum(
            acc_ref[...], jnp.min(jnp.where(oh, v[None, :], jnp.inf),
                                  axis=1))


def _full(shape):
    """BlockSpec for an operand every grid step sees whole."""
    nd = len(shape)
    return pl.BlockSpec(shape, lambda i, _nd=nd: (0,) * _nd)


def _vec(vec, pad_to=1):
    """Dynamic operand vector -> non-empty f32/i32 array the kernel can
    take a BlockSpec over (zero filters still needs a (1,) ref)."""
    if vec.shape[0] == 0:
        return jnp.zeros((pad_to,), vec.dtype)
    return vec


def fused_segment_agg(cols, n_rows, fvals, *, spec: FusedAggSpec,
                      block_rows: int = 1024, interpret=None):
    """Run ONE fused filter+group+aggregate pass over ``cols`` and
    return the engine's partial ``{"acc", "cnt"}`` (finalize with
    ``_seg_finalize``; merge across shards with sum/pmax/pmin like any
    XLA partial). ``cols`` is the store's column dict (only the spec's
    operand columns are read); ``n_rows`` masks capacity padding;
    ``fvals`` is the ``normalize()`` operand tuple
    ``(vals, floors, isint, oob)``.

    ``interpret=None`` picks interpret mode off-TPU (the CPU test
    path); pass an explicit bool to force either."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    vals, floors, isint, oob = fvals
    v = cols[spec.value]
    wide = v.ndim == 2
    cap = v.shape[0]
    num = spec.num_groups

    # operand columns: filters first (dedup by first use), keys, value
    names = []
    for col, _, _ in spec.filters:
        if col not in names:
            names.append(col)
    fpos = [(names.index(col), op, fidx)
            for col, op, fidx in spec.filters]
    kpos = []
    for col, n_ids, window in spec.keys:
        if col not in names:
            names.append(col)
        kpos.append((names.index(col), n_ids, window))
    names.append(spec.value)                      # always last

    bn = max(1, min(block_rows, cap))
    # at least one grid step even for a zero-capacity store (an empty
    # store still answers the query: every group empty), so the init
    # step always runs and the outputs are never left unwritten
    pad = max(bn, cap + (-cap % bn)) - cap
    operands = []
    for name in names:
        arr = cols[name]
        if pad:
            arr = jnp.pad(arr, ((0, pad),) + ((0, 0),) * (arr.ndim - 1))
        operands.append(arr)
    n_arr = jnp.reshape(n_rows.astype(jnp.int32), (1,))
    dyn = (n_arr, _vec(vals), _vec(floors),
           _vec(isint.astype(jnp.int32)), _vec(oob))

    col_specs = []
    if wide:
        # the (D, rows) view: a (rows, D) block pads D to 128 lanes on
        # TPU (32 GiB for a 2^26-row, 9-wide column), while the store
        # already lays the column out rows-minor, so the view is free
        operands[-1] = operands[-1].T
    for arr in operands:
        if arr.ndim == 2:
            col_specs.append(pl.BlockSpec((arr.shape[0], bn),
                                          lambda i: (0, i)))
        else:
            col_specs.append(pl.BlockSpec((bn,), lambda i: (i,)))
    acc_shape = (num, v.shape[1]) if wide else (num,)
    out = pl.pallas_call(
        functools.partial(_agg_kernel, filters=tuple(fpos),
                          keys=tuple(kpos), num=num, bn=bn, wide=wide,
                          agg=spec.agg),
        grid=((cap + pad) // bn,),
        in_specs=[_full(d.shape) for d in dyn] + col_specs,
        out_specs=[_full(acc_shape), _full((num,))],
        out_shape=[jax.ShapeDtypeStruct(acc_shape, jnp.float32),
                   jax.ShapeDtypeStruct((num,), jnp.float32)],
        interpret=interpret,
    )(*dyn, *operands)
    return {"acc": out[0], "cnt": out[1]}


# cost-model bounds for the auto dispatch: the one-hot contraction does
# O(num_groups) lane work per row where the scatter does O(1), so the
# fused kernel wins only while the whole accumulator set stays on-chip
# and the group count is modest (the scatter's serialization penalty it
# removes is large but not unbounded)
_AUTO_MAX_GROUPS = 2048
_AUTO_MAX_ACC_BYTES = 4 << 20


def pallas_auto(spec: FusedAggSpec, value_width: int = 1) -> bool:
    """Cost-based dispatch decision for ``use_pallas=None``: True only
    on a real TPU backend (interpret mode on CPU is a correctness
    path, not a fast path) and only when the accumulator footprint
    fits comfortably on-chip."""
    if jax.default_backend() != "tpu":
        return False
    num = spec.num_groups
    return (num <= _AUTO_MAX_GROUPS
            and num * max(1, value_width) * 4 <= _AUTO_MAX_ACC_BYTES)
