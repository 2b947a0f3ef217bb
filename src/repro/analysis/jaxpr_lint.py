"""Pass 1 — jaxpr lint: walk a closed jaxpr recursively and verify the
graph-level invariants the runtime cache probes cannot see.

Checks (each gated by the engine's invariants dict):

- **host callbacks**: ``pure_callback`` / ``debug_callback`` /
  ``io_callback`` / ``outside_call`` primitives anywhere in the program
  (including inside scan/while/cond/pjit/shard_map sub-jaxprs). A
  callback inside the fused scan re-enters Python T times per run.
- **f64 leaks**: any equation producing float64/complex128 — an
  ``x64`` leak silently doubles bytes and breaks the fp32 bit-exactness
  contracts the warehouse tests assert.
- **weak-type outputs**: top-level outputs with ``weak_type=True``
  re-promote whatever consumes them (the classic Python-scalar
  promotion pitfall surviving through a public boundary).
- **scatter/gather modes**: scatters must carry explicit
  drop/in-bounds semantics (``FILL_OR_DROP`` / ``PROMISE_IN_BOUNDS``);
  ``CLIP`` — the silent clamp — redirects out-of-bounds writes onto
  valid rows. The ShardedStore's masked cumulative-rank scatter RELIES
  on drop semantics, so the mode being explicit is a correctness
  invariant, not style. Same for gathers (CLIP reads a wrong row
  instead of a fill value).

The walk also emits a **scatter/gather census** per engine: static op
counts plus trip-weighted executed counts (scan lengths multiply; while
trip counts are unknowable statically and count as 1). The census is
the scatter-floor baseline every future Pallas query kernel must beat
(ROADMAP "Break the scatter floor").
"""
from __future__ import annotations

from typing import Any, Dict, List, Mapping, Tuple

import jax
import jax.extend.core as jex_core
import numpy as np

# primitive names that re-enter the host per execution
_CALLBACK_PRIMS = ("pure_callback", "debug_callback", "io_callback",
                   "outside_call", "callback")

# scatter-family primitive prefixes (scatter, scatter-add, scatter-mul,
# scatter-min, scatter-max) and the gather family
_SCATTER_PREFIX = "scatter"
_GATHER_PRIMS = ("gather",)

_BANNED_DTYPES = ("float64", "complex128")


def _mode_name(mode) -> str:
    """GatherScatterMode (or None) -> stable lowercase name."""
    if mode is None:
        return "unspecified"
    return str(getattr(mode, "name", mode)).lower()


# modes with explicit, clamp-free out-of-bounds semantics
_SAFE_MODES = ("fill_or_drop", "promise_in_bounds")


def _sub_jaxprs(params: Mapping[str, Any]):
    """Yield every sub-jaxpr in an equation's params (scan/while/cond
    bodies, pjit/shard_map inner jaxprs, custom_* call jaxprs)."""
    for v in params.values():
        if isinstance(v, jex_core.ClosedJaxpr):
            yield v.jaxpr
        elif isinstance(v, jex_core.Jaxpr):
            yield v
        elif isinstance(v, (tuple, list)):
            for b in v:
                if isinstance(b, jex_core.ClosedJaxpr):
                    yield b.jaxpr
                elif isinstance(b, jex_core.Jaxpr):
                    yield b


def lint_jaxpr(closed, invariants: Mapping[str, Any]
               ) -> Tuple[List[Dict], Dict]:
    """Lint one ``ClosedJaxpr``. Returns ``(violations, census)``.

    Each violation is ``{"pass": "jaxpr", "check": ..., "detail": ...,
    "path": ...}``. The census maps scatter/gather primitive names to
    ``{"count": static, "executed": trip-weighted}`` plus aggregate
    totals and the deepest scan-nesting trip product observed.
    """
    violations: List[Dict] = []
    census: Dict[str, Dict[str, float]] = {}
    totals = {"scatter_ops": 0, "gather_ops": 0,
              "scatter_executed": 0.0, "gather_executed": 0.0,
              "eqns": 0, "max_trip_product": 1.0}

    def bump(prim: str, mult: float):
        c = census.setdefault(prim, {"count": 0, "executed": 0.0})
        c["count"] += 1
        c["executed"] += mult

    def violate(check: str, detail: str, path: str):
        violations.append({"pass": "jaxpr", "check": check,
                           "detail": detail, "path": path})

    seen = set()

    def walk(jaxpr, mult: float, path: str):
        if id(jaxpr) in seen:       # pjit jaxprs can be shared
            return
        seen.add(id(jaxpr))
        for eqn in jaxpr.eqns:
            totals["eqns"] += 1
            name = eqn.primitive.name
            here = f"{path}/{name}"
            if invariants.get("no_callbacks") and any(
                    cb in name for cb in _CALLBACK_PRIMS):
                violate("host_callback",
                        f"host callback primitive {name!r}", here)
            if invariants.get("no_f64"):
                for var in eqn.outvars:
                    dt = getattr(getattr(var, "aval", None), "dtype", None)
                    if dt is not None and str(dt) in _BANNED_DTYPES:
                        violate("f64",
                                f"{name} produces {dt} (x64 leak)", here)
                        break
            if name.startswith(_SCATTER_PREFIX):
                bump(name, mult)
                totals["scatter_ops"] += 1
                totals["scatter_executed"] += mult
                mode = _mode_name(eqn.params.get("mode"))
                if invariants.get("no_clip_scatter") \
                        and mode not in _SAFE_MODES:
                    violate("scatter_mode",
                            f"{name} mode={mode} (needs explicit "
                            f"drop/in-bounds semantics)", here)
            elif name in _GATHER_PRIMS:
                bump(name, mult)
                totals["gather_ops"] += 1
                totals["gather_executed"] += mult
                mode = _mode_name(eqn.params.get("mode"))
                if invariants.get("no_clip_gather") \
                        and mode not in _SAFE_MODES:
                    violate("gather_mode",
                            f"{name} mode={mode} (silent index clamp)",
                            here)
            # recurse with the trip multiplier
            sub_mult = mult
            sub_path = here
            if name == "scan":
                sub_mult = mult * float(eqn.params.get("length", 1))
                sub_path = f"{here}[{eqn.params.get('length', '?')}]"
                totals["max_trip_product"] = max(
                    totals["max_trip_product"], sub_mult)
            elif name == "while":
                sub_path = f"{here}[?]"   # trip count unknown: count 1
            for sub in _sub_jaxprs(eqn.params):
                walk(sub, sub_mult, sub_path)

    walk(closed.jaxpr, 1.0, "")

    if invariants.get("no_weak_outputs"):
        for i, var in enumerate(closed.jaxpr.outvars):
            aval = getattr(var, "aval", None)
            if getattr(aval, "weak_type", False):
                violations.append({
                    "pass": "jaxpr", "check": "weak_type_output",
                    "detail": f"output #{i} is weakly typed "
                              f"({aval.dtype}, weak_type=True)",
                    "path": "/outputs"})

    census["totals"] = {k: (float(v) if isinstance(v, float) else v)
                        for k, v in totals.items()}
    return violations, census


def trace_closed_jaxpr(fn, args, kwargs):
    """ClosedJaxpr of a (possibly jitted) callable on example args.
    Prefers ``fn.trace`` (jax >= 0.4.34 pjit API); falls back to
    ``jax.make_jaxpr`` with the kwargs closed over (static kwargs can't
    be passed through make_jaxpr directly)."""
    trace = getattr(fn, "trace", None)
    if trace is not None:
        try:
            return trace(*args, **kwargs).jaxpr
        except Exception:                 # pragma: no cover - jax quirks
            pass
    return jax.make_jaxpr(lambda *a: fn(*a, **kwargs))(*args)
