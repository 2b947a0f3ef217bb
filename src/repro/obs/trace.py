"""Dispatch audit over the analysis registry.

Every engine the static auditor verifies is also *traceable*: this
module builds the engine's tiny example, runs it cold (compile) and
warm, brackets each call with the engine's jit-cache probe (so a
recompile shows up as a counted event, not a mystery latency), sizes
the argument/output pytrees, and counts host-transfer ops in the
compiled HLO. The records make the ``OBS.json`` report that
``python -m repro.obs --compare`` gates regressions against. Times are
not recorded here: a time comes from a ``jax.profiler`` trace of the
served path on the device (see ``repro.obs.spans``).
"""
from __future__ import annotations

from typing import Dict, Optional

import jax

from repro.analysis import registry
from repro.analysis.hlo_audit import audit_hlo


def traceable_engine_names() -> set:
    """Engines the tracer covers: every registry entry with a jit-cache
    probe (without one, recompiles inside a span are unobservable, so
    the engine does not count as traced — the coverage lint in
    ``repro.analysis`` flags it)."""
    registry.import_engine_modules()
    return {name for name, e in registry.engines().items()
            if e.probe is not None}


def _tree_bytes(tree) -> int:
    total = 0
    for leaf in jax.tree.leaves(tree):
        size = getattr(leaf, "size", None)
        dtype = getattr(leaf, "dtype", None)
        if size is not None and dtype is not None:
            total += int(size) * int(dtype.itemsize)
    return total


def _host_transfer_count(ex: registry.EngineExample) -> int:
    """Host-transfer ops surviving in the compiled module (infeed /
    outfeed / is_host_transfer sends / host callbacks) — counted via the
    same detector the HLO audit uses."""
    hlo = ex.fn.lower(*ex.args, **ex.kwargs).compile().as_text()
    violations, _info = audit_hlo(hlo, {"no_host_transfers": True})
    return sum(1 for v in violations if v["check"] == "host_transfer")


def trace_engine(engine: registry.Engine, reps: int = 3,
                 with_hlo: bool = True) -> Dict:
    """Audit one engine: a cold call (compile + first run), ``reps``
    warm calls, probe deltas, byte sizes, host-transfer count. Returns
    the engine's OBS.json record."""
    try:
        ex = engine.build()
    except registry.SkipEngine as e:
        return {"skipped": str(e)}

    probe = engine.probe or (lambda: 0)
    p0 = probe()
    out = jax.block_until_ready(ex.fn(*ex.args, **ex.kwargs))
    p1 = probe()

    recompiles = 0
    for _ in range(max(reps, 1)):
        q0 = probe()
        out = jax.block_until_ready(ex.fn(*ex.args, **ex.kwargs))
        recompiles += probe() - q0

    record = {
        "new_executables": int(p1 - p0),
        "recompiles": int(recompiles),
        "arg_bytes": _tree_bytes((ex.args, ex.kwargs)),
        "out_bytes": _tree_bytes(out),
    }
    if with_hlo:
        record["host_transfers"] = _host_transfer_count(ex)
    return record


def trace_all(only: Optional[str] = None, reps: int = 3,
              with_hlo: bool = True) -> Dict[str, Dict]:
    """Audit every registered engine (optionally substring-filtered);
    returns the per-engine records."""
    registry.import_engine_modules()
    engines = registry.engines()
    if only:
        engines = {k: v for k, v in engines.items() if only in k}
    return {name: trace_engine(engine, reps=reps, with_hlo=with_hlo)
            for name, engine in engines.items()}
