"""Engine registry for the static program auditor.

Every jitted entry point in the stack registers itself here — right
next to its ``register_cache_probe`` call — as a *lazy* triple:

    register_engine("fused_single", build_example,
                    invariants={...},
                    probe=lambda: _fused_run._cache_size(),
                    covers=("repro.core.ingest:_fused_run",))

``build_example`` is a zero-argument callable returning an
``EngineExample(fn, args, kwargs)``: the jitted callable plus small
example arguments (kwargs are the static ones) that trace in
milliseconds. Nothing is built at import time, so registering costs
nothing unless the auditor actually runs.

``covers`` lists the module-level jitted definitions this entry
exercises (``"module.path:function_name"``). The source-lint pass
cross-references the set of jitted definitions it finds in
``core/``, ``warehouse/`` and ``distribution/`` against the union of
all ``covers`` — a jitted entry point nobody registered is itself a
lint violation (the registry is the enforcement point, not a wiki).

This module is imported by the engine packages themselves, so it must
not import anything from ``repro`` (no cycles) and must stay cheap.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Mapping, NamedTuple, Optional, Tuple


class SkipEngine(Exception):
    """Raised by a ``build`` callable when the engine cannot run on
    this topology (e.g. a sharded kernel on a 1-device host). The
    auditor records the skip + reason instead of failing."""


class EngineExample(NamedTuple):
    """A jitted callable plus tiny example arguments for tracing.

    ``kwargs`` are the call's keyword arguments (static argnames
    included); ``args`` the positional operands.
    """
    fn: Callable
    args: Tuple[Any, ...]
    kwargs: Dict[str, Any] = {}

    def run(self):
        """One call of the engine on fresh copies of its array
        operands: a kernel that donates an input (the store's write
        kernels donate their columns) consumes what it is given, and
        the auditors call one example more than once."""
        import jax
        args = jax.tree.map(
            lambda x: x.copy() if isinstance(x, jax.Array) else x,
            self.args)
        return self.fn(*args, **self.kwargs)


class Engine(NamedTuple):
    name: str
    build: Callable[[], EngineExample]
    invariants: Mapping[str, Any]
    probe: Optional[Callable[[], int]]
    covers: Tuple[str, ...]
    probe_name: Optional[str] = None


# What a registered engine promises unless it overrides. These are the
# stack's headline claims (ROADMAP / benchmark asserts) restated as
# statically-checkable invariants:
#   no_callbacks          no pure_/debug_/io_callback anywhere in the
#                         program (host round-trips on the hot path)
#   no_f64                no float64/complex128 value is ever produced
#                         (an x64 leak doubles bytes and breaks fp32
#                         bit-exactness contracts)
#   no_weak_outputs       engine outputs are strongly typed (weak types
#                         re-promote downstream consumers)
#   no_clip_scatter       every scatter states drop/in-bounds semantics;
#                         CLIP silently redirects out-of-bounds writes
#                         onto valid rows (the ShardedStore routed
#                         append RELIES on drop)
#   no_clip_gather        same for gathers: CLIP reads a wrong row
#                         instead of a fill value
#   max_new_executables   jit cache entries one warm call may add
#                         (1 = the engine is ONE dispatch)
#   zero_recompile        a second identical call adds no executables
#   no_host_transfers     compiled HLO has no infeed/outfeed/
#                         host-transfer ops
#   balanced_collectives  no collective sits under a conditional branch
#                         in compiled HLO (every shard must execute the
#                         identical collective sequence or the mesh
#                         deadlocks — the bug class the sharded property
#                         suite can only catch probabilistically)
DEFAULT_INVARIANTS: Dict[str, Any] = {
    "no_callbacks": True,
    "no_f64": True,
    "no_weak_outputs": True,
    "no_clip_scatter": True,
    "no_clip_gather": True,
    "max_new_executables": 1,
    "zero_recompile": True,
    "no_host_transfers": True,
    "balanced_collectives": True,
}

_ENGINES: Dict[str, Engine] = {}


def register_engine(name: str, build: Callable[[], EngineExample], *,
                    invariants: Optional[Mapping[str, Any]] = None,
                    probe: Optional[Callable[[], int]] = None,
                    covers: Tuple[str, ...] = (),
                    probe_name: Optional[str] = None) -> None:
    """Register a jitted engine for static verification. ``invariants``
    overrides individual ``DEFAULT_INVARIANTS`` keys; ``probe`` is the
    engine's jit-cache probe (the same callable handed to
    ``register_cache_probe``); ``covers`` names the module-level jitted
    definitions this entry exercises; ``probe_name`` is the
    ``register_cache_probe`` key this engine's probe corresponds to —
    the coverage lint cross-references the probe table against the
    union of all engines' probe names, so a probe nobody claims (or an
    engine claiming a nonexistent probe) fails the audit."""
    inv = dict(DEFAULT_INVARIANTS)
    if invariants:
        unknown = set(invariants) - set(DEFAULT_INVARIANTS)
        assert not unknown, f"unknown invariants: {sorted(unknown)}"
        inv.update(invariants)
    _ENGINES[name] = Engine(name, build, inv, probe, tuple(covers),
                            probe_name)


def example_builder(name: str, *args: Any) -> Callable[[], EngineExample]:
    """Lazy builder bound to ``repro.analysis.examples.<name>(*args)``.
    The import happens at build time, never at registration time, so
    engine modules can register without pulling in the example deps."""
    def build() -> EngineExample:
        from repro.analysis import examples
        return getattr(examples, name)(*args)
    return build


def engines() -> Dict[str, Engine]:
    """Name -> Engine, in registration order."""
    return dict(_ENGINES)


def covered_jit_names() -> set:
    """Union of every registered engine's ``covers`` set."""
    out = set()
    for e in _ENGINES.values():
        out.update(e.covers)
    return out


def claimed_probe_names() -> set:
    """Union of every registered engine's ``probe_name`` — the cache
    probes the registry actually verifies dispatch counts through."""
    return {e.probe_name for e in _ENGINES.values()
            if e.probe_name is not None}


def import_engine_modules() -> None:
    """Import every module that registers engines (idempotent). The
    auditor calls this before reading the registry."""
    import importlib
    for mod in ("repro.core.switcher", "repro.core.ingest",
                "repro.core.api", "repro.core.forecaster",
                "repro.core.categories", "repro.core.planner",
                "repro.warehouse.query", "repro.warehouse.store",
                "repro.warehouse.tiers", "repro.warehouse.standing",
                "repro.runtime.elastic"):
        importlib.import_module(mod)
