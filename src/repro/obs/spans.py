"""Program spans on the profiler's clock (see the table in ``repro.obs``).

``span(name, **counts)`` is ``jax.profiler.TraceAnnotation``: under a
``jax.profiler`` trace the span lands in the same ``.xplane.pb`` as the
device's ``XLA Ops``, on the same clock, and its keyword counts (and
any given later with ``set_metadata``) become the event's stats. With
no trace running a span costs about a microsecond to enter and leave,
so spans sit at layer boundaries only, never inside a per-slot loop.

``install_gc_spans()`` adds one ``gc.callbacks`` hook that wraps every
Python collection in a ``host.gc`` span (stats ``generation`` and
``collected``), so a collection that stalls a tick has a name in the
trace. It installs once per process; with no trace running the hook
returns at once.
"""
from __future__ import annotations

import gc

import jax

span = jax.profiler.TraceAnnotation


class _GcSpans:
    """The ``gc.callbacks`` hook: opens ``host.gc`` on ``"start"`` and
    closes it, with the number collected, on ``"stop"``. Collections do
    not nest, so one open span at a time is all it keeps."""

    def __init__(self):
        self.open = None

    def __call__(self, phase, info):
        if phase == "start":
            if span.is_enabled():
                self.open = span("host.gc", generation=info["generation"])
                self.open.__enter__()
        elif self.open is not None:
            self.open.set_metadata(collected=info["collected"])
            self.open.__exit__(None, None, None)
            self.open = None


def install_gc_spans() -> None:
    """Wrap every Python collection of this process in a ``host.gc``
    span (idempotent: the hook is added once)."""
    if not any(isinstance(cb, _GcSpans) for cb in gc.callbacks):
        gc.callbacks.append(_GcSpans())
