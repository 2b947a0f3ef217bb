"""Observability: on-device run telemetry, host counters, program spans.

1. **Telemetry** — a fixed-shape counter pytree threaded through the
   fused engines' scan carries (``telemetry=True``), bit-exact against
   the ``telemetry_ref`` numpy mirror, adding zero dispatches and zero
   recompiles to the warm path (an auditor-pinned invariant).
2. **Counters** — the serving pool's ``HostTelemetry`` (ticks,
   replans, blocking device→host reads ``host_pulls``, host→device
   puts ``uploads``) and the store's ``obs`` dict (dispatches, standing
   refreshes, alerts, and its own ``host_pulls``/``uploads``). They
   count whether or not the pool records per-stream telemetry.
3. **Spans** — ``span(name, **counts)``, a ``jax.profiler``
   annotation, marks each layer boundary of the served tick, and
   ``install_gc_spans`` names every Python collection. Under
   ``jax.profiler.start_trace`` they share the device trace's clock;
   open the trace in Perfetto or TensorBoard. Every span of a tick
   runs inside ``pool.tick``; ``t``, the tick number, is a stat of
   every ``pool.*`` span and of ``sink.ingest``, and the spans without
   it belong to the ``pool.tick`` around them.

   ======================  ===================================  =====================================
   span                    what it covers                       stats
   ======================  ===================================  =====================================
   ``pool.tick``           all of ``SkyscraperPool.process``    ``t``; the tick's ``host_pulls`` and
                                                                ``uploads`` (pool and sink together)
   ``pool.dispatch``       argument uploads, ``_pool_tick``,    ``uploads``
                           ``_pool_shift``
   ``pool.replan``         the replan enqueue (in dispatch)     ``joint``
   ``pool.pull``           the blocking reads of the decisions  ``pulls``
   ``pool.transform``      per-slot statuses and ``proc_fn``    ``transformed``, ``dropped``
   ``pool.recorder``       ``HostTelemetry.update``             ``pulls``
   ``pool.load``           the sink's rows: stream ids,         ``t``
                           measured qualities, output vectors
   ``sink.ingest``         host side of a store's               ``rows``
                           ``ingest_tick``
   ``sink.grow``           the store growing a capacity rung    ``capacity``
                           (in ingest)
   ``sink.alert_poll``     ``StandingQueries.poll``             ``subscriptions``, ``pulls``, ``fired``
   ``host.gc``             one Python collection                ``generation``, ``collected``
   ======================  ===================================  =====================================

   Device name scopes (``jax.named_scope``; they label the HLO op
   metadata): ``pool.switch`` and ``pool.shed`` in the tick kernel,
   ``pool.forecast`` and ``pool.lp`` in the replan kernels,
   ``sink.write`` and ``sink.fold`` in the tick ingest kernels,
   ``sink.answer`` in the standing answer kernel.

``python -m repro.obs`` audits every analysis-registry engine for new
executables, warm recompiles and host transfers, and gates
``OBS.json`` regressions like ``ANALYSIS.json``.
"""
from repro.obs.spans import install_gc_spans, span
from repro.obs.telemetry import (HostTelemetry, StoreTelemetry, Telemetry,
                                 TEL_KEYS, telemetry_ref)
from repro.obs.trace import traceable_engine_names

__all__ = ["HostTelemetry", "StoreTelemetry", "Telemetry", "TEL_KEYS",
           "install_gc_spans", "span", "telemetry_ref",
           "traceable_engine_names"]
