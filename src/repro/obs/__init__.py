"""Unified observability: tracing, labelled metrics, timeline export.

Three pieces, consumed across the serve/comm/operator tiers:

* :mod:`repro.obs.trace` — span tracing on an injectable clock
  (``WallClock`` default, ``TickClock`` for virtual-tick benches,
  ``SimTime`` over the discrete-event sim), and ``phase``, a span that
  is also a ``jax.profiler`` annotation;
* :mod:`repro.obs.metrics` — labelled counter/gauge/histogram registry
  with JSON snapshot + Prometheus text exposition, and a process-wide
  count of XLA compilations;
* :mod:`repro.obs.export` — Chrome-trace-event (Perfetto) JSON, JSONL
  event logs, and the common BENCH provenance header.
"""
from repro.obs.export import (events_from_sim, provenance,  # noqa: F401
                              spans_from_handle, spans_from_pipeline,
                              to_chrome_trace, write_chrome_trace,
                              write_jsonl, write_metrics)
from repro.obs.metrics import (MetricsRegistry, compile_count,  # noqa: F401
                               compile_times, watch_compiles)
from repro.obs.trace import (ENGINE_PHASES, REQUEST_SPANS,  # noqa: F401
                             TTFT_SPANS, Clock, SimTime, Span, TickClock,
                             Tracer, WallClock, phase, ttft_breakdown)
