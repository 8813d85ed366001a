"""Unified observability: metrics registry, request tracing, flight
recorder.

One layer, three surfaces, shared by train→publish→serve:

* :mod:`.metrics` — typed, labeled Counter/Gauge/Histogram registry with
  ONE sliding-window percentile implementation (the snapshot idiom that
  used to be copied across the MicroBatcher, the pool router and the
  funnel scorer) and Prometheus text exposition (``GET /metrics``).
* :mod:`.trace` — end-to-end request tracing: an ``X-Trace-Id`` context
  minted at the router (or accepted from the client), propagated through
  worker predict/recommend and the MicroBatcher so each request
  accumulates per-stage spans; bounded recent-traces buffer behind
  ``GET /v1/trace/recent``; the training path's span recorder
  (``SpanRecorder``: feed worker, consumer and loop spans in a ring, as
  per-step means on the log line, and as ``TraceAnnotation``s in a
  profile; the set-up boundaries and jax's trace, lowering, compile and
  cache-load events by function, as the loop's ``startup`` and
  ``recompile`` events).
* :mod:`.flight` — a bounded ring of structured events every subsystem
  appends to through one hook, dumped as JSONL on SIGTERM/crash (riding
  PreemptionGuard) and on demand via ``GET /v1/flight``.

Everything here is host-side and dependency-light (numpy only; the span
recorder imports ``jax.profiler.TraceAnnotation`` on its first span and
nothing of jax before): instrumentation must never enter lowered code —
the ``audit_observability`` trace contract (analysis/trace_audit.py)
proves the jitted predict and train step stay free of host callbacks and
baked timer values.
"""

from .flight import FlightRecorder, get_recorder, record
from .metrics import Counter, Gauge, Histogram, MetricsRegistry, SlidingWindow
from .trace import (
    SPAN_HEADER,
    TRACE_HEADER,
    SpanRecorder,
    TraceContext,
    Tracer,
    current_trace,
    get_span_recorder,
)

__all__ = [
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "SlidingWindow",
    "Tracer",
    "TraceContext",
    "SpanRecorder",
    "get_span_recorder",
    "current_trace",
    "TRACE_HEADER",
    "SPAN_HEADER",
    "FlightRecorder",
    "get_recorder",
    "record",
]
