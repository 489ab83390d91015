"""Runtime telemetry subsystem (ISSUE 5): unified metrics registry,
compile/recompile tracing, and a crash flight recorder.

The measurement layer every perf/robustness PR is judged against:

* :class:`MetricsRegistry` — thread-safe counters/gauges/histograms
  (bounded reservoirs) plus an event stream fanned out to sinks; zero
  cost when disabled.  :data:`REGISTRY` is the process-wide instance the
  instrumented framework sites (``Model.fit``, ``CheckpointManager``,
  ``AsyncCheckpointer``, ``_DevicePrefetcher``, ``StepGuard``,
  ``profiler.RecordEvent``) record into.
* Sinks — :class:`JsonlSink` (append-only metrics stream),
  :func:`write_prometheus` (text-format dump), :class:`MemorySink`
  (tests/bench), and the :class:`FlightRecorder` ring that preserves the
  last N events and dumps them to disk on ``NonFiniteError``,
  ``TrainingPreempted`` (the SIGTERM path), or any unhandled exception.
* :class:`CompileMonitor` — ``jax.monitoring`` listener for compile /
  recompile counts and trace→lower→compile durations.
* :class:`TelemetrySession` / :func:`observe` — the one knob that wires
  all of the above; ``Model.fit(observe=True)`` uses it.
* :class:`SpanTracer` / :data:`TRACER` — end-to-end request tracing
  (ISSUE 20): per-request span timelines across wire → router →
  engine, with chrome-trace export and per-phase latency-budget
  attribution.  Disabled by default, one-boolean short-circuit like
  the registry; SLO-violating requests keep their span tree in the
  flight ring (``docs/observability.md``).
* :class:`TracedLock` / :class:`LockOrderRecorder` — test-time lock
  wrapper recording acquisition order, asserted against the static
  LK003 lock-order graph (``analysis/threads``) so runtime-only
  acquisition paths can't introduce an unmodeled deadlock.

All recording is host-side, outside traced code — a metrics call inside
a jit region is a TL001 hazard by construction, and the tracelint
ratchet pins this package at zero TL001/TL006 findings.  See
``docs/observability.md`` for the metric catalogue and file formats.
"""

from .registry import (Counter, Gauge, Histogram, MetricsRegistry,
                       REGISTRY)
from .sinks import JsonlSink, MemorySink, write_prometheus
from .flight_recorder import FlightRecorder
from .compile_monitor import CompileMonitor
from .hw import peak_flops_per_chip
from .session import TelemetrySession, observe
from .traced_lock import LockOrderRecorder, TracedLock
from .tracing import (Span, SpanTracer, Timeline, Trace, TRACER,
                      attribution, write_spans_jsonl)

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "REGISTRY",
    "JsonlSink", "MemorySink", "write_prometheus", "FlightRecorder",
    "CompileMonitor", "TelemetrySession", "observe",
    "peak_flops_per_chip",
    "LockOrderRecorder", "TracedLock",
    "Span", "SpanTracer", "Timeline", "Trace", "TRACER", "attribution",
    "write_spans_jsonl",
]
