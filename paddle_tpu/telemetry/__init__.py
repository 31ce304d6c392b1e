"""paddle_tpu.telemetry — framework-wide metrics, tracing, and
instrumentation.

The observability layer the north-star serving system needs (per-request
latency, throughput, recompile telemetry) and the reference only hinted
at with its profiler (SURVEY §5.1). The pieces:

- ``metrics``: process-global :class:`MetricsRegistry` with typed
  :class:`Counter` / :class:`Gauge` / :class:`Histogram` (fixed
  log-spaced buckets, lock-free snapshot reads).
- ``trace``: program spans. A :class:`Span` is one
  ``jax.profiler.TraceAnnotation`` per phase (a serving tick and its
  phases, a wait for the replica's lock, a train step), entered whether
  or not telemetry is enabled, so every profiler session — a
  ``POST /profilez`` capture, a benchmark's tracer, ``start_profiler``
  — holds the program's spans on the clock of the device's lines. With
  no session running the annotation is inert (about a microsecond of
  Python per span; spans sit at phase boundaries, never per token).
  The host-side event list (chrome-trace JSON, JSONL; superseding
  ``core/profiler.py``'s RecordEvent) is kept only while
  ``start_profiler()`` collects. ``named`` gives a jitted program its
  stable ``pt_*`` name.
- ``recompile``: jitted-call signature fingerprinting — counts trace
  cache misses per call-site (the #1 silent TPU perf killer).
- ``export``: Prometheus text format + ``summary()`` human table +
  atomic ``write_textfile`` for node-exporter's textfile collector.
- ``server``: debug HTTP endpoint on a daemon thread (/metrics /healthz
  /statusz /tracez /memz) — opt-in via ``TrainLoop.run(debug_port=)``,
  ``serving.BatchedDecoder.run(debug_port=)``, or ``server.start()``.
- ``costs``: program cost ledger — XLA cost/memory analysis per cached
  executable, MFU + arithmetic intensity + roofline verdict derivation
  (peaks from ``utils.flops.DEVICE_PEAKS``; the CPU has none).
- ``profiling``: goodput ledger (step-time bucket decomposition,
  active-slot-tokens vs capacity), bounded on-demand device capture
  (``POST /profilez``, 404→409→200), and the ``PT-PERF-80x``
  step-time/ITL regression sentinel with persisted baselines.
- ``diag``: device-memory monitor + :class:`FlightRecorder` (ring of
  recent steps, anomaly watch, atomic dump-on-anomaly bundles with a
  record/skip_step/halt policy).
- ``lockwatch``: runtime lock-order watchdog — :class:`WatchedLock`
  records acquisition order at test time, catches real lock-order
  inversions with witness stack pairs, and validates the static
  ``analysis/concurrency.py`` lock graph against observed reality.

Everything but ``trace.Span`` is OFF by default and zero-cost when off:
instrumented call-sites check :func:`enabled` (one module-global bool)
before any dict work, and instrumentation only ever records host-side
scalars outside jit — tracers never reach an instrument. A ``Span`` is
one inert annotation per phase, always entered.

Usage::

    import paddle_tpu.telemetry as telemetry
    telemetry.enable()          # or PT_TELEMETRY=1
    ... serve / train ...
    print(telemetry.summary())              # human table
    text = telemetry.prometheus_text()      # /metrics payload
"""

from __future__ import annotations

from . import (costs, diag, export, lockwatch, metrics, profiling,
               recompile, server, trace, tracing)
from .diag import (AnomalyHalt, FlightRecorder, device_memory,
                   peak_memory_bytes)
from .export import (openmetrics_text, prometheus_text, summary,
                     write_textfile)
from .metrics import (Counter, DEFAULT_BUCKETS, Gauge, Histogram,
                      MetricsRegistry, cached_instruments, disable,
                      enable, enabled, log_buckets, registry)
from .recompile import RecompileTracker, fingerprint
from .server import DebugServer
from .trace import (RecordEvent, Span, export_chrome_trace, export_jsonl,
                    span)
from .tracing import (TRACE_HEADER, TraceContext, TraceSpan,
                      merge_chrome_trace, new_trace)

__all__ = [
    "AnomalyHalt", "Counter", "DEFAULT_BUCKETS", "DebugServer",
    "FlightRecorder", "Gauge", "Histogram",
    "MetricsRegistry", "RecompileTracker", "RecordEvent", "Span",
    "TRACE_HEADER", "TraceContext", "TraceSpan",
    "cached_instruments", "costs", "device_memory", "diag",
    "disable", "enable", "enabled", "export", "export_chrome_trace",
    "export_jsonl", "fingerprint", "log_buckets",
    "lockwatch", "merge_chrome_trace", "metrics", "new_trace",
    "openmetrics_text", "peak_memory_bytes", "profiling",
    "prometheus_text", "recompile", "registry", "reset", "server",
    "span", "summary", "trace", "tracing", "write_textfile",
]


def reset() -> None:
    """Full telemetry reset: drop every metric, span, trace, and
    recompile fingerprint (tests / between benchmark phases). Leaves
    the enabled flag as-is."""
    registry().reset()
    trace.reset()
    tracing.reset()
    recompile.tracker().reset()
    costs.reset()
    profiling.reset()
