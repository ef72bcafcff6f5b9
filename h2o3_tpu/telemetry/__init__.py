"""h2o3_tpu.telemetry — the unified observability backbone.

One process-wide metrics registry (counters/gauges/histograms with
labels, lock-striped for the serve hot path), one span API (nested
timing contexts with explicit cross-thread parent handoff), and
device-aware collectors (XLA compile counter, compile-cache hit/miss,
h2d/d2h transfer bytes, device memory) — the single producer behind
``GET /metrics`` (Prometheus), ``GET /3/Telemetry`` (JSON snapshot) and
``GET /3/Timeline?format=trace`` (Perfetto), and the data source the
profiler tools (tools/profile_*.py) and the benchmark's per-layer
readers (benchmark/harness/readers/) read.

Where a span lands: ``span()`` reaches the ``h2o3_span_seconds``
histogram (/metrics, ``stage_seconds``), the finished-span ring
(``finished_spans``, ``/3/Timeline?format=trace``), the Flow timeline if
it is a root, and, while a ``jax.profiler`` session runs, the calling
thread's host line of the profiler's trace. ``open_span()``,
``record_span()`` and ``fold_span()`` reach the first three only
(spans.py says why).

``H2O3_TELEMETRY=0`` turns every producer into a checked no-op (one
attribute load + branch — guarded by tests/test_telemetry.py's
ns-budget microbench).
"""
from h2o3_tpu.telemetry import costmodel
from h2o3_tpu.telemetry.collectors import (device_get, device_memory_bytes,
                                           install, installed, record_d2d,
                                           record_d2h, record_h2d,
                                           sample_device_memory)
from h2o3_tpu.telemetry.export import (chrome_trace, chrome_trace_bytes,
                                       prometheus_text, telemetry_snapshot)
from h2o3_tpu.telemetry.profiling import profile
from h2o3_tpu.telemetry.registry import (Counter, Gauge, Histogram,
                                         Registry, enabled, registry,
                                         set_enabled)
from h2o3_tpu.telemetry.snapshot import (cluster_samples, cluster_snapshot,
                                         local_snapshot, merge_snapshots)
from h2o3_tpu.telemetry.spans import (Span, clear_spans, current_span,
                                      finished_spans, last_error_span,
                                      open_span, record_span,
                                      set_ring_capacity, span,
                                      stage_seconds)
from h2o3_tpu.telemetry import trace

__all__ = [
    "Counter", "Gauge", "Histogram", "Registry", "Span",
    "chrome_trace", "chrome_trace_bytes", "clear_spans",
    "cluster_samples", "cluster_snapshot", "costmodel", "current_span",
    "device_get", "device_memory_bytes", "enabled", "finished_spans", "install",
    "installed", "last_error_span", "local_snapshot", "merge_snapshots",
    "open_span", "profile", "prometheus_text",
    "record_d2d", "record_d2h",
    "record_h2d", "record_span", "registry", "sample_device_memory",
    "set_enabled", "set_ring_capacity", "span", "stage_seconds",
    "telemetry_snapshot", "trace",
]


def counter(name, labels=None, help=""):
    """Shorthand: a counter handle from the global registry."""
    return registry().counter(name, labels, help)


def gauge(name, labels=None, help=""):
    return registry().gauge(name, labels, help)


def histogram(name, labels=None, help="", **kw):
    return registry().histogram(name, labels, help, **kw)
