"""Runtime telemetry plane (DESIGN.md §11).

One low-overhead subsystem threaded through every serving layer:

* :mod:`repro.obs.metrics` — thread-safe :class:`MetricRegistry` of
  counters, gauges, and log-bucketed histograms (p50/p95/p99/max without
  stored samples), a process-global default, and the strict no-op
  :class:`NullRegistry` so disabled telemetry costs one attribute lookup;
* :mod:`repro.obs.trace`   — nested ``span("sync.flip")`` tracing with
  monotonic stamps that also enters ``jax.profiler`` named scopes, so
  wall-clock spans line up with XLA device traces, with a pairing onto
  the profiler's host clock; ``registry.timed(name)`` is such a span that
  also observes its ``<name>.us`` histogram;
* :mod:`repro.obs.export`  — Prometheus-style text exposition plus a
  bounded JSONL :class:`TelemetrySink` benchmarks and CI snapshot
  deterministically.

Instrumented layers: the kernel engine dispatch, the autotune cache,
:class:`~repro.core.DeviceImageStore` syncs,
:class:`~repro.serve.router.SessionRouter`,
:class:`~repro.serve.plane.ShardedLookupPlane`, and
:mod:`repro.launch.replicate`.  ``ScenarioDriver(telemetry=True)`` scopes
a registry to one replay; ``obs.enable()`` turns the process-global
default on.
"""
from .export import (NullSink, TelemetrySink, render_prometheus,
                     snapshot_text)
from .metrics import (Counter, Gauge, Histogram, MetricRegistry,
                      NullRegistry, bucket_index, bucket_upper,
                      count_compiles, default_registry, disable, enable,
                      ensure_real, set_default_registry)
from .trace import NullTracer, Span, Tracer

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricRegistry", "NullRegistry",
    "NullSink", "NullTracer", "Span", "TelemetrySink", "Tracer",
    "bucket_index", "bucket_upper", "count_compiles", "default_registry",
    "disable", "enable", "ensure_real", "render_prometheus",
    "set_default_registry", "snapshot_text",
]
