"""Observability layer (DESIGN.md §16): metrics, traces, exporters.

Three pieces, each independently usable:

* ``metrics`` — thread-safe ``MetricsRegistry`` of counters, gauges,
  labeled counters, array counters, and log-bucketed streaming
  ``Histogram``s (bounded memory, p50/p95/p99/max within a 5% bucket
  width, exact min/max).  The serving runtime owns one registry; the
  batcher, cache, and tier ladder all record into it under dotted
  ``layer.component.metric`` names.
* ``trace`` — spans with a near-zero-cost disabled path (a shared
  no-op singleton), a ``timed()`` helper that keeps the historical
  ``timings``-dict contract while emitting spans, and raw ``event()``
  emission for intervals measured elsewhere (per-request lifecycle).
  Build stages, ``refresh_index`` stages, hierarchy closures, the
  serve flush and the planner all trace through the module-level
  default tracer; while a JAX profiler session records, every span is
  also mirrored into it as a ``TraceAnnotation``.
* ``export`` — Chrome-trace JSONL writer/loader (opens in
  chrome://tracing), atomic periodic metrics snapshots + Prometheus
  text exposition (``serve.py --metrics-out/--metrics-port``), and
  the worst-N ``SlowQueryLog``.

The overhead contract: with tracing disabled (the default) and no
profiler recording, call sites cost one attribute read and one
``TraceAnnotation.is_enabled()`` call; with everything on, live road4000
serving stays within the measured <2% qps budget (``BENCH_serve.json``
section ``obs_overhead``, enforced by the A-B in ``tests/test_obs.py``).
"""
from .export import (MetricsExporter, MetricsServer, SlowQueryLog,
                     load_chrome_trace, write_chrome_trace,
                     write_snapshot)
from .metrics import (ArrayCounter, Counter, Gauge, Histogram,
                      HistogramSnapshot, LabeledCounter,
                      MetricsRegistry)
from .trace import Tracer, event, get_tracer, span, timed

__all__ = [
    "ArrayCounter", "Counter", "Gauge", "Histogram",
    "HistogramSnapshot", "LabeledCounter", "MetricsExporter",
    "MetricsRegistry", "MetricsServer", "SlowQueryLog", "Tracer",
    "event", "get_tracer", "load_chrome_trace", "span", "timed",
    "write_chrome_trace", "write_snapshot",
]
