"""Unified observability: metrics registry, exposition, traces, analysis.

The reference's observability was ``System.currentTimeMillis`` deltas and
printlns (SURVEY §5.1/§5.5); this package is the layer that exceeds it,
unifying what used to be four disconnected fragments (EventLog JSONL,
ServeMetrics counters, StageTimes, a test-only compile tally):

- :mod:`~marlin_tpu.obs.metrics` — thread-safe process-global registry of
  labeled ``Counter``/``Gauge``/``Histogram`` families with Prometheus
  text exposition (every existing counter in the library records here).
- :mod:`~marlin_tpu.obs.exposition` — stdlib ``http.server`` ``/metrics``
  endpoint; :func:`start_from_config` starts it from ``obs_http_port``.
- :mod:`~marlin_tpu.obs.collectors` — the jax.monitoring compile bridge
  and the process's start-up record (installed when this package is
  imported), device-memory gauges next to the planner's HBM budget.
- :mod:`~marlin_tpu.obs.trace` — contextvars span propagation so every
  EventLog record carries ``trace_id``/``span_id``/``parent_id`` and one
  serving request (or checkpoint save, or streamed op) is one joinable
  trace in the JSONL.
- :mod:`~marlin_tpu.obs.report` — the post-hoc analyzer
  (``python -m marlin_tpu.obs.report events.jsonl``).
- :mod:`~marlin_tpu.obs.timeseries` — bounded in-process windowed store
  (ring of aligned time buckets per series) fed from the registry by a
  render-time collector; rate/delta/percentile over trailing windows.
- :mod:`~marlin_tpu.obs.slo` — declarative serving SLOs (``serve_slo``
  config) evaluated over the time-series store: multi-window error-budget
  burn rates with hysteresis, ``marlin_slo_*`` gauges, breach hooks that
  drive graceful degradation, ``GET /debug/slo``.
- :mod:`~marlin_tpu.obs.console` — live terminal ops console
  (``python -m marlin_tpu.obs.console``) polling ``/metrics`` +
  ``/debug/slo``.
- :mod:`~marlin_tpu.obs.memledger` — the HBM ledger: process-global
  per-component device-memory attribution with exact debit on free,
  the reconciler (``marlin_mem_*`` gauges, ``GET /debug/memory``), leak
  detection, and OOM forensics dumps.
- :mod:`~marlin_tpu.obs.perf` — performance introspection: the
  single-flight on-demand profiler capture (``POST /debug/profile``,
  SIGUSR2), and the step-time flight recorder (``GET /debug/flight``).

docs/observability.md walks the whole surface.
"""

from . import trace  # noqa: F401  (stdlib-only; must import first — see below)
from . import memledger  # noqa: F401  (stdlib-only at import; jax lazy)
from .metrics import (  # noqa: F401
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    get_registry,
    percentile,
)
from .exposition import MetricsServer, start_from_config  # noqa: F401
from . import collectors  # noqa: F401  (imports utils.tracing lazily)
from . import perf  # noqa: F401  (imports jax lazily)
from .timeseries import TimeSeriesStore, install_collector  # noqa: F401
from .slo import SloEngine, fleet_merge, objectives_from_config  # noqa: F401

from .memledger import (  # noqa: F401
    MemoryLedger,
    get_leak_detector,
    get_ledger,
)

# every process hears its own compile path from here on (jax is imported by
# now: marlin_tpu.config came first); listeners only, nothing starts
collectors.install_compile_metrics()

__all__ = ["trace", "collectors", "memledger", "perf", "Counter", "Gauge",
           "Histogram", "MetricsRegistry", "get_registry", "percentile",
           "MetricsServer", "start_from_config", "TimeSeriesStore",
           "install_collector", "SloEngine", "fleet_merge",
           "objectives_from_config", "MemoryLedger", "get_ledger",
           "get_leak_detector"]
