"""Runtime collectors: the blind spots the registry makes visible.

Three sources that existed nowhere (or test-only) before this module:

- **XLA compiles** — :func:`install_compile_metrics` bridges
  ``jax.monitoring``'s backend-compile duration events into first-class
  metrics (``marlin_compile_total`` / ``marlin_compile_seconds``) plus a
  ``kind="compile"`` record in the default EventLog. This promotes the
  tally that previously lived ONLY in ``tests/conftest.py`` into the
  library: the per-call-recompile bug the test fixture caught in the
  streamed ops (parallel/streaming.py's hoisted jits) is exactly the class
  of regression production runs could not see. :func:`compile_count` is the
  process-wide tally the conftest fixture now reads.
- **Device memory** — :func:`install_device_memory_gauges` registers a
  render-time collector publishing ``memory_stats()`` of every local device
  (``bytes_in_use`` / ``bytes_limit``, labeled by device) next to the
  planner's HBM budget (``marlin_hbm_planner_budget_bytes``,
  :func:`~marlin_tpu.models.planner.usable_hbm_bytes`) — the pair the
  serving admission gate reasons about, finally on one dashboard.
  :func:`log_device_memory` emits the same numbers as an EventLog record
  for the analyzer's memory timeline.
- :func:`install_default_collectors` installs both (idempotent per
  registry); :class:`~marlin_tpu.obs.exposition.MetricsServer` calls it on
  start so every scrape endpoint carries them.

jax.monitoring offers registration but no selective deregistration, so the
compile listener registers once per process and keeps counting — which is
the Prometheus model anyway (counters are cumulative; consumers take
deltas)."""

from __future__ import annotations

import threading

from .metrics import MetricsRegistry, get_registry

__all__ = ["install_compile_metrics", "compile_count",
           "install_device_memory_gauges", "log_device_memory",
           "install_default_collectors"]

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_lock = threading.Lock()
_compile_installed = False
_compile_count = 0
_memory_installed: set[int] = set()  # id(registry) -> collector installed


def install_compile_metrics(registry: MetricsRegistry | None = None) -> None:
    """Register the jax.monitoring bridge (idempotent; first caller's
    registry wins — there is only one process-wide event stream). Every
    backend compile afterwards increments ``marlin_compile_total``,
    observes ``marlin_compile_seconds``, and lands a ``kind="compile"``
    record in the default EventLog when one is installed."""
    global _compile_installed
    with _lock:
        if _compile_installed:
            return
        _compile_installed = True
    reg = registry if registry is not None else get_registry()
    total = reg.counter(
        "marlin_compile_total",
        "XLA backend compiles observed via jax.monitoring")
    seconds = reg.histogram(
        "marlin_compile_seconds",
        "XLA backend compile durations (seconds)")
    from jax import monitoring

    def _on_duration(event, duration, **kw):
        global _compile_count
        if event != _COMPILE_EVENT:
            return
        _compile_count += 1  # GIL-atomic; fires from any compiling thread
        try:
            total.inc()
            seconds.observe(duration)
            from ..utils.tracing import get_default_event_log

            log = get_default_event_log()
            if log is not None:
                log.event("compile", seconds=duration)
        except Exception:
            pass  # a metrics failure must never fail the compile

    monitoring.register_event_duration_secs_listener(_on_duration)


def compile_count() -> int:
    """Process-wide backend-compile tally since
    :func:`install_compile_metrics` — the library home of what used to be
    the conftest-only ``_CompileTally``. Consumers (the conftest
    ``compile_count`` fixture, bench guards) take deltas around a block."""
    return _compile_count


def _collect_device_memory(reg: MetricsRegistry) -> None:
    import jax

    in_use = reg.gauge(
        "marlin_device_memory_bytes_in_use",
        "Per-device memory_stats()['bytes_in_use']", labelnames=("device",))
    limit = reg.gauge(
        "marlin_device_memory_bytes_limit",
        "Per-device memory_stats()['bytes_limit']", labelnames=("device",))
    budget = reg.gauge(
        "marlin_hbm_planner_budget_bytes",
        "The planner's usable-HBM budget (models.planner.usable_hbm_bytes) "
        "— what serving admission gates KV-cache bytes against")
    for d in jax.local_devices():
        try:
            stats = d.memory_stats() or {}
        except Exception:  # backends without memory introspection (CPU)
            stats = {}
        key = f"{d.platform}:{d.id}"
        if "bytes_in_use" in stats:
            in_use.labels(device=key).set(stats["bytes_in_use"])
        if "bytes_limit" in stats:
            limit.labels(device=key).set(stats["bytes_limit"])
    try:
        from ..models.planner import usable_hbm_bytes

        budget.set(usable_hbm_bytes())
    except Exception:
        pass


def install_device_memory_gauges(registry: MetricsRegistry | None = None,
                                 ) -> None:
    """Attach the device-memory/planner-budget collector to ``registry``
    (idempotent per registry): gauges refresh at every render, so a scrape
    reads live device state with no background poller."""
    reg = registry if registry is not None else get_registry()
    with _lock:
        if id(reg) in _memory_installed:
            return
        _memory_installed.add(id(reg))
    reg.add_collector(lambda: _collect_device_memory(reg))


def log_device_memory(log=None, **fields) -> None:
    """Emit one ``kind="memory"`` EventLog record with per-device
    ``bytes_in_use`` (the analyzer's memory-timeline sample). Uses the
    default log when none is given; no-ops without one."""
    import jax

    if log is None:
        from ..utils.tracing import get_default_event_log

        log = get_default_event_log()
    if log is None:
        return
    devices = {}
    for d in jax.local_devices():
        try:
            stats = d.memory_stats() or {}
        except Exception:
            stats = {}
        if "bytes_in_use" in stats:
            devices[f"{d.platform}:{d.id}"] = int(stats["bytes_in_use"])
    log.event("memory", devices=devices, **fields)


def install_default_collectors(registry: MetricsRegistry | None = None,
                               ) -> None:
    """Everything a scrape endpoint should carry: the compile bridge, the
    device-memory/planner gauges, the memory-ledger reconciler
    (obs/memledger.py — ``marlin_mem_*``, each scrape doubling as one
    leak-detection window), and the prefetch family pre-registration (so a
    serving-only process still exposes the prefetch series at zero instead
    of omitting them)."""
    reg = registry if registry is not None else get_registry()
    install_compile_metrics(reg)
    install_device_memory_gauges(reg)
    from .memledger import install_memledger_gauges

    install_memledger_gauges(reg)
    if reg is get_registry():
        # prefetch declares its families lazily on first pipeline; touch
        # them so the series exist (at zero) on processes that never stream
        from ..parallel import prefetch as _prefetch

        _prefetch._metric_families()
