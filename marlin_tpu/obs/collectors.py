"""Runtime collectors: the blind spots the registry makes visible.

Three sources that existed nowhere (or test-only) before this module:

- **The compile path, by program** — :func:`install_compile_metrics` bridges
  ``jax.monitoring`` into the process's one :class:`StartupRecord` and into
  first-class metrics. It hears the three timed stages every program goes
  through (``jaxpr_trace_duration``, ``jaxpr_to_mlir_module_duration``,
  ``backend_compile_duration``; each carries ``fun_name``, and sends its
  start as a scalar) and the persistent
  cache's ``cache_hits`` / ``cache_misses`` / ``cache_retrieval_time_sec``,
  and keeps one row a program: what tracing, lowering and the backend event
  cost, whether the backend event was a compile (``miss``, or ``off`` where
  JAX would not cache the program) or a load from the cache (``hit``: the
  backend event wraps ``compile_or_get_cached``, so it fires for a hit too),
  and which start-up spans were open anywhere in the process at that
  moment. ``marlin_compile_total{result}`` counts backend events by that
  result, ``marlin_compile_seconds`` observes their durations, and a
  ``kind="compile"`` record lands in the default EventLog when one is
  installed. The bridge is installed on the global registry when
  :mod:`marlin_tpu.obs` is imported, so every process has it.
  :func:`compile_count` is the tally of backend events (hits included) the
  conftest fixture reads: the per-call-recompile bug it caught in the
  streamed ops (parallel/streaming.py's hoisted jits) is exactly the class
  of regression production runs could not see.
- **Start-up spans** — :func:`startup_span` times the few places where a
  process spends its set-up (``startup.import``, ``serve.engine.init``,
  ``serve.kvpool.init``, ``serve.warmup``, ``matmul.first_dispatch``): a
  ``marlin:<name>`` annotation on the profiler's timeline plus a row in the
  record. :func:`startup_report` returns the record as one dict: spans,
  programs, the totals of the programs inside a span, and the programs
  compiled outside any by name (a program an engine compiled lazily under
  traffic shows there). ``benchmarks/layer_metrics/setup_*.py`` read it; a
  flight dump and ``obs.report`` carry it as one ``kind="startup"`` record.
- **Device memory** — :func:`install_device_memory_gauges` registers a
  render-time collector publishing ``memory_stats()`` of every local device
  (``bytes_in_use`` / ``bytes_limit``, labeled by device) next to the
  planner's HBM budget (``marlin_hbm_planner_budget_bytes``,
  :func:`~marlin_tpu.models.planner.usable_hbm_bytes`) — the pair the
  serving admission gate reasons about, finally on one dashboard.
  :func:`log_device_memory` emits the same numbers as an EventLog record
  for the analyzer's memory timeline.
- :func:`install_default_collectors` installs the gauges (idempotent per
  registry); :class:`~marlin_tpu.obs.exposition.MetricsServer` calls it on
  start so every scrape endpoint carries them.

jax.monitoring offers registration but no selective deregistration, so the
listeners register once per process and keep counting — which is the
Prometheus model anyway (counters are cumulative; consumers take deltas).
They run only on the compile path (a cached dispatch fires no event), cost
two clock reads and a list append an event, and never raise: a failure in
the record or the registry must not fail a compile."""

from __future__ import annotations

import collections
import contextlib
import threading
import time

from .metrics import MetricsRegistry, get_registry

__all__ = ["install_compile_metrics", "compile_count", "StartupRecord",
           "startup_span", "startup_record", "startup_report", "startup_event",
           "install_device_memory_gauges", "log_device_memory",
           "install_default_collectors"]

_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_RETRIEVAL_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
_CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": "hit",
                 "/jax/compilation_cache/cache_misses": "miss"}

_lock = threading.Lock()
_compile_installed = False
_compile_count = 0
_memory_installed: set[int] = set()  # id(registry) -> collector installed


class _Pending(threading.local):
    """One thread's part of a :class:`StartupRecord`: its open spans and the
    compile-path events it has heard that no row holds yet."""

    def __init__(self):
        self.stack: list[dict] = []   # the spans this thread has open
        self.trace = None             # (seconds, stamp): last finished trace
        self.own_traces: list = []    # one a lowering in progress here
        self.cache = None             # the cache's verdict: "hit" / "miss"
        self.retrieval_s = None


class StartupRecord:
    """What a process spent on the compile path and in its start-up spans,
    kept in memory (the module docstring has the vocabulary). One lock
    guards everything but a thread's own pending events. Bounded: past
    ``max_rows`` programs (``max_spans`` spans, and as many names of
    programs outside any span) only the totals and the ``dropped`` counts
    grow."""

    def __init__(self, max_rows: int = 4096, max_spans: int = 1024):
        self.max_rows, self.max_spans = int(max_rows), int(max_spans)
        self._lock = threading.Lock()
        self._tls = _Pending()
        self._spans: list[dict] = []
        self._open: list[dict] = []   # process-wide, in opening order
        self._rows: list[dict] = []
        #: module name -> lowerings no backend event has claimed yet: a
        #: program may be lowered on one thread and compiled on another
        #: (``hybrid._compile_side_by_side``)
        self._lowered: dict[str, collections.deque] = {}
        self._totals = {"trace_lower_s": 0.0, "compile_s": 0.0,
                        "cache_load_s": 0.0, "programs_compiled": 0,
                        "programs_loaded": 0}
        self._outside: dict[str, dict] = {}
        self._dropped = {"rows": 0, "spans": 0}

    # ------------------------------------------------------------- spans

    def open_span(self, name: str, fields: dict) -> dict:
        stack = self._tls.stack
        span = {"name": name, "parent": stack[-1]["name"] if stack else None,
                "t0": time.perf_counter(), "t1": None, "fields": fields}
        stack.append(span)
        with self._lock:
            self._open.append(span)
        return span

    def close_span(self, span: dict) -> None:
        span["t1"] = time.perf_counter()
        self._tls.stack.remove(span)
        with self._lock:
            self._open.remove(span)
            self._keep_span(span)

    def add_span(self, name: str, t0: float, t1: float, **fields) -> None:
        """A span timed by its caller (``startup.import``: the record does
        not exist yet at the package's first line)."""
        with self._lock:
            self._keep_span({"name": name, "parent": None, "t0": t0,
                             "t1": t1, "fields": fields})

    def _keep_span(self, span: dict) -> None:
        if len(self._spans) < self.max_spans:
            self._spans.append(span)
        else:
            self._dropped["spans"] += 1

    # ------------------------------------------------------ the compile path

    def on_duration(self, event: str, seconds: float, fun_name: str = "",
                    **_) -> dict | None:
        """One ``jax.monitoring`` duration event; returns the finished row
        on a backend event."""
        now = time.perf_counter()
        tls = self._tls
        if event == _TRACE_EVENT:
            # nested jits end inside the one that called them: the last
            # trace before a lowering begins is the whole program's
            tls.trace = (seconds, now)
        elif event == _LOWER_EVENT:
            own = tls.own_traces.pop() if tls.own_traces else None
            trace_s, t_trace = own or (0.0, None)
            tls.trace = None  # what the lowering traced is in its seconds
            entry = {"thread": threading.get_ident(), "trace_s": trace_s,
                     "t_trace": t_trace, "lower_s": seconds, "t_lower": now}
            with self._lock:
                self._lowered.setdefault(
                    fun_name, collections.deque(maxlen=64)).append(entry)
        elif event == _RETRIEVAL_EVENT:
            tls.retrieval_s = seconds
        elif event == _COMPILE_EVENT:
            cache, retrieval_s = tls.cache or "off", tls.retrieval_s
            tls.cache = tls.retrieval_s = None
            return self._finish(fun_name, seconds, now, cache, retrieval_s)
        return None

    def on_start(self, event: str) -> None:
        """A stage begins (``jax.monitoring`` sends its start as a scalar).
        A lowering traces jitted helpers of its own: the program's trace is
        the one this thread had finished when its lowering began."""
        if event == _LOWER_EVENT:
            tls = self._tls
            tls.own_traces.append(tls.trace)
            tls.trace = None

    def on_event(self, event: str, **_) -> None:
        """One ``jax.monitoring`` plain event: the cache's verdict, held for
        this thread's next backend event (it fires inside it)."""
        result = _CACHE_EVENTS.get(event)
        if result is not None:
            self._tls.cache = result

    def _finish(self, fun_name, backend_s, now, cache, retrieval_s) -> dict:
        me = threading.get_ident()
        with self._lock:
            lowered = self._lowered.get(fun_name)
            entry = None
            if lowered:
                # this thread's own newest lowering, else the oldest one
                # another thread left for a compiling thread to claim
                mine = [e for e in lowered if e["thread"] == me]
                entry = mine[-1] if mine else lowered[0]
                lowered.remove(entry)
                if not lowered:
                    del self._lowered[fun_name]
            entry = entry or {"trace_s": 0.0, "t_trace": None,
                              "lower_s": 0.0, "t_lower": None}
            row = {"fun_name": fun_name, "trace_s": entry["trace_s"],
                   "lower_s": entry["lower_s"], "backend_s": backend_s,
                   "cache": cache, "retrieval_s": retrieval_s,
                   "t_trace": entry["t_trace"], "t_lower": entry["t_lower"],
                   "t_backend": now,
                   "within": [s["name"] for s in self._open]}
            host_s = row["trace_s"] + row["lower_s"]
            if row["within"]:
                t = self._totals
                t["trace_lower_s"] += host_s
                if cache == "hit":
                    t["cache_load_s"] += backend_s
                    t["programs_loaded"] += 1
                else:
                    t["compile_s"] += backend_s
                    t["programs_compiled"] += 1
            else:
                key = (fun_name if fun_name in self._outside
                       or len(self._outside) < self.max_spans else "<other>")
                o = self._outside.setdefault(
                    key, {"programs": 0, "hits": 0, "trace_lower_s": 0.0,
                          "backend_s": 0.0, "t_first": now})
                o["programs"] += 1
                o["hits"] += cache == "hit"
                o["trace_lower_s"] += host_s
                o["backend_s"] += backend_s
                o["t_last"] = now
            if len(self._rows) < self.max_rows:
                self._rows.append(row)
            else:
                self._dropped["rows"] += 1
        return row

    # ------------------------------------------------------------- reading

    def report(self) -> dict:
        """The record as one dict of plain values. Stamps are
        ``time.perf_counter()`` of this process (``now`` is the clock at the
        call); a span still open has ``t1`` None."""
        with self._lock:
            spans = [dict(s, fields=dict(s["fields"]))
                     for s in self._spans + self._open]
            return {"now": time.perf_counter(), "spans": spans,
                    "programs": [dict(r, within=list(r["within"]))
                                 for r in self._rows],
                    "totals": dict(self._totals),
                    "outside": {k: dict(v)
                                for k, v in self._outside.items()},
                    "dropped": dict(self._dropped)}


_record = StartupRecord()


def startup_record() -> StartupRecord:
    """The process's one record (the bridge writes to it)."""
    return _record


def startup_report() -> dict:
    """:meth:`StartupRecord.report` of the process's record."""
    return _record.report()


def startup_event() -> dict:
    """The record as one EventLog-shaped ``kind="startup"`` record: what a
    flight dump ends with and ``obs.report`` renders."""
    return {"t": time.time(), "kind": "startup", **startup_report()}


@contextlib.contextmanager
def startup_span(name: str, **fields):
    """Time one piece of start-up: a ``marlin:<name>`` annotation (so a
    whole-process capture shows it on the device trace's clock) plus a span
    in the process's :class:`StartupRecord`, open process-wide while the
    body runs: a program compiled meanwhile, on any thread, is ``within``
    it. Usable as a decorator. Not for a per-iteration path."""
    from ..utils.tracing import annotate

    span = _record.open_span(name, fields)
    try:
        with annotate(name, **fields):
            yield span
    finally:
        _record.close_span(span)


def install_compile_metrics(registry: MetricsRegistry | None = None) -> None:
    """Register the jax.monitoring bridge (idempotent; first caller's
    registry wins — there is only one process-wide event stream, and
    importing :mod:`marlin_tpu.obs` is the first caller, with the global
    registry). Every trace, lowering and backend event afterwards lands in
    the process's :class:`StartupRecord`; every backend event increments
    ``marlin_compile_total{result}`` (``hit``: loaded from the persistent
    cache; ``miss``: compiled and written to it; ``off``: compiled, not
    cached), observes ``marlin_compile_seconds``, and lands a
    ``kind="compile"`` record in the default EventLog when one is
    installed."""
    global _compile_installed
    with _lock:
        if _compile_installed:
            return
        _compile_installed = True
    reg = registry if registry is not None else get_registry()
    total = reg.counter(
        "marlin_compile_total",
        "XLA backend compile events observed via jax.monitoring, by what "
        "the persistent cache did: hit (a load, not a compile), miss, off",
        labelnames=("result",))
    seconds = reg.histogram(
        "marlin_compile_seconds",
        "XLA backend compile event durations (seconds): a compile, or the "
        "cache read and executable load of a hit")
    from jax import monitoring

    on_duration, on_event, on_start = _listeners(_record, total, seconds)
    monitoring.register_event_duration_secs_listener(on_duration)
    monitoring.register_event_listener(on_event)
    monitoring.register_scalar_listener(on_start)


def _listeners(record: StartupRecord, total, seconds):
    """The three ``jax.monitoring`` callbacks (durations, plain events, the
    stages' starts) that feed ``record`` and the two metric families. None
    of them ever raises: JAX calls them inside the compile."""

    def on_duration(event, duration, **kw):
        global _compile_count
        if event == _COMPILE_EVENT:
            _compile_count += 1  # GIL-atomic; fires from any compiling thread
        try:
            row = record.on_duration(event, duration, **kw)
            if row is None:
                return
            total.labels(result=row["cache"]).inc()
            seconds.observe(duration)
            from ..utils.tracing import get_default_event_log

            log = get_default_event_log()
            if log is not None:
                log.event("compile", seconds=duration,
                          fun_name=row["fun_name"], cache=row["cache"])
        except Exception:
            pass  # a metrics failure must never fail the compile

    def on_event(event, **kw):
        try:
            record.on_event(event, **kw)
        except Exception:
            pass

    def on_start(event, *a, **kw):
        try:
            record.on_start(event)
        except Exception:
            pass

    return on_duration, on_event, on_start


def compile_count() -> int:
    """Process-wide tally of backend compile events (cache loads included)
    since :func:`install_compile_metrics` — the library home of what used
    to be the conftest-only ``_CompileTally``. Consumers (the conftest
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
