"""Timing and profiling hooks.

The reference's only observability is ad-hoc ``System.currentTimeMillis`` deltas
in examples and factorization loops plus ``MTUtils.evaluate`` to force lazy RDDs
(SURVEY.md §5.1 calls this a gap worth exceeding). Here:

- :func:`evaluate` — force-materialize (block_until_ready) without transferring,
  the analog of ``MTUtils.evaluate`` (utils/MTUtils.scala:218-220). Essential
  for honest timing under JAX's async dispatch.
- :func:`timer` — wall-clock context manager that prints millis like the
  examples do (e.g. examples/BLAS3.scala:34-56).
- :class:`StepTimer` — per-iteration timing hook for training loops.
- :class:`StageTimes` — per-stage wall-clock aggregation for pipelined
  operations (the streaming prefetch path's produce/transfer/compute/drain
  split), thread-safe because producer threads and the consumer record into
  the same instance.
- :func:`trace` — context manager around ``jax.profiler`` emitting a TensorBoard
  trace (XLA-level, per-op on TPU); no reference equivalent.
"""

from __future__ import annotations

import contextlib
import threading
import time

import jax


def evaluate(*xs):
    """Block until the given arrays (or matrices) are materialized on device;
    returns them. Accepts marlin matrices, jax arrays, or pytrees."""
    jax.block_until_ready([getattr(x, "data", x) for x in xs])
    return xs[0] if len(xs) == 1 else xs


@contextlib.contextmanager
def timer(label: str = "", results: list | None = None, quiet: bool = False):
    """Wall-clock the body, print millis like the reference's examples do —
    and, when a default :class:`~marlin_tpu.utils.tracing.EventLog` is
    installed, land the same timing there as a ``kind="timer"`` record
    (with the active trace context), so example/bench timings are part of
    the post-mortem stream instead of scrollback-only."""
    t0 = time.perf_counter()
    yield
    dt_ms = (time.perf_counter() - t0) * 1000.0
    if results is not None:
        results.append(dt_ms)
    from .tracing import get_default_event_log

    log = get_default_event_log()
    if log is not None:
        log.event("timer", label=label or "elapsed",
                  seconds=round(dt_ms / 1e3, 6))
    if not quiet:
        print(f"{label or 'elapsed'}: {dt_ms:.1f} ms")


class StepTimer:
    """Records per-step wall-clock; use around the body of an iterative loop."""

    def __init__(self):
        self.times_ms: list[float] = []
        self._t0 = None

    def start(self):
        self._t0 = time.perf_counter()

    def stop(self, sync=None):
        if sync is not None:
            evaluate(sync)
        self.times_ms.append((time.perf_counter() - self._t0) * 1000.0)

    @property
    def mean_ms(self) -> float:
        return sum(self.times_ms) / max(1, len(self.times_ms))

    def summary(self) -> str:
        if not self.times_ms:
            return "no steps recorded"
        return (
            f"{len(self.times_ms)} steps, mean {self.mean_ms:.1f} ms, "
            f"min {min(self.times_ms):.1f} ms, max {max(self.times_ms):.1f} ms"
        )


_stage_families = None  # lazy (registry import stays off the module path)


def _stage_metrics():
    global _stage_families
    if _stage_families is None:
        from ..obs.metrics import get_registry

        reg = get_registry()
        _stage_families = (
            reg.counter("marlin_stage_seconds_total",
                        "Wall-clock accumulated per pipeline stage "
                        "(StageTimes: produce/transfer/stall/compute/drain)",
                        labelnames=("stage",)),
            reg.counter("marlin_stage_events_total",
                        "StageTimes samples per pipeline stage",
                        labelnames=("stage",)),
        )
    return _stage_families


class StageTimes:
    """Aggregate wall-clock by named stage across threads.

    The streaming prefetch pipeline records ``produce`` (host read + dtype
    conversion), ``transfer`` (``jax.device_put`` dispatch), ``stall`` (time
    the consumer waited on the queue — the *un-overlapped* producer latency,
    ~0 when prefetch is keeping up), ``compute`` (device dispatch) and
    ``drain`` (blocking D2H fetches). Producer threads and the consumer write
    concurrently, hence the lock. Every sample also lands in the process
    metrics registry (``marlin_stage_seconds_total{stage=...}``), so stage
    budgets are scrapeable, not just printable."""

    def __init__(self):
        self.seconds: dict[str, float] = {}
        self.counts: dict[str, int] = {}
        self._lock = threading.Lock()

    def add(self, stage: str, seconds: float) -> None:
        with self._lock:
            self.seconds[stage] = self.seconds.get(stage, 0.0) + seconds
            self.counts[stage] = self.counts.get(stage, 0) + 1
        secs, events = _stage_metrics()
        secs.labels(stage=stage).inc(seconds)
        events.labels(stage=stage).inc()

    @contextlib.contextmanager
    def timed(self, stage: str):
        t0 = time.perf_counter()
        yield
        self.add(stage, time.perf_counter() - t0)

    def summary(self) -> str:
        with self._lock:
            if not self.seconds:
                return "no stages recorded"
            return ", ".join(
                f"{k} {self.seconds[k]:.3f}s/{self.counts[k]}"
                for k in sorted(self.seconds))

    def emit(self, kind: str = "stage_times", log=None, **fields) -> None:
        """Write one summary event to ``log`` (or the process-default
        EventLog); silently no-ops when neither exists."""
        from .tracing import get_default_event_log

        log = log or get_default_event_log()
        if log is None:
            return
        with self._lock:
            secs = {f"{k}_s": round(v, 6) for k, v in self.seconds.items()}
            counts = dict(self.counts)
        log.event(kind, **secs, counts=counts, **fields)


@contextlib.contextmanager
def trace(logdir: str = "/tmp/marlin_tpu_trace"):
    """Emit a jax.profiler trace viewable in TensorBoard/XProf.

    This is the inline, wrap-your-own-code spelling. For a *running*
    process, the same capture is a triggerable service:
    :func:`marlin_tpu.obs.perf.capture_profile` (single-flight, rotating
    size-capped capture dir, ``kind="profile"`` EventLog record), exposed
    as ``POST /debug/profile?seconds=N`` on the obs HTTP server and as a
    SIGUSR2 hook — no code change, no restart."""
    jax.profiler.start_trace(logdir)
    try:
        yield logdir
    finally:
        jax.profiler.stop_trace()
