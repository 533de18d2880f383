"""Asynchronous host→device prefetch for the out-of-core streaming layer.

The problem: the tall-skinny Gramian's rank update runs ~10,901 GFLOP/s with
operands resident (PERF.md, "Chip rows older than PR 1"), but end to end
the device idles while the caller's thread synchronously reads a chunk,
converts its dtype, and dispatches the upload, one chunk at a time. The
reference never faced this (Spark's shuffle fetches overlap task compute for
free); the TPU rebuild needs the overlap built explicitly, the conclusion of
both "Large Scale Distributed Linear Algebra With TPUs" (arxiv 2112.09017)
and JAMPI (arxiv 2007.01811): sustained throughput at scale is decided by
feed/communication overlap, not kernel speed.

:class:`ChunkPrefetcher` is that overlap: a bounded producer/consumer stage
where background threads pull chunks from the source (ndarray/memmap views,
file loaders, generators), run dtype conversion / transfer compression off
the critical path, and issue non-blocking ``jax.device_put`` so the H2D copy
of chunk i+1 rides under device compute of chunk i. Guarantees:

- **Ordering** — chunks come out in source order regardless of worker count
  (reads are serialized; a reorder buffer absorbs out-of-order completion).
- **Backpressure** — at most ``depth`` chunks in flight (read but not yet
  consumed), plus an optional in-flight HBM byte budget
  (``config.prefetch_hbm_budget_bytes``) so big chunks can't stack up in
  device memory; at least one chunk always proceeds, so no budget deadlock.
- **Exception propagation** — a producer-side error (source, transform, or
  upload) surfaces at the consumer as the original exception, at the position
  in the stream where it occurred; it never hangs the caller.
- **Clean shutdown** — :meth:`close` (idempotent, also called on exhaustion
  and by ``with``) stops and joins every worker; tests assert no
  ``marlin-prefetch-*`` thread outlives its pipeline.
- **Chaos hooks** — each read passes the ``prefetch.produce`` fault point
  (utils/faults.py), so delayed/failing sources are injectable.
- **Instrumentation** — per-stage seconds (``produce``/``transfer``/``stall``)
  accumulate in a :class:`~marlin_tpu.utils.profiling.StageTimes` and one
  summary event lands in the default EventLog on close, so the overlap is
  measurable, not asserted: ``stall`` is exactly the producer latency the
  consumer still sees.
"""

from __future__ import annotations

import itertools
import threading
import time
from typing import Any, Callable, Iterable

import jax

from ..config import get_config
from ..obs import perf, trace as obs_trace
from ..obs.metrics import get_registry
from ..utils import faults
from ..utils.profiling import StageTimes

__all__ = ["ChunkPrefetcher", "prefetch_chunks"]

_ids = itertools.count()

_families = None  # lazy singleton: one set of registry families, all pipelines

_flight = None  # lazy shared flight recorder: all pipelines, one black box
_flight_lock = threading.Lock()


def _ledger_add(delta: int) -> None:
    """Mirror an in-flight-bytes gauge delta into the process MemoryLedger
    (component ``prefetch``, one shared flow entry — the scrape-time
    reconciler attributes prefetched-but-unconsumed HBM). Never raises."""
    try:
        from ..obs.memledger import get_ledger

        get_ledger().add("prefetch:inflight", delta, "prefetch")
    except Exception:
        pass


def _flight_ring() -> "perf.FlightRecorder":
    """The prefetch flight recorder (obs/perf.py): per-chunk production
    records from every pipeline's producer threads, dumped to JSONL when a
    producer dies so the post-mortem shows the chunks leading up to the
    failure. Shared process-wide (pipelines are short-lived; a per-pipeline
    ring would vanish with the object that just crashed)."""
    global _flight
    with _flight_lock:
        if _flight is None:
            _flight = perf.FlightRecorder(name="prefetch")
        return _flight


def _metric_families():
    """(chunks counter, stall-seconds counter, ready-depth gauge,
    in-flight-bytes gauge) — shared by every pipeline in the process (the
    Prometheus model: the scrape sees the aggregate, per-op splits live in
    the per-op StageTimes). obs.collectors touches this at endpoint start
    so the series exist even before the first streamed op."""
    global _families
    if _families is None:
        reg = get_registry()
        _families = (
            reg.counter("marlin_prefetch_chunks_total",
                        "Chunks delivered to consumers by prefetch "
                        "pipelines"),
            reg.counter("marlin_prefetch_stall_seconds_total",
                        "Seconds consumers waited on the prefetch queue "
                        "(un-overlapped producer latency)"),
            reg.gauge("marlin_prefetch_ready_depth",
                      "Produced-but-unconsumed chunks buffered right now"),
            reg.gauge("marlin_prefetch_inflight_bytes",
                      "Bytes of prefetched-but-unconsumed chunks counted "
                      "against the HBM budget"),
        )
    return _families


class ChunkPrefetcher:
    """Iterate ``source``'s chunks with production moved to background threads.

    ``source``: any iterable of array chunks. ``transform``: optional host-side
    per-chunk function (dtype conversion, compression) run on a worker thread.
    ``device_put=True`` additionally issues a non-blocking ``jax.device_put``
    on the worker, so consumers receive committed-to-device arrays;
    ``device_put=False`` yields host arrays (host-only pipelines, e.g.
    ``OutOfCoreMatrix.sum``). ``depth``/``workers``/``hbm_budget_bytes``
    default from :mod:`marlin_tpu.config`.

    Use as an iterator (``for x in ChunkPrefetcher(src): ...``); wrap in
    ``with`` or call :meth:`close` when abandoning it mid-stream.
    """

    def __init__(self, source: Iterable, transform: Callable[[Any], Any] | None = None,
                 *, depth: int | None = None, workers: int | None = None,
                 device_put: bool = True, hbm_budget_bytes: int | None = None,
                 stats: StageTimes | None = None):
        cfg = get_config()
        self._depth = cfg.prefetch_depth if depth is None else depth
        n_workers = cfg.prefetch_workers if workers is None else workers
        self._budget = (cfg.prefetch_hbm_budget_bytes
                        if hbm_budget_bytes is None else hbm_budget_bytes)
        if self._depth < 1:
            raise ValueError(f"prefetch depth must be >= 1, got {self._depth}")
        if n_workers < 1:
            raise ValueError(f"prefetch workers must be >= 1, got {n_workers}")
        self._it = iter(source)
        self._transform = transform
        self._device_put = device_put
        self.stats = stats if stats is not None else StageTimes()
        self._metrics = _metric_families()
        # producer threads inherit the *creating* thread's span context, so
        # chunk-pipeline records (fault retries, the close summary) join the
        # streamed op's / checkpoint's trace (obs/trace.py thread handoff)
        self._span = obs_trace.capture()

        self._src_lock = threading.Lock()  # serializes next(it) + index assignment
        self._cv = threading.Condition()
        self._slots = threading.Semaphore(self._depth)
        self._stop = threading.Event()
        self._ready: dict[int, tuple] = {}   # idx -> ("ok", chunk, nbytes) | ("err", exc)
        self._next_read = 0
        self._next_yield = 0
        self._next_admit = 0  # HBM-budget admission cursor (stream order)
        self._end: int | None = None         # first index past the stream
        self._inflight_bytes = 0
        self._closed = False
        self._emitted = False

        pid = next(_ids)
        self._threads = [
            threading.Thread(target=self._work, daemon=True,
                             name=f"marlin-prefetch-{pid}-{w}")
            for w in range(n_workers)
        ]
        for t in self._threads:
            t.start()

    # ---------------------------------------------------------------- producer
    def _work(self) -> None:
        with obs_trace.use(self._span):
            self._work_loop()

    def _work_loop(self) -> None:
        while not self._stop.is_set():
            # bounded queue: one slot per chunk in flight; timed acquire so a
            # close() while blocked here is noticed (close also over-releases)
            if not self._slots.acquire(timeout=0.1):
                continue
            if self._stop.is_set():
                return
            t0 = time.perf_counter()
            with self._src_lock:
                if self._end is not None:
                    return  # stream already over (EOF or source error)
                i = self._next_read
                try:
                    faults.fire("prefetch.produce", path=f"chunk-{i}", index=i)
                    chunk = next(self._it)
                except StopIteration:
                    self._finish(i)
                    return
                except BaseException as e:  # source failure ends the stream
                    self._flight_fault(i, "source", e)
                    self._post(i, ("err", e, 0))
                    self._finish(i + 1)
                    return
                self._next_read = i + 1
            # off the source lock: convert + upload (the parallelizable part)
            admitted = 0
            try:
                if self._transform is not None:
                    chunk = self._transform(chunk)
                nbytes = int(getattr(chunk, "nbytes", 0))
                produce_s = time.perf_counter() - t0
                self.stats.add("produce", produce_s)
                _flight_ring().record("chunk", i=i, nbytes=nbytes,
                                      seconds=produce_s,
                                      ready=len(self._ready))
                if not self._wait_for_budget(i, nbytes):
                    return  # closed while waiting
                admitted = nbytes
                if self._device_put:
                    with self.stats.timed("transfer"):
                        chunk = jax.device_put(chunk)  # non-blocking dispatch
                self._post(i, ("ok", chunk, nbytes))
            except BaseException as e:  # transform/upload failure: positional
                with self._cv:
                    # refund admitted budget (the failed chunk occupies no
                    # HBM) and, on a pre-admission failure, advance the
                    # admission cursor past i — successors must not stall
                    # against a chunk that will never be admitted
                    self._inflight_bytes -= admitted
                    self._metrics[3].dec(admitted)  # refund the gauge too
                    _ledger_add(-admitted)
                    if self._next_admit == i:
                        self._next_admit = i + 1
                    self._cv.notify_all()
                self._flight_fault(i, "transform/upload", e)
                self._post(i, ("err", e, 0))

    @staticmethod
    def _flight_fault(i: int, stage: str, exc: BaseException) -> None:
        """A producer died: put the failure in the ring, then dump it —
        the chunks leading up to this are exactly what the post-mortem
        needs and the ring is about to stop filling. Never raises."""
        try:
            ring = _flight_ring()
            ring.record("produce_error", i=i, stage=stage,
                        error=f"{type(exc).__name__}: {exc}")
            ring.dump(reason="producer-died")
        except Exception:
            pass

    def _wait_for_budget(self, i: int, nbytes: int) -> bool:
        """Block until chunk ``i`` may occupy the in-flight HBM budget.

        Admission is in STREAM ORDER (``_next_admit`` cursor), not
        first-come: if chunk i+1's worker could claim the budget while chunk
        i's worker still waits for it, the consumer — which needs i before
        i+1 — would wait on a chunk whose budget is held by one it cannot
        consume yet: deadlock. Order-of-index admission makes the budget
        queue drain in the same order the consumer does. A lone chunk always
        fits (``inflight == 0``), so an undersized budget serializes instead
        of deadlocking. Returns False if closed while waiting."""
        with self._cv:
            if self._stop.is_set():
                return False  # closed: don't touch the (shared) gauges
            if self._budget > 0:
                while not self._stop.is_set() and (
                        self._next_admit != i
                        or (self._inflight_bytes > 0
                            and self._inflight_bytes + nbytes > self._budget)):
                    self._cv.wait(0.1)
                if self._stop.is_set():
                    return False
                self._next_admit = i + 1
            self._inflight_bytes += nbytes
            # gauges move by deltas: several pipelines may run concurrently
            # and the scrape must see their sum, not the last writer
            self._metrics[3].inc(nbytes)
            _ledger_add(nbytes)
            self._cv.notify_all()
            return True

    def _post(self, i: int, item: tuple) -> None:
        with self._cv:
            if not self._stop.is_set():
                self._ready[i] = item
                self._metrics[2].inc()
            self._cv.notify_all()

    def _finish(self, end: int) -> None:
        with self._cv:
            if self._end is None or end < self._end:
                self._end = end
            self._cv.notify_all()

    # ---------------------------------------------------------------- consumer
    def __iter__(self):
        return self

    def __next__(self):
        if self._closed:
            raise StopIteration
        j = self._next_yield
        t0 = time.perf_counter()
        with self._cv:
            while j not in self._ready:
                if self._end is not None and j >= self._end:
                    break
                # timed wait: a wedged producer must never hang the caller
                # forever without close() being able to intervene
                self._cv.wait(0.1)
            item = self._ready.pop(j, None)
            if item is not None:
                self._metrics[2].dec()
        stall = time.perf_counter() - t0
        self.stats.add("stall", stall)
        self._metrics[1].inc(stall)
        if item is None:  # clean exhaustion
            self.close()
            raise StopIteration
        self._next_yield = j + 1
        kind, payload, nbytes = item
        if kind == "err":
            self.close()
            raise payload
        with self._cv:
            self._inflight_bytes -= nbytes
            self._metrics[3].dec(nbytes)
            _ledger_add(-nbytes)
            self._cv.notify_all()
        self._slots.release()
        self._metrics[0].inc()
        return payload

    # ------------------------------------------------------------- lifecycle
    def close(self) -> None:
        """Stop and join every worker; safe to call repeatedly. Buffered
        chunks are dropped (their device buffers free with the references).

        Never raises: close() runs on the streamed ops' finally-path, where
        an exception would mask the caller's real one. A worker that outlives
        the join window (e.g. parked in a slow source read it will finish on
        its own — it is a daemon and observes the stop flag at its next
        checkpoint) is reported as a warning instead; the test suite's
        thread-leak fixture still fails genuinely stuck workers loudly."""
        with self._cv:
            if self._closed:
                return
            self._closed = True
            self._stop.set()
            # release only THIS pipeline's contribution to the shared
            # gauges — a concurrent pipeline's buffered chunks stay counted
            self._metrics[2].dec(len(self._ready))
            self._metrics[3].dec(self._inflight_bytes)
            _ledger_add(-self._inflight_bytes)
            self._ready.clear()
            self._inflight_bytes = 0
            self._cv.notify_all()
        for _ in self._threads:  # unblock any worker stuck on a full queue
            self._slots.release()
        for t in self._threads:
            t.join(timeout=10.0)
        alive = [t.name for t in self._threads if t.is_alive()]
        if alive:
            import warnings

            warnings.warn(f"prefetch worker(s) still running after close() "
                          f"(blocked in a slow source read?): {alive}",
                          RuntimeWarning, stacklevel=2)
        if not self._emitted:
            self._emitted = True
            self.stats.emit(kind="prefetch", chunks=self._next_yield,
                            depth=self._depth, workers=len(self._threads))

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


def prefetch_chunks(source: Iterable, transform: Callable[[Any], Any] | None = None,
                    **kwargs) -> ChunkPrefetcher:
    """Functional spelling of :class:`ChunkPrefetcher` (same signature)."""
    return ChunkPrefetcher(source, transform, **kwargs)
