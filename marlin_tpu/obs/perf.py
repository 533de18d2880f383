"""Performance introspection: profiler capture and the flight recorder.

Both passive (a broken probe must never fail the program it watches). How
fast a program ran is NOT read here: every rate, share and roofline number
comes from the device trace that ``benchmarks/`` reduces (``BENCHMARK.json``,
``PERF.md``); :func:`capture_profile` is how a running process hands an
operator such a trace.

- **On-demand profiler capture** — :func:`capture_profile` promotes
  ``utils.profiling.trace()`` into a triggerable service: a single-flight
  ``jax.profiler`` trace into a size-capped rotating capture directory
  (``obs_profile_dir`` / ``obs_profile_cap_bytes``), landing a
  ``kind="profile"`` EventLog record with the artifact path. Exposed as
  ``POST /debug/profile?seconds=N`` on the obs HTTP server (second
  concurrent request gets 409) and as a SIGUSR2 hook
  (:func:`install_profile_signal`).
- **Step-time flight recorder** — :class:`FlightRecorder`, a small locked
  ring buffer of per-iteration records (bucket, live slots, queue depth,
  step wall-times, compile tallies) written from the serving worker loop
  and prefetch producers, dumped to JSONL on unhandled worker exceptions,
  on ``engine.close()``, and on demand via ``GET /debug/flight`` — the
  black box for post-mortems where the EventLog tail alone cannot
  reconstruct the final iterations. Dumps are plain event records
  (``kind="flight"``), so ``obs.report`` parses them unchanged.

jax imports stay inside functions: ``obs`` must import on hosts where the
backend is broken (observability is how you debug exactly those hosts).
"""

from __future__ import annotations

import collections
import itertools
import json
import os
import shutil
import signal
import tempfile
import threading
import time
import weakref
from typing import Any

__all__ = ["FlightRecorder", "flight_records", "capture_profile",
           "ProfileBusy", "install_profile_signal"]


def _log_event(kind: str, log=None, **fields) -> None:
    """Land one record in ``log`` (default: the process EventLog, resolved
    per emit), swallowing every failure — the one emission idiom shared by
    flight dumps and profile captures: observability must never fail the
    path it observes."""
    try:
        if log is None:
            from ..utils.tracing import get_default_event_log

            log = get_default_event_log()
        if log is not None:
            log.event(kind, **fields)
    except Exception:
        pass


# ------------------------------------------------------------ flight recorder

_flights: "weakref.WeakSet" = weakref.WeakSet()


def _capture_dir() -> str:
    from ..config import get_config

    d = get_config().obs_profile_dir
    if not d:
        d = os.path.join(tempfile.gettempdir(), "marlin_tpu_captures")
    os.makedirs(d, exist_ok=True)
    return d


_dump_ids = itertools.count()  # distinct dump/capture paths within a second


class FlightRecorder:
    """Bounded in-memory ring of per-iteration records — the black box.

    ``record(ev, **fields)`` appends one dict (stamped ``t`` +
    ``kind="flight"`` + ``src``) under a single small lock (the writers are
    per-engine-iteration / per-chunk, never per-token, and snapshot readers
    must not race a mutating ``deque``). ``maxlen`` defaults from
    ``config.obs_flight_len``. Instances self-register in a process-wide
    weak set so ``GET /debug/flight`` sees every live recorder.

    :meth:`dump` writes the ring to a JSONL file under the capture
    directory (pruned to the newest :data:`_FLIGHT_KEEP` dumps) and lands a
    ``kind="flight"`` / ``ev="dump"`` record with the artifact path in the
    default EventLog. It never raises — dumps ride worker failure paths."""

    _FLIGHT_KEEP = 16  # dump files kept in the capture dir, newest first

    def __init__(self, maxlen: int | None = None, name: str = ""):
        from ..config import get_config

        if maxlen is None:
            maxlen = get_config().obs_flight_len
        self.name = name
        self._lock = threading.Lock()
        self._buf: collections.deque = collections.deque(maxlen=max(1, maxlen))
        _flights.add(self)

    def record(self, ev: str, **fields: Any) -> None:
        rec = {"t": time.time(), "kind": "flight", "src": self.name,
               "ev": ev, **fields}
        with self._lock:
            self._buf.append(rec)

    def records(self) -> list[dict]:
        with self._lock:
            return list(self._buf)

    def __len__(self) -> int:
        with self._lock:
            return len(self._buf)

    def dump(self, path: str | None = None, reason: str = "",
             log=None) -> str | None:
        """Write the ring (oldest first) to ``path`` (default: a fresh
        ``flight-<name>-<reason>-<stamp>.jsonl`` under the capture dir) as
        EventLog-shaped JSONL. Returns the path, or None when the ring is
        empty or the write failed (never raises)."""
        recs = self.records()
        if not recs:
            return None
        try:
            if path is None:
                # the counter keeps a fault dump and the close dump of the
                # same recorder in the same second from clobbering each
                # other; the reason slug rides in the name so pruning can
                # tell a post-mortem from a routine close
                slug = "".join(c if c.isalnum() else "-"
                               for c in (reason or "manual"))[:24]
                stamp = time.strftime("%Y%m%d-%H%M%S")
                path = os.path.join(
                    _capture_dir(),
                    f"flight-{self.name or 'ring'}-{slug}-{stamp}-"
                    f"{os.getpid()}-{next(_dump_ids)}.jsonl")
            from .collectors import startup_event

            with open(path, "w") as f:
                for r in recs:
                    if reason:
                        r = {**r, "reason": reason}
                    f.write(json.dumps(r) + "\n")
                # what the process spent starting up, and every program it
                # compiled since: a black box that says "compiles=15" should
                # also say which
                f.write(json.dumps(startup_event()) + "\n")
            self._prune_dumps(os.path.dirname(path))
        except Exception:
            return None
        _log_event("flight", log=log, ev="dump", src=self.name, path=path,
                   records=len(recs), reason=reason)
        return path

    @classmethod
    def _prune_dumps(cls, d: str) -> None:
        """Bound the dump dir, reason-aware: routine ``close`` dumps and
        fault post-mortems prune as SEPARATE pools (newest ``_FLIGHT_KEEP``
        each), so a process that churns engines cannot evict the one dump
        whose failure reason is the whole point of the black box."""
        try:
            dumps = sorted(
                (f for f in os.listdir(d)
                 if f.startswith("flight-") and f.endswith(".jsonl")),
                key=lambda f: os.path.getmtime(os.path.join(d, f)))
            routine = [f for f in dumps if "-close-" in f]
            faults_ = [f for f in dumps if "-close-" not in f]
            for pool in (routine, faults_):
                for f in pool[:-cls._FLIGHT_KEEP]:
                    os.remove(os.path.join(d, f))
        except OSError:
            pass


def flight_records() -> list[dict]:
    """Every live recorder's ring, merged oldest-first — the
    ``GET /debug/flight`` payload."""
    recs: list[dict] = []
    for fr in list(_flights):
        recs.extend(fr.records())
    recs.sort(key=lambda r: r.get("t", 0.0))
    return recs


# ------------------------------------------------------------ profile capture


class ProfileBusy(RuntimeError):
    """A capture is already in flight (captures are single-flight: two
    concurrent ``jax.profiler`` traces would corrupt each other)."""


_profile_lock = threading.Lock()


def capture_profile(seconds: float = 2.0, logdir: str | None = None,
                    log=None) -> str:
    """Run one ``jax.profiler`` trace for ``seconds`` into a fresh
    subdirectory of the capture dir (``config.obs_profile_dir``), prune the
    dir to ``config.obs_profile_cap_bytes``, land a ``kind="profile"``
    EventLog record with the artifact path, and return that path.

    Single-flight: a second caller while one capture runs gets
    :class:`ProfileBusy` immediately (the HTTP endpoint maps it to 409).
    The profiler is stopped even when the timed sleep is interrupted."""
    if not _profile_lock.acquire(blocking=False):
        raise ProfileBusy("a profiler capture is already in flight")
    try:
        import jax

        seconds = max(0.0, float(seconds))
        base = logdir if logdir is not None else _capture_dir()
        os.makedirs(base, exist_ok=True)
        # counter suffix: back-to-back captures in one second must not
        # commingle their artifacts in one directory (single-flight only
        # serializes them, it does not space them out)
        stamp = time.strftime("%Y%m%d-%H%M%S")
        path = os.path.join(
            base, f"profile-{stamp}-{os.getpid()}-{next(_dump_ids)}")
        t0 = time.perf_counter()
        jax.profiler.start_trace(path)
        try:
            time.sleep(seconds)
        finally:
            jax.profiler.stop_trace()
        wall = time.perf_counter() - t0
        _prune_captures(base)
        _log_event("profile", log=log, path=path, seconds=wall,
                   requested_s=seconds)
        return path
    finally:
        _profile_lock.release()


def _tree_bytes(path: str) -> int:
    total = 0
    for root, _, files in os.walk(path):
        for f in files:
            try:
                total += os.path.getsize(os.path.join(root, f))
            except OSError:
                pass
    return total


def _prune_captures(base: str) -> None:
    """Rotate the capture directory: drop the oldest ``profile-*`` capture
    trees until the total is under ``obs_profile_cap_bytes`` (the newest
    capture always survives, even oversized — deleting what the caller was
    just promised would be worse)."""
    from ..config import get_config

    cap = get_config().obs_profile_cap_bytes
    if not cap:
        return
    try:
        captures = sorted(
            (os.path.join(base, f) for f in os.listdir(base)
             if f.startswith("profile-")
             and os.path.isdir(os.path.join(base, f))),
            key=os.path.getmtime)
        sizes = {c: _tree_bytes(c) for c in captures}
        while len(captures) > 1 and sum(sizes[c] for c in captures) > cap:
            victim = captures.pop(0)
            shutil.rmtree(victim, ignore_errors=True)
    except OSError:
        pass


def install_profile_signal(seconds: float = 5.0) -> bool:
    """Install a SIGUSR2 handler that fires :func:`capture_profile` on a
    background thread (an in-flight capture makes the signal a no-op).
    Returns False where installation is impossible (non-main thread,
    platforms without SIGUSR2) — long-running entrypoints call this
    unconditionally."""
    if not hasattr(signal, "SIGUSR2"):
        return False

    def _on_signal(signum, frame):
        def _go():
            try:
                capture_profile(seconds)
            except ProfileBusy:
                pass
            except Exception:
                pass

        threading.Thread(target=_go, daemon=True,
                         name="marlin-profile-capture").start()

    try:
        signal.signal(signal.SIGUSR2, _on_signal)
        return True
    except ValueError:  # not the main thread
        return False
