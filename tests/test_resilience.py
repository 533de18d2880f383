"""Serving resilience suite: supervised worker recovery, per-request
deadlines/retries, and the multi-replica router (serving/supervisor.py,
serving/router.py; docs/robustness.md).

The crash-recovery acceptance (test_supervisor_recovers_worker_crash):
with ``serve.worker_crash`` injected mid-stream, the engine restarts
within its backoff budget, every accepted request reaches exactly one
terminal Result, and greedy outputs of retried requests are bit-identical
to an uninterrupted :func:`lm_generate` — the exactly-once ResultHandle
contract survives the worker dying under it. The rolling-restart
acceptance (test_router_rolling_restart_under_load): a full fleet
rotation over 2 replicas under continuous offered load drops zero
requests and double-delivers none.

Stuck-worker (watchdog) tests warm the engine first: the watchdog cannot
tell a wedged device call from a long first-use XLA compile, so
``serve_watchdog_s`` must exceed worst-case compile time unless buckets
are pre-compiled (docs/robustness.md).
"""

import json
import os
import threading
import time

import numpy as np
import pytest

import jax

from marlin_tpu.config import config_context
from marlin_tpu.models import TransformerLM
from marlin_tpu.models.transformer import lm_generate
from marlin_tpu.obs import report as obs_report
from marlin_tpu.obs.exposition import health_payload
from marlin_tpu.obs.metrics import get_registry
from marlin_tpu.serving import (
    STATUS_ERROR,
    STATUS_EXPIRED,
    STATUS_OK,
    STATUS_REJECTED,
    STATUS_SHUTTING_DOWN,
    Request,
    Router,
    ServeEngine,
    Supervisor,
)
from marlin_tpu.utils import EventLog, faults
from marlin_tpu.utils.faults import DelayFault, RaiseFault, Schedule

HEADS = 2
BUCKETS = ((8, 4), (16, 4))


class FakeClock:
    def __init__(self, t: float = 0.0):
        self.t = t

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> None:
        self.t += dt


@pytest.fixture(scope="module")
def params():
    return TransformerLM(vocab=32, d_model=16, heads=HEADS, layers=2,
                         seed=9).init_params()


def _engine(params, **kw):
    kw.setdefault("buckets", BUCKETS)
    kw.setdefault("max_batch", 4)
    kw.setdefault("max_wait_ms", 0.0)
    kw.setdefault("queue_depth", 512)
    # ample page capacity: the soak queues ~500 requests at once and the
    # page-unit admission charge must not become the gate under test
    kw.setdefault("num_pages", 1024)
    return ServeEngine(params, HEADS, **kw)


def _ref(params, prompt, steps, heads=HEADS):
    prompt = np.asarray(prompt, np.int32)
    return np.asarray(lm_generate(
        params, prompt, jax.random.key(0), heads=heads,
        max_len=len(prompt) + steps, steps=steps))


# --------------------------------------------------------------- supervisor


@pytest.mark.parametrize("kernel", ["gather", "pallas"])
def test_supervisor_recovers_worker_crash(params, kernel, tmp_path):
    """The crash-recovery invariant, under both decode kernels: a
    serve.worker_crash mid-stream kills the worker thread; the supervisor
    restarts it within the backoff budget, live rows re-queue within their
    attempt budget (page-unit reservations carried across attempts; the
    pool is dropped and rebuilt zeroed), every request reaches exactly one
    terminal ok Result, and greedy outputs are bit-identical to
    uninterrupted lm_generate."""
    log = EventLog(str(tmp_path / "serve.jsonl"))
    eng = _engine(params, decode_kernel=kernel, log=log)
    eng.warmup()
    sup = Supervisor(eng, backoff_s=0.005, poll_s=0.02, log=log)
    try:
        with faults.injected("serve.worker_crash", RaiseFault(times=1)):
            hs = [eng.submit(Request(prompt=[3, 1 + i % 4], steps=3,
                                     max_attempts=3)) for i in range(6)]
            results = [h.result(timeout=120) for h in hs]
        for h, r in zip(hs, results):
            assert r.status == STATUS_OK, (r.status, r.reason)
            assert r.tokens.tolist() == _ref(
                params, h.request.prompt, 3).tolist()
        assert all(h.done() for h in hs)
        assert sup.restart_count >= 1
        assert not sup.breaker_open
        # the engine keeps serving after recovery
        again = eng.submit(Request(prompt=[5, 6], steps=2))
        assert again.result(timeout=60).status == STATUS_OK
    finally:
        sup.close()
        eng.close()
    restarts = [r for r in log.read()
                if r["kind"] == "serve" and r.get("ev") == "restart"]
    assert restarts and restarts[0]["reason"].startswith("worker crashed")
    assert restarts[0]["gen"] >= 1
    assert eng.pending() == 0
    assert eng._queue.bytes_in_flight == 0


def test_supervisor_watchdog_recovers_stuck_worker(params):
    """A worker wedged mid-decode (DelayFault, not a raise — the thread is
    alive but making no progress) trips the heartbeat watchdog: the stale
    generation is superseded, its rows re-queue, and requests complete
    long before the wedge would have cleared."""
    eng = _engine(params, max_batch=2)
    eng.warmup()   # watchdog must not race first-use compiles
    sup = Supervisor(eng, watchdog_s=0.3, backoff_s=0.0, poll_s=0.05)
    try:
        with faults.injected("serve.decode_step",
                             DelayFault(seconds=2.0, times=1)):
            hs = [eng.submit(Request(prompt=[1, 2], steps=3,
                                     max_attempts=3)) for _ in range(2)]
            t0 = time.monotonic()
            for h in hs:
                r = h.result(timeout=60)
                assert r.status == STATUS_OK, (r.status, r.reason)
            took = time.monotonic() - t0
        assert sup.restart_count >= 1
        assert took < 1.8, f"recovery did not beat the 2s wedge ({took:.2f}s)"
    finally:
        sup.close()
        eng.close()
        time.sleep(2.1)  # stale generation wakes, sees its gen superseded,
        # exits — the conftest leak fixture then sees no marlin-serve thread


def test_supervisor_breaker_opens_after_restart_budget(params):
    """A deterministic crash loop must not restart forever: more than
    restart_max restarts inside the window opens the breaker, the engine
    is failed permanently, and everything still pending resolves with a
    clean terminal Result."""
    reg = get_registry()
    eng = _engine(params, max_batch=2, start=False)
    eng.warmup()
    sup = Supervisor(eng, restart_max=2, restart_window_s=60.0,
                     backoff_s=0.0, poll_s=0.02)
    try:
        with faults.injected("serve.worker_crash", RaiseFault(times=-1)):
            hs = [eng.submit(Request(prompt=[1, 2], steps=3,
                                     max_attempts=10)) for _ in range(3)]
            eng.start()
            statuses = [h.result(timeout=60).status for h in hs]
        assert sup.breaker_open
        assert sup.restart_count == 2     # the budget, then the breaker
        assert all(s in (STATUS_ERROR, STATUS_SHUTTING_DOWN)
                   for s in statuses), statuses
        # close() retires the queue (the handles resolve) BEFORE it joins the
        # worker and marks the engine closed: give that a moment
        deadline = time.monotonic() + 5.0
        while eng._state != "closed" and time.monotonic() < deadline:
            time.sleep(0.01)
        assert eng._state == "closed"
        # post-breaker submissions resolve deterministically too
        r = eng.submit(Request(prompt=[1], steps=1)).result(timeout=5)
        assert r.status == STATUS_SHUTTING_DOWN
        fam = reg._families.get("marlin_serve_breaker_state")
        assert fam is not None
        assert fam.labels(engine=eng._name).value == 1.0
    finally:
        sup.close()
        eng.close()
    assert eng._queue.bytes_in_flight == 0


def test_breaker_on_stuck_worker_does_not_hang_shutdown(params):
    """Regression (review): the breaker opening on repeatedly-STUCK (not
    crashed) workers must abandon the wedged generation, not join it —
    close() after a stuck-breaker previously hung forever on a thread
    that never returns from its device call. Held requests still resolve
    with error Results."""
    eng = _engine(params, max_batch=2)
    eng.warmup()
    sup = Supervisor(eng, watchdog_s=0.2, restart_max=1,
                     restart_window_s=60.0, backoff_s=0.0, poll_s=0.02)
    try:
        with faults.injected("serve.decode_step",
                             DelayFault(seconds=1.2, times=2)):
            h = eng.submit(Request(prompt=[1, 2], steps=3, max_attempts=5))
            # attempt 1 wedges -> watchdog restart (budget spent);
            # attempt 2 wedges -> second recovery overflows the window ->
            # breaker opens while that thread is STILL inside its wedge
            r = h.result(timeout=30)
            assert r.status == STATUS_ERROR, (r.status, r.reason)
            assert "breaker open" in r.reason
            assert sup.breaker_open
            t0 = time.monotonic()
            eng.close()   # must not join the wedged (abandoned) thread
            assert time.monotonic() - t0 < 1.0, "close() hung on the wedge"
            assert eng._state == "closed"
    finally:
        sup.close()
        eng.close()
        # both wedged stragglers drain out before the leak fixture looks
        deadline = time.monotonic() + 2.5
        while time.monotonic() < deadline and any(
                t.name.startswith("marlin-serve")
                for t in threading.enumerate()):
            time.sleep(0.02)


@pytest.mark.filterwarnings(
    "ignore::pytest.PytestUnhandledThreadExceptionWarning")
def test_unsupervised_crash_still_fails_held_requests(params):
    """Without a supervisor the legacy contract holds: a dying worker
    fails its held requests and the queued backlog with error Results —
    no submitter is ever stranded on .result() (and the exception still
    re-raises for the thread log — the warning this test ignores)."""
    eng = _engine(params, start=False)
    eng.warmup()
    try:
        hs = [eng.submit(Request(prompt=[1, 2], steps=3))
              for _ in range(3)]
        with faults.injected("serve.worker_crash", RaiseFault(times=1)):
            eng.start()
            for h in hs:
                r = h.result(timeout=60)
                assert r.status == STATUS_ERROR
                assert "worker died" in r.reason
    finally:
        eng.close()
    assert eng.pending() == 0
    assert eng._queue.bytes_in_flight == 0


def test_flight_dump_on_worker_crash_is_report_parseable(params, tmp_path):
    """A worker crash dumps the flight ring; the dump must parse through
    obs.report (load_events + analyze) — the post-mortem contract."""
    with config_context(obs_profile_dir=str(tmp_path)):
        eng = _engine(params)
        eng.warmup()
        sup = Supervisor(eng, backoff_s=0.0, poll_s=0.02)
        try:
            with faults.injected("serve.worker_crash", RaiseFault(times=1)):
                h = eng.submit(Request(prompt=[1, 2], steps=3,
                                       max_attempts=2))
                assert h.result(timeout=60).status == STATUS_OK
        finally:
            sup.close()
            eng.close()
        dumps = [f for f in os.listdir(tmp_path)
                 if f.startswith("flight-") and "worker-died" in f]
        assert dumps, os.listdir(tmp_path)
        events, skipped = obs_report.load_events(
            str(tmp_path / sorted(dumps)[0]))
        assert events and skipped == 0
        # the ring, then the process's start-up record
        assert all(r.get("kind") == "flight" for r in events[:-1])
        assert events[-1]["kind"] == "startup"
        text = obs_report.analyze(events)
        assert "marlin_tpu.obs.report" in text


# ------------------------------------------------------ deadlines / retries


def test_deadline_s_resolves_relative_to_submit(params):
    clock = FakeClock(100.0)
    eng = _engine(params, clock=clock, start=False)
    try:
        h = eng.submit(Request(prompt=[1, 2], steps=2, deadline_s=5.0))
        assert h.request.deadline == 105.0   # resolved once, absolute
        clock.advance(10.0)
        eng.start()
        r = h.result(timeout=60)
        assert r.status == STATUS_EXPIRED and "deadline" in r.reason
    finally:
        eng.close()


def test_default_deadline_from_config(params):
    clock = FakeClock(50.0)
    with config_context(serve_default_deadline_s=3.0):
        eng = _engine(params, clock=clock, start=False)
        try:
            h = eng.submit(Request(prompt=[1, 2], steps=2))
            assert h.request.deadline == 53.0
        finally:
            eng.close()


def test_deadline_and_deadline_s_are_exclusive():
    with pytest.raises(ValueError, match="not both"):
        Request(prompt=[1], steps=1, deadline=1.0, deadline_s=1.0)
    with pytest.raises(ValueError, match="max_attempts"):
        Request(prompt=[1], steps=1, max_attempts=0)


def test_unmeetable_deadline_rejected_at_admission(params):
    """With service history, a request whose projected completion behind
    the queue overshoots its deadline is refused at submit — rejected with
    a reason, not decoded into a guaranteed expiry."""
    clock = FakeClock()
    eng = _engine(params, clock=clock, start=False)
    try:
        eng._service_ewma = 2.0   # 2 s per request, measured
        for _ in range(8):        # queue up two batches' worth
            eng.submit(Request(prompt=[1, 2], steps=2))
        r = eng.submit(Request(prompt=[1, 2], steps=2,
                               deadline_s=0.5)).result(timeout=1)
        assert r.status == STATUS_REJECTED
        assert "deadline unmeetable" in r.reason
        # a generous deadline still admits at the same depth
        ok = eng.submit(Request(prompt=[1, 2], steps=2, deadline_s=1e6))
        assert not ok.done()
    finally:
        eng.close()


def test_sampled_retry_replays_identical_stream(params):
    """Sampled retries re-derive the same per-row fold_in(key(seed), step)
    stream: a request retried after a crash emits exactly the tokens the
    uninterrupted run emits (replay is attempt-independent)."""
    req = dict(prompt=[2, 4, 6], steps=4, temperature=0.7, seed=13)
    with _engine(params) as eng:
        baseline = eng.submit(Request(**req)).result(timeout=60)
    assert baseline.status == STATUS_OK
    eng = _engine(params)
    eng.warmup()
    sup = Supervisor(eng, backoff_s=0.0, poll_s=0.02)
    try:
        with faults.injected("serve.worker_crash", RaiseFault(times=1)):
            again = eng.submit(Request(**req, max_attempts=3)) \
                .result(timeout=60)
        assert again.status == STATUS_OK
        assert again.tokens.tolist() == baseline.tokens.tolist()
    finally:
        sup.close()
        eng.close()


# ------------------------------------------------------------------- router


def _factory(params, **kw):
    def make():
        return _engine(params, **kw)
    return make


def test_router_routes_and_fails_over_on_rejection(params):
    """Power-of-two routing with failover: a replica that rejects
    (zero-capacity queue here) is skipped and a ready peer serves the
    request; with every replica refusing, the caller still gets exactly
    one terminal Result."""
    import random
    full = _engine(params, queue_depth=1, start=False)
    stuffed = full.submit(Request(prompt=[9], steps=1))   # occupies depth 1
    ok_eng = _engine(params)
    router = Router(engines=[full, ok_eng], supervise=False,
                    rng=random.Random(0))
    try:
        hs = [router.submit(Request(prompt=[1, 2], steps=2))
              for _ in range(4)]
        for h in hs:
            r = h.result(timeout=60)
            assert r.status == STATUS_OK, (r.status, r.reason)
            assert r.tokens.tolist() == _ref(params, [1, 2], 2).tolist()
    finally:
        router.close()
    assert stuffed.result(timeout=5).status == STATUS_SHUTTING_DOWN


def test_router_route_fault_fails_over(params):
    """The serve.router_route chaos point: a raise during routing marks
    that replica failed for the request; the router fails over instead of
    surfacing the exception."""
    import random
    router = Router(_factory(params), replicas=2, supervise=False,
                    rng=random.Random(1))
    try:
        with faults.injected("serve.router_route",
                             RaiseFault(times=1)):
            h = router.submit(Request(prompt=[1, 2], steps=2))
            assert h.result(timeout=60).status == STATUS_OK
    finally:
        router.close()


def test_router_no_ready_replica_is_deterministic(params):
    router = Router(_factory(params), replicas=2, supervise=False)
    router.drain()
    r = router.submit(Request(prompt=[1], steps=1)).result(timeout=1)
    assert r.status == STATUS_REJECTED and "no ready replica" in r.reason
    router.close()


def test_router_health_and_replica_state_metric(params):
    """The router is ONE scrape target: adopted engines leave the /healthz
    registry, the aggregate stays ready while any replica accepts, and
    marlin_serve_replica_state publishes the per-replica codes."""
    reg = get_registry()
    router = Router(_factory(params), replicas=2, supervise=False)
    try:
        code, payload = health_payload()
        names = [e["name"] for e in payload["engines"]]
        assert router._name in names
        # adopted engines do not report individually
        for rep in router._replicas:
            assert rep.engine._name not in names
        assert code == 200
        mine = next(e for e in payload["engines"]
                    if e["name"] == router._name)
        assert mine["state"] == "accepting"
        assert len(mine["replicas"]) == 2
        fam = reg._families.get("marlin_serve_replica_state")
        states = {k: c.value for k, c in fam.children().items()
                  if k[0] == router._name}
        assert set(states.values()) == {0.0}   # all accepting
        # pull one replica: aggregate stays ready, gauge flips
        router._replicas[0].routable = False
        router._publish_states()
        code, payload = health_payload()
        assert code == 200
        states = {k: c.value for k, c in fam.children().items()
                  if k[0] == router._name}
        assert sorted(states.values()) == [0.0, 2.0]  # restarting + accepting
        router._replicas[0].routable = True
    finally:
        router.close()
    code, payload = health_payload()
    assert router._name not in [e["name"] for e in payload["engines"]]


def test_router_rolling_restart_under_load(params):
    """The rolling-restart acceptance: a full rotation over 2 replicas
    under continuous offered load completes with ZERO dropped and ZERO
    double-delivered requests — every handle reaches exactly one ok
    Result, bit-identical to the reference decode."""
    import random
    router = Router(_factory(params), replicas=2,
                    supervisor_kw=dict(backoff_s=0.005, poll_s=0.02),
                    rng=random.Random(7))
    handles, lock = [], threading.Lock()
    stop = threading.Event()

    def pump():
        i = 0
        while not stop.is_set():
            h = router.submit(Request(prompt=[5, 1 + i % 4], steps=2))
            with lock:
                handles.append(h)
            i += 1
            time.sleep(0.002)

    threads = [threading.Thread(target=pump) for _ in range(2)]
    try:
        for t in threads:
            t.start()
        time.sleep(0.1)
        rotated = router.rolling_restart()
        time.sleep(0.1)
        stop.set()
        for t in threads:
            t.join()
        router.drain()
        assert set(rotated) == {0, 1}
        results = [h.result(timeout=120) for h in handles]
    finally:
        stop.set()
        router.close()
    assert len(results) >= 20   # the load was really continuous
    # zero dropped (every handle terminal, none stranded), zero double
    # (ResultHandle raises on a second set — reaching here proves it),
    # and nothing was turned away mid-rotation: one replica always accepts
    for h, r in zip(handles, results):
        assert r.status == STATUS_OK, (r.status, r.reason)
        assert r.tokens.tolist() == _ref(
            params, h.request.prompt, 2).tolist()
    # both replicas were rebuilt: fresh engines, restart count advanced
    assert all(rep.restarts == 1 for rep in router._replicas)


def test_router_snapshot_aggregates(params):
    router = Router(_factory(params), replicas=2, supervise=False)
    try:
        hs = [router.submit(Request(prompt=[1, 2], steps=2))
              for _ in range(6)]
        for h in hs:
            assert h.result(timeout=60).status == STATUS_OK
        snap = router.snapshot()
        assert snap["completed"] == 6
        assert set(snap["replicas"]) == {0, 1}
        assert sum(s["completed"]
                   for s in snap["replicas"].values()) == 6
    finally:
        router.close()


# -------------------------------------------------------------- obs report


def test_report_serving_resilience_line(tmp_path):
    """The analyzer surfaces retries/restarts when the stream carries
    them, and attributes a retried request's latency to its final
    attempt (the result record's attempt field)."""
    path = str(tmp_path / "ev.jsonl")
    recs = [
        {"t": 1.0, "kind": "serve", "ev": "enqueue", "rid": 1,
         "bucket": [8, 4], "depth": 1},
        {"t": 1.1, "kind": "serve", "ev": "retry", "rid": 1, "attempt": 2,
         "max_attempts": 3, "reason": "decode step failed"},
        {"t": 1.2, "kind": "serve", "ev": "restart", "engine": "e0",
         "reason": "worker crashed", "gen": 1, "requeued": 1, "failed": 0},
        {"t": 1.5, "kind": "serve", "ev": "result", "rid": 1,
         "status": "ok", "attempt": 2, "queue_s": 0.3, "ttft_s": 0.4,
         "total_s": 0.5},
    ]
    with open(path, "w") as f:
        for r in recs:
            f.write(json.dumps(r) + "\n")
    events, skipped = obs_report.load_events(path)
    text = obs_report.analyze(events, skipped)
    assert "resilience: 1 attempt(s) re-queued, 1 worker restart(s)" in text
    assert "1 ok result(s) served by a retry" in text


# ------------------------------------------------------------- chaos soak


@pytest.mark.slow
def test_chaos_soak_crash_recovery_two_replicas(params, tmp_path):
    """The chaos soak: ~500 requests across 2 supervised replicas while
    serve.worker_crash kills workers roughly every 50 iterations. Every
    ResultHandle reaches a terminal state exactly once, ok results stay
    bit-identical to the reference, and every flight-recorder dump the
    crashes produced parses through obs.report."""
    import random
    n_req = 500
    refs = {n: _ref(params, [3, n % 5 + 1], 2).tolist() for n in range(5)}
    with config_context(obs_profile_dir=str(tmp_path)):
        router = Router(
            _factory(params, queue_depth=n_req), replicas=2,
            supervisor_kw=dict(backoff_s=0.002, poll_s=0.01,
                               restart_max=1000, restart_window_s=1e6),
            rng=random.Random(3))
        handles = []
        try:
            # every ~50th arrival at the fault point kills that worker
            with faults.injected(
                    "serve.worker_crash",
                    RaiseFault(times=-1,
                               schedule=Schedule(seed=5, rate=0.02))):
                for i in range(n_req):
                    handles.append(router.submit(Request(
                        prompt=[3, i % 5 + 1], steps=2, max_attempts=8)))
                    if i % 50 == 0:
                        time.sleep(0.01)
                router.drain()
            results = [h.result(timeout=600) for h in handles]
        finally:
            router.close()
        assert len(results) == n_req
        assert all(h.done() for h in handles)
        statuses = [r.status for r in results]
        # exactly-once, nothing stranded; crashes may exhaust budgets but
        # the overwhelming majority must complete
        assert set(statuses) <= {STATUS_OK, STATUS_ERROR}
        assert statuses.count(STATUS_OK) >= n_req * 0.9
        for h, r in zip(handles, results):
            if r.status == STATUS_OK:
                assert r.tokens.tolist() == refs[h.request.prompt[1] - 1]
        snap = router.snapshot()
        assert snap["completed"] == statuses.count(STATUS_OK)
        assert snap["errors"] == statuses.count(STATUS_ERROR)
        dumps = [f for f in os.listdir(tmp_path)
                 if f.startswith("flight-") and "worker-died" in f]
        assert dumps   # the crashes left post-mortems
        for d in dumps:
            events, skipped = obs_report.load_events(str(tmp_path / d))
            assert events and skipped == 0
            obs_report.analyze(events)   # must not raise
