"""Performance-introspection suite (obs/perf.py + its integrations).

The load-bearing tests: the flight recorder dumping a parseable black box
when the existing ``serve.decode_step`` fault point fires, the single-flight
guarantee of ``/debug/profile`` (second concurrent request gets 409 — two
overlapping jax.profiler traces corrupt each other), the upgraded
``/healthz`` readiness states, and what is left where the wall-clock
roofline registry stood: no ``marlin_program`` family on a scrape, one
flight dump however often an engine is closed, and an old log's
``kind="program"`` records parsed without a table.
"""

import json
import os
import threading
import urllib.error
import urllib.request

import pytest

from marlin_tpu.config import config_context
from marlin_tpu.obs import perf
from marlin_tpu.obs.exposition import (MetricsServer, health_payload,
                                       register_health_provider,
                                       unregister_health_provider)
from marlin_tpu.obs.perf import FlightRecorder
from marlin_tpu.obs.report import analyze, load_events
from marlin_tpu.utils import faults
from marlin_tpu.utils.faults import RaiseFault
from marlin_tpu.utils.tracing import EventLog, set_default_event_log

HEADS = 2


@pytest.fixture()
def default_log(tmp_path):
    log = EventLog(str(tmp_path / "events.jsonl"))
    prev = set_default_event_log(log)
    yield log
    set_default_event_log(prev)
    log.close()


@pytest.fixture(scope="module")
def lm_params():
    from marlin_tpu.models import TransformerLM

    return TransformerLM(vocab=32, d_model=16, heads=HEADS, layers=2,
                         seed=9).init_params()


# ------------------------------------------------------------ flight recorder


def test_flight_ring_bounded_and_ordered():
    fr = FlightRecorder(maxlen=8, name="t")
    for i in range(20):
        fr.record("step", i=i)
    recs = fr.records()
    assert len(recs) == len(fr) == 8
    assert [r["i"] for r in recs] == list(range(12, 20))
    assert all(r["kind"] == "flight" and r["src"] == "t" for r in recs)


def test_flight_ring_concurrency_stress():
    """Writers and snapshot readers race freely: no exception, no torn
    record, the ring stays bounded."""
    fr = FlightRecorder(maxlen=64, name="stress")
    stop = threading.Event()
    errors = []

    def write(tid):
        try:
            for i in range(500):
                fr.record("step", tid=tid, i=i)
        except Exception as e:  # pragma: no cover - the failure under test
            errors.append(e)

    def read():
        try:
            while not stop.is_set():
                for r in fr.records():
                    assert r["kind"] == "flight"
                _ = len(fr)
        except Exception as e:  # pragma: no cover
            errors.append(e)

    writers = [threading.Thread(target=write, args=(t,)) for t in range(4)]
    readers = [threading.Thread(target=read) for _ in range(2)]
    for t in readers + writers:
        t.start()
    for t in writers:
        t.join()
    stop.set()
    for t in readers:
        t.join()
    assert not errors
    assert len(fr) == 64


def test_flight_dump_and_prune(tmp_path, default_log):
    with config_context(obs_profile_dir=str(tmp_path)):
        fr = FlightRecorder(maxlen=4, name="dumpme")
        assert fr.dump(reason="empty") is None  # empty ring: no file
        fr.record("step", i=1, seconds=0.01)
        path = fr.dump(reason="test")
    assert path and os.path.exists(path)
    recs, skipped = load_events(path)
    assert skipped == 0 and recs[0]["ev"] == "step"
    assert recs[0]["reason"] == "test"
    dump_ev = [r for r in default_log.read() if r["kind"] == "flight"]
    assert dump_ev and dump_ev[0]["path"] == path


def test_flight_dump_on_decode_step_fault(lm_params, tmp_path, default_log):
    """The black-box acceptance path: an injected serve.decode_step fault
    fails the step's rows AND lands a flight-recorder JSONL dump whose
    records obs.report parses."""
    from marlin_tpu.serving import Request, ServeEngine

    with config_context(obs_profile_dir=str(tmp_path)):
        eng = ServeEngine(lm_params, HEADS, buckets=((8, 4),), max_batch=4,
                          max_wait_ms=0.0, queue_depth=32, start=False)
        try:
            hs = [eng.submit(Request(prompt=[1, 2], steps=3))
                  for _ in range(2)]
            with faults.injected("serve.decode_step", RaiseFault(times=1)):
                eng.start()
                for h in hs:
                    r = h.result(timeout=60)
                    assert r.status == "error"
            ok = eng.submit(Request(prompt=[3], steps=2))
            assert ok.result(timeout=60).ok  # engine keeps serving
        finally:
            eng.close()
    dumps = [r for r in default_log.read()
             if r["kind"] == "flight" and r.get("ev") == "dump"
             and r.get("reason") == "decode-step-failed"]
    assert dumps, "no flight dump landed for the injected decode fault"
    path = dumps[0]["path"]
    recs, skipped = load_events(path)
    assert skipped == 0 and recs
    # the ring shows the fault itself plus the iterations leading up to it
    *ring, startup = recs  # the dump ends with the process's start-up record
    evs = {r["ev"] for r in ring}
    assert "decode_fault" in evs and "prefill" in evs
    assert all(r["kind"] == "flight" for r in ring)
    assert startup["kind"] == "startup"
    assert "serve.engine.init" in {s["name"] for s in startup["spans"]}
    analyze(recs)  # parseable by the analyzer, end to end


# ------------------------------------------------------------------ /healthz


def test_health_payload_states():
    register_health_provider("t-accepting", lambda: {"state": "accepting"})
    try:
        code, body = health_payload()
        assert code == 200 and body["status"] == "ok"
        register_health_provider("t-draining", lambda: {"state": "draining"})
        code, body = health_payload()
        assert code == 503 and body["status"] == "unavailable"
        unregister_health_provider("t-draining")

        def broken():
            raise RuntimeError("probe died")

        register_health_provider("t-broken", broken)
        code, body = health_payload()
        assert code == 503
        assert any(e["state"] == "error" for e in body["engines"])
    finally:
        for n in ("t-accepting", "t-draining", "t-broken"):
            unregister_health_provider(n)


def test_healthz_reports_live_engine_and_503_when_draining(lm_params):
    from marlin_tpu.serving import ServeEngine

    with MetricsServer(port=0) as srv:
        base = f"http://127.0.0.1:{srv.port}"
        eng = ServeEngine(lm_params, HEADS, buckets=((8, 4),), max_batch=4,
                          max_wait_ms=0.0, queue_depth=8)
        try:
            body = urllib.request.urlopen(base + "/healthz",
                                          timeout=10).read().decode()
            payload = json.loads(body)
            mine = [e for e in payload["engines"]
                    if e["name"] == eng._name]
            assert mine and mine[0]["state"] == "accepting"
            assert "live_slots" in mine[0] and "queue_depth" in mine[0]
            # the 503 leg, deterministic: flip the state the provider reads
            eng._state = "draining"
            with pytest.raises(urllib.error.HTTPError) as exc:
                urllib.request.urlopen(base + "/healthz", timeout=10)
            assert exc.value.code == 503
            eng._state = "running"
        finally:
            eng.close()
        # terminal close unregistered the provider: healthz recovers
        code, payload = health_payload()
        assert all(e.get("name") != eng._name for e in payload["engines"])


def test_healthz_503_after_worker_death(lm_params):
    """A crashed worker (BaseException — not the absorbed per-step
    Exception class) must flip the engine out of 'accepting': the probe the
    readiness upgrade exists for is exactly 'stop routing to an engine
    that cannot make progress'."""
    from marlin_tpu.serving import Request, ServeEngine

    eng = ServeEngine(lm_params, HEADS, buckets=((8, 4),), max_batch=4,
                      max_wait_ms=0.0, queue_depth=16, start=False)
    try:
        hs = [eng.submit(Request(prompt=[1, 2], steps=3)) for _ in range(2)]
        with faults.injected("serve.decode_step",
                             RaiseFault(exc=KeyboardInterrupt, times=1)):
            eng.start()
            for h in hs:
                assert h.result(timeout=60).status == "error"
        eng._thread.join(timeout=30)
        assert not eng._thread.is_alive()
        code, body = health_payload()
        mine = [e for e in body["engines"] if e.get("name") == eng._name]
        assert code == 503 and mine and mine[0]["state"] == "closed"
    finally:
        eng.close()


def test_engine_heartbeat_ages(lm_params):
    from marlin_tpu.serving import Request, ServeEngine

    with ServeEngine(lm_params, HEADS, buckets=((8, 4),), max_batch=4,
                     max_wait_ms=0.0, queue_depth=8) as eng:
        h = eng.submit(Request(prompt=[1, 2], steps=2))
        assert h.result(timeout=30).ok
        info = eng._health_info()
        assert info["worker_started"]
        assert info["heartbeat_age_s"] is not None
        assert info["heartbeat_age_s"] >= 0


# ------------------------------------------------------------- debug HTTP


def test_debug_profile_single_flight_409(tmp_path):
    """Second concurrent /debug/profile gets 409 (single-flight), and the
    capture lands an artifact + kind="profile" record once free."""
    with config_context(obs_profile_dir=str(tmp_path)):
        with MetricsServer(port=0) as srv:
            base = f"http://127.0.0.1:{srv.port}"
            assert perf._profile_lock.acquire(blocking=False)
            try:  # a capture "in flight": the next request must 409
                with pytest.raises(urllib.error.HTTPError) as exc:
                    urllib.request.urlopen(base + "/debug/profile?seconds=0",
                                           data=b"", timeout=10)
                assert exc.value.code == 409
            finally:
                perf._profile_lock.release()
            body = urllib.request.urlopen(
                base + "/debug/profile?seconds=0.05", data=b"",
                timeout=60).read().decode()
            out = json.loads(body)
            assert os.path.isdir(out["path"])
            assert out["path"].startswith(str(tmp_path))
            for bad in ("nope", "nan", "inf"):  # nan slides past min/max
                with pytest.raises(urllib.error.HTTPError) as exc:
                    urllib.request.urlopen(
                        base + f"/debug/profile?seconds={bad}",
                        data=b"", timeout=10)
                assert exc.value.code == 400, bad


def test_capture_profile_programmatic(tmp_path, default_log):
    path = perf.capture_profile(seconds=0.0, logdir=str(tmp_path))
    assert os.path.isdir(path)
    recs = [r for r in default_log.read() if r["kind"] == "profile"]
    assert recs and recs[0]["path"] == path


def test_debug_flight_endpoint(lm_params):
    from marlin_tpu.serving import Request, ServeEngine

    with MetricsServer(port=0) as srv:
        base = f"http://127.0.0.1:{srv.port}"
        with ServeEngine(lm_params, HEADS, buckets=((8, 4),), max_batch=4,
                         max_wait_ms=0.0, queue_depth=8) as eng:
            h = eng.submit(Request(prompt=[1, 2], steps=3))
            assert h.result(timeout=30).ok
            body = urllib.request.urlopen(base + "/debug/flight",
                                          timeout=10).read().decode()
        recs = [json.loads(line) for line in body.splitlines() if line]
        mine = [r for r in recs if r.get("src") == eng._name]
        assert mine and any(r["ev"] in ("step", "prefill") for r in mine)


# ------------------------------------------- where the roofline registry stood

FIXTURE_LOG = os.path.join(os.path.dirname(__file__), "..", "tools",
                           "fixtures", "obs_events.jsonl")


def test_engine_closed_twice_lands_one_flight_dump_and_a_clean_healthz(
        lm_params, tmp_path, default_log):
    """``close()`` is the terminal flush: the ring is dumped once, whoever
    closes again (a supervisor, a ``with`` block's exit) lands nothing
    more, and the engine has left ``/healthz`` for good."""
    from marlin_tpu.serving import Request, ServeEngine

    with config_context(obs_profile_dir=str(tmp_path)):
        eng = ServeEngine(lm_params, HEADS, buckets=((8, 4),), max_batch=4,
                          max_wait_ms=0.0, queue_depth=8)
        assert eng.submit(Request(prompt=[1, 2], steps=3)).result(
            timeout=60).ok
        eng.close()
        eng.close()
    dumps = [r for r in default_log.read() if r["kind"] == "flight"
             and r.get("ev") == "dump" and r.get("src") == eng._name]
    assert [d["reason"] for d in dumps] == ["close"]
    recs, skipped = load_events(dumps[0]["path"])
    assert skipped == 0 and {"prefill", "step"} <= {
        r["ev"] for r in recs if r["kind"] == "flight"}
    # nothing the engine emitted is a record of the registry that went
    assert not [r for r in default_log.read() if r["kind"] == "program"]
    _, payload = health_payload()
    assert all(e.get("name") != eng._name for e in payload["engines"])


def test_metrics_scrape_has_no_program_family(lm_params):
    """A served request and the default collectors leave ``/metrics`` with
    the serving and memory families and none of the ``marlin_program`` ones:
    no wall-clock rate is exposed as a device number."""
    from marlin_tpu.obs.collectors import install_default_collectors
    from marlin_tpu.obs.metrics import get_registry
    from marlin_tpu.serving import Request, ServeEngine

    install_default_collectors()
    with ServeEngine(lm_params, HEADS, buckets=((8, 4),), max_batch=4,
                     max_wait_ms=0.0, queue_depth=8) as eng:
        eng.warmup()
        assert eng.submit(Request(prompt=[1, 2], steps=3)).result(
            timeout=60).ok
        text = get_registry().render()
    names = {line.split()[2] for line in text.splitlines()
             if line.startswith("# TYPE ")}
    assert "marlin_serve_step_seconds" in names
    assert "marlin_mem_registered_bytes" in names
    assert not [n for n in names if n.startswith("marlin_program")]


@pytest.mark.parametrize("window", [(), ("--since", "1004", "--until",
                                         "1009")])
def test_report_parses_old_program_records_without_a_table(window, capsys):
    """The checked-in fixture log holds ``kind="program"`` records of the
    registry that is gone: they load, count as events and render no
    section, over the whole file and over a window."""
    from marlin_tpu.obs.report import KNOWN_KINDS, main

    recs, skipped = load_events(FIXTURE_LOG)
    assert skipped <= 1  # the fixture's one torn line
    assert [r for r in recs if r["kind"] == "program"]
    assert "program" in KNOWN_KINDS
    assert main([*window, FIXTURE_LOG]) == 0
    out = capsys.readouterr().out
    assert "program utilization" not in out and "roofline" not in out
    assert out.startswith("== marlin_tpu.obs.report ==")
