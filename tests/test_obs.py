"""Observability-layer suite: registry/exposition, trace propagation,
EventLog torn-line tolerance + rotation, reservoir sampling, the compile
bridge, the live-serve scrape, and the analyzer golden.

The cross-thread propagation tests are the load-bearing ones: the span
context must survive the hop onto the serving worker thread (every record
one request produces joins one trace) and onto prefetch producer threads —
contextvars do NOT cross ``threading.Thread`` by default, so these assert
the explicit capture/use handoff actually happens everywhere it must.
"""

import json
import os
import random
import threading
import urllib.request

import numpy as np
import pytest

from marlin_tpu import obs
from marlin_tpu.obs import collectors, trace
from marlin_tpu.obs.metrics import MetricsRegistry, percentile
from marlin_tpu.obs.report import analyze, load_events
from marlin_tpu.serving.metrics import Reservoir, ServeMetrics
from marlin_tpu.utils.tracing import EventLog, set_default_event_log

FIXTURE = os.path.join(os.path.dirname(__file__), "..", "tools", "fixtures",
                       "obs_events.jsonl")
GOLDEN = os.path.join(os.path.dirname(__file__), "..", "tools", "fixtures",
                      "obs_report_golden.txt")


@pytest.fixture()
def default_log(tmp_path):
    """A fresh default EventLog, restored afterwards."""
    log = EventLog(str(tmp_path / "events.jsonl"))
    prev = set_default_event_log(log)
    yield log
    set_default_event_log(prev)
    log.close()


# ------------------------------------------------------------------ registry


def test_registry_counter_gauge_labels():
    reg = MetricsRegistry()
    c = reg.counter("c_total", "help", labelnames=("status",))
    c.labels(status="ok").inc()
    c.labels(status="ok").inc(2)
    c.labels("err").inc()  # positional addressing, same family
    assert c.labels(status="ok").value == 3
    assert c.labels(status="err").value == 1
    g = reg.gauge("g")
    g.set(5)
    g.dec(2)
    assert g.value == 3
    with pytest.raises(ValueError, match="counter increment"):
        c.labels(status="ok").inc(-1)
    with pytest.raises(ValueError, match="label"):
        c.labels(wrong="x")
    with pytest.raises(ValueError, match="has labels"):
        c.inc()  # labeled family needs .labels()


def test_registry_idempotent_and_conflict():
    reg = MetricsRegistry()
    a = reg.counter("x_total", "h", labelnames=("k",))
    b = reg.counter("x_total", "h", labelnames=("k",))
    assert a is b  # subsystems re-register freely (one family per name)
    with pytest.raises(ValueError, match="already registered"):
        reg.gauge("x_total")
    with pytest.raises(ValueError, match="already registered"):
        reg.counter("x_total", labelnames=("other",))


def test_histogram_buckets_cumulative():
    reg = MetricsRegistry()
    h = reg.histogram("h_seconds", "h", buckets=(0.01, 0.1, 1.0))
    for v in (0.005, 0.05, 0.5, 5.0):
        h.observe(v)
    text = reg.render()
    assert 'h_seconds_bucket{le="0.01"} 1' in text
    assert 'h_seconds_bucket{le="0.1"} 2' in text
    assert 'h_seconds_bucket{le="1"} 3' in text
    assert 'h_seconds_bucket{le="+Inf"} 4' in text
    assert "h_seconds_count 4" in text
    assert "h_seconds_sum 5.555" in text


def test_render_format_and_escaping():
    reg = MetricsRegistry()
    c = reg.counter("esc_total", 'says "hi"', labelnames=("path",))
    c.labels(path='a"b\\c\nd').inc()
    text = reg.render()
    assert "# TYPE esc_total counter" in text
    assert '# HELP esc_total says "hi"' in text
    assert r'esc_total{path="a\"b\\c\nd"} 1' in text


def test_registry_collector_runs_at_render_and_may_fail():
    reg = MetricsRegistry()
    g = reg.gauge("live")
    reg.add_collector(lambda: g.set(42))

    def broken():
        raise RuntimeError("probe died")

    reg.add_collector(broken)  # must not fail the scrape
    assert "live 42" in reg.render()
    reg.remove_collector(broken)


# ------------------------------------------------------------------- tracing


def test_span_nesting_and_context_fields():
    assert trace.current() is None
    assert trace.context_fields() == {}
    with trace.span("outer") as outer:
        assert trace.current() is outer
        assert outer.trace_id == outer.span_id  # root is recognizable
        f = trace.context_fields()
        assert f == {"trace_id": outer.trace_id, "span_id": outer.span_id}
        with trace.span("inner") as inner:
            assert inner.trace_id == outer.trace_id
            assert inner.parent_id == outer.span_id
            assert inner.span_id != outer.span_id
            assert trace.context_fields()["parent_id"] == outer.span_id
        assert trace.current() is outer
    assert trace.current() is None


def test_eventlog_records_carry_span_context(tmp_path):
    log = EventLog(str(tmp_path / "ev.jsonl"))
    log.event("bare")
    with trace.span("work") as ctx:
        log.event("traced", x=1)
    log.close()
    bare, traced = log.read()
    assert "trace_id" not in bare
    assert traced["trace_id"] == ctx.trace_id
    assert traced["span_id"] == ctx.span_id


def test_span_survives_explicit_thread_handoff(tmp_path):
    log = EventLog(str(tmp_path / "ev.jsonl"))
    with trace.span("parent") as ctx:
        captured = trace.capture()

    def worker():
        with trace.use(captured):
            log.event("from_thread")

    t = threading.Thread(target=worker)
    t.start()
    t.join()
    log.close()
    (rec,) = log.read()
    assert rec["trace_id"] == ctx.trace_id


# ------------------------------------------------- EventLog torn line + rotation


def test_eventlog_read_skips_torn_final_line(tmp_path):
    path = str(tmp_path / "ev.jsonl")
    log = EventLog(path)
    log.event("a", i=1)
    log.event("b", i=2)
    log.close()
    with open(path, "a") as f:  # a crash mid-write: partial JSON, no newline
        f.write('{"t": 1.0, "kind": "tor')
    with pytest.warns(RuntimeWarning, match="torn/partial"):
        recs = log.read()
    assert [r["kind"] for r in recs] == ["a", "b"]
    assert log.last_read_skipped == 1


def test_eventlog_rotation(tmp_path):
    path = str(tmp_path / "rot.jsonl")
    log = EventLog(path, max_bytes=300)
    for i in range(40):
        log.event("tick", i=i, pad="x" * 20)
    log.close()
    assert os.path.exists(path + ".1")
    assert os.path.exists(path + ".2")
    assert not os.path.exists(path + ".3")  # two backups, oldest dropped
    assert os.path.getsize(path) <= 300
    recs = log.read(include_rotated=True)
    assert log.last_read_skipped == 0
    idx = [r["i"] for r in recs]
    assert idx == sorted(idx)  # rotated stream reads oldest-first, in order
    assert idx[-1] == 39  # newest record is in the live file
    assert len(recs) > len(log.read())  # backups really contribute


def test_eventlog_rotation_follows_config(tmp_path):
    from marlin_tpu.config import config_context

    path = str(tmp_path / "cfg.jsonl")
    log = EventLog(path)  # max_bytes=None -> config at write time
    with config_context(obs_log_max_bytes=200):
        for i in range(20):
            log.event("tick", i=i, pad="y" * 20)
    log.close()
    assert os.path.exists(path + ".1")


def test_eventlog_concurrent_writers_no_torn_lines(tmp_path):
    """8 threads x 200 events through one log (rotation on, tiny bound):
    every line parses and every event lands exactly once — the lock really
    covers write+rotate."""
    path = str(tmp_path / "stress.jsonl")
    # bound sized so the stream rotates (~90 KB of records vs 64 KB) but
    # main + two backups retain everything — retention is assertable
    log = EventLog(path, max_bytes=64_000)
    n_threads, n_events = 8, 200

    def writer(tid):
        for i in range(n_events):
            log.event("w", tid=tid, i=i)

    threads = [threading.Thread(target=writer, args=(t,))
               for t in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    log.close()
    assert os.path.exists(path + ".1")  # the stream really rotated
    recs = log.read(include_rotated=True)
    assert log.last_read_skipped == 0
    seen = {(r["tid"], r["i"]) for r in recs}
    assert len(seen) == len(recs), "duplicated records"
    assert len(seen) == n_threads * n_events, "records lost or torn"


# ------------------------------------------------------------- reservoirs


def test_reservoir_uniform_not_first_n_biased():
    r = Reservoir(100, random.Random(7))
    for v in range(10_000):
        r.add(float(v))
    assert r.n == 10_000
    assert len(r.items) == 100
    # a first-N-then-drop reservoir would hold only 0..99; uniform sampling
    # must keep late values and an unbiased mean
    assert max(r.items) > 9_000
    mean = sum(r.items) / len(r.items)
    assert abs(mean - 4999.5) < 1_000


def test_serve_metrics_percentiles_cover_whole_run():
    """The regression the reservoir swap fixes: latencies that degrade over
    the run must show up in p50 even past keep_latencies samples."""
    m = ServeMetrics(keep_latencies=64, rng=random.Random(3))
    for i in range(4096):
        m.record_result(rid=i, status="ok", total_s=float(i))
    snap = m.snapshot()
    # first-64-then-drop would report p50 ~= 32; uniform sampling tracks
    # the full stream (true p50 ~= 2048)
    assert snap["p50_total_s"] > 1_000
    assert snap["completed"] == 4096


# ------------------------------------------------------- timer / StageTimes


def test_timer_routes_through_default_log(default_log, capsys):
    from marlin_tpu.utils.profiling import timer

    with trace.span("bench") as ctx:
        with timer("unit-test", quiet=True):
            pass
    recs = [r for r in default_log.read() if r["kind"] == "timer"]
    assert len(recs) == 1
    assert recs[0]["label"] == "unit-test"
    assert recs[0]["seconds"] >= 0
    assert recs[0]["trace_id"] == ctx.trace_id
    assert capsys.readouterr().out == ""  # quiet still prints nothing


def test_stage_times_feed_registry():
    from marlin_tpu.obs.metrics import get_registry
    from marlin_tpu.utils.profiling import StageTimes

    fam = get_registry().counter("marlin_stage_seconds_total",
                                 labelnames=("stage",))
    before = fam.labels(stage="obs_test_stage").value
    st = StageTimes()
    st.add("obs_test_stage", 0.25)
    st.add("obs_test_stage", 0.25)
    assert fam.labels(stage="obs_test_stage").value == pytest.approx(
        before + 0.5)


# ------------------------------------------------------------ compile bridge


def test_compile_bridge_counts_and_logs(default_log):
    import jax

    fam = obs.get_registry().counter("marlin_compile_total",
                                     labelnames=("result",))

    def events():
        return sum(c.value for c in fam.children().values())

    before_metric = events()
    before_count = collectors.compile_count()

    @jax.jit
    def f(x):
        return x * 1.00042 + 17.0

    f(np.float32(2.0))
    assert collectors.compile_count() - before_count >= 1
    assert events() - before_metric >= 1
    compiles = [r for r in default_log.read() if r["kind"] == "compile"]
    assert compiles and all(r["seconds"] > 0 for r in compiles)
    assert any(r["fun_name"] == "jit(f)" and r["cache"] in ("off", "miss")
               for r in compiles)


# --------------------------------------------------------------- exposition


def test_metrics_server_scrape_healthz_404():
    with obs.MetricsServer(port=0) as srv:
        base = f"http://127.0.0.1:{srv.port}"
        text = urllib.request.urlopen(base + "/metrics",
                                      timeout=10).read().decode()
        assert "# TYPE marlin_compile_total counter" in text
        assert "# TYPE marlin_prefetch_chunks_total counter" in text
        ok = urllib.request.urlopen(base + "/healthz",
                                    timeout=10).read().decode()
        assert ok == "ok\n"
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(base + "/nope", timeout=10)


def test_start_from_config(tmp_path):
    from marlin_tpu.config import config_context

    assert obs.start_from_config() is None  # default: disabled
    with config_context(obs_http_port=0):
        srv = obs.start_from_config()
    try:
        assert srv is not None and srv.port > 0
        text = urllib.request.urlopen(srv.url, timeout=10).read().decode()
        assert "marlin_compile_total" in text
    finally:
        srv.close()


# -------------------------------------------- cross-thread: serving + prefetch


HEADS = 2


@pytest.fixture(scope="module")
def lm_params():
    from marlin_tpu.models import TransformerLM

    return TransformerLM(vocab=32, d_model=16, heads=HEADS, layers=2,
                         seed=9).init_params()


def test_serving_request_records_join_one_trace(lm_params, default_log):
    from marlin_tpu.serving import Request, ServeEngine

    with ServeEngine(lm_params, HEADS, buckets=((8, 4),), max_batch=4,
                     max_wait_ms=0.0, queue_depth=16) as eng:
        handles = [eng.submit(Request(prompt=[1 + i, 2, 3], steps=3))
                   for i in range(3)]
        eng.drain()
    results = [h.result(timeout=5) for h in handles]
    assert all(r.ok for r in results)
    serve = [r for r in default_log.read() if r["kind"] == "serve"]
    by_rid = {}
    for rec in serve:
        if "rid" in rec:
            by_rid.setdefault(rec["rid"], []).append(rec)
    assert len(by_rid) == 3
    tids = set()
    for rid, recs in by_rid.items():
        evs = {r["ev"] for r in recs}
        assert {"enqueue", "result"} <= evs
        assert "prefill" in evs  # row-level default: prefill carries rid
        rid_tids = {r.get("trace_id") for r in recs}
        assert len(rid_tids) == 1 and None not in rid_tids, (
            f"rid {rid} records span traces {rid_tids}")
        tids.add(rid_tids.pop())
    # submitted outside any span: each request is its own root trace
    assert len(tids) == 3


def test_serving_trace_joins_caller_span(lm_params, default_log):
    from marlin_tpu.serving import Request, ServeEngine

    with trace.span("client") as client:
        with ServeEngine(lm_params, HEADS, buckets=((8, 4),), max_batch=4,
                         max_wait_ms=0.0, queue_depth=16) as eng:
            h = eng.submit(Request(prompt=[1, 2, 3], steps=2))
            eng.drain()
    assert h.result(timeout=5).ok
    serve = [r for r in default_log.read()
             if r["kind"] == "serve" and "rid" in r]
    assert serve
    # with a caller span active, the request joins the CALLER's trace
    assert {r["trace_id"] for r in serve} == {client.trace_id}
    enq = next(r for r in serve if r["ev"] == "enqueue")
    assert enq["parent_id"] == client.span_id


def test_prefetch_producer_threads_inherit_span(default_log):
    from marlin_tpu.parallel.prefetch import ChunkPrefetcher

    probe_ctx = []

    def transform(c):
        # runs on a marlin-prefetch-* worker thread
        probe_ctx.append(trace.current())
        default_log.event("probe", n=int(c.sum()))
        return c

    chunks = [np.ones((4, 4), np.float32) * i for i in range(5)]
    with trace.span("stream") as ctx:
        out = list(ChunkPrefetcher(chunks, transform, device_put=False))
    assert len(out) == 5
    assert all(c is not None and c.trace_id == ctx.trace_id
               for c in probe_ctx)
    recs = default_log.read()
    probes = [r for r in recs if r["kind"] == "probe"]
    assert len(probes) == 5
    assert {r["trace_id"] for r in probes} == {ctx.trace_id}
    summary = next(r for r in recs if r["kind"] == "prefetch")
    assert summary["trace_id"] == ctx.trace_id


def test_checkpoint_save_load_traced(tmp_path, default_log):
    import jax.numpy as jnp

    from marlin_tpu.io.checkpoint import load_checkpoint, save_checkpoint

    state = {"w": jnp.arange(8.0), "step_scale": jnp.float32(2.0)}
    save_checkpoint(state, str(tmp_path / "ck"), step=3)
    restored, step = load_checkpoint(state, str(tmp_path / "ck"))
    assert step == 3
    np.testing.assert_array_equal(np.asarray(restored["w"]),
                                  np.arange(8.0))
    ckpts = [r for r in default_log.read() if r["kind"] == "ckpt"]
    assert [r["ev"] for r in ckpts] == ["save", "load"]
    assert all(r["ok"] and r["seconds"] >= 0 and r["trace_id"]
               for r in ckpts)
    # save and load were separate operations: distinct traces
    assert ckpts[0]["trace_id"] != ckpts[1]["trace_id"]


# ----------------------------------------------------- live-serve scrape e2e


def test_scrape_during_live_serve_returns_live_series(lm_params,
                                                      default_log):
    """The acceptance path: while an engine is serving (not yet drained) a
    /metrics scrape must carry nonzero serving + prefetch + compile series
    — the three blind spots the obs layer closes."""
    from marlin_tpu.parallel.streaming import streamed_gramian
    from marlin_tpu.serving import Request, ServeEngine

    collectors.install_compile_metrics()
    # tick the prefetch series (any streamed op runs the pipeline)
    streamed_gramian(iter([np.ones((8, 4), np.float32)] * 3), prefetch=True)
    with obs.MetricsServer(port=0) as srv:
        with ServeEngine(lm_params, HEADS, buckets=((8, 4),), max_batch=4,
                         max_wait_ms=0.0, queue_depth=32) as eng:
            handles = [eng.submit(Request(prompt=[1, 2, i % 7 + 1], steps=4))
                       for i in range(8)]
            text = urllib.request.urlopen(srv.url, timeout=10).read().decode()
            eng.drain()
        assert all(h.result(timeout=5).ok for h in handles)

    def value(name):
        for line in text.splitlines():
            if line.startswith(name + " ") or line.startswith(name + "{"):
                return float(line.rsplit(" ", 1)[1])
        return None

    assert value("marlin_serve_submitted_total") >= 8
    assert value("marlin_prefetch_chunks_total") >= 3
    assert value("marlin_compile_total") >= 1
    # gauges exist (live queue state; may be any current value)
    for g in ("marlin_serve_queue_depth", "marlin_serve_kv_inflight_bytes",
              "marlin_serve_slot_occupancy"):
        assert f"# TYPE {g} gauge" in text
    # the planner-budget gauge the KV admission gate reasons against
    assert value("marlin_hbm_planner_budget_bytes") > 0


# ------------------------------------------------------------------ analyzer


def test_report_golden_on_fixture():
    events, skipped = load_events(FIXTURE)
    assert skipped == 1  # the fixture ends in a torn line, by construction
    got = analyze(events, skipped)
    with open(GOLDEN) as f:
        assert got == f.read()


def test_report_main_cli(tmp_path, capsys):
    from marlin_tpu.obs.report import main

    assert main([FIXTURE]) == 0
    out = capsys.readouterr().out
    assert "trace join: 6/6 requests" in out
    assert main([]) == 2
    assert main([str(tmp_path / "missing.jsonl")]) == 1


def test_report_empty_stream():
    assert analyze([]) == "== marlin_tpu.obs.report ==\nevents: 0\n"


# --------------------------------------------------- analysis time windows


def test_parse_when_forms():
    from marlin_tpu.obs.report import parse_when

    assert parse_when("1234.5") == 1234.5
    assert parse_when("5m ago", now=1000.0) == 700.0
    assert parse_when("2h ago", now=10000.0) == 10000.0 - 7200.0
    assert parse_when("30s ago", now=100.0) == 70.0
    assert parse_when("1d ago", now=90000.0) == 90000.0 - 86400.0
    # ISO-8601; a naive stamp is taken as UTC (EventLog stamps time.time())
    assert parse_when("1970-01-01T00:10:00+00:00") == 600.0
    assert parse_when("1970-01-01T00:10:00") == 600.0
    with pytest.raises(ValueError, match="cannot parse time"):
        parse_when("next tuesday")


def test_load_events_window():
    all_events, _ = load_events(FIXTURE)
    windowed, skipped = load_events(FIXTURE, since=1004.0, until=1009.0)
    assert skipped == 1
    assert 0 < len(windowed) < len(all_events)
    assert all(1004.0 <= r["t"] <= 1009.0 for r in windowed)
    # a record with no numeric t is kept, not silently dropped
    import tempfile

    with tempfile.NamedTemporaryFile("w", suffix=".jsonl",
                                     delete=False) as f:
        f.write(json.dumps({"kind": "x", "t": 5.0}) + "\n")
        f.write(json.dumps({"kind": "y"}) + "\n")
        path = f.name
    try:
        recs, _ = load_events(path, since=100.0)
        assert [r["kind"] for r in recs] == ["y"]
    finally:
        os.unlink(path)


WINDOW_GOLDEN = os.path.join(os.path.dirname(__file__), "..", "tools",
                             "fixtures", "obs_report_window_golden.txt")


def test_report_cli_since_until_golden(capsys):
    from marlin_tpu.obs.report import main

    assert main(["--since", "1004", "--until", "1009", FIXTURE]) == 0
    out = capsys.readouterr().out
    with open(WINDOW_GOLDEN) as f:
        assert out == f.read()
    # flag error paths fail loudly with usage, not a traceback
    assert main(["--since"]) == 2
    assert main(["--since", "next tuesday", FIXTURE]) == 2
    assert main(["--since", "1004", FIXTURE, "extra.jsonl"]) == 2
    capsys.readouterr()


# ----------------------------------------------- concurrent scrape stress


def test_concurrent_scrape_no_torn_exposition(lm_params, default_log):
    """8 client threads hammering /metrics, /healthz, /debug/slo and
    /debug/kvpool during a live serve: every response is well-formed (no
    500s, no torn exposition) and the serve is undisturbed."""
    import marlin_tpu as mt
    from marlin_tpu.serving import Request, ServeEngine

    slo = ({"name": "ttft", "metric": "p95:marlin_serve_ttft_seconds",
            "target": 30.0, "window_s": 60.0},)
    with mt.config_context(serve_slo=slo, serve_slo_eval_interval_s=0.05,
                           serve_ts_bucket_s=0.5):
        eng = ServeEngine(lm_params, HEADS, buckets=((8, 4),), max_batch=4,
                          max_wait_ms=0.0, queue_depth=64)
    failures: list[str] = []

    def hammer(base, n=6):
        paths = ("/metrics", "/healthz", "/debug/slo", "/debug/kvpool")
        for i in range(n):
            for p in paths:
                try:
                    with urllib.request.urlopen(base + p, timeout=10) as r:
                        body = r.read().decode()
                        code = r.status
                except urllib.error.HTTPError as e:
                    body, code = e.read().decode(), e.code
                except Exception as e:  # connection-level failure
                    failures.append(f"{p}: {type(e).__name__}: {e}")
                    continue
                if code >= 500:
                    failures.append(f"{p}: HTTP {code}")
                elif p == "/metrics":
                    if (not body.endswith("\n")
                            or "# TYPE marlin_serve_submitted_total"
                            not in body):
                        failures.append(f"{p}: torn exposition")
                else:
                    try:
                        json.loads(body)
                    except ValueError:
                        failures.append(f"{p}: torn JSON body")

    try:
        with obs.MetricsServer(port=0) as srv:
            base = srv.url.rsplit("/metrics", 1)[0]
            handles = [eng.submit(Request(prompt=[1, 2, i % 7 + 1],
                                          steps=3))
                       for i in range(16)]
            threads = [threading.Thread(target=hammer, args=(base,))
                       for _ in range(8)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=120)
            assert not any(t.is_alive() for t in threads)
            results = [h.result(timeout=60) for h in handles]
    finally:
        eng.close()
    assert not failures, failures[:10]
    assert all(r.ok for r in results)
