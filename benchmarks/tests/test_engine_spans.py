"""The readers of the program's spans: the arithmetic on hand-built spans and
idle intervals, a real CPU capture found (or refused) by its window, and the
rehearsal, which must leave the nine metrics out without an error."""

import json
import os
import subprocess
import sys

import pytest

from benchmarks import costs, engine_spans as es, run, trace_reduce as tr
from benchmarks.engine_spans import Span
from benchmarks.trace_reduce import DeviceTrace, Event

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
BENCH = os.path.join(HERE, "rehearsal", "BENCHMARK.spans.json")
NEW = ("idle_pct.schedule", "idle_pct.prefill", "idle_pct.decode",
       "idle_pct.unattributed", "queue_wait_ms", "kv_reserved_pct",
       "kv_filled_pct", "decode_kv_useful_pct", "attn_roofline_pct")
CONFIG = {"n_heads": 32, "d_model": 4096, "n_layers": 8,
          "compute_dtype": "bfloat16", "engine": {"page_len": 16}}
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _worker(iters=((0.0, 10.0),)):
    """Ten seconds on line 1: claim 0..0.2, wait 0.2..0.8, claim 0.8..1,
    then the iteration 1..10: admit 1..2, prefill 2..5 (dispatch 2..3, sync
    3..4.5), decode 5..9.5 (dispatch 5..6, sync 6..8, retire 8..9); a
    caller's submit on line 2."""
    spans = []
    for lo, hi in iters:
        t = lambda x, lo=lo, hi=hi: lo + x * (hi - lo) / 10.0  # noqa: E731
        spans += [
            Span("serve.claim", t(0), t(0.2), {"claimed": 0}, 1),
            Span("serve.wait", t(0.2), t(0.8), {}, 1),
            Span("serve.claim", t(0.8), t(1), {"claimed": 1}, 1),
            Span("serve.iter", t(1), t(10), {"row_pages": 50,
                                             "pages_total": 200,
                                             "kv_tokens": 400}, 1),
            Span("serve.admit", t(1), t(2), {"rid": 7, "queue_wait_ms": 30.0},
                 1),
            Span("serve.prefill", t(2), t(5), {"chunks": 1, "tokens": 8}, 1),
            Span("serve.prefill.dispatch", t(2), t(3),
                 {"rid": 7, "final": 1}, 1),
            Span("serve.prefill.sync", t(3), t(4.5), {"rid": 7, "final": 1}, 1),
            Span("serve.decode", t(5), t(9.5), {"buckets": 1}, 1),
            Span("serve.decode.dispatch", t(5), t(6),
                 {"bucket": "256x128", "rows": 5, "padded_rows": 16,
                  "table_width": 24, "kv_tokens": 1536}, 1),
            Span("serve.decode.sync", t(6), t(8), {"bucket": "256x128"}, 1),
            Span("serve.decode.retire", t(8), t(9), {"retired": 0}, 1),
            Span("serve.submit", t(0.1), t(0.3), {"rid": 8}, 2)]
    spans.sort(key=lambda s: (s.start, -s.end))
    return spans


def test_self_segments_name_every_instant_by_the_innermost_span():
    segs = es.self_segments(es.worker_spans(_worker()))
    assert segs == [
        (0.0, 0.2, "serve.claim"), (0.2, 0.8, "serve.wait"),
        (0.8, 1.0, "serve.claim"), (1.0, 2.0, "serve.admit"),
        (2.0, 3.0, "serve.prefill.dispatch"), (3.0, 4.5, "serve.prefill.sync"),
        (4.5, 5.0, "serve.prefill"), (5.0, 6.0, "serve.decode.dispatch"),
        (6.0, 8.0, "serve.decode.sync"), (8.0, 9.0, "serve.decode.retire"),
        (9.0, 9.5, "serve.decode"), (9.5, 10.0, "serve.iter")]
    assert tr.total([(a, b) for a, b, _ in segs]) == pytest.approx(10.0)


def test_the_four_idle_shares_add_up_to_the_idle_time():
    idle = [(0.5, 1.5), (4.0, 5.5), (8.5, 9.75), (10.0, 12.0)]
    got = es.idle_by_phase(idle, _worker(), 0.0, 12.0)
    # schedule: 0.5..1.5 and 9.5..9.75; prefill 4..5; decode 5..5.5 and
    # 8.5..9.5; after the iteration nothing covers 10..12
    assert got == pytest.approx({"schedule": 1.25, "prefill": 1.0,
                                 "decode": 1.5, "unattributed": 2.0})
    assert sum(got.values()) == pytest.approx(tr.total(idle))


def test_a_phase_without_a_span_lands_in_unattributed():
    spans = [s for s in _worker() if not s.name.startswith("serve.decode")]
    idle = [(5.0, 9.5)]
    assert es.idle_by_phase(idle, spans, 0.0, 10.0)["schedule"] == \
        pytest.approx(4.5)  # inside serve.iter: its self time
    bare = [s for s in spans if s.name != "serve.iter"]
    bare.append(Span("serve.iter", 20.0, 21.0, {}, 1))  # keeps the line known
    got = es.idle_by_phase(idle, bare, 0.0, 10.0)
    assert got["unattributed"] == pytest.approx(4.5)
    assert got["schedule"] == got["decode"] == 0.0


@pytest.mark.parametrize("name,phase", [
    ("serve.wait", "schedule"), ("serve.iter", "schedule"),
    ("serve.prefill", "prefill"), ("serve.prefill.sync", "prefill"),
    ("serve.decode.retire", "decode"), ("serve.prefetch", "schedule"),
    ("serve.spec_decode", "schedule")])
def test_phase_of(name, phase):
    assert es.phase_of(name) == phase


def _ctx(spans, monkeypatch, devices=(), window=(0.0, 10.0), counters=None):
    monkeypatch.setattr(es, "capture_for", lambda ctx, trace_root=None: {
        "spans": spans, "window": window, "load_s": 0.0, "memo": {}})
    return {"trace": tr.Trace(list(devices), []), "window": window,
            "config": CONFIG, "peaks": PEAKS, "counters": counters or {}}


def _read(metric, ctx):
    return run.load_module("layer_metrics", metric).read(ctx)


def test_counter_readers_on_the_hand_built_spans(monkeypatch):
    ctx = _ctx(_worker(), monkeypatch)
    assert _read("queue_wait_ms", ctx) == pytest.approx(30.0)
    assert _read("kv_reserved_pct", ctx) == pytest.approx(25.0)
    assert _read("kv_filled_pct", ctx) == pytest.approx(100 * 400 / (50 * 16))
    assert _read("decode_kv_useful_pct", ctx) == pytest.approx(
        100 * 1536 / (16 * 24 * 16))
    assert _read("attn_roofline_pct", ctx) is None  # no device in the trace


def test_attn_roofline_reads_100_at_exactly_the_memory_bound(monkeypatch):
    cost = costs.paged_attention_cost(16, 24, 16, 32, 1, 128, 2)
    per_layer = cost["bytes"] / PEAKS["hbm_bytes_per_s"]
    assert per_layer > cost["flops"] / PEAKS["bf16_flops_per_s"]
    kernel = ("%_paged_decode_attention_call.8 = bf16[16,32,1,128]{3,2,1,0} "
              "custom-call(s32[16,24] %a, bf16[16,32,1,128] %q)")
    consumer = ("%fusion.3 = bf16[16,4096] fusion(bf16[16,32,1,128] "
                "%_paged_decode_attention_call.8), kind=kLoop")
    ops = [Event(kernel, 5.2 + i * 0.01, 5.2 + i * 0.01 + per_layer,
                 "custom-call") for i in range(8)]
    ops.append(Event(consumer, 5.5, 5.6, "fusion"))  # not the kernel
    dev = DeviceTrace("/device:TPU:0", ops, [])
    ctx = _ctx(_worker(), monkeypatch, devices=[dev])
    assert _read("attn_roofline_pct", ctx) == pytest.approx(100.0)
    least = es.attention_least_seconds(es.dispatches(_worker(), 0, 10),
                                       CONFIG, PEAKS)
    assert least["bound"] == "memory"
    assert least["seconds"] == pytest.approx(8 * per_layer)


def test_idle_readers_add_up_to_the_device_idle_share(monkeypatch):
    busy = [Event("%fusion.1 = f32[8] fusion(f32[8] %a), kind=kLoop", a, b,
                  "fusion") for a, b in ((0.0, 0.5), (1.5, 4.0), (5.5, 8.5),
                                         (9.75, 10.0))]
    dev = DeviceTrace("/device:TPU:0", busy, [])
    ctx = _ctx(_worker(), monkeypatch, devices=[dev])
    parts = [_read(f"idle_pct.{p}", ctx)
             for p in ("schedule", "prefill", "decode", "unattributed")]
    assert parts == pytest.approx([12.5, 10.0, 15.0, 0.0])
    assert sum(parts) == pytest.approx(tr.worst_idle_pct(ctx))


def test_the_phase_table(monkeypatch, capsys):
    spans = _worker()
    table = es.phases(spans, [(4.0, 5.5)], 0.0, 10.0)
    rows = {k: dict(v) for k, v in table["spans"].items()}
    fields = {k: v.pop("fields") for k, v in rows.items()}
    assert rows["serve.prefill"] == pytest.approx(
        {"count": 1, "seconds": 3.0, "self_s": 0.5, "idle_s": 0.5})
    assert rows["serve.prefill.sync final=1"] == pytest.approx(
        {"count": 1, "seconds": 1.5, "self_s": 1.5, "idle_s": 0.5})
    # every count a span carries is read: its mean over the label's spans;
    # what names a span (rid, final, the bucket's tag) is not a count
    assert fields["serve.claim"] == {"claimed": 0.5}
    assert fields["serve.iter"] == {"row_pages": 50, "pages_total": 200,
                                    "kv_tokens": 400}
    assert fields["serve.prefill"] == {"chunks": 1, "tokens": 8}
    assert fields["serve.prefill.sync final=1"] == {}
    assert fields["serve.decode.dispatch"] == {
        "rows": 5, "padded_rows": 16, "table_width": 24, "kv_tokens": 1536}
    assert table["spans"]["serve.submit"]["idle_s"] is None
    assert table["queue_wait_ms"] == {"count": 1, "samples": [30.0]}
    assert table["request_parts"] == pytest.approx(
        {"admit_to_prefill_ms": 1e3, "prefill_to_first_token_ms": 2.5e3,
         "samples": [1, 1]})
    assert (table["decode_dispatches"], table["decode_rows"]) == (1, 5)


# ------------------------------------------------- a real capture, on the CPU


@pytest.fixture(scope="module")
def capture(tmp_path_factory):
    """``<root>/cell/plugins/profile/...xplane.pb`` as ``run.py`` leaves
    it: a ``bench:window`` with the program's primitive inside it."""
    import jax

    from marlin_tpu.utils.tracing import annotate

    root = tmp_path_factory.mktemp("bench_trace")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(root / "cell"), profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench:window"):
        with annotate("serve.iter", row_pages=3, pages_total=12,
                      kv_tokens=24):
            with annotate("serve.admit", rid=1) as span:
                span.set_metadata(queue_wait_ms=2.5)
    jax.profiler.stop_trace()
    loaded = tr.load(tr.find_xplane(str(root / "cell")))
    return str(root), tr.window_of(loaded)


def test_the_capture_is_found_by_its_window_and_read(capture, monkeypatch):
    root, window = capture
    monkeypatch.setattr(es, "TRACE_ROOT", root)
    ctx = {"trace": tr.Trace([], []), "window": window,
           "config": {"engine": {"page_len": 16}}, "counters": {}}
    spans = es.for_ctx(ctx)
    assert [s.name for s in spans] == ["serve.iter", "serve.admit"]
    assert spans[1].fields == {"rid": 1, "queue_wait_ms": 2.5}
    assert _read("queue_wait_ms", ctx) == pytest.approx(2.5)
    assert _read("kv_reserved_pct", ctx) == pytest.approx(25.0)
    assert _read("kv_filled_pct", ctx) == pytest.approx(50.0)
    assert _read("idle_pct.decode", ctx) is None  # no device plane on a CPU


def test_a_wrong_window_xplane_is_refused(capture, monkeypatch):
    root, (lo, hi) = capture
    monkeypatch.setattr(es, "TRACE_ROOT", root)
    stale = {"trace": tr.Trace([], []), "window": (lo + 1.0, hi + 1.0)}
    assert es.for_ctx(stale) is None
    for metric in NEW:
        assert _read(metric, {**stale, "config": CONFIG, "peaks": PEAKS,
                              "counters": {}}) is None
    assert es.for_ctx({"trace": None, "window": (lo, hi)}) is None


def test_the_script_prints_the_phase_table_of_a_capture(capture):
    root, _ = capture
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "engine_spans.py"),
         tr.find_xplane(os.path.join(root, "cell"))],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-2000:]
    table = json.loads(p.stdout)
    assert table["spans"]["serve.iter"]["count"] == 1
    assert table["queue_wait_ms"]["samples"] == [2.5]


def test_the_rehearsal_leaves_the_nine_metrics_out_without_an_error():
    bench = json.load(open(BENCH))
    assert {m["name"] for m in bench["per_layer"]} >= set(NEW)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--bench", BENCH, "--allow-cpu-rehearsal", "--workload",
         "serve.tiny-closed", "--seed", "3000000123", "--seconds", "2",
         "--trace", "1"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    lines = [json.loads(x) for x in p.stdout.strip().splitlines()]
    assert lines[-1]["correct"] is True and lines[-1]["metrics"] == {}
    layer = next(n for n in lines
                 if n.get("note") == "cpu_rehearsal_layer_values_not_measurements")
    assert set(layer) == {"note", "rows_per_step"}
    assert not any(n.get("note") == "engine_phases" for n in lines[:-1])


def test_the_benchmark_lists_the_nine_for_the_serving_cell_only():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    entries = {m["name"]: m for m in bench["per_layer"]}
    assert [m["name"] for m in bench["per_layer"]][-9:] == list(NEW)
    for name in NEW:
        assert entries[name]["workloads"] == ["serve.closed16"]
        assert os.path.isfile(os.path.join(ROOT, "benchmarks",
                                           "layer_metrics", name + ".py"))
