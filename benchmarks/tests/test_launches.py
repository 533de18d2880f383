"""The join of dispatch spans and executions (``benchmarks/launches.py``) on a
recorded chip trace (``data/serve_tiny.xplane.pb``: a one-layer model behind
``ServeEngine`` on one v5e chip, ``record_launches.py``), where the ordinal
join is held to the runtime's own flow ids pair by pair; and the seven readers
on a hand-built pipelined window."""

import json
import os
import subprocess
import sys

import pytest

from benchmarks import engine_spans, launches, run, trace_reduce
from benchmarks.engine_spans import Span
from benchmarks.trace_reduce import DeviceTrace, Event, Trace

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
RECORDED = os.path.join(HERE, "data", "serve_tiny.xplane.pb")
SERVING = ["serve.closed16", "serve.laguna-longtail32",
           "serve.mistral4-docqa32", "serve.falconh1-chat64"]
NEW = {"iter_device_ms_p95": SERVING, "chunk_iters_pct": SERVING,
       "launch_slack_ms": SERVING, "prefill_chunk_ms": SERVING,
       "prefill_us_per_token": SERVING, "prefill_fill_pct": SERVING,
       "attn_grid_live_pct": SERVING[1:]}


# ------------------------------------------------------- the recorded trace


@pytest.fixture(scope="module")
def recorded():
    spans = engine_spans.load(RECORDED)["spans"]
    trace = trace_reduce.load(RECORDED)
    return spans, trace, launches.join_events(spans, trace.devices[0].modules)


def test_the_recorded_trace_holds_both_kinds_and_a_few_tens_of_dispatches(
        recorded):
    spans, trace, got = recorded
    assert os.path.getsize(RECORDED) < 300 * 1024
    assert len(trace.devices) == 1
    dispatched = launches.numbered(spans)
    assert 20 <= len(dispatched) <= 100
    assert {s.name for s in dispatched} == set(launches.DISPATCH)
    assert any(not s.fields["final"] for s in dispatched
               if s.name == "serve.prefill.dispatch")
    assert got.ok, got.why
    assert max(got.head_runs, got.head_spans, got.tail_spans) <= launches.EDGE
    assert got.counts()["matched"] >= len(dispatched) - launches.EDGE


def test_the_ordinal_join_is_the_runtimes_own_pair_by_pair(recorded):
    """Every execution both joins know was launched by the same span: the
    ordinal join goes by order and kind alone, the exact one by the flow ids
    from the ``XLA Modules`` event back to the calling thread."""
    spans, trace, got = recorded
    exact = launches.by_flow(RECORDED, trace.devices[0].name)
    ordinal = {int(round(x.run.start * 1e9)): x.seq
               for x in got.launches if x.run is not None}
    assert len(exact) >= 20 and set(exact) == set(ordinal)
    assert exact == ordinal
    # and the executions are of the kind the span says
    for x in got.launches:
        if x.run is not None:
            assert launches.PROGRAM[x.kind] in x.run.name


def test_each_execution_follows_its_dispatch_and_precedes_its_landing(
        recorded):
    _, _, got = recorded
    matched = [x for x in got.launches if x.run is not None]
    for a, b in zip(matched, matched[1:]):
        assert a.run.end <= b.run.start  # one after the other on the chip
    for x in matched:
        assert x.run.start >= x.span.start
        if x.landed is not None:
            assert x.run.end <= x.landed
        line = x.line()
        assert line["seq"] == x.seq and line["device_ms"] > 0
        assert ("rows" in line) == (x.kind == "decode")
        assert ("width" in line) == (x.kind == "prefill")


def test_the_command_prints_a_line_a_dispatch_and_says_the_joins_agree():
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "launches.py"),
         RECORDED], capture_output=True, text=True, timeout=300, check=True)
    lines = [json.loads(x) for x in out.stdout.splitlines()
             if x.startswith("{")]
    last = lines[-1]
    assert last["agree"] is True and last["differ"] == []
    assert last["why"] is None and last["compared"] == last["matched"] >= 20
    assert len(lines) - 1 == (last["matched"] + last["head_spans"]
                              + last["tail_spans"])
    assert {"seq", "kind", "dispatch_ms", "queued_ms", "device_ms"} <= set(
        lines[0])


# ------------------------------------------------- the readers' arithmetic

DECODE, CHUNK = 0.020, 0.010


def _window(seq_fields=True, spec=False):
    """Six iterations of a worker that is ahead of the chip, in a window
    0..1: decode-only, but the third carries one chunk (48 of 64 tokens)
    and the fifth two (64 and 16 of 64). Iteration ``i`` lands call
    ``i - 1``. Returns the spans and the chip's program events."""
    spans, runs, seq, t, dev, flying = [], [], 0, 0.010, 0.011, None
    for chunks in ([], [], [48], [], [64, 16], []):
        lo, inner = t, []
        for tokens in chunks + [None]:
            seq += 1
            if tokens is None:
                name, fields, cost = "serve.decode.dispatch", {
                    "rows": 4, "padded_rows": 8, "table_width": 10}, DECODE
                if spec:
                    fields.update(global_table_width=10, global_kv_pages=20)
            else:
                name, fields, cost = "serve.prefill.dispatch", {
                    "tokens": tokens, "width": 64, "final": 0}, CHUNK
            if seq_fields:
                fields["seq"] = seq
            inner.append(Span(name, t, t + 0.002, fields, 1))
            t += 0.002
            start = max(dev, t)
            dev = start + cost
            runs.append(Event(
                "jit__lm_decode_paged_jit(7)" if tokens is None
                else "jit__lm_prefill_paged_jit(8)", start, dev))
        if flying is not None:
            end = max(t, flying[1]) + 0.0002
            inner.append(Span("serve.decode.sync", t, end,
                              {"seq": flying[0]} if seq_fields else {}, 1))
            t = end
        flying = (seq, dev)
        spans += [Span("serve.iter", lo, t + 0.0001, {}, 1)] + inner
        t += 0.0003
    runs.append(Event("jit_feed_token(9)", dev, dev + 0.0005))
    return spans, runs


@pytest.fixture
def ctx(monkeypatch, request):
    kw = getattr(request, "param", {})
    spans, runs = _window(**kw)
    got = {"window": (0.0, 1.0), "spans": spans, "load_s": 0.0, "memo": {}}
    monkeypatch.setattr(engine_spans, "capture_for", lambda ctx, *a: got)
    return {"trace": Trace([DeviceTrace("/device:TPU:0", [], runs)], []),
            "window": (0.0, 1.0), "counters": {}, "config": {}}


def _read(name, ctx):
    return run.load_module("layer_metrics", name).read(ctx)


def test_the_readers_on_a_pipelined_window(ctx, capsys):
    # device time an iteration: 20, 20, 30, 20, 40, 20 ms
    assert _read("iter_device_ms_p95", ctx) == pytest.approx(
        1e3 * (0.030 + 0.75 * 0.010))
    assert _read("chunk_iters_pct", ctx) == pytest.approx(100 * 2 / 6)
    assert _read("prefill_chunk_ms", ctx) == pytest.approx(10.0)
    assert _read("prefill_us_per_token", ctx) == pytest.approx(
        1e6 * 0.030 / (48 + 64 + 16))
    assert _read("prefill_fill_pct", ctx) == pytest.approx(
        100 * (48 + 64 + 16) / (3 * 64))
    assert _read("attn_grid_live_pct", ctx) is None  # the dense model
    # the worker is ahead: every program but the first sat queued, and the
    # least wait of a decode-only iteration is the running call's remainder
    got = launches.join(ctx)
    waits = [x.queued_s for x in got.launches]
    assert waits[0] == 0.0 and all(w > 0.005 for w in waits[1:])
    assert 5.0 < _read("launch_slack_ms", ctx) < 20.0
    note = [json.loads(x) for x in capsys.readouterr().out.splitlines()]
    assert [n["note"] for n in note] == ["launch_join"]
    note = note[0]
    assert (note["matched"], note["head_runs"] + note["head_spans"],
            note["tail_spans"],
            note["why"]) == (9, 0, 0, None)
    assert note["iterations"] == 6
    assert {k: v["n"] for k, v in note["iter_device_ms"].items()} == {
        "0": 4, "1": 1, "2": 1}
    assert note["iter_device_ms"]["2"]["p50"] == pytest.approx(40.0)
    assert note["other_programs_ms"] == {"feed_token": pytest.approx(0.5)}
    assert note["other_programs_pct"] == pytest.approx(0.05)
    # the landing's span ends with the call it waits for: the two agree
    assert note["decode_sync_ms_p50"] == pytest.approx(
        note["launch_slack_ms_p50"], abs=3.0)


@pytest.mark.parametrize("ctx", [{"spec": True}], indirect=True)
def test_a_spec_models_grid_steps_that_hold_attended_positions(ctx):
    assert _read("attn_grid_live_pct", ctx) == pytest.approx(
        100 * 20 / (8 * 10))


@pytest.mark.parametrize("ctx", [{"seq_fields": False}], indirect=True)
@pytest.mark.parametrize("name", sorted(NEW))
def test_a_program_that_numbers_nothing_prints_none_of_them(ctx, name,
                                                            capsys):
    assert _read(name, ctx) is None
    assert capsys.readouterr().out == ""


@pytest.mark.parametrize("name", sorted(NEW))
def test_nothing_without_a_trace(name):
    assert _read(name, {"trace": None, "window": (0.0, 1.0)}) is None


def test_the_benchmark_lists_the_seven_for_the_serving_cells():
    bench = run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    listed = {m["name"]: m for m in bench["per_layer"]}
    for name, cells in NEW.items():
        assert listed[name]["workloads"] == cells
        assert os.path.isfile(os.path.join(
            ROOT, "benchmarks", "layer_metrics", name + ".py"))
    assert listed["iter_device_ms_p95"]["moves"] == "itl_p95_ms"
    assert listed["launch_slack_ms"]["better"] == "higher"
