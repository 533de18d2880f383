"""The ``serve_laguna`` driver and its readers: the CPU rehearsal of the tiny
cell (correct; not correct with the x 2.5 left out of the program), the
readers' arithmetic on made-up spans and device operations, the cost
functions by hand, and the committed configuration against the catalog's
rules."""

import json
import os
import subprocess
import sys

import pytest

from benchmarks import (costs_laguna, engine_spans as es, laguna_spans, run,
                        trace_reduce as tr)
from benchmarks.engine_spans import Span
from benchmarks.trace_reduce import DeviceTrace, Event

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
BENCH = os.path.join(HERE, "rehearsal", "BENCHMARK.laguna.json")
NEW = ("moe_share_pct", "moe_roofline_pct", "moe_experts_touched_pct",
       "moe_local_assign_pct", "attn_window_roofline_pct",
       "attn_global_roofline_pct", "kv_window_pages_pct")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _config():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "laguna-s21-ep4-l9.json")) as f:
        return json.load(f)


def _spans():
    """One iteration 0..10 on line 1: a prefill chunk that lands at 3, one
    decode dispatch 5..6 that lands in 6..8."""
    spans = [
        Span("serve.iter", 0.0, 10.0, {"row_pages": 60, "global_pages": 40,
                                       "window_pages": 20,
                                       "pages_total": 1536}, 1),
        Span("serve.prefill.sync", 3.0, 3.5,
             {"rid": 1, "final": 1, "moe_assignments": 40960,
              "moe_local_assignments": 10000, "moe_experts_touched": 512}, 1),
        Span("serve.decode.dispatch", 5.0, 6.0,
             {"rows": 10, "padded_rows": 32, "table_width": 64,
              "global_table_width": 64, "window_table_width": 5,
              "global_kv_pages": 120, "window_kv_pages": 45,
              "kv_tokens": 9000}, 1),
        Span("serve.decode.sync", 6.0, 8.0,
             {"moe_assignments": 800, "moe_local_assignments": 240,
              "moe_experts_touched": 128}, 1)]
    spans.sort(key=lambda s: (s.start, -s.end))
    return spans


def _ctx(monkeypatch, ops=(), modules=()):
    monkeypatch.setattr(es, "capture_for", lambda ctx, trace_root=None: {
        "spans": _spans(), "window": (0.0, 10.0), "load_s": 0.0, "memo": {}})
    devices = [DeviceTrace("/device:TPU:0", list(ops), list(modules))]
    return {"trace": tr.Trace(devices if ops else [], []),
            "window": (0.0, 10.0), "config": _config(), "peaks": PEAKS,
            "counters": {}}


def _read(metric, ctx):
    return run.load_module("layer_metrics", metric).read(ctx)


def test_costs_by_hand():
    cfg = _config()
    assert costs_laguna.expert_bytes(cfg) == 3 * 3072 * 1024 * 2 == 18874368
    assert costs_laguna.expert_flops(cfg) == 6 * 3072 * 1024
    assert costs_laguna.kv_page_bytes(cfg) == 2 * 256 * 8 * 128 * 2
    assert costs_laguna.layers_of(cfg, "full_attention") == 3
    assert costs_laguna.layers_of(cfg, "sliding_attention") == 6
    least = costs_laguna.moe_least_seconds(128, 240, cfg, PEAKS)
    assert least["bound"] == "memory"
    assert least["seconds"] == pytest.approx(128 * 18874368 / 819e9)
    busy = costs_laguna.moe_least_seconds(1, 10 ** 6, cfg, PEAKS)
    assert busy["bound"] == "compute"
    assert costs_laguna.attention_least_seconds(
        45, "sliding_attention", cfg, PEAKS) == pytest.approx(
            45 * 6 * 1048576 / 819e9)


def test_the_counter_readers_on_made_up_spans(monkeypatch):
    ctx = _ctx(monkeypatch)
    assert _read("moe_experts_touched_pct", ctx) == pytest.approx(
        100 * 128 / (64 * 8))
    assert _read("moe_local_assign_pct", ctx) == pytest.approx(
        100 * (240 + 10000) / (800 + 40960))
    assert _read("kv_window_pages_pct", ctx) == pytest.approx(50.0)
    for name in ("moe_share_pct", "moe_roofline_pct",
                 "attn_window_roofline_pct", "attn_global_roofline_pct"):
        assert _read(name, ctx) is None  # no device in the trace


def test_the_roofline_readers_read_100_at_exactly_their_bound(monkeypatch):
    cfg = _config()
    moe_s = costs_laguna.moe_least_seconds(128, 240, cfg, PEAKS)["seconds"]
    win_s = costs_laguna.attention_least_seconds(45, "sliding_attention",
                                                 cfg, PEAKS)
    glo_s = costs_laguna.attention_least_seconds(120, "full_attention", cfg,
                                                 PEAKS)

    def op(name, start, seconds):
        return Event(f"%{name} = bf16[320,1024]{{1,0}} custom-call(bf16[320,"
                     f"3072] %a)", start, start + seconds, "custom-call")

    ops = [op("ragged-dot-none.3", 6.1, moe_s),
           op("ragged-dot-metadata.3", 6.0, 0.05),      # not a matmul
           op("ragged-dot-none.9", 2.0, 0.5),           # a prefill's: outside
           op("_paged_decode_attention_window_call.4", 6.5, win_s),
           op("_paged_decode_attention_call.2", 6.8, glo_s / 2),
           op("_paged_decode_attention_call.7", 7.2, glo_s / 2)]
    modules = [Event("jit__lm_decode_paged_spec_jit(1)", 5.9, 8.0),
               Event("jit__lm_prefill_paged_spec_jit(2)", 1.0, 3.0)]
    ctx = _ctx(monkeypatch, ops, modules)
    assert _read("moe_roofline_pct", ctx) == pytest.approx(100.0)
    assert _read("attn_window_roofline_pct", ctx) == pytest.approx(100.0)
    assert _read("attn_global_roofline_pct", ctx) == pytest.approx(100.0)


def test_operation_scopes_are_read_from_the_recorded_trace(monkeypatch):
    """``ProfileData`` shows an event's own stats; the scope an operation
    was traced under is in its METADATA's ``tf_op`` stat, which
    ``laguna_spans.op_scopes`` reads from the file's wire format."""
    path = os.path.join(HERE, "data", "mesh4_3s.xplane.pb")
    scopes = laguna_spans.op_scopes(path)
    trace = tr.load(path)
    names = {e.name for e in trace.devices[0].ops}
    assert names and names <= set(scopes)
    dot = next(v for k, v in scopes.items() if k.startswith("%fusion.1 ="))
    assert dot == "jit(f)/shard_map/dot_general:"
    monkeypatch.setattr(laguna_spans, "xplane_path",
                        lambda ctx, trace_root=None: path)
    lo, hi = tr.window_of(trace)
    ctx = {"trace": trace, "window": (lo, hi)}
    got = laguna_spans.scoped_intervals(ctx, "shard_map")
    want = [(e.start, e.end) for e in trace.devices[0].ops
            if "shard_map" in scopes[e.name]]
    assert got == want and len(got) > 10
    assert laguna_spans.scoped_intervals(ctx, "moe_experts") is None
    assert list(laguna_spans._fields(bytes([0x08, 0x96, 0x01, 0x12, 0x02,
                                            0x68, 0x69]))) \
        == [(1, 150), (2, b"hi")]


def _rehearse(trace, env_extra=None):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--bench", BENCH, "--allow-cpu-rehearsal", "--workload",
         "serve.tiny-laguna", "--seed", "3000000123", "--seconds", "2",
         "--trace", str(trace)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-2000:]
    return [json.loads(x) for x in p.stdout.strip().splitlines()]


def test_the_rehearsal_is_correct_and_leaves_the_seven_out_without_an_error():
    bench = json.load(open(BENCH))
    assert {m["name"] for m in bench["per_layer"]} >= set(NEW)
    lines = _rehearse(1)
    assert lines[-1]["correct"] is True and lines[-1]["failed"] == 0
    assert lines[-1]["metrics"] == {}
    window = next(n for n in lines if n.get("note") == "window")
    assert window["compiles_in_window"] == 0
    layer = next(n for n in lines if n.get("note")
                 == "cpu_rehearsal_layer_values_not_measurements")
    assert set(layer) == {"note", "rows_per_step"}


def test_the_routed_sum_without_its_factor_is_not_correct(capsys,
                                                          monkeypatch):
    """The program's expert layer with the x 2.5 left out serves tokens whose
    reference logits lie far below the reference's best."""
    import jax

    from marlin_tpu.models import moe

    sound = moe.moe_experts_ffn
    monkeypatch.setattr(
        moe, "moe_experts_ffn",
        lambda *a, **kw: sound(*a, **{**kw, "routed_scale": 1.0}))
    jax.clear_caches()
    try:
        rc = run.main(["--bench", BENCH, "--allow-cpu-rehearsal",
                       "--workload", "serve.tiny-laguna", "--seed", "11",
                       "--seconds", "1", "--trace", "0"])
    finally:
        jax.clear_caches()
    lines = [json.loads(x)
             for x in capsys.readouterr().out.strip().splitlines()]
    assert rc == 0 and lines[-1]["correct"] is False
    gap = next(n for n in lines if n.get("name") == "served_logit_gap")
    assert gap["value"] > 10 * gap["limit"]


def test_the_configuration_keeps_every_published_number():
    """Every number of the catalog's ``config`` under the same key, but for
    the three keys the file lists under ``reduced``; nested groups whole."""
    cfg = _config()
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(catalog):
        pytest.skip("no catalog here")
    row = next(json.loads(x) for x in open(catalog)
               if '"Laguna-S-2.1"' in x)
    assert cfg["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in cfg["reduced"]:
            assert cfg["source_values"][key] == value and cfg[key] < value
        else:
            assert cfg[key] == value, key
    assert (cfg["num_hidden_layers"], cfg["num_experts"],
            cfg["vocab_size"]) == (9, 64, 25088)
    traffic = json.load(open(os.path.join(ROOT, "benchmarks", "traffic",
                                          "closed-longtail32.json")))
    assert traffic["arrival"] == {"kind": "closed", "callers": 32}
    assert traffic["prompt_len"] == {"dist": "lognormal", "median": 768,
                                     "sigma": 1.1, "min": 64, "max": 7680}
    assert traffic["output_len"] == {"dist": "lognormal", "median": 192,
                                     "sigma": 0.6, "min": 32, "max": 512}
    assert (traffic["max_total_len"], traffic["pool"], traffic["strata"],
            traffic["ramp_s"], traffic["shared_prefix"],
            traffic["temperature"]) == (8192, 64, 4, 12, None, 0)


def test_the_benchmark_lists_the_cell_and_the_seven():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    cell = "serve.laguna-longtail32"
    assert bench["workloads"][-1] == {
        "name": cell, "config": "laguna-s21-ep4-l9",
        "traffic": "closed-longtail32", "chips": 1,
        "why": bench["workloads"][-1]["why"]}
    assert [m["name"] for m in bench["per_layer"]][-7:] == list(NEW)
    for m in bench["per_layer"][-7:]:
        assert m["workloads"] == [cell]
        assert os.path.isfile(os.path.join(ROOT, "benchmarks",
                                           "layer_metrics",
                                           m["name"] + ".py"))
    lists = {m["name"]: m.get("workloads") for m in
             bench["end_to_end"] + bench["per_layer"]}
    for name in ("tokens_s", "itl_p95_ms", "device_idle_pct.serve",
                 "decode_step_ms", "prefill_share_pct", "rows_per_step",
                 "idle_pct.prefill", "idle_pct.decode",
                 "idle_pct.unattributed", "queue_wait_ms"):
        assert lists[name] == ["serve.closed16", cell]
    # idle_pct.schedule's reader also prints the engine_phases note, whose
    # table intersects the chip's whole idle list once per worker segment:
    # 323 s on this cell's 800,000-operation trace, past a run's time limit
    for name in ("attn_roofline_pct", "decode_kv_useful_pct",
                 "kv_reserved_pct", "kv_filled_pct", "idle_pct.schedule"):
        assert lists[name] == ["serve.closed16"]
