"""The ``serve_falconh1`` driver and its readers: the cost functions by hand,
the readers' arithmetic on made-up spans and device operations, the CPU
rehearsal of the tiny cell (correct; not correct with a reused state slot
left as its last row wrote it), and the committed configuration against the
catalog's rules."""

import json
import os
import subprocess
import sys

import pytest

from benchmarks import costs_falconh1, engine_spans as es, laguna_spans, run, \
    trace_reduce as tr
from benchmarks.engine_spans import Span
from benchmarks.trace_reduce import DeviceTrace, Event

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
BENCH = os.path.join(HERE, "rehearsal", "BENCHMARK.falconh1.json")
CELL = "serve.falconh1-chat64"
NEW = ("ssm_share_pct", "ssm_decode_roofline_pct", "ssm_prefill_roofline_pct",
       "state_slots_filled_pct")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _config():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "falcon-h1-34b-l6.json")) as f:
        return json.load(f)


def _spans():
    """One iteration 0..10 on line 1: a chunk of 600 valid tokens dispatched
    1.0..1.1, one decode dispatch 5..6 over 60 live rows."""
    spans = [
        Span("serve.iter", 0.0, 10.0, {"row_pages": 260, "pages_total": 640,
                                       "kv_tokens": 40000, "state_slots": 64,
                                       "state_rows": 48,
                                       "state_bytes": 48 * 25350144}, 1),
        Span("serve.prefill.dispatch", 1.0, 1.1,
             {"rid": 2, "start": 0, "tokens": 600, "ssm_tokens": 600,
              "final": 1}, 1),
        Span("serve.decode.dispatch", 5.0, 6.0,
             {"rows": 60, "padded_rows": 64, "table_width": 20,
              "global_table_width": 20, "window_table_width": 0,
              "global_kv_pages": 200, "window_kv_pages": 0,
              "state_rows": 60, "kv_tokens": 38000}, 1)]
    spans.sort(key=lambda s: (s.start, -s.end))
    return spans


def _ctx(monkeypatch, ops=(), scopes=None):
    monkeypatch.setattr(es, "capture_for", lambda ctx, trace_root=None: {
        "spans": _spans(), "window": (0.0, 10.0), "load_s": 0.0, "memo": {}})
    monkeypatch.setattr(laguna_spans, "xplane_path",
                        lambda ctx, trace_root=None: "made-up")
    monkeypatch.setattr(laguna_spans, "op_scopes",
                        lambda path, stat="tf_op": dict(scopes or {}))
    devices = [DeviceTrace("/device:TPU:0", list(ops), [])]
    return {"trace": tr.Trace(devices if ops else [], []),
            "window": (0.0, 10.0), "config": _config(), "peaks": PEAKS,
            "counters": {}}


def _read(metric, ctx):
    return run.load_module("layer_metrics", metric).read(ctx)


def test_costs_by_hand():
    cfg = _config()
    assert costs_falconh1.state_bytes(cfg) == 32 * 128 * 256 * 4 == 4194304
    assert costs_falconh1.conv_dim(cfg) == 4096 + 2 * 2 * 256 == 5120
    assert costs_falconh1.tail_bytes(cfg) == 3 * 5120 * 2
    assert costs_falconh1.slot_bytes(cfg) == 6 * (4194304 + 30720) \
        == 25350144
    # as much as 2,063 tokens of this model's KV (12,288 B a token)
    kv_token = 6 * 2 * 4 * 128 * 2
    assert kv_token == 12288 and 25350144 // kv_token == 2063
    assert costs_falconh1.ssm_decode_least_seconds(60, cfg, PEAKS) \
        == pytest.approx(60 * 6 * 2 * (4194304 + 30720) / 819e9)
    assert costs_falconh1.scan_token_flops(cfg) == 2 * (
        2 * 128 * 256 + 32 * 128 * 128 + 2 * 32 * 128 * 256)
    assert costs_falconh1.scan_token_bytes(cfg) == 5120 * 2 + 4 * 4096
    least = costs_falconh1.ssm_prefill_least_seconds(600, 1, cfg, PEAKS)
    assert least["bound"] == "memory"  # 32.5 ns a token against 27.3
    assert least["seconds"] == pytest.approx(
        6 * (600 * 26624 + 2 * 4194304) / 819e9)
    assert least["compute_s"] == pytest.approx(600 * 6 * 5373952 / 197e12)


def test_the_counter_readers_on_made_up_spans(monkeypatch):
    ctx = _ctx(monkeypatch)
    assert _read("state_slots_filled_pct", ctx) == pytest.approx(75.0)
    assert _read("kv_filled_pct", ctx) == pytest.approx(
        100 * 40000 / (260 * 256))
    for name in ("ssm_share_pct", "ssm_decode_roofline_pct",
                 "ssm_prefill_roofline_pct", "attn_global_roofline_pct"):
        assert _read(name, ctx) is None  # no device in the trace


def test_the_rooflines_read_100_at_exactly_their_bounds(monkeypatch):
    cfg = _config()
    dec = costs_falconh1.ssm_decode_least_seconds(60, cfg, PEAKS)
    pre = costs_falconh1.ssm_prefill_least_seconds(600, 1, cfg,
                                                   PEAKS)["seconds"]
    attn = 200 * 6 * (2 * 256 * 4 * 128 * 2) / 819e9

    def op(name, start, seconds):
        return Event(f"%{name} = f32[64,32,128]{{2,1,0}} custom-call("
                     f"f32[65,32,256,128] %a)", start, start + seconds,
                     "custom-call")

    ops = [op("_ssm_decode_update_call.3", 6.1, dec / 2),
           op("fusion.tails", 6.6, dec / 2),          # under ssm_update
           op("fusion.scan.1", 1.0, pre / 4),         # under ssm_scan
           op("fusion.scan.2", 1.2, pre / 4),
           op("fusion.in_proj", 2.0, 0.5),            # under ssm_mixer only
           op("_paged_decode_attention_call.2", 7.0, 2 * attn),
           op("fusion.other", 8.0, 1.0 - dec - pre / 2 - 2 * attn)]
    jit = "jit(_lm_decode_paged_spec_jit)/jit(main)"
    scopes = {
        ops[0].name: f"{jit}/ssm_mixer/ssm_update/pallas_call",
        ops[1].name: f"{jit}/ssm_mixer/ssm_update/scatter",
        ops[2].name: "jit(p)/jit(main)/ssm_mixer/ssm_scan/dot_general",
        ops[3].name: "jit(p)/jit(main)/ssm_mixer/ssm_scan/exp",
        ops[4].name: "jit(p)/jit(main)/ssm_mixer/dot_general",
        ops[6].name: "jit(p)/jit(main)/ffn_dense/dot_general"}
    ctx = _ctx(monkeypatch, ops, scopes)
    assert _read("ssm_decode_roofline_pct", ctx) == pytest.approx(100.0)
    assert _read("ssm_prefill_roofline_pct", ctx) == pytest.approx(200.0)
    assert _read("attn_global_roofline_pct", ctx) == pytest.approx(50.0)
    # busy 1.5 s in all; the mixer's: the update, the scan, the projection
    assert _read("ssm_share_pct", ctx) == pytest.approx(
        100 * (dec + pre / 2 + 0.5) / 1.5)


def test_a_program_without_the_spans_or_the_scopes_reads_nothing(monkeypatch):
    """On the parent's trace (no ``state_rows``, no ``ssm_tokens``, no
    ``ssm_mixer`` scope) every new reader returns ``None`` and raises
    nothing."""
    bare = [Span("serve.iter", 0.0, 10.0, {"row_pages": 9, "kv_tokens": 90},
                 1),
            Span("serve.prefill.dispatch", 1.0, 1.1,
                 {"rid": 1, "start": 0, "tokens": 9, "final": 1}, 1),
            Span("serve.decode.dispatch", 5.0, 6.0,
                 {"rows": 3, "padded_rows": 16, "table_width": 8,
                  "kv_tokens": 90}, 1)]
    monkeypatch.setattr(es, "capture_for", lambda ctx, trace_root=None: {
        "spans": bare, "window": (0.0, 10.0), "load_s": 0.0, "memo": {}})
    ops = [Event("%fusion.1 = f32[8]{0} fusion(f32[8] %a)", 1.0, 2.0,
                 "fusion")]
    for path in (None, "made-up"):
        monkeypatch.setattr(laguna_spans, "xplane_path",
                            lambda ctx, trace_root=None, path=path: path)
        monkeypatch.setattr(laguna_spans, "op_scopes",
                            lambda p, stat="tf_op": {ops[0].name: "jit(x)/mul"})
        ctx = {"trace": tr.Trace([DeviceTrace("/device:TPU:0", ops, [])],
                                 []),
               "window": (0.0, 10.0), "config": _config(), "peaks": PEAKS,
               "counters": {}}
        for name in NEW:
            assert _read(name, ctx) is None, name


def _rehearse(trace):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--bench", BENCH, "--allow-cpu-rehearsal", "--workload",
         "serve.tiny-falconh1", "--seed", "3000000123", "--seconds", "2",
         "--trace", str(trace)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-2000:]
    return [json.loads(x) for x in p.stdout.strip().splitlines()]


def test_the_rehearsal_is_correct_and_leaves_the_new_out_without_an_error():
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


def test_a_state_slot_left_dirty_is_not_correct(capsys, monkeypatch):
    """The timed path with a row's first chunk entering on what the slot's
    last row left there (the zeroing taken out) serves tokens whose
    reference logits lie below the reference's best by more than the limit
    (a sound program: 0): slots are reused all through the run."""
    import jax

    from marlin_tpu.models import hybrid

    monkeypatch.setattr(hybrid, "_enter_state",
                        lambda fresh, state, tail: (state, tail))
    jax.clear_caches()
    try:
        rc = run.main(["--bench", BENCH, "--allow-cpu-rehearsal",
                       "--workload", "serve.tiny-falconh1", "--seed", "11",
                       "--seconds", "1", "--trace", "0"])
    finally:
        jax.clear_caches()
    lines = [json.loads(x)
             for x in capsys.readouterr().out.strip().splitlines()]
    assert rc == 0 and lines[-1]["correct"] is False
    gap = next(n for n in lines if n.get("name") == "served_logit_gap")
    assert gap["value"] > gap["limit"]


def test_the_configuration_keeps_every_published_number():
    """Every number of the catalog's ``config`` under the same key, but for
    ``num_hidden_layers``; no width, head count or vocabulary row cut."""
    cfg = _config()
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(catalog):
        pytest.skip("no catalog here")
    row = next(json.loads(x) for x in open(catalog)
               if '"Falcon-H1-34B-Instruct"' in x)
    assert cfg["source"] == row["source_url"]
    assert cfg["reduced"] == ["num_hidden_layers"]
    for key, value in row["config"].items():
        if key in cfg["reduced"]:
            assert cfg["source_values"][key] == value and cfg[key] < value
        else:
            assert cfg[key] == value, key
    assert 4 <= cfg["num_hidden_layers"] <= 6
    assert cfg["layer_types"] == ["full_attention"] * cfg["num_hidden_layers"]
    assert cfg["engine"]["max_batch"] == 64
    assert cfg["engine"]["state_slots"] == 65
    assert cfg["engine"]["prefill_chunk"] % cfg["mamba_chunk_size"] == 0
    for key in ("deployment", "assumed", "departures", "weights", "check"):
        assert cfg[key], key
    traffic = json.load(open(os.path.join(ROOT, "benchmarks", "traffic",
                                          "closed-chat64.json")))
    assert traffic["arrival"] == {"kind": "closed", "callers": 64}
    assert traffic["shared_prefix"] is None
    assert traffic["prompt_len"] == {"dist": "lognormal", "median": 384,
                                     "sigma": 1.0, "min": 32, "max": 4096}
    assert traffic["output_len"] == {"dist": "lognormal", "median": 256,
                                     "sigma": 0.6, "min": 32, "max": 1024}
    assert (traffic["max_total_len"], traffic["pool"], traffic["strata"],
            traffic["temperature"]) == (5120, 128, 4, 0)
    assert traffic["ramp_s"] >= 15
    # every (prompt, output) pair fits a bucket and the pool holds them all
    from benchmarks.generators import requests as gen

    sizes = gen.plan(traffic, 1, cfg)["sizes"]
    buckets = cfg["engine"]["buckets"]
    page = cfg["engine"]["page_len"]
    assert all(any(p <= b[0] and o <= b[1] for b in buckets)
               for p, o in sizes)
    assert sum(-(-(p + o - 1) // page) for p, o in sizes) \
        < cfg["engine"]["num_pages"]


def test_the_benchmark_lists_the_cell_and_the_four():
    """Membership, not position: later PRs append."""
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    entry = next(c for c in bench["configs"]
                 if c["name"] == "falcon-h1-34b-l6")
    assert entry["reduced"] == ["num_hidden_layers"]
    assert entry["file"] == "benchmarks/configs/falcon-h1-34b-l6.json"
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == ("falcon-h1-34b-l6", "closed-chat64", 1)
    lists = {m["name"]: m.get("workloads") for m in
             bench["end_to_end"] + bench["per_layer"]}
    for name in NEW:
        assert CELL in lists[name]
        assert os.path.isfile(os.path.join(ROOT, "benchmarks",
                                           "layer_metrics", name + ".py"))
    for name in ("tokens_s", "itl_p95_ms", "device_idle_pct.serve",
                 "decode_step_ms", "prefill_share_pct", "rows_per_step",
                 "rows_per_dispatch", "idle_pct.prefill", "idle_pct.decode",
                 "idle_pct.unattributed", "queue_wait_ms", "kv_filled_pct",
                 "attn_global_roofline_pct"):
        assert CELL in lists[name], name
    # the schedule reader's note is quadratic in spans; the others read
    # MPT's shapes, an expert layer, a window or a latent cache
    for name in ("idle_pct.schedule", "kv_reserved_pct", "attn_roofline_pct",
                 "decode_kv_useful_pct", "attn_window_roofline_pct",
                 "kv_window_pages_pct", "moe_share_pct", "moe_roofline_pct",
                 "mla_decode_roofline_pct", "prefix_hit_pct"):
        assert CELL not in lists[name], name
