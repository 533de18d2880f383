"""The ``serve_solaropen2`` driver and its readers: the cost functions by
hand, the readers' arithmetic on made-up spans and device operations, the
CPU rehearsal of the tiny cell (correct; not correct with a hit entered from
zeros, the snapshot's copy taken out), and the committed configuration
against the catalog's rules."""

import json
import os
import subprocess
import sys

import pytest

from benchmarks import costs_laguna, costs_solaropen2 as costs, \
    engine_spans as es, laguna_spans, run, trace_reduce as tr
from benchmarks.engine_spans import Span
from benchmarks.trace_reduce import DeviceTrace, Event

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
BENCH = os.path.join(HERE, "rehearsal", "BENCHMARK.solaropen2.json")
CELL = "serve.solaropen2-reason128"
NEW = ("kda_share_pct", "kda_decode_roofline_pct", "kda_prefill_roofline_pct")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
STATE, TAIL = 64 * 128 * 128 * 4, 3 * 24576 * 2


def _config(name="solar-open2-ep8-l4"):
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           name + ".json")) as f:
        return json.load(f)


def _spans():
    """One iteration 0..10 on line 1: an admission that hit 2048 of 2432
    tokens, a chunk of 384 valid tokens dispatched 1.0..1.1, one decode
    dispatch 5..6 over 120 live rows whose result lands 6..6.5."""
    spans = [
        Span("serve.iter", 0.0, 10.0, {"row_pages": 1800, "pages_total": 3584,
                                       "kv_tokens": 400000,
                                       "state_slots": 128, "state_rows": 120,
                                       "snapshot_slots": 64,
                                       "snapshots_held": 16}, 1),
        Span("serve.admit", 0.5, 0.6, {"rid": 2, "prompt_tokens": 2432,
                                       "shared_tokens": 2048,
                                       "snapshot_tokens": 2048}, 1),
        Span("serve.prefill.dispatch", 1.0, 1.1,
             {"rid": 2, "start": 2048, "tokens": 384, "kda_tokens": 384,
              "width": 512, "final": 1}, 1),
        Span("serve.decode.dispatch", 5.0, 6.0,
             {"rows": 120, "padded_rows": 128, "table_width": 32,
              "global_table_width": 32, "window_table_width": 0,
              "global_kv_pages": 1500, "window_kv_pages": 0,
              "state_rows": 120, "kv_tokens": 380000}, 1),
        Span("serve.decode.sync", 6.0, 6.5,
             {"moe_assignments": 4 * 120 * 8, "moe_local_assignments": 480,
              "moe_experts_touched": 150}, 1)]
    spans.sort(key=lambda s: (s.start, -s.end))
    return spans


def _ctx(monkeypatch, ops=(), scopes=None, config=None):
    monkeypatch.setattr(es, "capture_for", lambda ctx, trace_root=None: {
        "spans": _spans(), "window": (0.0, 10.0), "load_s": 0.0, "memo": {}})
    monkeypatch.setattr(laguna_spans, "xplane_path",
                        lambda ctx, trace_root=None: "made-up")
    monkeypatch.setattr(laguna_spans, "op_scopes",
                        lambda path, stat="tf_op": dict(scopes or {}))
    devices = [DeviceTrace("/device:TPU:0", list(ops), [])]
    return {"trace": tr.Trace(devices if ops else [], []),
            "window": (0.0, 10.0), "config": config or _config(),
            "peaks": PEAKS, "counters": {}}


def _read(metric, ctx):
    return run.load_module("layer_metrics", metric).read(ctx)


def test_costs_by_hand():
    cfg = _config()
    assert costs.kda_layers(cfg) == 3
    # a kind of layer that owns no page: ONE layer holds keys and values
    assert costs_laguna.layers_of(cfg, "full_attention") == 1
    assert costs_laguna.kv_page_bytes(cfg) == 2 * 256 * 8 * 128 * 2 == 1 << 20
    assert costs_laguna.expert_bytes(cfg) == 3 * 4096 * 1280 * 2 == 31457280
    assert costs.state_bytes(cfg) == STATE == 4194304
    assert costs.conv_dim(cfg) == 3 * 64 * 128 == 24576
    assert costs.tail_bytes(cfg) == TAIL == 147456
    assert costs.slot_bytes(cfg) == 3 * (STATE + TAIL) == 13025280
    # as much as 3180 tokens of this model's KV (4096 B a token, one layer in
    # four): a snapshot is twelve and a half pages of 256
    assert 13025280 // (2 * 8 * 128 * 2) == 3180
    assert costs.kda_decode_least_seconds(120, cfg, PEAKS) \
        == pytest.approx(120 * 3 * 2 * (STATE + TAIL) / 819e9)
    assert costs.scan_token_flops(cfg) == 2 * 64 * (
        2 * 40 * 128 + 32 * 256 + 3 * 128 * 128 + 64 * 128)
    assert costs.scan_token_bytes(cfg) == 24576 * 2 + 2 * 4 * 64 * 128
    least = costs.kda_prefill_least_seconds(384, 1, cfg, PEAKS)
    assert least["bound"] == "memory"  # a chunk's state in and out, decays
    assert least["seconds"] == pytest.approx(
        3 * (384 * 114688 + 2 * STATE) / 819e9)
    assert least["compute_s"] == pytest.approx(
        384 * 3 * costs.scan_token_flops(cfg) / 197e12)


def test_the_counter_readers_on_made_up_spans(monkeypatch):
    ctx = _ctx(monkeypatch)
    assert _read("snapshot_slots_filled_pct", ctx) == pytest.approx(25.0)
    assert _read("state_slots_filled_pct", ctx) == pytest.approx(93.75)
    assert _read("prefix_hit_pct", ctx) == pytest.approx(100 * 2048 / 2432)
    assert _read("kv_filled_pct", ctx) == pytest.approx(
        100 * 400000 / (1800 * 256))
    # 40 held experts x 4 expert layers a landing; an eighth of the picks
    assert _read("moe_experts_touched_pct", ctx) == pytest.approx(
        100 * 150 / 160)
    assert _read("moe_local_assign_pct", ctx) == pytest.approx(12.5)
    for name in NEW + ("attn_global_roofline_pct", "moe_roofline_pct"):
        assert _read(name, ctx) is None  # no device in the trace


def test_the_rooflines_read_100_at_exactly_their_bounds(monkeypatch):
    cfg = _config()
    dec = costs.kda_decode_least_seconds(120, cfg, PEAKS)
    pre = costs.kda_prefill_least_seconds(384, 1, cfg, PEAKS)["seconds"]
    attn = 1500 * (1 << 20) / 819e9
    moe = costs_laguna.moe_least_seconds(150, 480, cfg, PEAKS)
    assert moe["bound"] == "memory"

    def op(name, start, seconds, module=""):
        return Event(f"%{name} = f32[128,1,8192]{{2,1,0}} custom-call("
                     f"f32[193,128,8192] %a)", start, start + seconds,
                     "custom-call")

    ops = [op("_delta_decode_update_call.3", 6.1, dec / 2),
           op("fusion.tails", 6.6, dec / 2),          # under kda_update
           op("fusion.scan.1", 1.0, pre / 4),         # under kda_scan
           op("fusion.scan.2", 1.2, pre / 4),
           op("fusion.w_qkv", 2.0, 0.5),              # under attn_kda only
           op("_paged_decode_attention_call.2", 7.0, 2 * attn),
           op("fusion.other", 8.0, 1.0 - dec - pre / 2 - 2 * attn)]
    jit = "jit(_lm_decode_paged_spec_jit)/jit(main)"
    scopes = {
        ops[0].name: f"{jit}/attn_kda/kda_update/pallas_call",
        ops[1].name: f"{jit}/attn_kda/kda_update/scatter",
        ops[2].name: "jit(p)/jit(main)/attn_kda/kda_scan/dot_general",
        ops[3].name: "jit(p)/jit(main)/attn_kda/kda_scan/exp",
        ops[4].name: "jit(p)/jit(main)/attn_kda/dot_general",
        ops[6].name: "jit(p)/jit(main)/moe_experts/dot_general"}
    ctx = _ctx(monkeypatch, ops, scopes)
    assert _read("kda_decode_roofline_pct", ctx) == pytest.approx(100.0)
    assert _read("kda_prefill_roofline_pct", ctx) == pytest.approx(200.0)
    assert _read("attn_global_roofline_pct", ctx) == pytest.approx(50.0)
    # busy 1.5 s in all; the mixer's: the update, the scan, the projection
    assert _read("kda_share_pct", ctx) == pytest.approx(
        100 * (dec + pre / 2 + 0.5) / 1.5)


def test_a_program_without_the_spans_or_the_scopes_reads_nothing(monkeypatch):
    """On the parent's trace (no ``kda_tokens``, no ``attn_kda`` scope;
    Olmo-Hybrid's has ``state_rows``, ``delta_tokens`` and the update
    kernel's name) every new reader returns ``None`` and raises nothing,
    under any cell's configuration."""
    bare = [Span("serve.iter", 0.0, 10.0, {"row_pages": 9, "kv_tokens": 90,
                                           "state_slots": 4, "state_rows": 3},
                 1),
            Span("serve.prefill.dispatch", 1.0, 1.1,
                 {"rid": 1, "start": 0, "tokens": 9, "delta_tokens": 9,
                  "final": 1}, 1),
            Span("serve.decode.dispatch", 5.0, 6.0,
                 {"rows": 3, "padded_rows": 16, "table_width": 8,
                  "state_rows": 3, "kv_tokens": 90}, 1)]
    monkeypatch.setattr(es, "capture_for", lambda ctx, trace_root=None: {
        "spans": bare, "window": (0.0, 10.0), "load_s": 0.0, "memo": {}})
    ops = [Event("%_delta_decode_update_call.1 = f32[8]{0} custom-call("
                 "f32[8] %a)", 1.0, 2.0, "custom-call")]
    for path in (None, "made-up"):
        monkeypatch.setattr(laguna_spans, "xplane_path",
                            lambda ctx, trace_root=None, path=path: path)
        monkeypatch.setattr(
            laguna_spans, "op_scopes", lambda p, stat="tf_op": {
                ops[0].name: "jit(x)/linear_attn/delta_update/pallas_call"})
        for config in (_config(), _config("olmo-hybrid-7b-l16")):
            ctx = {"trace": tr.Trace([DeviceTrace("/device:TPU:0", ops, [])],
                                     []),
                   "window": (0.0, 10.0), "config": config, "peaks": PEAKS,
                   "counters": {}}
            for name in NEW:
                assert _read(name, ctx) is None, name


def _rehearse(trace):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--bench", BENCH, "--allow-cpu-rehearsal", "--workload",
         "serve.tiny-solaropen2", "--seed", "3000000123", "--seconds", "2",
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
    shared = next(n for n in lines
                  if n.get("name") == "sampled_requests_shared")
    assert shared["ok"] and shared["value"] >= shared["limit"] == 8
    layer = next(n for n in lines if n.get("note")
                 == "cpu_rehearsal_layer_values_not_measurements")
    assert set(layer) == {"note", "rows_per_step"}


def test_a_hit_entered_from_zeros_is_not_correct(capsys, monkeypatch):
    """The timed path with the snapshot's copy into the row's slot taken out
    (a hit then enters on whatever its slot holds) serves tokens whose
    reference logits lie below the reference's best by more than the limit
    (a sound program: 0): nearly every request of the run is a hit."""
    import jax

    from marlin_tpu.serving import kvpool

    real = kvpool.PagedKVPool.copy_state

    def only_taking(self, src, dst):   # snapshots are taken, never entered
        if dst >= self.state_slots:
            real(self, src, dst)

    monkeypatch.setattr(kvpool.PagedKVPool, "copy_state", only_taking)
    jax.clear_caches()
    try:
        rc = run.main(["--bench", BENCH, "--allow-cpu-rehearsal",
                       "--workload", "serve.tiny-solaropen2", "--seed", "11",
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
    the three cuts of the chip's share; no width, head count or state size
    cut; the traffic is the issue's letter for number."""
    cfg = _config()
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(catalog):
        pytest.skip("no catalog here")
    row = next(json.loads(x) for x in open(catalog)
               if '"Solar-Open2-250B"' in x)
    assert cfg["source"] == row["source_url"]
    assert cfg["reduced"] == ["num_hidden_layers", "n_routed_experts",
                              "vocab_size"]
    for key, value in row["config"].items():
        if key in cfg["reduced"]:
            assert cfg["source_values"][key] == value and cfg[key] < value
        else:
            assert cfg[key] == value, key
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"],
            cfg["vocab_size"]) == (4, 40, 24576)
    share = cfg["deployment_share"]
    assert share["chips_sharing_a_layer"] * cfg["n_routed_experts"] \
        == share["experts_total"] == 320
    assert share["pipeline_stages"] * cfg["num_hidden_layers"] == 48
    assert share["chips_sharing_a_layer"] * cfg["vocab_size"] == 196608
    eng = cfg["engine"]
    assert eng["max_batch"] == 128 and eng["prefix_cache"] is True
    assert eng["state_slots"] == 129 and eng["snapshot_slots"] >= 8
    assert 2048 % eng["prefill_chunk"] == 0
    assert eng["prefill_chunk"] % cfg["kda_chunk_size"] == 0
    for key in ("deployment", "assumed", "departures", "weights", "check",
                "guarantees"):
        assert cfg[key], key
    traffic = json.load(open(os.path.join(ROOT, "benchmarks", "traffic",
                                          "closed-reason128.json")))
    assert traffic["generator"] == "requests"
    assert traffic["arrival"] == {"kind": "closed", "callers": 128}
    assert traffic["shared_prefix"] == {"count": 8, "length": 2048,
                                        "share": 1.0}
    assert traffic["prompt_len"] == {"dist": "lognormal", "median": 2432,
                                     "sigma": 0.2, "min": 2112, "max": 4096}
    assert traffic["output_len"] == {"dist": "lognormal", "median": 1024,
                                     "sigma": 0.6, "min": 256, "max": 4096}
    assert (traffic["max_total_len"], traffic["pool"], traffic["strata"],
            traffic["temperature"]) == (8192, 128, 4, 0)
    assert traffic["ramp_s"] >= 30
    # every (prompt, output) pair fits the bucket; the pool holds the whole
    # first wave (128 misses, every pair of the pool once, nothing shared:
    # admission allocates a request in full) with room for the cache
    from benchmarks.generators import requests as gen

    sizes = gen.plan(traffic, 1, cfg)["sizes"]
    page = eng["page_len"]
    assert all(any(p <= b[0] and o <= b[1] for b in eng["buckets"])
               for p, o in sizes)
    pages = [-(-(p + o - 1) // page) for p, o in sizes]
    assert sum(pages) + 8 * 2048 // page < eng["num_pages"]
    assert 2048 % page == 0 and traffic["shared_prefix"]["count"] \
        <= eng["snapshot_slots"]


def test_the_benchmark_lists_the_cell_and_the_three():
    """Membership, not position: later PRs append."""
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    entry = next(c for c in bench["configs"]
                 if c["name"] == "solar-open2-ep8-l4")
    assert entry["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                "vocab_size"]
    assert entry["file"] == "benchmarks/configs/solar-open2-ep8-l4.json"
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == ("solar-open2-ep8-l4", "closed-reason128", 1)
    assert len(cell["why"]) <= 200
    lists = {m["name"]: m.get("workloads") for m in
             bench["end_to_end"] + bench["per_layer"]}
    for name in NEW:
        assert CELL in lists[name]
        assert os.path.isfile(os.path.join(ROOT, "benchmarks",
                                           "layer_metrics", name + ".py"))
    for name in ("tokens_s", "itl_p95_ms", "device_idle_pct.serve",
                 "decode_step_ms", "prefill_share_pct", "rows_per_step",
                 "rows_per_dispatch", "idle_pct.prefill", "idle_pct.decode",
                 "idle_pct.unattributed", "queue_wait_ms",
                 "iter_device_ms_p95", "chunk_iters_pct", "launch_slack_ms",
                 "prefill_chunk_ms", "prefill_us_per_token",
                 "prefill_fill_pct", "moe_share_pct", "moe_roofline_pct",
                 "moe_experts_touched_pct", "moe_local_assign_pct",
                 "attn_global_roofline_pct", "kv_filled_pct",
                 "prefix_hit_pct", "state_slots_filled_pct",
                 "snapshot_slots_filled_pct"):
        assert CELL in lists[name], name
    # the schedule reader's note is quadratic in spans; kv_reserved_pct
    # counts a shared page once a row; the others read MPT's shapes, a
    # window, a latent cache or another family's mixer
    for name in ("idle_pct.schedule", "kv_reserved_pct", "attn_roofline_pct",
                 "decode_kv_useful_pct", "attn_window_roofline_pct",
                 "kv_window_pages_pct", "mla_decode_roofline_pct",
                 "ssm_share_pct", "gdn_share_pct", "gdn_decode_roofline_pct",
                 "conv_share_pct", "lightning_share_pct"):
        assert CELL not in lists[name], name
