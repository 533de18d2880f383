"""The ``serve_lfm2`` driver and its readers: the cost functions by hand,
the readers' arithmetic on made-up spans and device operations (the shared
``moe_*`` and ``attn_global_roofline_pct`` readers price THIS configuration
from its own keys), the CPU rehearsal of the tiny cell (correct; not correct
with a hit entered from zeros, the snapshot's copy taken out), and the
committed configuration against the catalog's rules."""

import json
import os
import subprocess
import sys

import pytest

from benchmarks import costs_laguna, costs_lfm2 as costs, \
    engine_spans as es, laguna_spans, run, trace_reduce as tr
from benchmarks.engine_spans import Span
from benchmarks.trace_reduce import DeviceTrace, Event

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
BENCH = os.path.join(HERE, "rehearsal", "BENCHMARK.lfm2.json")
CELL = "serve.lfm2-agent96"
NEW = ("conv_share_pct", "conv_decode_roofline_pct",
       "conv_prefill_roofline_pct")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
WEIGHTS, TAIL = (2048 * 6144 + 2048 * 2048) * 2, 2 * 2048 * 2


def _config():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "lfm2-8b-a1b-l16.json")) as f:
        return json.load(f)


def _spans():
    """One iteration 0..10 on line 1: an admission that hit 2048 of 2438
    tokens, a chunk of 390 valid tokens dispatched 1.0..1.1, one decode
    dispatch 5..6 over 94 live rows, its landing 6..7."""
    spans = [
        Span("serve.iter", 0.0, 10.0, {"row_pages": 1100, "pages_total": 1536,
                                       "kv_tokens": 270000, "state_slots": 96,
                                       "state_rows": 96,
                                       "snapshot_slots": 256,
                                       "snapshots_held": 64}, 1),
        Span("serve.admit", 0.5, 0.6, {"rid": 2, "prompt_tokens": 2438,
                                       "shared_tokens": 2048,
                                       "snapshot_tokens": 2048}, 1),
        Span("serve.prefill.dispatch", 1.0, 1.1,
             {"rid": 2, "start": 2048, "tokens": 390, "conv_tokens": 390,
              "width": 512, "final": 1}, 1),
        Span("serve.decode.dispatch", 5.0, 6.0,
             {"rows": 94, "padded_rows": 96, "table_width": 24,
              "global_table_width": 24, "window_table_width": 0,
              "global_kv_pages": 1100, "window_kv_pages": 0,
              "state_rows": 94, "kv_tokens": 270000}, 1),
        Span("serve.decode.sync", 6.0, 7.0,
             {"moe_assignments": 94 * 4 * 14,
              "moe_local_assignments": 94 * 4 * 14,
              "moe_experts_touched": 14 * 32 - 7}, 1)]
    spans.sort(key=lambda s: (s.start, -s.end))
    return spans


def _ctx(monkeypatch, ops=(), scopes=None, modules=()):
    monkeypatch.setattr(es, "capture_for", lambda ctx, trace_root=None: {
        "spans": _spans(), "window": (0.0, 10.0), "load_s": 0.0, "memo": {}})
    monkeypatch.setattr(laguna_spans, "xplane_path",
                        lambda ctx, trace_root=None: "made-up")
    monkeypatch.setattr(laguna_spans, "op_scopes",
                        lambda path, stat="tf_op": dict(scopes or {}))
    devices = [DeviceTrace("/device:TPU:0", list(ops), list(modules))]
    return {"trace": tr.Trace(devices if ops else [], []),
            "window": (0.0, 10.0), "config": _config(), "peaks": PEAKS,
            "counters": {}}


def _read(metric, ctx):
    return run.load_module("layer_metrics", metric).read(ctx)


def test_costs_by_hand():
    cfg = _config()
    assert costs.conv_layers(cfg) == 12
    assert costs.mixer_params(cfg) == 16_777_216      # the issue's 16.78 M
    assert costs.weight_bytes(cfg) == WEIGHTS == 33_554_432
    assert costs.tail_bytes(cfg) == TAIL == 8192
    assert costs.slot_bytes(cfg) == 12 * TAIL == 98_304
    assert costs.conv_decode_least_seconds(1, 94, cfg, PEAKS) \
        == pytest.approx(12 * (WEIGHTS + 94 * 2 * TAIL) / 819e9)
    assert costs.token_flops(cfg) == 2 * 16_777_216 + 2 * 3 * 2048 + 2 * 2048
    assert costs.conv_prefill_least_seconds(390, cfg, PEAKS) \
        == pytest.approx(390 * 12 * costs.token_flops(cfg) / 197e12)
    # the shared readers' prices, from THIS configuration's keys: FOUR
    # layers hold keys and values, 524,288 B a live page and layer; an
    # expert touched is 22.02 MB; 14 expert layers of 32 experts
    assert costs_laguna.layers_of(cfg, "full_attention") == 4
    assert costs_laguna.kv_page_bytes(cfg) == 2 * 256 * 8 * 64 * 2 == 524_288
    assert costs_laguna.expert_bytes(cfg) == 3 * 2048 * 1792 * 2 \
        == 22_020_096
    assert costs_laguna.expert_flops(cfg) == 6 * 2048 * 1792
    n = cfg["num_hidden_layers"]
    assert cfg["mlp_layer_types"][:n].count("sparse") == 14
    # a page id over the four layers, against a slot: a twentieth
    assert 4 * 524_288 == 2_097_152 and 2_097_152 // 98_304 == 21


def test_the_counter_readers_on_made_up_spans(monkeypatch):
    ctx = _ctx(monkeypatch)
    assert _read("state_slots_filled_pct", ctx) == pytest.approx(100.0)
    assert _read("prefix_hit_pct", ctx) == pytest.approx(100 * 2048 / 2438)
    assert _read("kv_filled_pct", ctx) == pytest.approx(
        100 * 270000 / (1100 * 256))
    assert _read("moe_experts_touched_pct", ctx) == pytest.approx(
        100 * (14 * 32 - 7) / (14 * 32))
    for name in NEW + ("attn_global_roofline_pct", "moe_roofline_pct"):
        assert _read(name, ctx) is None  # no device in the trace


def test_the_rooflines_read_100_at_exactly_their_bounds(monkeypatch):
    cfg = _config()
    dec = costs.conv_decode_least_seconds(1, 94, cfg, PEAKS)
    pre = costs.conv_prefill_least_seconds(390, cfg, PEAKS)
    attn = 1100 * 4 * 524_288 / 819e9
    moe = (14 * 32 - 7) * 22_020_096 / 819e9

    def op(name, start, seconds):
        return Event(f"%{name} = bf16[96,2048]{{1,0}} fusion(bf16[96,2048] "
                     f"%a)", start, start + seconds, "fusion")

    ops = [op("fusion.w_in.d", 5.1, dec / 2),        # decode, short_conv
           op("fusion.tails.d", 5.1 + dec, dec / 2),
           op("fusion.w_in.p", 1.0, pre / 4),        # prefill, short_conv
           op("fusion.w_out.p", 1.0 + pre, pre / 4),
           op("_paged_decode_attention_call.2", 7.0, 2 * attn),
           op("gmm.3", 8.0, 4 * moe),
           op("fusion.other", 9.0, 0.5)]
    modules = [Event("jit__lm_prefill_paged_spec_jit(1)", 0.9, 2.9, "m"),
               Event("jit__lm_decode_paged_spec_jit(2)", 5.0, 9.9, "m")]
    scopes = {
        ops[0].name: "jit(d)/jit(main)/short_conv/dot_general",
        ops[1].name: "jit(d)/jit(main)/short_conv/scatter",
        ops[2].name: "jit(p)/jit(main)/short_conv/dot_general",
        ops[3].name: "jit(p)/jit(main)/short_conv/dot_general",
        ops[6].name: "jit(p)/jit(main)/ffn_dense/dot_general"}
    ctx = _ctx(monkeypatch, ops, scopes, modules)
    assert _read("conv_decode_roofline_pct", ctx) == pytest.approx(100.0)
    assert _read("conv_prefill_roofline_pct", ctx) == pytest.approx(200.0)
    assert _read("attn_global_roofline_pct", ctx) == pytest.approx(50.0)
    assert _read("moe_roofline_pct", ctx) == pytest.approx(25.0)
    busy = dec + pre / 2 + 2 * attn + 4 * moe + 0.5
    assert _read("conv_share_pct", ctx) == pytest.approx(
        100 * (dec + pre / 2) / busy)


def test_a_program_without_the_spans_or_the_scope_reads_nothing(monkeypatch):
    """On the parent's trace (no ``conv_tokens``, no ``short_conv`` scope;
    Olmo-Hybrid's has ``state_rows`` and ``delta_tokens``) every new reader
    returns ``None`` and raises nothing, under any cell's configuration."""
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
    ops = [Event("%fusion.1 = f32[8]{0} fusion(f32[8] %a)", 1.0, 2.0,
                 "fusion")]
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "olmo-hybrid-7b-l16.json")) as f:
        olmo = json.load(f)
    for path in (None, "made-up"):
        monkeypatch.setattr(laguna_spans, "xplane_path",
                            lambda ctx, trace_root=None, path=path: path)
        monkeypatch.setattr(laguna_spans, "op_scopes",
                            lambda p, stat="tf_op": {ops[0].name: "jit(x)/mul"})
        for config in (_config(), olmo):
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
         "serve.tiny-lfm2", "--seed", "3000000123", "--seconds", "2",
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
    gap = next(n for n in lines if n.get("name") == "served_logit_gap")
    assert gap["ok"] and {"gap_max", "gap_p99", "gap_mean"} <= set(gap)
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
                       "--workload", "serve.tiny-lfm2", "--seed", "11",
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
    ``num_hidden_layers``; no width, head count, expert or vocabulary row
    cut."""
    cfg = _config()
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(catalog):
        pytest.skip("no catalog here")
    row = next(json.loads(x) for x in open(catalog)
               if '"name": "LFM2-8B-A1B"' in x)
    assert cfg["source"] == row["source_url"]
    assert cfg["reduced"] == ["num_hidden_layers"]
    for key, value in row["config"].items():
        if key in cfg["reduced"]:
            assert cfg["source_values"][key] == value and cfg[key] < value
        else:
            assert cfg[key] == value, key
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"],
            cfg["intermediate_size"], cfg["num_experts"],
            cfg["moe_intermediate_size"], cfg["num_experts_per_tok"],
            cfg["vocab_size"], cfg["conv_L_cache"]) \
        == (2048, 32, 8, 64, 7168, 32, 1792, 4, 65536, 3)
    held = cfg["layer_types"][:cfg["num_hidden_layers"]]
    assert held == ["conv", "conv", "full_attention", "conv"] * 4
    assert cfg["mlp_layer_types"] == ["dense"] * 2 + ["sparse"] * 14
    eng = cfg["engine"]
    assert eng["max_batch"] == 96 and eng["prefix_cache"] is True
    assert eng["state_slots"] == 97 and eng["snapshot_slots"] >= 8
    assert (eng["page_len"], eng["prefill_chunk"]) == (256, 512)
    for key in ("deployment", "assumed", "departures", "weights",
                "guarantees", "check"):
        assert cfg[key], key
    assert cfg["deployment_share"]["chips_sharing_a_layer"] == 1
    for key in ("pre_norm", "w_in_thirds", "convolution", "qk_norm", "rope",
                "router", "tied_head", "sizing"):
        assert cfg["assumed"][key], key
    traffic = json.load(open(os.path.join(ROOT, "benchmarks", "traffic",
                                          "closed-agent96.json")))
    assert traffic["arrival"] == {"kind": "closed", "callers": 96}
    assert traffic["shared_prefix"] == {"count": 8, "length": 2048,
                                        "share": 1.0}
    assert traffic["prompt_len"] == {"dist": "lognormal", "median": 2432,
                                     "sigma": 0.2, "min": 2112, "max": 4096}
    assert traffic["output_len"] == {"dist": "lognormal", "median": 640,
                                     "sigma": 0.6, "min": 128, "max": 2048}
    assert (traffic["max_total_len"], traffic["pool"], traffic["strata"],
            traffic["temperature"]) == (6144, 96, 4, 0)
    # every (prompt, output) pair fits a bucket; the pool holds the 96
    # requests WHOLE with nothing shared (the first wave)
    from benchmarks.generators import requests as gen

    sizes = gen.plan(traffic, 1, cfg)["sizes"]
    page = eng["page_len"]
    assert all(any(p <= b[0] and o <= b[1] for b in eng["buckets"])
               for p, o in sizes)
    assert sum(-(-(p + o - 1) // page) for p, o in sizes) < eng["num_pages"]
    assert 2048 % eng["prefill_chunk"] == 0 and 2048 % page == 0


def test_the_benchmark_lists_the_cell_and_the_three():
    """MEMBERSHIP of the shared metrics' lists (later PRs append), equality
    only for the three that are this configuration's own."""
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    entry = next(c for c in bench["configs"]
                 if c["name"] == "lfm2-8b-a1b-l16")
    assert entry["reduced"] == ["num_hidden_layers"]
    assert entry["file"] == "benchmarks/configs/lfm2-8b-a1b-l16.json"
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == ("lfm2-8b-a1b-l16", "closed-agent96", 1)
    assert "16 of 24" in cell["why"] and "head" in cell["why"]
    lists = {m["name"]: m.get("workloads") for m in
             bench["end_to_end"] + bench["per_layer"]}
    for name in NEW:
        assert lists[name] == [CELL]
        assert os.path.isfile(os.path.join(ROOT, "benchmarks",
                                           "layer_metrics", name + ".py"))
    for name in ("tokens_s", "itl_p95_ms", "device_idle_pct.serve",
                 "decode_step_ms", "prefill_share_pct", "rows_per_step",
                 "rows_per_dispatch", "idle_pct.prefill", "idle_pct.decode",
                 "idle_pct.unattributed", "queue_wait_ms", "kv_filled_pct",
                 "prefix_hit_pct", "state_slots_filled_pct", "moe_share_pct",
                 "moe_roofline_pct", "moe_experts_touched_pct",
                 "attn_global_roofline_pct", "iter_device_ms_p95",
                 "chunk_iters_pct", "launch_slack_ms", "prefill_chunk_ms",
                 "prefill_us_per_token", "prefill_fill_pct"):
        assert CELL in lists[name], name
    # 100 by construction; describes a table; a quadratic note; shared pages
    # counted once a row; cheap slots never fill; other families' readers
    for name in ("moe_local_assign_pct", "attn_grid_live_pct",
                 "idle_pct.schedule", "kv_reserved_pct",
                 "snapshot_slots_filled_pct", "attn_roofline_pct",
                 "attn_window_roofline_pct", "mla_decode_roofline_pct",
                 "ssm_share_pct", "gdn_share_pct"):
        assert CELL not in lists[name], name
