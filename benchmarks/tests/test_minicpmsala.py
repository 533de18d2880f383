"""The ``serve_minicpmsala`` driver and its readers: the cost functions by
hand, the readers' arithmetic on made-up spans and device operations, the CPU
rehearsal of the tiny cell (correct; not correct with dense attention in the
selection's place), and the committed configuration against the catalog's
rules."""

import json
import os
import subprocess
import sys

import pytest

from benchmarks import costs_minicpmsala as costs, engine_spans as es, \
    laguna_spans, minicpmsala_spans as sala, run, trace_reduce as tr
from benchmarks.engine_spans import Span
from benchmarks.trace_reduce import DeviceTrace, Event

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
BENCH = os.path.join(HERE, "rehearsal", "BENCHMARK.minicpmsala.json")
CELL = "serve.minicpm-sala-longdoc16"
NEW = ("sparse_share_pct", "lightning_share_pct",
       "sparse_decode_roofline_pct", "sparse_prefill_roofline_pct",
       "sparse_select_share_pct", "lightning_decode_roofline_pct",
       "lightning_prefill_roofline_pct", "sparse_kv_read_pct")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _config():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "minicpm-sala-l16.json")) as f:
        return json.load(f)


def _spans():
    """One iteration 0..10: a chunk of 300 valid tokens from 32768
    dispatched 1.0..1.1, one decode dispatch 5..6 over 15 live rows at
    ~33.5 k, its landing 6..7 with the counters."""
    spans = [
        Span("serve.iter", 0.0, 10.0, {"row_pages": 2100, "state_rows": 16},
             1),
        Span("serve.prefill.dispatch", 1.0, 1.1,
             {"rid": 2, "start": 32768, "tokens": 300,
              "lightning_tokens": 300, "width": 512, "final": 1}, 1),
        Span("serve.decode.dispatch", 5.0, 6.0,
             {"rows": 15, "padded_rows": 16, "table_width": 136,
              "state_rows": 15, "kv_tokens": 500000}, 1),
        Span("serve.decode.sync", 6.0, 7.0,
             {"sparse_blocks_attended": 15 * 64 * 8,
              "sparse_blocks_held": 15 * 524 * 8, "sparse_rows": 15}, 1)]
    return spans


def _ctx(monkeypatch, ops=(), scopes=None, modules=(), spans=None):
    sala._scopes.cache_clear()
    monkeypatch.setattr(es, "capture_for", lambda ctx, trace_root=None: {
        "spans": _spans() if spans is None else spans,
        "window": (0.0, 10.0), "load_s": 0.0, "memo": {}})
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
    assert costs.layers(cfg, "minicpm4") == 4
    assert costs.layers(cfg, "lightning-attn") == 12
    assert costs.block_bytes(cfg) == 2 * 64 * 128 * 2 == 32_768
    assert costs.state_bytes(cfg) == 32 * 128 * 128 * 4 == 2_097_152
    assert costs.slot_bytes(cfg) == 25_165_824
    # a row at 33 k: 64 blocks x 2 heads x 4 layers x 32 KB = 16.8 MB
    least = costs.sparse_decode_least_seconds(64 * 8, 1, cfg, PEAKS)
    assert least == pytest.approx((64 * 8 * 32768 + 2 * 4 * 32 * 128 * 2)
                                  / 819e9)
    assert costs.lightning_decode_least_seconds(16, cfg, PEAKS) \
        == pytest.approx(16 * 12 * 2 * 2_097_152 / 819e9)
    # dense below dense_len (and where fewer than topk blocks exist), 63
    # whole blocks and the query's own up to itself from it on
    assert costs.selected_pairs(0, 4, cfg) == 1 + 2 + 3 + 4
    assert costs.selected_pairs(8191, 1, cfg) == 8192
    assert costs.selected_pairs(8192, 2, cfg) == (63 * 64 + 1) + (63 * 64 + 2)
    assert costs.selected_pairs(32768 + 63, 1, cfg) == 64 * 64
    assert costs.sparse_prefill_least_seconds(1000, cfg, PEAKS) \
        == pytest.approx(1000 * 4 * 32 * 4 * 128 / 197e12)
    assert costs.scan_token_flops(cfg) == 2 * 32 * (2 * 128 * 128
                                                    + 2 * 128 * 128)
    assert costs.scan_token_bytes(cfg) == 3 * 4096 * 2 + 4 * 4096
    got = costs.lightning_prefill_least_seconds(512, 1, cfg, PEAKS)
    # memory-bound: 21 MB of q, k, v and outputs and 4 MB of state a layer
    # (30.7 us) against 2.1 GFLOP (10.9 us)
    assert got["bound"] == "memory" and got["seconds"] == pytest.approx(
        12 * (512 * 40_960 + 2 * 2_097_152) / 819e9)
    assert got["compute_s"] == pytest.approx(512 * 12 * 4_194_304 / 197e12)


def test_the_counter_readers_on_made_up_spans(monkeypatch):
    ctx = _ctx(monkeypatch)
    assert _read("sparse_kv_read_pct", ctx) == pytest.approx(100 * 64 / 524)
    for name in NEW[:-1]:
        assert _read(name, ctx) is None  # no device in the trace


def test_the_readers_read_100_at_exactly_their_bounds(monkeypatch):
    cfg = _config()
    dec = costs.sparse_decode_least_seconds(15 * 64 * 8, 15, cfg, PEAKS)
    pre = costs.sparse_prefill_least_seconds(
        costs.selected_pairs(32768, 300, cfg), cfg, PEAKS)
    upd = costs.lightning_decode_least_seconds(15, cfg, PEAKS)
    scan = costs.lightning_prefill_least_seconds(300, 1, cfg,
                                                 PEAKS)["seconds"]

    def op(name, start, seconds):
        return Event(f"%{name} = bf16[16,4096]{{1,0}} fusion(bf16[16,4096] "
                     f"%a)", start, start + seconds, "fusion")

    ops = [op("_paged_decode_attention_blocks_call.1", 5.0, 2 * dec),
           op("fusion.select.d", 5.5, dec),
           op("fusion.attend.p", 1.0, 4 * pre),
           op("_lightning_decode_update_call.3", 6.0, upd / 2),
           op("fusion.upd", 6.5, upd / 2),
           op("fusion.scan.p", 2.0, scan),
           op("fusion.other", 9.0, 0.5)]
    modules = [Event("jit__lm_prefill_paged_spec_jit(1)", 0.9, 4.0, "m"),
               Event("jit__lm_decode_paged_spec_jit(2)", 5.0, 8.9, "m")]
    scopes = {
        ops[1].name: "jit(d)/jit(main)/attn_sparse/sparse_select/top_k",
        ops[2].name: "jit(p)/jit(main)/attn_sparse/sparse_attend/dot",
        ops[4].name: "jit(d)/jit(main)/lightning_attn/lightning_update/mul",
        ops[5].name: "jit(p)/jit(main)/lightning_attn/lightning_scan/dot",
        ops[6].name: "jit(p)/jit(main)/ffn_dense/dot_general"}
    ctx = _ctx(monkeypatch, ops, scopes, modules)
    assert _read("sparse_decode_roofline_pct", ctx) == pytest.approx(50.0)
    assert _read("sparse_prefill_roofline_pct", ctx) == pytest.approx(25.0)
    assert _read("lightning_decode_roofline_pct", ctx) == pytest.approx(100.0)
    assert _read("lightning_prefill_roofline_pct", ctx) == pytest.approx(100.0)
    sparse = 3 * dec + 4 * pre
    busy = sparse + upd + scan + 0.5
    assert _read("sparse_share_pct", ctx) == pytest.approx(100 * sparse / busy)
    assert _read("lightning_share_pct", ctx) == pytest.approx(
        100 * (upd + scan) / busy)
    assert _read("sparse_select_share_pct", ctx) == pytest.approx(
        100 * dec / sparse)


def test_a_program_without_the_spans_or_the_scopes_reads_nothing(monkeypatch):
    """On the parent's trace (Olmo-Hybrid's has ``state_rows`` and
    ``delta_tokens``; no sparse counter, no scope, no kernel of this family)
    every new reader returns ``None`` and raises nothing, under any cell's
    configuration, with and without the capture's file."""
    bare = [Span("serve.iter", 0.0, 10.0, {"row_pages": 9, "state_rows": 3},
                 1),
            Span("serve.prefill.dispatch", 1.0, 1.1,
                 {"rid": 1, "start": 0, "tokens": 9, "delta_tokens": 9,
                  "final": 1}, 1),
            Span("serve.decode.dispatch", 5.0, 6.0,
                 {"rows": 3, "padded_rows": 16, "table_width": 8,
                  "state_rows": 3, "kv_tokens": 90}, 1),
            Span("serve.decode.sync", 6.0, 7.0, {"seq": 4}, 1)]
    ops = [Event("%fusion.1 = f32[8]{0} fusion(f32[8] %a)", 1.0, 2.0,
                 "fusion")]
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "olmo-hybrid-7b-l16.json")) as f:
        olmo = json.load(f)
    for path in (None, "made-up"):
        ctx = _ctx(monkeypatch, ops, {ops[0].name: "jit(x)/mul"}, spans=bare)
        monkeypatch.setattr(laguna_spans, "xplane_path",
                            lambda ctx, trace_root=None, path=path: path)
        for config in (_config(), olmo):
            for name in NEW:
                assert _read(name, dict(ctx, config=config)) is None, name


def _rehearse(trace, *more):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--bench", BENCH, "--allow-cpu-rehearsal", "--workload",
         "serve.tiny-minicpmsala", "--seed", "3000000123", "--seconds", "2",
         "--trace", str(trace), *more],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-2000:]
    return [json.loads(x) for x in p.stdout.strip().splitlines()]


def test_the_rehearsal_is_correct_and_leaves_the_new_out_without_an_error():
    lines = _rehearse(1)
    assert lines[-1]["correct"] is True and lines[-1]["failed"] == 0
    compared = {x["name"]: x for x in lines if x.get("note") == "compared"}
    assert compared["served_logit_gap"]["value"] < 1e-4
    assert compared["sampled_requests_shared"]["ok"]
    layer = next(x for x in lines if x.get("note")
                 == "cpu_rehearsal_layer_values_not_measurements")
    assert not set(NEW) & set(layer)   # no device, no capture: left out
    samples = next(x for x in lines if x.get("note") == "serve_samples")
    assert samples["counters"]["other_records"]["sparse"] > 0


def test_dense_attention_in_the_selections_place_is_not_correct(capsys,
                                                                monkeypatch):
    import dataclasses

    from benchmarks.drivers import serve_olmohybrid

    real = serve_olmohybrid.model_spec
    monkeypatch.setattr(
        serve_olmohybrid, "model_spec",
        lambda cfg: dataclasses.replace(real(cfg), sparse=dataclasses.replace(
            real(cfg).sparse, dense_len=10 ** 6)))
    rc = run.main(["--bench", BENCH, "--allow-cpu-rehearsal", "--workload",
                   "serve.tiny-minicpmsala", "--seed", "3000000123",
                   "--seconds", "2", "--trace", "0"])
    out = [json.loads(x) for x in capsys.readouterr().out.strip().splitlines()]
    assert rc == 0 and out[-1]["correct"] is False


def test_the_configuration_keeps_every_published_number():
    cfg = _config()
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(catalog):
        pytest.skip("no catalog here")
    row = next(json.loads(x) for x in open(catalog)
               if '"name": "MiniCPM-SALA"' in x)
    assert cfg["source"] == row["source_url"]
    assert cfg["reduced"] == ["num_hidden_layers", "mixer_types"]
    for key, value in row["config"].items():
        if key in cfg["reduced"]:
            assert cfg["source_values"][key] == value and cfg[key] != value
        else:
            assert cfg[key] == value, key
    first = cfg["first_layer"]
    assert cfg["mixer_types"] == row["config"]["mixer_types"][first:first + 16]
    assert cfg["mixer_types"].count("minicpm4") == 4
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"],
            cfg["intermediate_size"], cfg["vocab_size"], cfg["lightning_nh"],
            cfg["lightning_head_dim"]) == (4096, 32, 2, 128, 16384, 73448,
                                           32, 128)
    assert cfg["sparse_config"] == {
        "kernel_size": 32, "kernel_stride": 16, "block_size": 64, "topk": 64,
        "init_blocks": 1, "window_size": 2048, "dense_len": 8192}
    eng = cfg["engine"]
    assert (eng["max_batch"], eng["state_slots"], eng["page_len"],
            eng["prefill_chunk"], eng["prefix_cache"]) == (16, 17, 256, 512,
                                                           True)
    for key in ("deployment", "assumed", "departures", "weights",
                "guarantees", "check"):
        assert cfg[key], key
    traffic = json.load(open(os.path.join(ROOT, "benchmarks", "traffic",
                                          "closed-longdoc16.json")))
    assert traffic["arrival"] == {"kind": "closed", "callers": 16}
    assert traffic["shared_prefix"] == {"count": 8, "length": 32768,
                                        "share": 1.0}
    assert (traffic["max_total_len"], traffic["temperature"]) == (34816, 0)
    from benchmarks.generators import requests as gen

    sizes = gen.plan(traffic, 1, cfg)["sizes"]
    assert all(32800 <= p <= eng["buckets"][0][0] and 64 <= o <= 1024
               and p + o <= 34816 for p, o in sizes)
    # the first wave's sixteen private copies fit the pool whole
    assert 16 * (34816 // eng["page_len"]) < eng["num_pages"]
    assert 32768 % eng["prefill_chunk"] == 0


def test_the_benchmark_lists_the_cell_and_the_eight():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    entry = next(c for c in bench["configs"] if c["name"] == "minicpm-sala-l16")
    assert entry["reduced"] == ["num_hidden_layers", "mixer_types"]
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == ("minicpm-sala-l16", "closed-longdoc16", 1)
    lists = {m["name"]: m.get("workloads") for m in
             bench["end_to_end"] + bench["per_layer"]}
    for name in NEW:
        assert lists[name] == [CELL]
        assert os.path.isfile(os.path.join(ROOT, "benchmarks",
                                           "layer_metrics", name + ".py"))
    for name in ("tokens_s", "itl_p95_ms", "device_idle_pct.serve",
                 "decode_step_ms", "prefix_hit_pct", "state_slots_filled_pct",
                 "snapshot_slots_filled_pct", "prefill_chunk_ms"):
        assert CELL in lists[name], name
    for name in ("attn_global_roofline_pct", "attn_grid_live_pct",
                 "idle_pct.schedule", "kv_reserved_pct", "gdn_share_pct"):
        assert CELL not in lists[name], name
