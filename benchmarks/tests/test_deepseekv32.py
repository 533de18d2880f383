"""The ``serve_deepseekv32`` driver and its readers: the cost functions by
hand, the readers' arithmetic on made-up spans and device operations, the CPU
rehearsal of the tiny cell (correct; not correct with dense attention in the
selection's place, nor with the float8 control's tokens), and the committed
configuration against the catalog's rules."""

import json
import os
import subprocess
import sys

import pytest

from benchmarks import costs_deepseekv32 as costs, engine_spans as es, \
    laguna_spans, minicpmsala_spans as sala, run, trace_reduce as tr
from benchmarks.engine_spans import Span
from benchmarks.trace_reduce import DeviceTrace, Event

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
BENCH = os.path.join(HERE, "rehearsal", "BENCHMARK.deepseekv32.json")
CELL = "serve.deepseekv32-longctx32"
NEW = ("dsa_share_pct", "dsa_select_share_pct",
       "dsa_index_decode_roofline_pct", "dsa_index_prefill_roofline_pct",
       "dsa_attend_decode_roofline_pct", "dsa_attend_prefill_roofline_pct",
       "dsa_kv_read_pct")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
LAYERS = 5


def _config():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "deepseek-v32-ep16-l5.json")) as f:
        return json.load(f)


def _spans():
    """One iteration 0..10: a chunk of 300 valid tokens from 65536
    dispatched 1.0..1.1 and landed 1.5..1.6, one decode dispatch 5..6 over
    30 live rows at ~66 k, its landing 6..7 with the counters (positions the
    widest bucket the family was first sized for holds; the readers take
    them from the spans, not from the configuration)."""
    pairs = LAYERS * int(costs.causal_pairs(65536, 300))
    return [
        Span("serve.iter", 0.0, 10.0, {"row_pages": 2100}, 1),
        Span("serve.prefill.dispatch", 1.0, 1.1,
             {"rid": 2, "start": 65536, "tokens": 300, "width": 1024,
              "final": 1}, 1),
        Span("serve.prefill.sync", 1.5, 1.6,
             {"rid": 2, "final": 1, "dsa_queries": 300,
              "dsa_pairs_scored": pairs}, 1),
        Span("serve.decode.dispatch", 5.0, 6.0,
             {"rows": 30, "padded_rows": 32, "table_width": 264,
              "kv_tokens": 30 * 66000}, 1),
        Span("serve.decode.sync", 6.0, 7.0,
             {"dsa_tokens_attended": 30 * 2048 * LAYERS,
              "dsa_tokens_held": 30 * 66000 * LAYERS, "dsa_rows": 30}, 1)]


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
    assert costs.layers(cfg) == 5
    assert costs.entry_width(cfg) == 640 and costs.entry_bytes(cfg) == 1280
    assert costs.index_key_bytes(cfg) == 256
    assert costs.pair_flops(cfg) == 2 * 64 * 128 == 16_384
    assert costs.entry_flops(cfg) == 2 * 128 * (576 + 512) == 278_528
    assert not costs.selects(0, cfg) and not costs.selects(1792, cfg)
    assert costs.selects(2048, cfg) and costs.selects(65536, cfg)
    assert costs.causal_pairs(0, 4) == 1 + 2 + 3 + 4
    assert costs.causal_pairs(100, 2) == 101 + 102
    assert costs.selected_entries(0, 4, cfg) == 1 + 2 + 3 + 4
    assert costs.selected_entries(2046, 3, cfg) == 2047 + 2048 + 2048
    assert costs.selected_entries(65536, 10, cfg) == 20_480
    # a decode row at 66 k: 16.9 MB of index keys a layer, memory-bound
    # (1.08 GFLOP a layer is 5.5 us of the MXU, the bytes 20.6 us)
    got = costs.index_decode_least_seconds(66_000 * 5, cfg, PEAKS)
    assert got["bound"] == "memory"
    assert got["seconds"] == pytest.approx(66_000 * 5 * 256 / 819e9)
    assert got["compute_s"] == pytest.approx(66_000 * 5 * 16_384 / 197e12)
    # its 2048 entries a layer: 2.6 MB (3.2 us) against 570 MFLOP (2.9 us)
    got = costs.attend_least_seconds(2048 * 5, cfg, PEAKS)
    assert got["bound"] == "memory"
    assert got["seconds"] == pytest.approx(2048 * 5 * 1280 / 819e9)
    assert got["compute_s"] == pytest.approx(2048 * 5 * 278_528 / 197e12)
    # a chunk of 1024 at 65536: compute-bound
    pairs = 5 * costs.causal_pairs(65536, 1024)
    got = costs.index_prefill_least_seconds(pairs, 1024, 66_560, cfg, PEAKS)
    assert got["bound"] == "compute"
    assert got["seconds"] == pytest.approx(pairs * 16_384 / 197e12)
    assert got["memory_s"] == pytest.approx(
        5 * (1024 * 64 * 128 * 2 + 66_560 * 256) / 819e9)


def test_the_counter_reader_on_made_up_spans(monkeypatch):
    ctx = _ctx(monkeypatch)
    assert _read("dsa_kv_read_pct", ctx) == pytest.approx(100 * 2048 / 66000)
    for name in NEW[:-1]:
        assert _read(name, ctx) is None  # no device in the trace


def test_the_readers_read_100_at_exactly_their_bounds(monkeypatch):
    cfg = _config()
    ix_dec = costs.index_decode_least_seconds(30 * 66000 * 5, cfg,
                                              PEAKS)["seconds"]
    at_dec = costs.attend_least_seconds(30 * 2048 * 5, cfg, PEAKS)["seconds"]
    ix_pre = costs.index_prefill_least_seconds(
        5 * costs.causal_pairs(65536, 300), 300, 65836, cfg, PEAKS)["seconds"]
    at_pre = costs.attend_least_seconds(5 * 300 * 2048, cfg, PEAKS)["seconds"]

    def op(name, start, seconds):
        return Event(f"%{name} = bf16[16,4096]{{1,0}} fusion(bf16[16,4096] "
                     f"%a)", start, start + seconds, "fusion")

    ops = [op("_dsa_index_paged_call.1", 5.0, 2 * ix_dec),
           op("_dsa_index_chunk_call.2", 1.0, 4 * ix_pre),
           op("fusion.select.d", 5.5, at_dec),
           op("fusion.attend.d", 6.0, at_dec),
           op("fusion.attend.p", 2.0, 2 * at_pre),
           op("fusion.proj.p", 3.0, at_pre),
           op("fusion.other", 9.0, 0.5)]
    modules = [Event("jit__lm_prefill_paged_spec_jit(1)", 0.9, 4.0, "m"),
               Event("jit__lm_decode_paged_spec_jit(2)", 5.0, 8.9, "m")]
    scopes = {
        ops[2].name: "jit(d)/jit(main)/attn_latent/dsa_select/reduce_sum",
        ops[3].name: "jit(d)/jit(main)/attn_latent/dsa_attend/gather",
        ops[4].name: "jit(p)/jit(main)/attn_latent/while/body/dsa_attend/dot",
        ops[5].name: "jit(p)/jit(main)/attn_latent/dsa_index/dot_general",
        ops[6].name: "jit(p)/jit(main)/ffn_dense/dot_general"}
    ctx = _ctx(monkeypatch, ops, scopes, modules)
    assert _read("dsa_index_decode_roofline_pct", ctx) == pytest.approx(50.0)
    assert _read("dsa_index_prefill_roofline_pct", ctx) == pytest.approx(25.0)
    assert _read("dsa_attend_decode_roofline_pct", ctx) == pytest.approx(100.0)
    assert _read("dsa_attend_prefill_roofline_pct", ctx) == pytest.approx(50.0)
    whole = 2 * ix_dec + 4 * ix_pre + 2 * at_dec + 3 * at_pre
    assert _read("dsa_share_pct", ctx) == pytest.approx(
        100 * whole / (whole + 0.5))
    assert _read("dsa_select_share_pct", ctx) == pytest.approx(
        100 * at_dec / whole)


def test_a_chunk_below_index_topk_is_not_priced(monkeypatch):
    spans = _spans()
    spans[1] = Span("serve.prefill.dispatch", 1.0, 1.1,
                    {"rid": 2, "start": 0, "tokens": 1024, "width": 1024}, 1)
    ctx = _ctx(monkeypatch, [Event("%fusion.1 = f32[8]{0} fusion(f32[8] %a)",
                                   1.0, 2.0, "fusion")],
               {"%fusion.1 = f32[8]{0} fusion(f32[8] %a)":
                "jit(p)/attn_latent/dsa_attend/dot"}, spans=spans)
    assert _read("dsa_attend_prefill_roofline_pct", ctx) is None


def test_a_program_without_the_spans_or_the_scopes_reads_nothing(monkeypatch):
    """On the parent's trace (Mistral-Small-4's has ``latent_kv_pages`` and
    the expert counts; no indexer counter, no scope, no kernel of this
    family) every new reader returns ``None`` and raises nothing, under any
    cell's configuration, with and without the capture's file."""
    bare = [Span("serve.iter", 0.0, 10.0, {"row_pages": 9}, 1),
            Span("serve.prefill.dispatch", 1.0, 1.1,
                 {"rid": 1, "start": 16384, "tokens": 9, "final": 1}, 1),
            Span("serve.prefill.sync", 1.5, 1.6,
                 {"rid": 1, "moe_assignments": 36}, 1),
            Span("serve.decode.dispatch", 5.0, 6.0,
                 {"rows": 3, "padded_rows": 32, "table_width": 70,
                  "latent_kv_pages": 200, "kv_tokens": 90}, 1),
            Span("serve.decode.sync", 6.0, 7.0,
                 {"seq": 4, "moe_assignments": 12}, 1)]
    ops = [Event("%fusion.1 = f32[8]{0} fusion(f32[8] %a)", 1.0, 2.0,
                 "fusion")]
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "mistral-small4-ep4-l6.json")) as f:
        mistral = json.load(f)
    for path in (None, "made-up"):
        ctx = _ctx(monkeypatch, ops, {ops[0].name: "jit(x)/attn_latent/mul"},
                   spans=bare)
        monkeypatch.setattr(laguna_spans, "xplane_path",
                            lambda ctx, trace_root=None, path=path: path)
        for config in (_config(), mistral):
            for name in NEW:
                assert _read(name, dict(ctx, config=config)) is None, name


def test_the_new_readers_read_nothing_on_the_recorded_parent_trace():
    """The chip trace the repository keeps (the dense decoder's, recorded
    before this family existed): every new reader returns ``None``."""
    path = os.path.join(HERE, "data", "serve_tiny.xplane.pb")
    trace = tr.load(path)
    mods = [e for d in trace.devices for e in d.modules]
    lo, hi = min(e.start for e in mods), max(e.end for e in mods)
    ctx = {"trace": trace, "window": (lo, hi), "spans": [], "counters": {},
           "facts": {}, "config": _config(), "traffic": {}, "chips": 1,
           "peaks": PEAKS}
    for name in NEW:
        assert _read(name, ctx) is None, name


def _rehearse(trace, *more):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--bench", BENCH, "--allow-cpu-rehearsal", "--workload",
         "serve.tiny-deepseekv32", "--seed", "3000000123", "--seconds", "2",
         "--trace", str(trace), *more],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-2000:]
    return [json.loads(x) for x in p.stdout.strip().splitlines()]


def test_the_rehearsal_is_correct_and_leaves_the_new_out_without_an_error():
    lines = _rehearse(1, "--control", "1")
    assert lines[-1]["correct"] is True and lines[-1]["failed"] == 0
    compared = {x["name"]: x for x in lines if x.get("note") == "compared"}
    assert compared["served_logit_gap"]["value"] < 1e-4
    assert compared["sampled_requests_shared"]["ok"]
    # the cache's index keys of the sampled requests' documents: float32
    # here, so the reference's to rounding
    assert compared["cached_index_key_gap"]["value"] < 1e-5
    assert compared["cached_index_key_gap"]["pages_x_layers"] >= 6
    # the float8 control's tokens lie far below the reference's best
    assert compared["control_served_logit_gap"]["value"] > 0.05
    assert compared["control_served_logit_gap"]["would_pass"] is False
    layer = next(x for x in lines if x.get("note")
                 == "cpu_rehearsal_layer_values_not_measurements")
    assert not set(NEW) & set(layer)   # no device, no capture: left out


def test_dense_attention_in_the_selections_place_is_not_correct(capsys,
                                                                monkeypatch):
    import dataclasses

    from benchmarks.drivers import serve_mistral4

    real = serve_mistral4.model_spec

    def dense(cfg):
        spec = real(cfg)
        return dataclasses.replace(spec, latent=dataclasses.replace(
            spec.latent, indexer=dataclasses.replace(spec.latent.indexer,
                                                     topk=10 ** 6)))

    # run.py loads the driver anew, and the driver takes its model_spec
    # from serve_mistral4 as it is then
    monkeypatch.setattr(serve_mistral4, "model_spec", dense)
    rc = run.main(["--bench", BENCH, "--allow-cpu-rehearsal", "--workload",
                   "serve.tiny-deepseekv32", "--seed", "3000000123",
                   "--seconds", "2", "--trace", "0"])
    out = [json.loads(x) for x in capsys.readouterr().out.strip().splitlines()]
    assert rc == 0 and out[-1]["correct"] is False


def test_a_broken_timed_path_is_not_correct(capsys, monkeypatch):
    """The selection of the position before, in the program: the served
    tokens then miss the reference's."""
    import jax.numpy as jnp

    from marlin_tpu.ops import dsa

    real = dsa.selection_mask

    def shifted(scores, n_valid, k):
        mask, count = real(scores, jnp.maximum(n_valid - 1, 1), k)
        return mask, count

    monkeypatch.setattr(dsa, "selection_mask", shifted)
    rc = run.main(["--bench", BENCH, "--allow-cpu-rehearsal", "--workload",
                   "serve.tiny-deepseekv32", "--seed", "3000000124",
                   "--seconds", "2", "--trace", "0"])
    out = [json.loads(x) for x in capsys.readouterr().out.strip().splitlines()]
    assert rc == 0 and out[-1]["correct"] is False


def test_the_configuration_keeps_every_published_number():
    cfg = _config()
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(catalog):
        pytest.skip("no catalog here")
    row = next(json.loads(x) for x in open(catalog)
               if '"name": "DeepSeek-V3.2"' in x)
    assert cfg["source"] == row["source_url"]
    assert cfg["reduced"] == ["num_hidden_layers", "first_k_dense_replace",
                              "n_routed_experts", "vocab_size"]
    for key, value in row["config"].items():
        if key in cfg["reduced"]:
            assert cfg["source_values"][key] == value and cfg[key] != value
        else:
            assert cfg[key] == value, key
    assert (cfg["num_hidden_layers"], cfg["first_k_dense_replace"],
            cfg["n_routed_experts"], cfg["vocab_size"]) == (5, 1, 16, 16160)
    assert 8 * cfg["vocab_size"] == row["config"]["vocab_size"]
    share = cfg["deployment_share"]
    assert (share["experts_total"], share["first_expert"],
            share["chips_sharing_a_layer"]) == (256, 0, 16)
    eng = cfg["engine"]
    assert (eng["max_batch"], eng["page_len"], eng["prefix_cache"],
            eng["buckets"]) == (32, 256, True, [[66560, 1024]])
    for key in ("deployment", "assumed", "departures", "weights",
                "guarantees", "check"):
        assert cfg[key], key
    traffic = json.load(open(os.path.join(ROOT, "benchmarks", "traffic",
                                          "closed-longctx32.json")))
    assert traffic["arrival"] == {"kind": "closed", "callers": 32}
    assert (traffic["max_total_len"], traffic["temperature"]) == (
        sum(eng["buckets"][0]), 0)
    from benchmarks.generators import requests as gen

    doc = traffic["shared_prefix"]["length"]
    sizes = gen.plan(traffic, 1, cfg)["sizes"]
    assert all(doc + 32 <= p <= eng["buckets"][0][0] and 64 <= o <= 1024
               and p + o <= traffic["max_total_len"] for p, o in sizes)
    assert doc % eng["page_len"] == 0 and doc % eng["prefill_chunk"] == 0
    assert doc == 65536     # the issue's documents, not a fallback
    # the documents and every row's own pages fit the pool; a private copy
    # of a document for every row does not: the driver serves each document
    # once before the callers start (drivers/serve_deepseekv32.py: measure)
    own = -(-(traffic["max_total_len"] - doc) // eng["page_len"])
    assert (traffic["shared_prefix"]["count"] * doc // eng["page_len"]
            + eng["max_batch"] * own) < eng["num_pages"]
    assert eng["max_batch"] * (traffic["max_total_len"] // eng["page_len"]) \
        > eng["num_pages"]
    assert set(cfg["check"]["limits"]) == {"served_logit_gap_p99",
                                           "served_logit_gap_mean"}
    assert 0 < cfg["check"]["cached_index_key_gap"] < 1


def test_the_benchmark_lists_the_cell_and_the_seven():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    entry = next(c for c in bench["configs"]
                 if c["name"] == "deepseek-v32-ep16-l5")
    assert entry["reduced"] == _config()["reduced"]
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == ("deepseek-v32-ep16-l5", "closed-longctx32", 1)
    lists = {m["name"]: m.get("workloads") for m in
             bench["end_to_end"] + bench["per_layer"]}
    for name in NEW:
        assert lists[name] == [CELL]
        assert os.path.isfile(os.path.join(ROOT, "benchmarks",
                                           "layer_metrics", name + ".py"))
    for name in ("tokens_s", "itl_p95_ms", "device_idle_pct.serve",
                 "decode_step_ms", "prefix_hit_pct", "kv_filled_pct",
                 "mla_share_pct", "moe_share_pct", "moe_local_assign_pct",
                 "prefill_chunk_ms", "setup_import_s"):
        assert CELL in lists[name], name
    for name in ("mla_decode_roofline_pct", "mla_prefill_roofline_pct",
                 "attn_grid_live_pct", "idle_pct.schedule",
                 "kv_reserved_pct", "sparse_share_pct"):
        assert CELL not in lists[name], name


def test_a_shared_page_without_its_index_keys_is_not_correct(capsys,
                                                             monkeypatch):
    """What the served tokens alone let through (PERF.md section 7): the
    index keys of one page of a document never reach the pool's second
    array. The comparison of the cache's index keys with the reference's
    reads 1 on that page."""
    import jax.numpy as jnp

    from marlin_tpu.models import hybrid

    real = hybrid.prefill_paged

    def forgetful(params, pages, tables, *a, **kw):
        new, *rest = real(params, pages, tables, *a, **kw)
        lost = tables[0][1]     # the row's second page, whoever wrote it
        return ({name: arrs if len(arrs) != 2 else (
            arrs[0], arrs[1].at[lost].set(jnp.zeros_like(arrs[1][0])))
            for name, arrs in new.items()}, *rest)

    forgetful._cache_size = real._cache_size
    monkeypatch.setattr(hybrid, "prefill_paged", forgetful)
    rc = run.main(["--bench", BENCH, "--allow-cpu-rehearsal", "--workload",
                   "serve.tiny-deepseekv32", "--seed", "3000000125",
                   "--seconds", "2", "--trace", "0"])
    out = [json.loads(x) for x in capsys.readouterr().out.strip().splitlines()]
    compared = {x["name"]: x for x in out if x.get("note") == "compared"}
    assert rc == 0 and out[-1]["correct"] is False
    assert compared["cached_index_key_gap"]["value"] > 0.9
