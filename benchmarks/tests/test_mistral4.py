"""The ``serve_mistral4`` driver and its readers: the CPU rehearsal of the
tiny cell (correct; not correct with softmax scoring in the program's place),
the readers' arithmetic on made-up spans and device operations, the cost
functions by hand, and the committed configuration against the catalog's
rules."""

import json
import os
import subprocess
import sys

import pytest

from benchmarks import costs_mistral4, engine_spans as es, laguna_spans, run, \
    trace_reduce as tr
from benchmarks.engine_spans import Span
from benchmarks.trace_reduce import DeviceTrace, Event

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
BENCH = os.path.join(HERE, "rehearsal", "BENCHMARK.mistral4.json")
CELL = "serve.mistral4-docqa32"
NEW = ("mla_decode_roofline_pct", "mla_share_pct", "prefix_hit_pct",
       "mla_prefill_roofline_pct")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _config():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "mistral-small4-ep4-l6.json")) as f:
        return json.load(f)


def _spans():
    """One iteration 0..10 on line 1: two admissions (a cold prompt, a hit),
    one decode dispatch 5..6 that lands in 6..8."""
    spans = [
        Span("serve.iter", 0.0, 10.0, {"row_pages": 2100, "pages_total": 2560,
                                       "kv_tokens": 520000,
                                       "shared_pages": 512,
                                       "cached_pages": 600}, 1),
        Span("serve.admit", 0.1, 0.2, {"rid": 1, "queue_wait_ms": 2.0,
                                       "shared_pages": 0,
                                       "prompt_tokens": 16600,
                                       "shared_tokens": 0}, 1),
        Span("serve.admit", 0.3, 0.4, {"rid": 2, "queue_wait_ms": 4.0,
                                       "shared_pages": 64,
                                       "prompt_tokens": 16800,
                                       "shared_tokens": 16384}, 1),
        Span("serve.prefill.dispatch", 1.0, 1.1,
             {"rid": 2, "start": 16384, "tokens": 416, "final": 1}, 1),
        Span("serve.decode.dispatch", 5.0, 6.0,
             {"rows": 30, "padded_rows": 32, "table_width": 70,
              "latent_table_width": 70, "latent_kv_pages": 2000,
              "kv_tokens": 500000}, 1),
        Span("serve.decode.sync", 6.0, 8.0,
             {"moe_assignments": 720, "moe_local_assignments": 180,
              "moe_experts_touched": 120}, 1)]
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
    assert costs_mistral4.entry_dim(cfg) == 256 + 64 == 320
    assert costs_mistral4.entry_width(cfg) == 384
    assert costs_mistral4.latent_page_bytes(cfg) == 256 * 384 * 2 == 196608
    assert costs_mistral4.latent_position_flops(cfg) == 2 * 32 * (320 + 256)
    least = costs_mistral4.mla_decode_least_seconds(2000, 500000, cfg, PEAKS)
    assert least["bound"] == "memory"
    assert least["seconds"] == pytest.approx(2000 * 6 * 196608 / 819e9)
    assert least["compute_s"] == pytest.approx(
        500000 * 6 * 36864 / 197e12)
    assert least["memory_s"] > 4 * least["compute_s"]
    assert costs_mistral4.prefill_pair_flops(cfg) == 2 * 32 * (64 + 64 + 128)
    assert costs_mistral4.chunk_pairs(16384, 1024) \
        == 1024 * 16384 + 1024 * 1025 / 2
    assert costs_mistral4.chunk_pairs(0, 4) == 1 + 2 + 3 + 4
    assert costs_mistral4.mla_prefill_least_seconds(1e9, cfg, PEAKS) \
        == pytest.approx(1e9 * 6 * 16384 / 197e12)


def test_each_limit_names_its_statistic_of_the_gaps():
    """``check.limits`` of the committed configuration bound the 99th
    percentile and the mean of the gaps (the largest saturates: a served
    token whose routing flipped in several layers lies as far under the
    reference's best as an unrelated token does, in sound runs and in the
    float8 control alike: PERF.md section 2)."""
    import numpy as np

    from benchmarks.drivers import serve_mistral4 as driver

    gaps = np.concatenate([np.zeros(980), np.full(15, 0.5), np.full(5, 2.0)])
    assert driver.STATISTICS["served_logit_gap"](gaps) == 2.0
    assert driver.STATISTICS["served_logit_gap_mean"](gaps) \
        == pytest.approx((15 * 0.5 + 5 * 2.0) / 1000)
    assert driver.STATISTICS["served_logit_gap_p99"](gaps) == 0.5
    limits = _config()["check"]["limits"]
    assert set(limits) == {"served_logit_gap_p99", "served_logit_gap_mean"}
    assert set(limits) <= set(driver.STATISTICS)


def test_the_counter_readers_on_made_up_spans(monkeypatch):
    ctx = _ctx(monkeypatch)
    assert _read("prefix_hit_pct", ctx) == pytest.approx(
        100 * 16384 / (16600 + 16800))
    assert _read("kv_filled_pct", ctx) == pytest.approx(
        100 * 520000 / (2100 * 256))
    assert _read("moe_experts_touched_pct", ctx) == pytest.approx(
        100 * 120 / (32 * 6))
    assert _read("moe_local_assign_pct", ctx) == pytest.approx(25.0)
    for name in ("mla_decode_roofline_pct", "mla_share_pct",
                 "mla_prefill_roofline_pct"):
        assert _read(name, ctx) is None  # no device in the trace


def test_the_roofline_reads_100_at_exactly_its_bound(monkeypatch):
    cfg = _config()
    least = costs_mistral4.mla_decode_least_seconds(2000, 500000, cfg,
                                                    PEAKS)["seconds"]
    chunk_s = costs_mistral4.mla_prefill_least_seconds(
        costs_mistral4.chunk_pairs(16384, 1024), cfg, PEAKS)

    def op(name, start, seconds):
        return Event(f"%{name} = bf16[32,32,256]{{2,1,0}} custom-call("
                     f"bf16[32,32,384] %a)", start, start + seconds,
                     "custom-call")

    ops = [op("_paged_decode_attention_latent_call.3", 6.1, least / 2),
           op("_paged_decode_attention_latent_call.5", 6.6, least / 2),
           op("_paged_decode_attention_call.2", 7.0, 0.3),   # another's
           op("vmap_jit__latent_prefill_flash_head__.7", 1.0, chunk_s / 4),
           op("vmap_jit__latent_prefill_flash_head__.9", 1.05, chunk_s / 4)]
    modules = [Event("jit__lm_decode_paged_spec_jit(1)", 5.9, 8.0)]
    ctx = _ctx(monkeypatch, ops, modules)
    assert _read("mla_decode_roofline_pct", ctx) == pytest.approx(100.0)
    assert _read("mla_prefill_roofline_pct", ctx) == pytest.approx(200.0)
    # the Laguna cell's readers find nothing of theirs in this kernel's name
    assert laguna_spans.op_seconds(ctx, laguna_spans.GLOBAL_KERNEL,
                                   laguna_spans.KERNEL_HINT) \
        == pytest.approx(0.3)


def test_a_program_without_the_spans_or_the_scope_reads_nothing(monkeypatch):
    """On the parent's trace (no ``latent_kv_pages``, no ``prompt_tokens``,
    no ``attn_latent`` scope) every new reader returns ``None`` and raises
    nothing."""
    bare = [Span("serve.admit", 0.1, 0.2, {"rid": 1, "queue_wait_ms": 2.0,
                                           "shared_pages": 0}, 1),
            Span("serve.decode.dispatch", 5.0, 6.0,
                 {"rows": 3, "padded_rows": 16, "table_width": 8,
                  "kv_tokens": 90}, 1)]
    monkeypatch.setattr(es, "capture_for", lambda ctx, trace_root=None: {
        "spans": bare, "window": (0.0, 10.0), "load_s": 0.0, "memo": {}})
    monkeypatch.setattr(laguna_spans, "xplane_path",
                        lambda ctx, trace_root=None: None)
    ops = [Event("%fusion.1 = f32[8]{0} fusion(f32[8] %a)", 1.0, 2.0,
                 "fusion")]
    ctx = {"trace": tr.Trace([DeviceTrace("/device:TPU:0", ops, [])], []),
           "window": (0.0, 10.0), "config": _config(), "peaks": PEAKS,
           "counters": {}}
    for name in NEW:
        assert _read(name, ctx) is None


def _rehearse(trace):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--bench", BENCH, "--allow-cpu-rehearsal", "--workload",
         "serve.tiny-mistral4", "--seed", "3000000123", "--seconds", "2",
         "--trace", str(trace)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-2000:]
    return [json.loads(x) for x in p.stdout.strip().splitlines()]


def test_the_rehearsal_is_correct_and_leaves_the_four_out_without_an_error():
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


def test_softmax_scoring_in_the_programs_place_is_not_correct(capsys,
                                                              monkeypatch):
    """The program's expert layer with softmax where the configuration's
    family scores with a sigmoid serves tokens whose reference logits lie
    below the reference's best by more than the limit (a sound program: 0)."""
    import jax

    from marlin_tpu.models import moe

    sound = moe.moe_experts_ffn
    monkeypatch.setattr(
        moe, "moe_experts_ffn",
        lambda *a, **kw: sound(*a, **{**kw, "scoring": "softmax"}))
    jax.clear_caches()
    try:
        rc = run.main(["--bench", BENCH, "--allow-cpu-rehearsal",
                       "--workload", "serve.tiny-mistral4", "--seed", "11",
                       "--seconds", "1", "--trace", "0"])
    finally:
        jax.clear_caches()
    lines = [json.loads(x)
             for x in capsys.readouterr().out.strip().splitlines()]
    assert rc == 0 and lines[-1]["correct"] is False
    gap = next(n for n in lines if n.get("name") == "served_logit_gap")
    # the picks are the same without the bias (both scorings rise with the
    # router's output): what differs is the picks' weights, tenths of a logit
    assert gap["value"] > gap["limit"]


def test_the_configuration_keeps_every_published_number():
    """Every number of the catalog's ``config`` under the same key, but for
    the three keys the file lists under ``reduced``; nested groups whole."""
    cfg = _config()
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(catalog):
        pytest.skip("no catalog here")
    row = next(json.loads(x) for x in open(catalog)
               if '"Mistral-Small-4-119B-2603"' in x)
    assert cfg["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in cfg["reduced"]:
            assert cfg["source_values"][key] == value and cfg[key] < value
        else:
            assert cfg[key] == value, key
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"],
            cfg["vocab_size"]) == (6, 32, 32768)
    assert cfg["num_experts"] == cfg["n_routed_experts"]
    assert cfg["mlp_layer_types"] == ["sparse"] * 6
    traffic = json.load(open(os.path.join(ROOT, "benchmarks", "traffic",
                                          "closed-docqa32.json")))
    assert traffic["arrival"] == {"kind": "closed", "callers": 32}
    assert traffic["shared_prefix"] == {"count": 8, "length": 16384,
                                        "share": 1.0}
    assert traffic["prompt_len"] == {"dist": "lognormal", "median": 16704,
                                     "sigma": 0.012, "min": 16416,
                                     "max": 17408}
    assert traffic["output_len"] == {"dist": "lognormal", "median": 160,
                                     "sigma": 0.6, "min": 32, "max": 512}
    assert (traffic["max_total_len"], traffic["pool"], traffic["strata"],
            traffic["temperature"]) == (17920, 64, 4, 0)
    assert 32 <= traffic["ramp_s"] <= 48


def test_the_benchmark_lists_the_cell_and_the_four():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == ("mistral-small4-ep4-l6", "closed-docqa32", 1)
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
                 "moe_share_pct", "moe_roofline_pct",
                 "moe_experts_touched_pct", "moe_local_assign_pct"):
        assert CELL in lists[name], name
    # row_pages counts a shared page once for every row that holds it, so
    # over pages_total it would say nothing; the schedule reader's note is
    # quadratic in spans; the others read MPT's or Laguna's shapes
    for name in ("kv_reserved_pct", "idle_pct.schedule", "attn_roofline_pct",
                 "decode_kv_useful_pct", "attn_window_roofline_pct",
                 "attn_global_roofline_pct", "kv_window_pages_pct"):
        assert CELL not in lists[name], name
