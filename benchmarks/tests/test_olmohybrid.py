"""The ``serve_olmohybrid`` driver and its readers: the cost functions by
hand, the readers' arithmetic on made-up spans and device operations, the
CPU rehearsal of the tiny cell (correct; not correct with a hit entered from
zeros, the snapshot's copy taken out), and the committed configuration
against the catalog's rules."""

import json
import os
import subprocess
import sys

import pytest

from benchmarks import costs_laguna, costs_olmohybrid as costs, \
    engine_spans as es, laguna_spans, run, trace_reduce as tr
from benchmarks.engine_spans import Span
from benchmarks.trace_reduce import DeviceTrace, Event

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
BENCH = os.path.join(HERE, "rehearsal", "BENCHMARK.olmohybrid.json")
CELL = "serve.olmohybrid-sessions24"
NEW = ("gdn_share_pct", "gdn_decode_roofline_pct", "gdn_prefill_roofline_pct",
       "snapshot_slots_filled_pct")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
STATE, TAIL = 30 * 96 * 192 * 4, 3 * 11520 * 2


def _config():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "olmo-hybrid-7b-l16.json")) as f:
        return json.load(f)


def _spans():
    """One iteration 0..10 on line 1: an admission that hit 2048 of 2368
    tokens, a chunk of 320 valid tokens dispatched 1.0..1.1, one decode
    dispatch 5..6 over 22 live rows."""
    spans = [
        Span("serve.iter", 0.0, 10.0, {"row_pages": 250, "pages_total": 319,
                                       "kv_tokens": 56000, "state_slots": 24,
                                       "state_rows": 24, "snapshot_slots": 28,
                                       "snapshots_held": 14}, 1),
        Span("serve.admit", 0.5, 0.6, {"rid": 2, "prompt_tokens": 2368,
                                       "shared_tokens": 2048,
                                       "snapshot_tokens": 2048}, 1),
        Span("serve.prefill.dispatch", 1.0, 1.1,
             {"rid": 2, "start": 2048, "tokens": 320, "delta_tokens": 320,
              "width": 512, "final": 1}, 1),
        Span("serve.decode.dispatch", 5.0, 6.0,
             {"rows": 22, "padded_rows": 24, "table_width": 15,
              "global_table_width": 15, "window_table_width": 0,
              "global_kv_pages": 220, "window_kv_pages": 0,
              "state_rows": 22, "kv_tokens": 52000}, 1)]
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
    assert costs.linear_layers(cfg) == 12
    # a kind of layer that owns no page: FOUR layers hold keys and values
    assert costs_laguna.layers_of(cfg, "full_attention") == 4
    assert costs_laguna.kv_page_bytes(cfg) == 2 * 256 * 30 * 128 * 2
    assert costs.state_bytes(cfg) == STATE == 2211840
    assert costs.conv_dim(cfg) == 30 * (96 + 96 + 192) == 11520
    assert costs.tail_bytes(cfg) == TAIL == 69120
    assert costs.slot_bytes(cfg) == 12 * (STATE + TAIL) == 27371520
    # as much as 445 tokens of this model's KV (61,440 B a token): a
    # snapshot is cheaper than two pages of 256
    kv_token = 4 * 2 * 30 * 128 * 2
    assert kv_token == 61440 and 27371520 // kv_token == 445
    assert costs.gdn_decode_least_seconds(22, cfg, PEAKS) \
        == pytest.approx(22 * 12 * 2 * (STATE + TAIL) / 819e9)
    assert costs.scan_token_flops(cfg) == 2 * 30 * (
        2 * 64 * 96 + 32 * (192 + 96) + 3 * 96 * 192 + 64 * 192)
    assert costs.scan_token_bytes(cfg) == 11520 * 2 + 4 * 30 * 192
    least = costs.gdn_prefill_least_seconds(320, 1, cfg, PEAKS)
    assert least["bound"] == "memory"  # a chunk's state in and out dominates
    assert least["seconds"] == pytest.approx(
        12 * (320 * 46080 + 2 * STATE) / 819e9)
    assert least["compute_s"] == pytest.approx(
        320 * 12 * costs.scan_token_flops(cfg) / 197e12)


def test_the_counter_readers_on_made_up_spans(monkeypatch):
    ctx = _ctx(monkeypatch)
    assert _read("snapshot_slots_filled_pct", ctx) == pytest.approx(50.0)
    assert _read("state_slots_filled_pct", ctx) == pytest.approx(100.0)
    assert _read("prefix_hit_pct", ctx) == pytest.approx(100 * 2048 / 2368)
    assert _read("kv_filled_pct", ctx) == pytest.approx(
        100 * 56000 / (250 * 256))
    for name in ("gdn_share_pct", "gdn_decode_roofline_pct",
                 "gdn_prefill_roofline_pct", "attn_global_roofline_pct"):
        assert _read(name, ctx) is None  # no device in the trace


def test_the_rooflines_read_100_at_exactly_their_bounds(monkeypatch):
    cfg = _config()
    dec = costs.gdn_decode_least_seconds(22, cfg, PEAKS)
    pre = costs.gdn_prefill_least_seconds(320, 1, cfg, PEAKS)["seconds"]
    attn = 220 * 4 * (2 * 256 * 30 * 128 * 2) / 819e9

    def op(name, start, seconds):
        return Event(f"%{name} = f32[24,1,5760]{{2,1,0}} custom-call("
                     f"f32[53,96,5760] %a)", start, start + seconds,
                     "custom-call")

    ops = [op("_delta_decode_update_call.3", 6.1, dec / 2),
           op("fusion.tails", 6.6, dec / 2),          # under delta_update
           op("fusion.scan.1", 1.0, pre / 4),         # under delta_scan
           op("fusion.scan.2", 1.2, pre / 4),
           op("fusion.w_qkv", 2.0, 0.5),              # under linear_attn only
           op("_paged_decode_attention_call.2", 7.0, 2 * attn),
           op("fusion.other", 8.0, 1.0 - dec - pre / 2 - 2 * attn)]
    jit = "jit(_lm_decode_paged_spec_jit)/jit(main)"
    scopes = {
        ops[0].name: f"{jit}/linear_attn/delta_update/pallas_call",
        ops[1].name: f"{jit}/linear_attn/delta_update/scatter",
        ops[2].name: "jit(p)/jit(main)/linear_attn/delta_scan/dot_general",
        ops[3].name: "jit(p)/jit(main)/linear_attn/delta_scan/exp",
        ops[4].name: "jit(p)/jit(main)/linear_attn/dot_general",
        ops[6].name: "jit(p)/jit(main)/ffn_dense/dot_general"}
    ctx = _ctx(monkeypatch, ops, scopes)
    assert _read("gdn_decode_roofline_pct", ctx) == pytest.approx(100.0)
    assert _read("gdn_prefill_roofline_pct", ctx) == pytest.approx(200.0)
    assert _read("attn_global_roofline_pct", ctx) == pytest.approx(50.0)
    # busy 1.5 s in all; the mixer's: the update, the scan, the projection
    assert _read("gdn_share_pct", ctx) == pytest.approx(
        100 * (dec + pre / 2 + 0.5) / 1.5)


def test_a_program_without_the_spans_or_the_scopes_reads_nothing(monkeypatch):
    """On the parent's trace (no ``snapshot_slots``, no ``delta_tokens``, no
    ``linear_attn`` scope; Falcon-H1's has ``state_rows``) every new reader
    returns ``None`` and raises nothing, under any cell's configuration."""
    bare = [Span("serve.iter", 0.0, 10.0, {"row_pages": 9, "kv_tokens": 90,
                                           "state_slots": 4, "state_rows": 3},
                 1),
            Span("serve.prefill.dispatch", 1.0, 1.1,
                 {"rid": 1, "start": 0, "tokens": 9, "ssm_tokens": 9,
                  "final": 1}, 1),
            Span("serve.decode.dispatch", 5.0, 6.0,
                 {"rows": 3, "padded_rows": 16, "table_width": 8,
                  "state_rows": 3, "kv_tokens": 90}, 1)]
    monkeypatch.setattr(es, "capture_for", lambda ctx, trace_root=None: {
        "spans": bare, "window": (0.0, 10.0), "load_s": 0.0, "memo": {}})
    ops = [Event("%fusion.1 = f32[8]{0} fusion(f32[8] %a)", 1.0, 2.0,
                 "fusion")]
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "falcon-h1-34b-l6.json")) as f:
        falcon = json.load(f)
    for path in (None, "made-up"):
        monkeypatch.setattr(laguna_spans, "xplane_path",
                            lambda ctx, trace_root=None, path=path: path)
        monkeypatch.setattr(laguna_spans, "op_scopes",
                            lambda p, stat="tf_op": {ops[0].name: "jit(x)/mul"})
        for config in (_config(), falcon):
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
         "serve.tiny-olmohybrid", "--seed", "3000000123", "--seconds", "2",
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
                       "--workload", "serve.tiny-olmohybrid", "--seed", "11",
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
               if '"Olmo-Hybrid-7B"' in x)
    assert cfg["source"] == row["source_url"]
    assert cfg["reduced"] == ["num_hidden_layers"]
    for key, value in row["config"].items():
        if key in cfg["reduced"]:
            assert cfg["source_values"][key] == value and cfg[key] < value
        else:
            assert cfg[key] == value, key
    assert cfg["num_hidden_layers"] in (12, 16)
    held = cfg["layer_types"][:cfg["num_hidden_layers"]]
    assert held == (["linear_attention"] * 3 + ["full_attention"]) \
        * (cfg["num_hidden_layers"] // 4)
    eng = cfg["engine"]
    assert eng["max_batch"] == 24 and eng["prefix_cache"] is True
    assert eng["state_slots"] == 25 and eng["snapshot_slots"] >= 12
    assert 2048 % eng["prefill_chunk"] == 0
    assert eng["prefill_chunk"] % cfg["linear_chunk_size"] == 0
    for key in ("deployment", "assumed", "departures", "weights", "check"):
        assert cfg[key], key
    traffic = json.load(open(os.path.join(ROOT, "benchmarks", "traffic",
                                          "closed-sessions24.json")))
    assert traffic["arrival"] == {"kind": "closed", "callers": 24}
    assert traffic["shared_prefix"] == {"count": 12, "length": 2048,
                                        "share": 1.0}
    assert traffic["prompt_len"] == {"dist": "lognormal", "median": 2368,
                                     "sigma": 0.11, "min": 2080, "max": 3072}
    assert traffic["output_len"] == {"dist": "lognormal", "median": 192,
                                     "sigma": 0.6, "min": 32, "max": 768}
    assert (traffic["max_total_len"], traffic["pool"], traffic["strata"],
            traffic["temperature"]) == (3840, 64, 4, 0)
    assert traffic["ramp_s"] >= 15
    # every (prompt, output) pair fits a bucket; the pool holds 24 of the
    # largest requests WHOLE (admission charges a request in full) and the
    # twelve histories' pages beside 24 mean ones
    from benchmarks.generators import requests as gen

    sizes = gen.plan(traffic, 1, cfg)["sizes"]
    page = eng["page_len"]
    assert all(any(p <= b[0] and o <= b[1] for b in eng["buckets"])
               for p, o in sizes)
    pages = sorted(-(-(p + o - 1) // page) for p, o in sizes)
    assert sum(pages[-24:]) < eng["num_pages"]
    assert 2048 % page == 0 and traffic["shared_prefix"]["count"] \
        <= eng["snapshot_slots"]


def test_the_benchmark_lists_the_cell_and_the_four():
    """Membership, not position: later PRs append."""
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    entry = next(c for c in bench["configs"]
                 if c["name"] == "olmo-hybrid-7b-l16")
    assert entry["reduced"] == ["num_hidden_layers"]
    assert entry["file"] == "benchmarks/configs/olmo-hybrid-7b-l16.json"
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == ("olmo-hybrid-7b-l16", "closed-sessions24", 1)
    lists = {m["name"]: m.get("workloads") for m in
             bench["end_to_end"] + bench["per_layer"]}
    for name in NEW:
        assert lists[name] == [CELL]
        assert os.path.isfile(os.path.join(ROOT, "benchmarks",
                                           "layer_metrics", name + ".py"))
    for name in ("tokens_s", "itl_p95_ms", "device_idle_pct.serve",
                 "decode_step_ms", "prefill_share_pct", "rows_per_step",
                 "rows_per_dispatch", "idle_pct.prefill", "idle_pct.decode",
                 "idle_pct.unattributed", "queue_wait_ms",
                 "iter_device_ms_p95", "chunk_iters_pct", "launch_slack_ms",
                 "prefill_chunk_ms", "prefill_us_per_token",
                 "prefill_fill_pct", "prefix_hit_pct",
                 "state_slots_filled_pct", "attn_global_roofline_pct",
                 "attn_grid_live_pct"):
        assert CELL in lists[name], name
    # the schedule reader's note is quadratic in spans; kv_reserved_pct
    # counts a shared page once a row; the others read MPT's shapes, an
    # expert layer, a window, a latent cache or a state-space mixer
    for name in ("idle_pct.schedule", "kv_reserved_pct", "attn_roofline_pct",
                 "decode_kv_useful_pct", "attn_window_roofline_pct",
                 "kv_window_pages_pct", "moe_share_pct", "moe_roofline_pct",
                 "mla_decode_roofline_pct", "ssm_share_pct",
                 "ssm_decode_roofline_pct"):
        assert CELL not in lists[name], name
