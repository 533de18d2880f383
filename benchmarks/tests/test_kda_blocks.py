"""``kda_scan_blocks_run_pct``: the reader's arithmetic on made-up spans,
``None`` where the program's spans carry no ``kda_blocks`` (the parent's),
and its entry in the committed benchmark. (The engine's side, the span's
field on a served chunk, is ``tests/test_kda.py``'s: a CPU rehearsal has no
trace to read spans from.)"""

import json
import os

import pytest

from benchmarks import engine_spans as es, run
from benchmarks.engine_spans import Span

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
NAME = "kda_scan_blocks_run_pct"


def _read(monkeypatch, chunks, config=None):
    spans = [Span("serve.iter", 0.0, 10.0, {}, 1)] + [
        Span("serve.prefill.dispatch", 1.0 + i, 1.1 + i, dict(f), 1)
        for i, f in enumerate(chunks)]
    monkeypatch.setattr(es, "capture_for", lambda ctx, trace_root=None: {
        "spans": spans, "window": (0.0, 10.0), "load_s": 0.0, "memo": {}})
    return run.load_module("layer_metrics", NAME).read(
        {"window": (0.0, 10.0), "config": config or {"kda_chunk_size": 64},
         "counters": {}})


def test_the_blocks_run_over_the_blocks_held(monkeypatch):
    chunks = [{"tokens": 512, "kda_tokens": 512, "kda_blocks": 8,
               "width": 512},
              {"tokens": 65, "kda_tokens": 65, "kda_blocks": 2, "width": 512},
              {"tokens": 1, "kda_tokens": 1, "kda_blocks": 1, "width": 64}]
    assert _read(monkeypatch, chunks) == pytest.approx(100 * 11 / 17)
    # a chunk narrower than the block is one block
    assert _read(monkeypatch, [{"kda_blocks": 1, "width": 32}]) \
        == pytest.approx(100.0)
    assert _read(monkeypatch, chunks[:1], {"kda_chunk_size": 128}) \
        == pytest.approx(200.0)


def test_a_program_without_the_field_reads_nothing(monkeypatch):
    assert _read(monkeypatch, [{"tokens": 384, "kda_tokens": 384,
                                "width": 512}]) is None
    assert _read(monkeypatch, []) is None


def test_the_committed_benchmark_lists_it_for_the_solar_cell_alone():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    entry = bench["per_layer"][-1]
    assert entry["name"] == NAME and entry["moves"] == "itl_p95_ms"
    assert entry["workloads"] == ["serve.solaropen2-reason128"]
