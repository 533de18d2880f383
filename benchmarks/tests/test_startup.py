"""The six ``setup_*`` readers of the program's start-up record
(``marlin_tpu/obs/collectors.py`` ``startup_report``) in a CPU rehearsal of a
matrix cell and of a serving cell: every value is printed, the parts fit
inside the run's ``setup_s``, the ``startup`` note's rows are the programs
the harness counted, and a program without the record reads ``None``."""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
RUN = os.path.join(ROOT, "benchmarks", "run.py")
BENCH = os.path.join(HERE, "rehearsal", "BENCHMARK.startup.json")
READERS = ("setup_import_s", "setup_engine_s", "setup_trace_lower_s",
           "setup_compile_s", "setup_cache_load_s", "setup_programs_compiled")
SPANS = {"matmul.tiny-startup": {"startup.import": None,
                                 "matmul.first_dispatch": None},
         "serve.tiny-startup": {"startup.import": None,
                                "serve.engine.init": None,
                                "serve.warmup": None,
                                "serve.kvpool.init": "serve.warmup"}}


def _notes(cell):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    p = subprocess.run(
        [sys.executable, RUN, "--bench", BENCH, "--allow-cpu-rehearsal",
         "--workload", cell, "--seed", "3000000127", "--seconds", "1",
         "--trace", "1"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    lines = [json.loads(x) for x in p.stdout.strip().splitlines()]
    assert lines[-1]["correct"] is True
    return {n["note"]: n for n in lines[:-1]}


@pytest.mark.parametrize("cell", sorted(SPANS))
def test_a_rehearsal_prints_the_six_parts_and_the_note(cell):
    notes = _notes(cell)
    values = notes["cpu_rehearsal_layer_values_not_measurements"]
    assert set(READERS) <= set(values)
    window, startup = notes["window"], notes["startup"]
    assert values["setup_import_s"] > 0 and values["setup_engine_s"] > 0
    assert (values["setup_import_s"] + values["setup_engine_s"]
            < window["setup_s"])
    spans = {s["name"]: s for s in startup["spans"]}
    assert set(spans) == set(SPANS[cell])
    for name, parent in SPANS[cell].items():
        # (the worker's thread may have made the pool before the warm-up)
        assert spans[name]["parent"] in (None, parent)
    assert values["setup_engine_s"] <= sum(
        s["t1"] - s["t0"] for s in startup["spans"]
        if s["name"] != "startup.import") + 1e-3
    # the rows up to the window are the backend events the harness counted
    # (its listener is registered after ``import marlin_tpu``, which compiles
    # nothing; nothing compiles inside the window, so its middle is the cut:
    # the note's clock starts a few milliseconds after the harness's), and
    # the hits are its hits
    assert window["compiles_in_window"] == 0
    upto = window["setup_s"] + window["window_s"] / 2
    rows = [r for r in startup["programs"] if r["t"] <= upto]
    assert len(rows) == window["compiled_in_process"]
    assert (sum(r["cache"] == "hit" for r in rows)
            == window["compile_cache_hits"])
    inside = [r for r in startup["programs"] if r["within"]]
    assert inside and all(r["t"] <= window["setup_s"] for r in inside)
    totals = startup["totals"]
    assert (totals["programs_compiled"] + totals["programs_loaded"]
            == len(inside))
    assert values["setup_programs_compiled"] == totals["programs_compiled"]
    assert values["setup_trace_lower_s"] == pytest.approx(
        sum(r["trace_s"] + r["lower_s"] for r in inside), abs=1e-3 * len(inside))
    assert (values["setup_compile_s"] + values["setup_cache_load_s"]
            == pytest.approx(sum(r["backend_s"] for r in inside),
                             abs=1e-3 * len(inside)))
    # what the harness makes itself is compiled outside any span, by name
    assert startup["outside"]


@pytest.mark.parametrize("reader", READERS)
def test_a_program_without_the_record_reads_none(reader, monkeypatch):
    from benchmarks import run
    from marlin_tpu.obs import collectors

    monkeypatch.delattr(collectors, "startup_report")
    assert run.load_module("layer_metrics", reader).read({}) is None
