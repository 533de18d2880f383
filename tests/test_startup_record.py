"""The process's start-up record (``obs/collectors.py``): a row a program
from ``jax.monitoring``'s trace / lowering / backend events with the
persistent cache's verdict, the start-up spans open process-wide when it was
compiled, the totals ``benchmarks/layer_metrics/setup_*.py`` read, and the
rules the bridge keeps (bounded, passive, installed in every process).

Real compiles go through the process's one record (deltas are taken around
them); pairing under concurrency, the bound and a failing registry are shown
on a private :class:`StartupRecord` handed the events JAX would send."""

import contextlib
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import marlin_tpu as mt
from marlin_tpu import obs
from marlin_tpu.obs import collectors
from marlin_tpu.obs.collectors import StartupRecord, startup_span
from marlin_tpu.obs.report import analyze, load_events

TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND = "/jax/core/compile/backend_compile_duration"
HIT = "/jax/compilation_cache/cache_hits"
MISS = "/jax/compilation_cache/cache_misses"
RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"
HEADS = 2


def _rows_since(n):
    return collectors.startup_report()["programs"][n:]


def _n_rows():
    return len(collectors.startup_report()["programs"])


def _program(rec, name, trace_s=0.25, lower_s=0.5, backend_s=1.0,
             cache=None, retrieval_s=None):
    """The events of one program, as JAX sends them on one thread."""
    rec.on_duration(TRACE, trace_s, fun_name=name)
    rec.on_start(LOWER)
    rec.on_duration(LOWER, lower_s, fun_name=f"jit({name})")
    if cache is not None:
        rec.on_event(cache)
    if retrieval_s is not None:
        rec.on_duration(RETRIEVAL, retrieval_s)
    return rec.on_duration(BACKEND, backend_s, fun_name=f"jit({name})")


@contextlib.contextmanager
def _persistent_cache(path):
    """JAX's persistent cache at ``path`` for the block, every program
    eligible, and the process's settings back afterwards."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    keys = ("jax_compilation_cache_dir", "jax_enable_compilation_cache",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    before = {k: getattr(jax.config, k) for k in keys}
    try:
        for k, v in zip(keys, (str(path), True, 0.0, -1)):
            jax.config.update(k, v)
        cc.reset_cache()
        yield
    finally:
        for k, v in before.items():
            jax.config.update(k, v)
        cc.reset_cache()


# ------------------------------------------------------- real compiles


def test_a_compile_is_one_row_with_its_name_and_a_reload_is_a_hit(tmp_path):
    def startup_record_probe(x):
        return jnp.tanh(x) * 1.000123 + 41.0

    with _persistent_cache(tmp_path / "cache"):
        n = _n_rows()
        jax.jit(startup_record_probe)(np.float32(2.0))
        rows = [r for r in _rows_since(n)
                if r["fun_name"] == "jit(startup_record_probe)"]
        assert len(rows) == 1
        row = rows[0]
        assert row["trace_s"] > 0 and row["lower_s"] > 0
        assert row["backend_s"] > 0 and row["within"] == []
        assert row["t_trace"] <= row["t_lower"] <= row["t_backend"]
        # (a backend that will not cache a program sends no verdict: `off`)
        assert row["cache"] in ("miss", "off")
        assert row["retrieval_s"] is None
        if row["cache"] == "off":
            return
        jax.clear_caches()
        n = _n_rows()
        jax.jit(startup_record_probe)(np.float32(2.0))
        again = [r for r in _rows_since(n)
                 if r["fun_name"] == "jit(startup_record_probe)"]
        assert [r["cache"] for r in again] == ["hit"]
        assert again[0]["retrieval_s"] > 0
        assert again[0]["backend_s"] >= again[0]["retrieval_s"]


def test_the_events_of_a_hit_make_a_hit_row():
    """The hit path by the events JAX sends, whatever this backend caches."""
    rec = StartupRecord()
    row = _program(rec, "f", cache=HIT, retrieval_s=0.125, backend_s=0.5)
    assert (row["fun_name"], row["cache"]) == ("jit(f)", "hit")
    assert (row["trace_s"], row["lower_s"], row["backend_s"],
            row["retrieval_s"]) == (0.25, 0.5, 0.5, 0.125)
    # the verdict is used up: the thread's next program has none of its own
    assert _program(rec, "g")["cache"] == "off"
    assert _program(rec, "h", cache=MISS)["cache"] == "miss"


def test_a_trace_made_by_the_lowering_is_not_the_programs():
    """A lowering traces jitted helpers of its own (their events end inside
    it): the program's trace is the last one before the lowering began."""
    rec = StartupRecord()
    rec.on_duration(TRACE, 0.01, fun_name="inner")   # nested: ends first
    rec.on_duration(TRACE, 2.0, fun_name="program")
    rec.on_start(LOWER)
    for _ in range(100):
        rec.on_duration(TRACE, 0.001, fun_name="add")
    rec.on_duration(LOWER, 3.0, fun_name="jit(program)")
    row = rec.on_duration(BACKEND, 1.0, fun_name="jit(program)")
    assert (row["trace_s"], row["lower_s"]) == (2.0, 3.0)
    # a program whose trace was cached has none
    rec.on_start(LOWER)
    rec.on_duration(LOWER, 0.5, fun_name="jit(program)")
    assert rec.on_duration(BACKEND, 1.0,
                           fun_name="jit(program)")["trace_s"] == 0.0


def test_a_compile_on_another_thread_is_within_the_span_open_here():
    def startup_record_threaded(x):
        return jnp.cos(x) * 1.000321 - 43.0

    n = _n_rows()
    with startup_span("test.open_on_main", buckets=2) as span:
        t = threading.Thread(
            target=lambda: jax.jit(startup_record_threaded)(np.float32(1.0)))
        t.start()
        t.join()
    rows = [r for r in _rows_since(n)
            if r["fun_name"] == "jit(startup_record_threaded)"]
    assert len(rows) == 1 and rows[0]["within"] == ["test.open_on_main"]
    assert span["t1"] >= rows[0]["t_backend"] >= span["t0"]
    kept = [s for s in collectors.startup_report()["spans"]
            if s["name"] == "test.open_on_main"]
    assert kept[-1]["fields"] == {"buckets": 2} and kept[-1]["parent"] is None
    # closed: a later program is within nothing
    n = _n_rows()
    jax.jit(lambda x: x * 1.000777 + 47.0)(np.float32(1.0))
    assert all(r["within"] == [] for r in _rows_since(n))


def test_spans_nest_on_their_own_thread_and_are_open_process_wide():
    rec = StartupRecord()
    outer = rec.open_span("outer", {})
    inner = rec.open_span("inner", {"pages_total": 8})
    seen = {}

    def other():
        alone = rec.open_span("alone", {})
        seen["row"] = _program(rec, "f")
        rec.close_span(alone)

    t = threading.Thread(target=other)
    t.start()
    t.join()
    rec.close_span(inner)
    rec.close_span(outer)
    by_name = {s["name"]: s for s in rec.report()["spans"]}
    assert by_name["inner"]["parent"] == "outer"
    assert by_name["outer"]["parent"] is None
    assert by_name["alone"]["parent"] is None  # another thread's stack
    assert seen["row"]["within"] == ["outer", "inner", "alone"]
    assert all(s["t1"] >= s["t0"] for s in by_name.values())


def test_verdicts_pair_with_their_own_threads_backend_event():
    """Two threads compile at once: each hears its cache's verdict and its
    retrieval time between its own lowering and backend events."""
    rec = StartupRecord()
    step = threading.Barrier(2)
    rows = {}

    def compiler(name, verdict, retrieval_s):
        rec.on_duration(TRACE, 0.25, fun_name=name)
        rec.on_start(LOWER)
        rec.on_duration(LOWER, 0.5, fun_name=f"jit({name})")
        step.wait()
        rec.on_event(verdict)
        if retrieval_s is not None:
            rec.on_duration(RETRIEVAL, retrieval_s)
        step.wait()  # both verdicts are in before either backend event
        rows[name] = rec.on_duration(BACKEND, 1.0, fun_name=f"jit({name})")

    threads = [threading.Thread(target=compiler, args=a)
               for a in (("a", HIT, 0.125), ("b", MISS, None))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert (rows["a"]["cache"], rows["a"]["retrieval_s"]) == ("hit", 0.125)
    assert (rows["b"]["cache"], rows["b"]["retrieval_s"]) == ("miss", None)
    assert rows["a"]["trace_s"] == rows["b"]["trace_s"] == 0.25


def test_a_program_lowered_here_and_compiled_on_a_worker_keeps_its_parts():
    """``hybrid._compile_side_by_side``: lowered one after another on the
    caller's thread, each handed to a thread of its own to compile."""
    rec = StartupRecord()
    span = rec.open_span("serve.warmup", {})
    for trace_s in (1.0, 2.0):
        rec.on_duration(TRACE, trace_s, fun_name="prefill")
        rec.on_start(LOWER)
        rec.on_duration(LOWER, 10 * trace_s, fun_name="jit(prefill)")
    rows = []

    def worker():
        rec.on_event(MISS)
        rows.append(rec.on_duration(BACKEND, 30.0, fun_name="jit(prefill)"))

    for _ in range(2):
        t = threading.Thread(target=worker)
        t.start()
        t.join()
    rec.close_span(span)
    assert [(r["trace_s"], r["lower_s"]) for r in rows] == [(1.0, 10.0),
                                                            (2.0, 20.0)]
    totals = rec.report()["totals"]
    assert totals["trace_lower_s"] == 33.0 and totals["compile_s"] == 60.0
    assert totals["programs_compiled"] == 2 and totals["programs_loaded"] == 0


def test_totals_split_hits_from_compiles_and_inside_from_outside():
    rec = StartupRecord()
    _program(rec, "make", backend_s=4.0, cache=MISS)       # outside any span
    _program(rec, "make", backend_s=0.5, cache=HIT, retrieval_s=0.25)
    span = rec.open_span("serve.warmup", {"buckets": 1})
    _program(rec, "decode", backend_s=20.0, cache=MISS)
    _program(rec, "copy", backend_s=2.0)                    # off: a compile
    _program(rec, "prefill", backend_s=1.0, cache=HIT, retrieval_s=0.5)
    rec.close_span(span)
    rep = rec.report()
    assert rep["totals"] == {"trace_lower_s": 2.25, "compile_s": 22.0,
                             "cache_load_s": 1.0, "programs_compiled": 2,
                             "programs_loaded": 1}
    outside = rep["outside"]
    assert set(outside) == {"jit(make)"}
    assert (outside["jit(make)"]["programs"], outside["jit(make)"]["hits"],
            outside["jit(make)"]["backend_s"]) == (2, 1, 4.5)
    assert len(rep["programs"]) == 5 and rep["dropped"] == {"rows": 0,
                                                            "spans": 0}


def test_the_record_is_bounded_and_the_totals_go_on():
    rec = StartupRecord(max_rows=3, max_spans=2)
    for i in range(5):
        span = rec.open_span("matmul.first_dispatch", {"program": "rmm"})
        _program(rec, f"f{i}", cache=MISS)
        rec.close_span(span)
    rep = rec.report()
    assert len(rep["programs"]) == 3 and len(rep["spans"]) == 2
    assert rep["dropped"] == {"rows": 2, "spans": 3}
    assert rep["totals"]["programs_compiled"] == 5
    assert rep["totals"]["compile_s"] == 5.0
    # names outside any span are bounded too: the rest share one entry
    for i in range(6):
        _program(rec, f"g{i}")
    assert set(rec.report()["outside"]) == {"jit(g0)", "jit(g1)", "<other>"}
    assert rec.report()["outside"]["<other>"]["programs"] == 4


class _Raising:
    def labels(self, **kw):
        raise RuntimeError("registry is broken")

    observe = labels


def test_a_raising_registry_or_record_never_reaches_the_compile():
    rec = StartupRecord()
    on_duration, on_event, on_start = collectors._listeners(
        rec, _Raising(), _Raising())
    on_duration(TRACE, 0.25, fun_name="f")
    on_start(LOWER, 12345.0, fun_name="jit(f)")
    on_duration(LOWER, 0.5, fun_name="jit(f)")
    on_event(MISS)
    on_duration(BACKEND, 1.0, fun_name="jit(f)")   # the counter raises here
    assert [r["cache"] for r in rec.report()["programs"]] == ["miss"]

    class Broken(StartupRecord):
        def on_duration(self, *a, **kw):
            raise RuntimeError("record is broken")

        on_event = on_start = on_duration

    on_duration, on_event, on_start = collectors._listeners(
        Broken(), _Raising(), _Raising())
    on_duration(BACKEND, 1.0, fun_name="jit(f)")
    on_event(HIT)
    on_start(LOWER, 12345.0, fun_name="jit(f)")
    # and with the real bridge installed a compile still compiles
    assert float(jax.jit(lambda x: x * 1.000999 + 53.0)(
        np.float32(1.0))) == pytest.approx(54.000999)


# ------------------------------------------------- the process's record


def test_the_import_span_exists_exactly_once():
    spans = [s for s in collectors.startup_report()["spans"]
             if s["name"] == "startup.import"]
    assert len(spans) == 1
    assert spans[0]["t1"] > spans[0]["t0"] and spans[0]["parent"] is None
    assert set(spans[0]["fields"]) == {"jax_preloaded"}


def test_every_process_has_the_bridge_and_the_counter_says_what_happened():
    """No ``MetricsServer`` was started and nobody called
    ``install_compile_metrics``: importing the package did."""
    fam = obs.get_registry().counter("marlin_compile_total",
                                     labelnames=("result",))
    before = {k: c.value for k, c in fam.children().items()}
    count = collectors.compile_count()
    n = _n_rows()
    jax.jit(lambda x: x * 1.000555 + 59.0)(np.float32(1.0))
    rows = _rows_since(n)
    assert rows and collectors.compile_count() - count == len(rows)
    after = {k: c.value for k, c in fam.children().items()}
    for result in ("hit", "miss", "off"):
        assert (after.get((result,), 0) - before.get((result,), 0)
                == sum(r["cache"] == result for r in rows))
    text = obs.get_registry().render()
    assert "# TYPE marlin_compile_total counter" in text
    assert f'marlin_compile_total{{result="{rows[0]["cache"]}"}}' in text
    assert "marlin_compile_seconds_count" in text


@pytest.fixture(scope="module")
def lm_params():
    from marlin_tpu.models import TransformerLM

    return TransformerLM(vocab=32, d_model=16, heads=HEADS, layers=2,
                         seed=9).init_params()


def test_an_engines_warm_up_holds_its_programs_and_traffic_adds_none(
        lm_params):
    from marlin_tpu.serving import Request, ServeEngine

    n, n_spans = _n_rows(), len(collectors.startup_report()["spans"])
    eng = ServeEngine(lm_params, HEADS, buckets=((8, 4), (16, 4)),
                      max_batch=2, page_len=4, prefill_chunk=8,
                      queue_depth=16, start=False)
    try:
        assert eng.warmup() == 2
        spans = collectors.startup_report()["spans"][n_spans:]
        assert [(s["name"], s["parent"]) for s in spans] == [
            ("serve.engine.init", None),
            ("serve.kvpool.init", "serve.warmup"),
            ("serve.warmup", None)]
        init, pool, warm = spans
        assert warm["fields"] == {"buckets": 2}
        assert set(pool["fields"]) == {"pages_total", "state_slots"}
        assert pool["fields"]["pages_total"] > 0
        assert warm["t0"] <= pool["t0"] <= pool["t1"] <= warm["t1"]
        assert init["t1"] <= warm["t0"]
        rows = _rows_since(n)
        warmed = [r for r in rows if "serve.warmup" in r["within"]]
        names = {r["fun_name"] for r in warmed}
        assert {"jit(_lm_prefill_paged_jit)", "jit(_lm_decode_paged_jit)",
                "jit(_kv_page_copy_jit)"} <= names
        assert sum(r["fun_name"] == "jit(_lm_prefill_paged_jit)"
                   for r in warmed) == 2   # a program a bucket
        assert all(warm["t0"] <= r["t_backend"] <= warm["t1"]
                   for r in warmed)
        n = _n_rows()
        eng.start()
        handles = [eng.submit(Request(prompt=list(range(1, 3 + 5 * i)),
                                      steps=3)) for i in range(3)]
        assert all(h.result(timeout=120).ok for h in handles)
        # warmed: traffic compiles nothing the engine owns
        assert [r["fun_name"] for r in _rows_since(n)
                if "paged" in r["fun_name"] or "copy" in r["fun_name"]] == []
    finally:
        eng.close()


def test_a_plans_first_product_is_a_span_with_its_program_inside():
    from marlin_tpu.parallel.matmul import _plan

    _plan.cache_clear()
    a = mt.DenseVecMatrix.from_array(
        np.arange(35 * 21, dtype=np.float32).reshape(35, 21) / 64.0)
    b = mt.DenseVecMatrix.from_array(
        np.arange(21 * 28, dtype=np.float32).reshape(21, 28) / 64.0)
    n, n_spans = _n_rows(), len(collectors.startup_report()["spans"])
    c = a.multiply(b, precision="highest")
    np.testing.assert_allclose(c.to_numpy(), a.to_numpy() @ b.to_numpy(),
                               rtol=1e-5)
    spans = collectors.startup_report()["spans"][n_spans:]
    assert [s["name"] for s in spans] == ["matmul.first_dispatch"]
    assert set(spans[0]["fields"]) == {"program", "split"}
    inside = [r for r in _rows_since(n)
              if r["within"] == ["matmul.first_dispatch"]]
    assert inside and all(
        spans[0]["t0"] <= r["t_backend"] <= spans[0]["t1"] for r in inside)
    a.multiply(b, precision="highest")   # the plan is dispatched: no span
    assert len(collectors.startup_report()["spans"]) == n_spans + 1


# ------------------------------------------------ where an operator reads it


def test_a_flight_dump_ends_with_the_record_and_the_report_renders_it(
        tmp_path):
    from marlin_tpu.obs.perf import FlightRecorder

    flight = FlightRecorder(maxlen=4, name="startup-test")
    flight.record("step", rows=1)
    path = flight.dump(path=str(tmp_path / "flight.jsonl"), reason="test")
    recs, skipped = load_events(path)
    assert skipped == 0 and [r["kind"] for r in recs] == ["flight",
                                                          "startup"]
    startup = recs[-1]
    assert {"spans", "programs", "totals", "outside", "dropped",
            "now"} <= set(startup)
    assert "startup.import" in {s["name"] for s in startup["spans"]}
    text = analyze(recs)
    assert "== startup ==" in text and "startup.import" in text
    assert "programs inside a span:" in text
    # a stream without the record renders no such section
    assert "== startup ==" not in analyze(recs[:1])


def test_the_report_names_what_was_compiled_and_what_ran_outside():
    rec = StartupRecord()
    _program(rec, "make", backend_s=4.0, cache=MISS)
    span = rec.open_span("serve.warmup", {"buckets": 2})
    _program(rec, "decode", backend_s=20.0, cache=MISS)
    _program(rec, "prefill", backend_s=1.0, cache=HIT, retrieval_s=0.5)
    rec.close_span(span)
    text = analyze([{"t": 1.0, "kind": "startup", **rec.report()}])
    assert "serve.warmup" in text and "buckets=2" in text
    assert "1 compiled 20.000s, 1 loaded from the cache 1.000s" in text
    assert "compiled jit(decode)  20.000s (miss) in serve.warmup" in text
    assert "jit(make)  x1  backend 4.000s  hits 0" in text
    assert "jit(prefill)" not in text   # a hit is not listed as a compile
