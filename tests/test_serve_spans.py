"""Timed spans of ``ServeEngine``'s paged loop, and the step time they sit on.

``utils.tracing.annotate`` writes ``marlin:<name>`` events with fields into
the ``jax.profiler`` session and nowhere else. Under a CPU capture a small
paged engine must emit every span of ``docs/observability.md``'s table, nested
as the table says, with counts that agree with the engine's own; with no
capture it serves the same tokens and announces the same records as before.
One decode call carries the live rows of every bucket: ``serve.decode``
says how many buckets and how many calls. The last part holds the repaired
step time: with three live buckets ``ServeMetrics.busy_s`` is no more than
the wall time.
"""

import collections
import time
import tracemalloc

import numpy as np
import pytest

import jax

from benchmarks import engine_spans, launches
from benchmarks.trace_reduce import find_xplane
from marlin_tpu.models import TransformerLM, hybrid
from marlin_tpu.models.planner import request_pages
from marlin_tpu.models.transformer import lm_generate
from marlin_tpu.serving import (STATUS_OK, Request, ServeEngine,
                                pick_bucket)
from marlin_tpu.serving.kvpool import group_chunk
from marlin_tpu.utils.tracing import annotate
from tests.test_state_space import tiny_cfg

HEADS = 2
PAGE_LEN = 4
BUCKETS = ((8, 4), (16, 4), (32, 8))
#: (prompt length, steps): two rows in each bucket, one prompt of two chunks
#: and one of four, so non-final chunks exist
SCHEDULE = ((3, 4), (7, 3), (12, 4), (14, 2), (20, 8), (30, 5))

#: span -> (fields it must carry, its parent on the worker's thread)
SPANS = {
    "serve.submit": ({"rid", "bucket"}, None),
    "serve.wait": (set(), None),
    "serve.claim": ({"claimed"}, None),
    "serve.iter": ({"queue_depth", "resident_rows", "live_rows", "row_pages",
                    "pages_used", "pages_total", "kv_tokens"}, None),
    "serve.admit": ({"rid", "bucket", "queue_wait_ms", "pages",
                     "shared_pages"}, "serve.iter"),
    "serve.prefill": ({"chunks", "tokens"}, "serve.iter"),
    "serve.prefill.dispatch": ({"rid", "bucket", "start", "tokens", "width",
                                "final", "seq"}, "serve.prefill"),
    # (a first token lands after the decode call its row rides has gone out)
    "serve.prefill.sync": ({"rid", "final", "seq"}, "serve.decode"),
    "serve.decode": ({"buckets", "dispatches"}, "serve.iter"),
    "serve.decode.dispatch": ({"bucket", "rows", "ahead", "seq"},
                              "serve.decode"),
    "serve.decode.sync": ({"bucket", "seq"}, "serve.decode"),
    "serve.decode.retire": ({"bucket", "retired", "discarded"},
                            "serve.decode"),
}


class ListSink:
    """What ``ServeEngine(log=...)`` writes to, kept in order."""

    def __init__(self):
        self.records = []

    def event(self, kind, **fields):
        self.records.append(fields)


@pytest.fixture(scope="module")
def params():
    return TransformerLM(vocab=32, d_model=16, heads=HEADS, layers=2,
                         seed=9).init_params()


def _engine(params, **kw):
    kw.setdefault("buckets", BUCKETS)
    kw.setdefault("max_batch", 4)
    kw.setdefault("queue_depth", 64)
    kw.setdefault("page_len", PAGE_LEN)
    kw.setdefault("prefill_chunk", 8)
    return ServeEngine(params, HEADS, **kw)


def _requests(schedule=SCHEDULE):
    return [Request(prompt=list(range(1, 1 + n)), steps=steps)
            for n, steps in schedule]


def _parked(eng, timeout=120.0):
    """Return once the worker is in its wait, every span of its last
    iteration closed. The worker clears ``_idle`` as it wakes and sets it
    under the lock just before it waits again, so after a result has come
    back this cannot see the wait before that request."""
    deadline = time.monotonic() + timeout
    while True:
        with eng._cond:
            if eng._idle:
                return
        assert time.monotonic() < deadline, "the worker never parked"
        time.sleep(0.001)


def _serve(params, schedule=SCHEDULE, capture_dir=None, requests=None,
           make=_engine, **kw):
    """Queue ``schedule`` (or ``requests``, where greedy ones will not do)
    on a paused engine (``make``'s), then let the worker run: the iterations
    are the same in every run. With ``capture_dir`` the whole of
    it happens under a profiler capture, which also sees one late request
    wake the worker from its wait. Returns results, records, spans and the
    engine's last snapshot."""
    sink = ListSink()
    eng = make(params, log=sink, start=False, **kw)
    eng.warmup()
    if capture_dir is not None:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(capture_dir), profiler_options=opts)
    try:
        handles = eng.submit_many(requests or _requests(schedule))
        t0 = time.perf_counter()
        eng.start()
        results = [h.result(timeout=120) for h in handles]
        wall = time.perf_counter() - t0
        n_records = len(sink.records)
        if capture_dir is not None:
            _parked(eng)
            late = eng.submit(Request(prompt=[1, 2, 3], steps=2))
            assert late.result(timeout=120).status == STATUS_OK
            # a result arrives from inside serve.decode.retire: the spans
            # around it close only as the worker goes back into its wait
            _parked(eng)
    finally:
        if capture_dir is not None:
            jax.profiler.stop_trace()
        snap = eng.metrics.snapshot()
        eng.close()
    spans = (engine_spans.load(find_xplane(str(capture_dir)))["spans"]
             if capture_dir is not None else None)
    return {"results": results, "records": sink.records[:n_records],
            "late_records": sink.records[n_records:],
            "spans": spans, "snapshot": snap, "wall": wall}


@pytest.fixture(scope="module")
def traced(params, tmp_path_factory):
    return _serve(params, capture_dir=tmp_path_factory.mktemp("capture"))


@pytest.fixture(scope="module")
def untraced(params):
    return _serve(params)


def _parents(spans):
    """``{id(span): parent span or None}`` on each thread, by nesting."""
    out, stacks = {}, collections.defaultdict(list)
    for s in spans:  # sorted by (start, -end)
        stack = stacks[s.line]
        while stack and stack[-1].end <= s.start:
            stack.pop()
        out[id(s)] = stack[-1] if stack else None
        stack.append(s)
    return out


@pytest.mark.parametrize("name", sorted(SPANS))
def test_every_span_is_emitted_with_its_fields(traced, name):
    fields, _ = SPANS[name]
    found = [s for s in traced["spans"] if s.name == name]
    assert found, f"no marlin:{name} span in the capture"
    best = max(found, key=lambda s: len(s.fields))
    assert fields <= set(best.fields), (name, best.fields)


# ---------------------------------------------------- the dispatches' numbers

#: what a configuration-built engine (attention and a state-space mixer in
#: every block, a state slot a row) is made with
SPEC_ENGINE = {"buckets": ((16, 8), (32, 8), (48, 16)), "max_batch": 3,
               "page_len": 8, "prefill_chunk": 16, "num_pages": 64}
SPEC_SCHEDULE = ((5, 4), (37, 9), (12, 8), (40, 16), (9, 3), (30, 7))


@pytest.fixture(scope="module", params=["dense", "spec"])
def numbered(request, tmp_path_factory):
    """A traced run of the dense engine and of a configuration-built one,
    with what each was made with."""
    if request.param == "dense":
        run = request.getfixturevalue("traced")
        return {**run, "buckets": BUCKETS, "max_batch": 4,
                "page_len": PAGE_LEN, "prefill_chunk": 8, "stateful": False}
    spec = hybrid.ModelSpec.from_config(tiny_cfg())

    def make(params, **kw):
        return ServeEngine(params, spec, **SPEC_ENGINE, **kw)

    run = _serve(hybrid.init_params(spec, jax.random.key(3)),
                 schedule=SPEC_SCHEDULE, make=make,
                 capture_dir=tmp_path_factory.mktemp("spec"))
    assert all(r.status == STATUS_OK for r in run["results"])
    return {**run, **SPEC_ENGINE, "stateful": True}


def test_seq_rises_by_one_across_chunks_and_calls(numbered):
    launched = launches.numbered(numbered["spans"])
    seqs = [s.fields["seq"] for s in launched]
    assert len(seqs) > 10
    assert {s.name for s in launched} == set(launches.DISPATCH)
    assert seqs == list(range(seqs[0], seqs[0] + len(seqs)))
    # a sweep that found no live row launched nothing and has no number
    for s in numbered["spans"]:
        if s.name == "serve.decode.dispatch":
            assert ("seq" in s.fields) == bool(s.fields["rows"])


def test_a_landing_names_a_dispatch_seen_earlier_and_calls_land_once(
        numbered):
    spans = numbered["spans"]
    began = {s.fields["seq"]: s for s in launches.numbered(spans)}
    for name in ("serve.prefill.sync", "serve.decode.sync"):
        for sync in (s for s in spans if s.name == name):
            dispatch = began[sync.fields["seq"]]
            assert dispatch.name == name.replace(".sync", ".dispatch")
            assert dispatch.end <= sync.start
            assert dispatch.fields["bucket"] == sync.fields.get(
                "bucket", dispatch.fields["bucket"])
    calls = [s.fields["seq"] for s in launches.numbered(spans)
             if s.name == "serve.decode.dispatch"]
    landed = [s.fields["seq"] for s in spans if s.name == "serve.decode.sync"]
    # each once, in dispatch order; only the last could still be in flight
    assert landed in (calls, calls[:-1])
    firsts = [s.fields["seq"] for s in spans if s.name == "serve.prefill.sync"]
    assert firsts == [s.fields["seq"] for s in launches.numbered(spans)
                      if s.fields.get("final")]


def test_width_is_the_groups_compiled_chunk(numbered):
    chunks = [s.fields for s in numbered["spans"]
              if s.name == "serve.prefill.dispatch"]
    assert chunks
    for f in chunks:
        bucket = tuple(int(x) for x in f["bucket"].split("x"))
        assert f["width"] == group_chunk(bucket, numbered["page_len"],
                                         numbered["prefill_chunk"])
        assert 0 < f["tokens"] <= f["width"]
    assert any(f["tokens"] < f["width"] for f in chunks)  # a prompt's tail
    assert any(f["tokens"] == f["width"] for f in chunks)


def test_fed_rows_and_the_admissions_state_bytes_are_gone(numbered):
    by = collections.defaultdict(list)
    for s in numbered["spans"]:
        by[s.name].append(s.fields)
    assert all("fed_rows" not in f for f in by["serve.decode.dispatch"])
    assert by["serve.admit"]
    assert all("state_bytes" not in f for f in by["serve.admit"])
    slots = (len(numbered["buckets"]) * numbered["max_batch"]
             if numbered["stateful"] else None)
    for f in by["serve.iter"]:
        # what the slots hold is the iteration's to say: a slot a resident
        # row, of as many as the buckets have rows
        assert f.get("state_slots") == slots
        assert f.get("state_rows") == (f["resident_rows"] if slots else None)
    calls = [f for f in by["serve.decode.dispatch"] if f["rows"]]
    assert all(f.get("state_rows") == (f["rows"] if slots else None)
               for f in calls)


def test_dispatched_decode_spans_carry_the_work_they_were_given(traced):
    calls = [s for s in traced["spans"] if s.name == "serve.decode.dispatch"
             and s.fields["rows"]]
    assert calls
    tags = [f"{p}x{s}" for p, s in BUCKETS]
    widest = max(-(-(p + s) // PAGE_LEN) for p, s in BUCKETS)
    assert any("+" in s.fields["bucket"] for s in calls)
    for s in calls:
        # the buckets the call carries, in bucket order; its table is the
        # widest bucket's whichever they are
        carried = s.fields["bucket"].split("+")
        assert carried == [t for t in tags if t in carried]
        assert len(carried) <= s.fields["rows"] <= 4
        assert s.fields["padded_rows"] == 4
        assert s.fields["sampled_rows"] == 0  # every request is greedy
        assert s.fields["table_width"] == widest
        assert 0 < s.fields["kv_tokens"] <= (s.fields["rows"]
                                             * s.fields["table_width"]
                                             * PAGE_LEN)
        # the pages that hold them: at least one a row, none past the table
        assert (-(-s.fields["kv_tokens"] // PAGE_LEN) <= s.fields["kv_pages"]
                <= s.fields["rows"] * s.fields["table_width"])


#: (prompt length, steps) that are live together from the first decode step:
#: one chunk each, all inside one iteration's prefill budget of 64 tokens
TOGETHER = {2: ((3, 4), (5, 3), (12, 4), (14, 3)),
            3: ((3, 4), (12, 4), (20, 6), (3, 8))}


@pytest.mark.parametrize("live_buckets", sorted(TOGETHER))
def test_serve_decode_counts_the_buckets_and_the_one_call(params, tmp_path,
                                                          live_buckets):
    """Rows live in two and in three buckets: ``serve.decode`` has
    ``buckets`` 2 / 3 beside ``dispatches`` 1, its one dispatch span names
    the buckets it carries and counts all their rows, and the sink gets one
    ``step`` record for each of them."""
    schedule = TOGETHER[live_buckets]
    got = _serve(params, schedule=schedule, capture_dir=tmp_path,
                 prefill_chunk=64)
    assert all(r.status == STATUS_OK for r in got["results"])
    decodes = [s.fields for s in got["spans"] if s.name == "serve.decode"
               and s.fields["buckets"]]
    calls = [s.fields for s in got["spans"]
             if s.name == "serve.decode.dispatch" and s.fields["rows"]]
    assert all(d["dispatches"] == 1 for d in decodes)
    assert len(calls) == len(decodes)
    assert decodes[0]["buckets"] == live_buckets
    tags = sorted({"x".join(map(str, _bucket(n, s))) for n, s in schedule},
                  key=lambda t: int(t.split("x")[0]))
    assert calls[0]["bucket"] == "+".join(tags)
    assert calls[0]["rows"] == len(schedule)
    # every landing names the same buckets as its dispatch
    for name in ("serve.decode.sync", "serve.decode.retire"):
        named = [s.fields["bucket"] for s in got["spans"] if s.name == name]
        assert named == [c["bucket"] for c in calls]
    steps = [r for r in got["records"] + got["late_records"]
             if r.get("ev") == "step"]
    assert len(steps) == sum(d["buckets"] for d in decodes)
    assert ([(tuple(r["bucket"]), r["rows"])
             for r in steps[:live_buckets]]
            == [(b, [_bucket(n, s) for n, s in schedule].count(b))
                for b in sorted({_bucket(n, s) for n, s in schedule})])
    assert sum(r["rows"] for r in steps) == sum(c["rows"] for c in calls)


def _bucket(n, steps):
    return pick_bucket(n, steps, BUCKETS)


def test_sampled_rows_counts_live_sampled_rows_only(params, tmp_path,
                                                    monkeypatch):
    """A greedy request decodes while a sampled one of the same bucket is
    still prefilling (30 tokens at 8 an iteration): until its final chunk
    the bucket is handed temperature 0 in every row, so the decode program
    takes its argmax branch, and ``sampled_rows`` is what it was handed."""
    from marlin_tpu.models import transformer

    handed = []
    decode = transformer.lm_decode_paged

    def spy(*args, **kw):
        handed.append(np.asarray(args[7]).copy())  # temperature
        return decode(*args, **kw)

    monkeypatch.setattr(transformer, "lm_decode_paged", spy)
    got = _serve(params, buckets=((32, 8),), capture_dir=tmp_path, requests=[
        Request(prompt=[1, 2, 3], steps=8),
        Request(prompt=list(range(1, 31)), steps=6, temperature=0.9,
                seed=11)])
    assert all(r.status == STATUS_OK for r in got["results"])
    calls = [(s.fields["rows"], s.fields["sampled_rows"])
             for s in got["spans"]
             if s.name == "serve.decode.dispatch" and s.fields["rows"]]
    # three decodes of the greedy row alone, four of both, one of the
    # sampled row alone (it has one token more to go); then _serve's late
    # greedy request
    assert calls == [(1, 0)] * 3 + [(2, 1)] * 4 + [(1, 1), (1, 0)]
    handed = handed[-len(calls):]  # warm-up's calls came first
    assert [int((t > 0).sum()) for t in handed] == [n for _, n in calls]
    assert all(t.dtype == np.float32 and t.shape == (4,) for t in handed)


def test_kv_pages_is_the_pages_the_live_rows_positions_fill(params, tmp_path,
                                                            monkeypatch):
    """``kv_pages`` of every dense dispatch against what the program was
    handed: ``positions // page_len + 1`` summed over the rows whose table
    names a page (a free or prefilling slot's is all dummy)."""
    from marlin_tpu.models import transformer

    handed = []
    decode = transformer.lm_decode_paged

    def spy(*args, **kw):
        tables, positions = np.asarray(args[2]), np.asarray(args[3])
        live = tables[:, 0] != 0
        handed.append((int(live.sum()),
                       int((positions[live] // PAGE_LEN + 1).sum())))
        return decode(*args, **kw)

    monkeypatch.setattr(transformer, "lm_decode_paged", spy)
    got = _serve(params, capture_dir=tmp_path)
    calls = [(s.fields["rows"], s.fields["kv_pages"]) for s in got["spans"]
             if s.name == "serve.decode.dispatch" and s.fields["rows"]]
    assert len(calls) > len(SCHEDULE)
    assert calls == handed[-len(calls):]  # warm-up's calls came first
    assert any(pages > rows for rows, pages in calls)  # rows past one page


def test_spans_nest_as_the_table_says(traced):
    spans = traced["spans"]
    parents = _parents(spans)
    worker = engine_spans.worker_line(spans)
    assert worker is not None
    for s in spans:
        _, parent = SPANS[s.name]
        got = parents[id(s)]
        if s.name == "serve.submit":
            assert s.line != worker and got is None
            continue
        assert s.line == worker, s
        assert (got.name if got is not None else None) == parent, (s, got)


def test_decode_dispatch_rows_are_the_new_tokens_less_first_tokens(traced):
    spans, snap = traced["spans"], traced["snapshot"]
    rows = sum(s.fields["rows"] for s in spans
               if s.name == "serve.decode.dispatch")
    firsts = sum(1 for s in spans if s.name == "serve.prefill.sync"
                 and s.fields["final"])
    assert firsts == len(SCHEDULE) + 1  # the late request too
    assert rows == snap["new_tokens"] - firsts
    # a device call per dispatch span that carried rows, a ``step`` record
    # (``snap["steps"]``) per bucket that held live rows
    decodes = [s for s in spans if s.name == "serve.decode"]
    calls = [s for s in spans if s.name == "serve.decode.dispatch"
             and s.fields["rows"]]
    assert len(calls) == sum(s.fields["dispatches"] for s in decodes)
    assert sum(s.fields["buckets"] for s in decodes) == snap["steps"]
    assert all(s.fields["dispatches"] == -(-rows // 4)
               for s, rows in zip(decodes, _rows_by_decode(spans)))
    retired = sum(s.fields["retired"] for s in spans
                  if s.name == "serve.decode.retire")
    assert retired == len(SCHEDULE) + 1


def _rows_by_decode(spans):
    """Live rows dispatched inside each ``serve.decode`` span, in order."""
    decodes = [s for s in spans if s.name == "serve.decode"]
    return [sum(c.fields["rows"] for c in spans
                if c.name == "serve.decode.dispatch"
                and d.start <= c.start and c.end <= d.end)
            for d in decodes]


def test_queue_wait_ms_is_the_results_queue_s(traced):
    waits = {s.fields["rid"]: s.fields["queue_wait_ms"]
             for s in traced["spans"] if s.name == "serve.admit"}
    for r in traced["results"]:
        assert waits[r.rid] == pytest.approx(1e3 * r.metrics["queue_s"],
                                             rel=1e-9)


def test_prefill_chunks_of_one_request_join_by_rid(traced):
    by_rid = collections.defaultdict(list)
    for s in traced["spans"]:
        if s.name == "serve.prefill.dispatch":
            by_rid[s.fields["rid"]].append(s.fields)
    for req, (n, _) in zip(traced["results"], SCHEDULE):
        chunks = by_rid[req.rid]
        assert [c["start"] for c in chunks] == list(range(0, n, 8))
        assert sum(c["tokens"] for c in chunks) == n
        assert [c["final"] for c in chunks] == [0] * (len(chunks) - 1) + [1]


def test_row_pages_and_kv_tokens_match_the_pool_by_hand(params, tmp_path):
    """Two requests in one bucket, prompts 5 and 7, three tokens each: both
    prefill in the first iteration (one chunk each) and ride its decode
    call; the second dispatches their last step, lands the first, and the
    rows leave their slots (their budget ends with the call in flight); the
    third has nothing to dispatch and lands it."""
    got = _serve(params, schedule=((5, 3), (7, 3)), capture_dir=tmp_path)
    pages = request_pages(5, 3, PAGE_LEN) + request_pages(7, 3, PAGE_LEN)
    assert pages == 2 + 3
    iters = [s.fields for s in got["spans"] if s.name == "serve.iter"]
    total = iters[0]["pages_total"]
    empty = {"resident_rows": 0, "live_rows": 0, "row_pages": 0,
             "pages_used": 0, "kv_tokens": 0, "shared_pages": 0,
             "cached_pages": 0}
    # as each iteration begins: nothing resident; both rows live with their
    # prompts (5 + 7) and the first decode's entry each in the cache (the
    # cursors count what is dispatched); each
    # prompt has one whole page before its last token's, and the prompts
    # begin alike, so the prefix cache holds it once, for the row that
    # finished first: one entry, one page with two referents
    cached = 1
    assert iters[0] == {"queue_depth": 2, "pages_total": total, **empty}
    assert iters[1] == {"queue_depth": 2, "pages_total": total,
                        "resident_rows": 2, "live_rows": 2,
                        "row_pages": pages, "pages_used": pages,
                        "kv_tokens": 5 + 7 + 2, "shared_pages": cached,
                        "cached_pages": cached}
    assert iters[2]["resident_rows"] == 0 and iters[2]["row_pages"] == 0
    admits = [s.fields for s in got["spans"] if s.name == "serve.admit"]
    assert [a["pages"] for a in admits[:2]] == [2, 3]
    calls = [s.fields for s in got["spans"]
             if s.name == "serve.decode.dispatch" and s.fields["rows"]]
    # a call attends each live row's cache plus the entry it writes
    assert [c["kv_tokens"] for c in calls[:2]] == [5 + 7 + 2, 6 + 8 + 2]
    assert [c["rows"] for c in calls[:2]] == [2, 2]
    # ... held in positions // page_len + 1 pages a row (positions 5 and 7,
    # then 6 and 8): the decode kernel's grid steps that compute
    assert [c["kv_pages"] for c in calls[:2]] == [
        5 // PAGE_LEN + 1 + 7 // PAGE_LEN + 1,
        6 // PAGE_LEN + 1 + 8 // PAGE_LEN + 1]


def test_a_capture_begun_while_the_worker_waits_holds_the_iteration_whole(
        params, tmp_path):
    """``serve.iter`` opens once the claim has returned, so a parked worker
    holds no open iteration: the request that wakes it is captured from its
    claim on, with counts taken after the wake-up."""
    eng = _engine(params)
    eng.warmup()
    try:
        _parked(eng)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            res = eng.submit(Request(prompt=[1, 2, 3], steps=2)).result(120)
            _parked(eng)
        finally:
            jax.profiler.stop_trace()
    finally:
        eng.close()
    assert res.status == STATUS_OK
    spans = engine_spans.load(find_xplane(str(tmp_path)))["spans"]
    worker = [s for s in engine_spans.worker_spans(spans)
              if s.name in ("serve.claim", "serve.wait", "serve.iter")]
    # the wait it was parked in began before the capture and is not in it
    assert [s.name for s in worker[:2]] == ["serve.claim", "serve.iter"]
    assert worker[0].fields == {"claimed": 1}
    assert worker[1].fields["queue_depth"] == 1
    assert worker[0].end <= worker[1].start
    admits = [s for s in spans if s.name == "serve.admit"]
    assert [s.fields["rid"] for s in admits] == [res.rid]
    assert worker[1].start <= admits[0].start <= admits[0].end <= worker[1].end


def test_tokens_are_the_reference_with_and_without_a_capture(
        params, traced, untraced):
    for run in (traced, untraced):
        for res, (n, steps) in zip(run["results"], SCHEDULE):
            assert res.status == STATUS_OK
            prompt = np.arange(1, 1 + n, dtype=np.int32)
            want = np.asarray(lm_generate(
                params, prompt, jax.random.key(0), heads=HEADS,
                max_len=n + steps, steps=steps))
            assert res.tokens.tolist() == want.tolist()


#: the keys of the records the benchmark's TokenSink reads, as the parent
#: of this change announced them
RECORD_KEYS = {
    "step": {"ev", "bucket", "rows", "occupancy", "seconds", "new_tokens",
             "tok_s"},
    "prefill": {"ev", "bucket", "new_tokens", "seconds", "chunk", "rid"},
}


@pytest.mark.parametrize("ev", sorted(RECORD_KEYS))
def test_records_keep_keys_count_and_order(traced, untraced, ev):
    def stream(run):
        rid0 = run["results"][0].rid  # rids count on through the process
        return [(r["ev"], tuple(r["bucket"]), r.get("rows"), r["new_tokens"],
                 r["rid"] - rid0 if "rid" in r else None,
                 tuple(r.get("chunk", ())))
                for r in run["records"] if r.get("ev") == ev]

    assert stream(traced) == stream(untraced)
    records = [r for r in untraced["records"] if r.get("ev") == ev]
    for r in records:
        assert set(r) - {"trace_id", "span_id", "parent_id"} == RECORD_KEYS[ev]
    if ev == "step":  # one record per dispatched bucket, a token per row
        assert len(records) == untraced["snapshot"]["steps"]
        assert (sum(r["new_tokens"] for r in records) + len(SCHEDULE)
                == untraced["snapshot"]["new_tokens"])
    else:             # one per chunk; the final one is the first token
        assert len(records) == sum(-(-n // 8) for n, _ in SCHEDULE)
        assert sum(r["new_tokens"] for r in records) == len(SCHEDULE)


def test_annotate_keeps_nothing_in_memory():
    assert not jax.profiler.TraceAnnotation.is_enabled()
    with annotate("warm", rows=1) as span:
        span.set_metadata(retired=0)
    tracemalloc.start()
    try:
        before = tracemalloc.take_snapshot()
        for i in range(20000):
            with annotate("serve.iter", rows=i, bucket="8x4") as span:
                span.set_metadata(retired=i)
        after = tracemalloc.take_snapshot()
    finally:
        tracemalloc.stop()
    grown = sum(d.size_diff for d in after.compare_to(before, "filename")
                if d.size_diff > 0)
    assert grown < 64 * 1024, f"{grown} bytes kept after 20000 spans"


def test_busy_s_is_no_more_than_wall_time_with_three_live_buckets(params):
    """Every bucket has live rows in every iteration and decode dominates:
    dispatch-to-landing walls summed over the buckets count the device two
    and three times; the per-bucket intervals cannot pass the clock."""
    got = _serve(params, buckets=((8, 24), (16, 24), (32, 24)),
                 schedule=((4, 24), (5, 24), (12, 24), (13, 24), (24, 24),
                           (25, 24)))
    assert all(r.status == STATUS_OK for r in got["results"])
    steps = [r for r in got["records"] if r.get("ev") == "step"]
    assert {tuple(r["bucket"]) for r in steps} == {(8, 24), (16, 24), (32, 24)}
    assert got["snapshot"]["steps"] == len(steps) >= 3 * 23
    assert 0 < got["snapshot"]["busy_s"] <= got["wall"]
