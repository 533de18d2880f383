"""One decode call a token: the live rows of EVERY bucket ride one call of
the one decode program (``ServeEngine._step_paged``).

Rows stay per bucket (admission, pages, chunked prefill); the decode step
packs them (``kvpool.decode_inputs``) into ``max_batch`` rows over the widest
bucket's table, ``ceil(live / max_batch)`` calls where more are live, and
scatters the tokens back. Held here, on the CPU with both decode kernels
(the Pallas one in interpret mode): the number of calls, every request's
tokens against ``lm_generate``, one ``step`` record per live bucket, the
faults, the compile count. ``tests/test_serve_spans.py`` holds the spans,
``tests/test_hybrid_model.py`` a configuration-built model's rings.
"""

import math

import numpy as np
import pytest

import jax

from marlin_tpu.models import TransformerLM, transformer
from marlin_tpu.models.transformer import lm_generate
from marlin_tpu.serving import (STATUS_ERROR, STATUS_OK, Request,
                                ServeEngine, pick_bucket)
from marlin_tpu.serving.kvpool import PagedGroup, decode_inputs, decode_pages
from marlin_tpu.utils import faults
from marlin_tpu.utils.faults import RaiseFault

HEADS = 2
PAGE_LEN = 4
BUCKETS = ((8, 4), (16, 4), (32, 8))
KERNELS = pytest.mark.parametrize("kernel", ["gather", "pallas"])
#: (prompt length, steps) by the buckets that hold live rows together: every
#: prompt is one chunk and all of them fit one iteration's prefill budget,
#: so from the first decode step on every row is live
TOGETHER = {
    2: ((3, 4), (5, 3), (12, 4), (14, 3)),
    3: ((3, 4), (12, 4), (20, 6), (3, 8)),
}
#: three rows in each bucket: nine live rows for calls of four
CROWDED = ((3, 4), (4, 4), (5, 4), (10, 4), (12, 4), (14, 4),
           (20, 8), (3, 8), (4, 8))


class ListSink:
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
    kw.setdefault("prefill_chunk", 128)
    return ServeEngine(params, HEADS, start=False, **kw)


def _requests(schedule, **kw):
    return [Request(prompt=[(7 * i + j) % 31 + 1 for j in range(n)],
                    steps=steps, **kw)
            for i, (n, steps) in enumerate(schedule)]


def _reference(params, request):
    prompt = np.asarray(request.prompt, np.int32)
    return np.asarray(lm_generate(
        params, prompt, jax.random.key(0), heads=HEADS,
        max_len=len(prompt) + request.steps, steps=request.steps)).tolist()


def _watch(eng, monkeypatch):
    """Spy on the decode program: one list per ``_step_paged`` (a worker
    iteration), holding for each call the first page of every live row it
    was handed (a row's identity while it is resident) and the shape of its
    table. Calls outside a step (the warm-up's) are not kept."""
    steps = []
    decode = transformer.lm_decode_paged
    step = eng._step_paged

    def spy(*args, **kw):
        tables = np.asarray(args[2])
        if steps:
            live = tables[:, 0] != 0
            steps[-1].append({"rows": tables[live, 0].tolist(),
                              "shape": tables.shape})
        return decode(*args, **kw)

    def counted(pool, pools, pipe):
        steps.append([])
        return step(pool, pools, pipe)

    monkeypatch.setattr(transformer, "lm_decode_paged", spy)
    monkeypatch.setattr(eng, "_step_paged", counted)
    return steps


def _expected_records(schedule):
    """The ``step`` records of a schedule whose rows are all live from the
    first decode step: per iteration one for each bucket that still holds a
    row, in bucket order, with that bucket's rows."""
    out = []
    for it in range(max(steps for _, steps in schedule) - 1):
        left = [pick_bucket(n, steps, BUCKETS) for n, steps in schedule
                if steps - 1 > it]
        out += [(b, left.count(b)) for b in sorted(set(left))]
    return out


def _step_records(sink):
    return [(tuple(r["bucket"]), r["rows"]) for r in sink.records
            if r.get("ev") == "step"]


@KERNELS
@pytest.mark.parametrize("live_buckets", sorted(TOGETHER))
def test_rows_of_every_bucket_share_one_decode_call(params, monkeypatch,
                                                    kernel, live_buckets):
    schedule = TOGETHER[live_buckets]
    sink = ListSink()
    eng = _engine(params, decode_kernel=kernel, log=sink)
    eng.warmup()
    steps = _watch(eng, monkeypatch)
    reqs = _requests(schedule)
    try:
        handles = eng.submit_many(reqs)
        eng.start()
        results = [h.result(timeout=120) for h in handles]
    finally:
        eng.close()
    for r, q in zip(results, reqs):
        assert r.status == STATUS_OK, r.reason
        assert r.tokens.tolist() == _reference(params, q)
    records = _step_records(sink)
    assert records == _expected_records(schedule)
    # ONE call an iteration while rows are live, whatever the buckets that
    # hold them; each carries every live row, over the widest bucket's table
    calls = [s for s in steps if s]
    assert all(len(s) == 1 for s in calls)
    assert len(calls) == max(steps for _, steps in schedule) - 1
    assert len(calls[0][0]["rows"]) == len(schedule)
    width = decode_pages(BUCKETS, eng._page_len)
    assert {c["shape"] for s in calls for c in s} == {(4, width)}
    # ... and one record for each bucket the call carried
    assert len({b for b, _ in records[:live_buckets]}) == live_buckets
    assert (sum(rows for _, rows in records)
            == sum(len(c["rows"]) for s in calls for c in s)
            == sum(steps - 1 for _, steps in schedule))


@KERNELS
def test_more_live_rows_than_a_call_holds_take_ceil_calls(params, monkeypatch,
                                                          kernel):
    sink = ListSink()
    eng = _engine(params, decode_kernel=kernel, log=sink)
    eng.warmup()
    steps = _watch(eng, monkeypatch)
    reqs = _requests(CROWDED)
    try:
        handles = eng.submit_many(reqs)
        eng.start()
        results = [h.result(timeout=120) for h in handles]
    finally:
        eng.close()
    for r, q in zip(results, reqs):
        assert r.status == STATUS_OK, r.reason
        assert r.tokens.tolist() == _reference(params, q)
    calls = [s for s in steps if s]
    # nine live rows for three iterations, then the last bucket's three
    assert [len(s) for s in calls] == [3, 3, 3, 1, 1, 1, 1]
    for s in calls:
        rows = [row for c in s for row in c["rows"]]
        assert len(s) == math.ceil(len(rows) / 4)
        assert len(set(rows)) == len(rows)  # no row rides two calls
        assert all(len(c["rows"]) == 4 for c in s[:-1])  # packed full
    assert [len(c["rows"]) for c in calls[0]] == [4, 4, 1]
    # a bucket whose rows two calls carried still has ONE record an
    # iteration, with all of its rows
    assert _step_records(sink) == _expected_records(CROWDED)
    snap = eng.metrics.snapshot()
    assert snap["new_tokens"] == sum(steps for _, steps in CROWDED)


def test_a_call_is_packed_in_order_over_the_widest_table():
    class Entry:
        def __init__(self, n, temperature=0.0):
            self.request = type("R", (), dict(
                prompt=np.arange(n), seed=n, top_p=None, top_k=None,
                temperature=temperature))()

    narrow = PagedGroup((8, 4), 4, PAGE_LEN, 8)
    wide = PagedGroup((32, 8), 4, PAGE_LEN, 8)
    narrow.assign(2, Entry(3), [7], 0, 0)
    narrow.begin_decode(2)
    narrow.land_first(2, first=5)
    wide.assign(0, Entry(20), [1, 2, 3, 4, 5, 6], 0, 0)
    wide.assign(3, Entry(30, temperature=0.5), list(range(10, 20)), 0, 0)
    wide.begin_decode(0)
    wide.land_first(0, first=8)
    wide.begin_decode(3)
    wide.land_first(3, first=9)
    wide.seeds[3], wide.top_k[3] = 77, 3
    width = decode_pages([narrow.bucket, wide.bucket], PAGE_LEN)
    assert width == wide.pages_per_row == 10 > narrow.pages_per_row == 3
    wide.fed_serial[3], wide.fed_index[3] = 7, 2  # its token: feed 7's
    narrow.fed_serial[2], narrow.fed_index[2] = 6, 1  # an older feed's
    (tables, positions, cur, steps_done, seeds, temperature, top_p,
     top_k, prev_index) = decode_inputs([(narrow, [2]), (wide, [0, 3])], 4,
                                        width, serial=7)
    assert prev_index.tolist() == [-1, -1, 2, -1]
    assert tables.shape == (4, 10) and tables.dtype == np.int32
    assert tables.tolist() == [[7] + [0] * 9, [1, 2, 3, 4, 5, 6, 0, 0, 0, 0],
                               list(range(10, 20)), [0] * 10]
    assert positions.tolist() == [3, 20, 30, 0]
    assert cur.tolist() == [5, 8, 9, 0]
    assert steps_done.tolist() == [1, 1, 1, 0]
    assert seeds.tolist() == [3, 20, 77, 0] and seeds.dtype == np.uint32
    assert temperature.tolist() == [0.0, 0.0, 0.5, 0.0]
    assert top_p.tolist() == [1.0] * 4 and top_k.tolist() == [0, 0, 3, 0]


@KERNELS
@pytest.mark.parametrize("attempts", [1, 2])
def test_a_decode_fault_fails_or_requeues_every_carried_row_once(
        params, kernel, attempts):
    """``serve.decode_step`` fires once per CALL: the first call's fault
    takes every live row it carried, in all three buckets, each exactly
    once — an error Result with one attempt, a second attempt that serves
    the reference's tokens with two — and the pool balances afterwards."""
    schedule = TOGETHER[3]
    sink = ListSink()
    eng = _engine(params, decode_kernel=kernel, log=sink)
    eng.warmup()
    reqs = _requests(schedule, max_attempts=attempts)
    try:
        handles = eng.submit_many(reqs)
        with faults.injected("serve.decode_step", RaiseFault(times=1)) as f:
            eng.start()
            results = [h.result(timeout=120) for h in handles]
            assert f.fired == 1
        after = eng.submit(Request(prompt=[5, 6, 7], steps=3))
        assert after.result(timeout=120).status == STATUS_OK
        snap = eng.metrics.snapshot()
        eng.drain()
        audit = eng.kvpool_audit()  # exact on a drained engine
    finally:
        eng.close()
    buckets = {pick_bucket(n, s, BUCKETS) for n, s in schedule}
    assert len(buckets) == 3
    if attempts == 1:
        assert [r.status for r in results] == [STATUS_ERROR] * 4
        assert all("FaultInjected" in r.reason for r in results)
        assert snap["errors"] == 4 and snap["retries"] == 0
    else:
        for r, q in zip(results, reqs):
            assert r.status == STATUS_OK, r.reason
            assert r.metrics["attempt"] == 2
            assert r.tokens.tolist() == _reference(params, q)
        assert snap["errors"] == 0 and snap["retries"] == 4
    faulted = [r for r in sink.records if r.get("ev") == "retry"]
    assert len(faulted) == (4 if attempts == 2 else 0)
    assert audit["ok"], audit["errors"]
    assert audit["used"] == audit["cached"]  # no row's pages left behind
    assert eng.pending() == 0 and eng._queue.bytes_in_flight == 0


@KERNELS
def test_mixed_traffic_after_warmup_compiles_nothing(params, compile_count,
                                                     kernel):
    """``warmup()`` compiles a prefill program per bucket, ONE decode
    program and the page copy; rows of one, two and three buckets, calls
    that are full and calls that are not, then compile nothing."""
    probes = [f._cache_size for f in (transformer.lm_prefill_paged,
                                      transformer.lm_decode_paged)]
    eng = _engine(params, decode_kernel=kernel, max_batch=5)
    before = [p() for p in probes]
    eng.warmup()
    grew = [p() - b for p, b in zip(probes, before)]
    assert grew[0] <= len(BUCKETS) and grew[1] <= 1
    try:
        with compile_count() as c:
            eng.start()
            for schedule in (TOGETHER[2], CROWDED, ((3, 2),), TOGETHER[3]):
                reqs = _requests(schedule, temperature=0.0)
                for r in [h.result(timeout=120)
                          for h in eng.submit_many(reqs)]:
                    assert r.status == STATUS_OK, r.reason
            sampled = eng.submit(Request(prompt=[1, 2, 3], steps=4,
                                         temperature=0.8, top_k=5, seed=3))
            assert sampled.result(timeout=120).status == STATUS_OK
        assert c.count == 0, f"{c.count} programs compiled under traffic"
    finally:
        eng.close()
