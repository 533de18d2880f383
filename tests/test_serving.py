"""Serving-engine suite: admission, bucketing, lifecycle, chaos, metrics.

The acceptance scenario (test_acceptance_continuous_batching) drives 36
concurrent requests across two shape buckets through :class:`ServeEngine` on
an injectable clock — under BOTH decode-attention kernels (the gather
program the CPU resolves to, and the Pallas kernel the chip resolves to,
here in interpret mode) — and asserts the subsystem's contracts: exactly
one Result per request, per-row outputs bit-identical to the direct
:func:`lm_generate` call on the unpadded prompt (greedy decode is
composition-independent), deadline expiry surfaced (never silently
dropped), and a bounded compile count (≤ 3 programs per bucket:
prefill-chunk + decode + the shared page-copy; the conftest
``compile_count`` fixture). Everything runs greedy/seeded on the CPU mesh,
so it is fully deterministic. Paged-pool internals (alloc/refcount/COW/
prefix cache/chunked-prefill resumability) live in tests/test_paging.py.
"""

import threading
import types

import numpy as np
import pytest

import jax

from marlin_tpu.models import TransformerLM
from marlin_tpu.models.transformer import (lm_decode_paged, lm_generate,
                                           lm_prefill_paged)
from marlin_tpu.serving import (
    STATUS_ERROR,
    STATUS_EXPIRED,
    STATUS_OK,
    STATUS_REJECTED,
    STATUS_SHUTTING_DOWN,
    AdmissionQueue,
    BatchFormer,
    Request,
    ServeEngine,
    normalize_buckets,
    percentile,
    pick_bucket,
)
from marlin_tpu.utils import EventLog, faults
from marlin_tpu.utils.faults import FaultInjected, RaiseFault, Schedule

HEADS = 2
BUCKETS = ((8, 4), (16, 4))
PAGE_LEN = 4  # small pages so every bucket is genuinely multi-page
# the one fork the platform chooses (resolve_decode_kernel): the engine-level
# contracts hold under the kernel the chip runs as under the CPU's
KERNELS = pytest.mark.parametrize("kernel", ["gather", "pallas"])


class FakeClock:
    """Deterministic engine clock: only advances when the test says so."""

    def __init__(self, t: float = 0.0):
        self.t = t

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> None:
        self.t += dt


@pytest.fixture(scope="module")
def params():
    """One tiny LM for the whole module, so every engine shares the jit
    cache (compile-count assertions measure deltas, not absolutes)."""
    return TransformerLM(vocab=32, d_model=16, heads=HEADS, layers=2,
                         seed=9).init_params()


def _engine(params, **kw):
    kw.setdefault("buckets", BUCKETS)
    kw.setdefault("max_batch", 4)
    kw.setdefault("max_wait_ms", 0.0)
    kw.setdefault("queue_depth", 64)
    kw.setdefault("page_len", PAGE_LEN)
    # ample page capacity: these tests exercise the queue-depth / explicit
    # HBM gates, not pool sizing (test_paging.py covers page-unit admission
    # and the auto-sized pool)
    kw.setdefault("num_pages", 1024)
    return ServeEngine(params, HEADS, **kw)


def _reference_single(params, prompt, steps_req, heads=HEADS):
    """The row-level acceptance bar: lm_generate on the UNPADDED prompt at
    its own max_len — per-row greedy output must be bit-identical to it
    regardless of bucket padding, slot width, or co-resident rows."""
    prompt = np.asarray(prompt, np.int32)
    return np.asarray(lm_generate(
        params, prompt, jax.random.key(0), heads=heads,
        max_len=len(prompt) + steps_req, steps=steps_req))


# --------------------------------------------------------------- unit layer


def test_normalize_and_pick_bucket():
    assert normalize_buckets([(16, 4), (8, 4)]) == ((8, 4), (16, 4))
    assert pick_bucket(3, 4, BUCKETS) == (8, 4)
    assert pick_bucket(8, 4, BUCKETS) == (8, 4)     # exact fit, no pad
    assert pick_bucket(9, 4, BUCKETS) == (16, 4)
    assert pick_bucket(17, 4, BUCKETS) is None      # prompt too long
    assert pick_bucket(4, 5, BUCKETS) is None       # steps too deep
    with pytest.raises(ValueError, match="duplicate"):
        normalize_buckets([(8, 4), (8, 4)])
    with pytest.raises(ValueError, match="at least one"):
        normalize_buckets([])


def test_admission_queue_bounds():
    q = AdmissionQueue(depth=2, budget_bytes=100)
    assert q.try_admit(60) is None
    assert "HBM" in q.try_admit(60)          # byte budget
    assert q.try_admit(30) is None
    assert "queue full" in q.try_admit(1)    # depth
    q.release(60)
    assert q.try_admit(1) is None
    q.close("draining")
    assert q.try_admit(1) == "draining"


def test_admission_queue_oversized_first_request():
    """A request dearer than the whole budget must still admit when the
    queue is empty — otherwise it deadlocks the engine forever."""
    q = AdmissionQueue(depth=4, budget_bytes=10)
    assert q.try_admit(1000) is None
    assert "HBM" in q.try_admit(1)


def _stub_entry(priority=0, enq_t=0.0, bucket=(8, 4)):
    r = types.SimpleNamespace(priority=priority, temperature=0.0,
                              top_p=None, top_k=None, seed=0)
    return types.SimpleNamespace(request=r, enq_t=enq_t, bucket=bucket)


def test_batch_former_priority_and_bucket_queues():
    """The post-gang former: one priority-ordered FIFO per bucket. Higher
    priority claims first, FIFO among equals, sampling knobs never
    partition (they are per-row traced in the decode programs)."""
    f = BatchFormer(BUCKETS, max_batch=2)
    for pri, seed, temp in ((0, 1, 0.0), (5, 2, 0.7), (3, 1, 0.9)):
        e = _stub_entry(priority=pri)
        e.request.seed = seed
        e.request.temperature = temp
        f.add(e)
    f.add(_stub_entry(priority=0, bucket=(16, 4)))
    assert f.pending() == 4
    assert f.pending_buckets() == {(8, 4), (16, 4)}
    taken = f.take_for_bucket((8, 4), 2)
    assert [e.request.priority for e in taken] == [5, 3]
    assert f.take_for_bucket((99, 99), 4) == []
    rest = f.take_all()
    assert {e.bucket for e in rest} == {(8, 4), (16, 4)}
    assert f.pending() == 0


def test_batch_former_fifo_among_equal_priority():
    f = BatchFormer(BUCKETS, max_batch=4)
    entries = [_stub_entry(priority=1) for _ in range(3)]
    for e in entries:
        f.add(e)
    assert f.take_for_bucket((8, 4), 3) == entries  # arrival order kept


# ------------------------------------------------------------- engine layer


@KERNELS
def test_acceptance_continuous_batching(params, kernel):
    """The tentpole acceptance: >= 32 concurrent requests, >= 2 buckets,
    deterministic clock — exactly one Result each, per-row bit-identical to
    the direct lm_generate call on the unpadded prompt (both decode
    kernels), expired deadlines surfaced, drain() completes in-flight work,
    and a bounded compile count (<= 2 per bucket plus the one shared
    page-copy program — <= 3 total per bucket, for any knob mix)."""
    clock = FakeClock()
    rng = np.random.default_rng(4)
    reqs = []
    for i in range(32):
        n = int(rng.integers(2, 17))            # both buckets exercised
        steps = int(rng.integers(1, 5))
        reqs.append(Request(prompt=rng.integers(0, 32, n).astype(np.int32),
                            steps=steps, seed=0))
    expired = [Request(prompt=[1, 2], steps=2, deadline=-1.0)
               for _ in range(4)]

    probes = [f._cache_size for f in (lm_prefill_paged, lm_decode_paged)]
    per_bucket = 2  # the shared page-copy program is not bucket-shaped
    before = sum(p() for p in probes)

    eng = _engine(params, clock=clock, decode_kernel=kernel)
    assert eng._decode_kernel == kernel
    try:
        handles = {}
        lock = threading.Lock()

        def submit(chunk):
            for r in chunk:
                h = eng.submit(r)
                with lock:
                    handles[r.rid] = h

        threads = [threading.Thread(target=submit, args=(reqs[i::4],))
                   for i in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for r in expired:           # resolved synchronously, still a Result
            handles[r.rid] = eng.submit(r)

        results = {rid: h.result(timeout=120) for rid, h in handles.items()}
        # exactly one Result per request, none dropped
        assert len(results) == 36
        assert all(h.done() for h in handles.values())

        # deadline expiry is surfaced, not silently dropped
        for r in expired:
            assert results[r.rid].status == STATUS_EXPIRED
            assert "deadline" in results[r.rid].reason

        # compile count: bounded by the bucket set (measured BEFORE the
        # direct-call references below add their own programs)
        grew = sum(p() for p in probes) - before
        assert grew <= per_bucket * len(BUCKETS), \
            f"recompiled: {grew} programs for {BUCKETS}"

        # per-row bit-identical to the direct call: lm_generate on the
        # unpadded prompt itself — regardless of bucket padding, page
        # boundaries, chunked prefill, or co-resident rows
        for r in reqs:
            res = results[r.rid]
            assert res.status == STATUS_OK, (r.rid, res.reason)
            bucket = pick_bucket(len(r.prompt), r.steps, BUCKETS)
            ref = _reference_single(params, r.prompt, r.steps)
            assert res.tokens.tolist() == ref.tolist(), r.rid
            assert res.metrics["bucket"] == bucket
            assert res.metrics["total_s"] >= 0.0
            assert res.metrics["ttft_s"] <= res.metrics["total_s"]

        # drain() completes in-flight work (fresh wave, then drain)
        tail = [eng.submit(Request(prompt=[7, 8, 9], steps=2))
                for _ in range(3)]
        eng.drain()
        for h in tail:
            assert h.result(timeout=5).status == STATUS_OK
        assert eng.pending() == 0

        snap = eng.metrics.snapshot()
        assert snap["completed"] == 35 and snap["expired"] == 4
        assert snap["submitted"] == 35  # expired-at-submit never enqueued
    finally:
        eng.close()


def test_queue_full_rejects(params):
    eng = _engine(params, queue_depth=2, start=False)
    try:
        a = eng.submit(Request(prompt=[1], steps=1))
        b = eng.submit(Request(prompt=[2], steps=1))
        c = eng.submit(Request(prompt=[3], steps=1))
        assert not a.done() and not b.done()
        r = c.result(timeout=1)
        assert r.status == STATUS_REJECTED and "queue full" in r.reason
    finally:
        eng.close()
    assert a.result(timeout=1).status == STATUS_SHUTTING_DOWN


def test_hbm_budget_rejects(params):
    eng = _engine(params, hbm_budget_bytes=1, start=False)
    try:
        a = eng.submit(Request(prompt=[1], steps=1))   # first always admits
        b = eng.submit(Request(prompt=[2], steps=1))
        assert not a.done()
        r = b.result(timeout=1)
        assert r.status == STATUS_REJECTED and "HBM" in r.reason
    finally:
        eng.close()


def test_no_bucket_rejects(params):
    with _engine(params) as eng:
        r = eng.submit(Request(prompt=list(range(30)), steps=2)) \
            .result(timeout=1)
        assert r.status == STATUS_REJECTED and "no bucket" in r.reason
        r = eng.submit(Request(prompt=[1], steps=99)).result(timeout=1)
        assert r.status == STATUS_REJECTED and "no bucket" in r.reason


def test_deadline_expired_at_dispatch(params):
    """Requests admitted in time but dispatched late are retired expired —
    the retire-expired-rows half of the engine cycle."""
    clock = FakeClock()
    eng = _engine(params, clock=clock, start=False)
    try:
        stale = [eng.submit(Request(prompt=[1, 2], steps=2, deadline=5.0))
                 for _ in range(2)]
        fresh = eng.submit(Request(prompt=[1, 2], steps=2, deadline=1e9))
        clock.advance(10.0)          # past the stale deadlines, engine idle
        eng.start()
        for h in stale:
            r = h.result(timeout=30)
            assert r.status == STATUS_EXPIRED and "before dispatch" in r.reason
        assert fresh.result(timeout=30).status == STATUS_OK
    finally:
        eng.close()


def test_close_retires_queued_with_shutting_down(params):
    eng = _engine(params, start=False)
    handles = [eng.submit(Request(prompt=[i + 1], steps=1)) for i in range(3)]
    eng.close()
    for h in handles:
        r = h.result(timeout=1)
        assert r.status == STATUS_SHUTTING_DOWN and "closed" in r.reason
    assert eng.pending() == 0
    # close is terminal for admission too: a deterministic shutting_down
    # Result, not a generic rejection (the router's failover signal)
    r = eng.submit(Request(prompt=[9], steps=1)).result(timeout=1)
    assert r.status == STATUS_SHUTTING_DOWN


def test_serve_enqueue_fault_propagates_to_caller(params):
    with _engine(params, start=False) as eng:
        with faults.injected("serve.enqueue", RaiseFault(times=1)):
            with pytest.raises(FaultInjected):
                eng.submit(Request(prompt=[1], steps=1))
        assert eng.pending() == 0      # nothing admitted by the failed call
        h = eng.submit(Request(prompt=[1], steps=1))
        assert eng.pending() == 1
        eng.drain()
        assert h.result(timeout=30).status == STATUS_OK


def test_metrics_eventlog_records(params, tmp_path):
    """Paged engine event stream: prefill/step/page records with occupancy
    and pool accounting."""
    log = EventLog(str(tmp_path / "serve.jsonl"))
    with _engine(params, log=log) as eng:
        hs = [eng.submit(Request(prompt=[1, 2, 3], steps=2))
              for _ in range(3)]
        for h in hs:
            assert h.result(timeout=60).status == STATUS_OK
        eng.submit(Request(prompt=list(range(30)), steps=1)).result(timeout=1)
    recs = [r for r in log.read() if r["kind"] == "serve"]
    evs = [r["ev"] for r in recs]
    assert evs.count("enqueue") == 3 and evs.count("reject") == 1
    steps = [r for r in recs if r["ev"] == "step"]
    assert steps and all(0.0 < s["occupancy"] <= 1.0 for s in steps)
    prefills = [r for r in recs if r["ev"] == "prefill"]
    assert len(prefills) == 3  # one chunk each (3-token prompts)
    assert all(p["chunk"][0] == 0 and p["chunk"][1] == 3 for p in prefills)
    assert sum(p["new_tokens"] for p in prefills) == 3  # final chunks only
    pages = [r for r in recs if r["ev"] == "page"]
    allocs = [r for r in pages if r["action"] == "alloc"]
    frees = [r for r in pages if r["action"] == "free"]
    assert len(allocs) == 3 and len(frees) == 3
    assert all(0 < r["used"] <= r["total"] for r in allocs)
    assert sum(r["pages"] for r in allocs) == sum(r["pages"] for r in frees)
    results = [r for r in recs if r["ev"] == "result" and r["status"] == "ok"]
    assert len(results) == 3
    for r in results:
        assert r["total_s"] >= r["ttft_s"] >= r["queue_s"] >= 0.0


def test_priority_orders_dispatch(params, tmp_path):
    """Higher-priority requests claim slots first when a bucket queue is
    deeper than one batch."""
    log = EventLog(str(tmp_path / "serve.jsonl"))
    eng = _engine(params, log=log, start=False)
    try:
        low = [eng.submit(Request(prompt=[1, 2], steps=2, priority=0))
               for _ in range(4)]
        high = [eng.submit(Request(prompt=[3, 4], steps=2, priority=5))
                for _ in range(4)]
        eng.start()
        eng.drain()
        for h in low + high:
            assert h.result(timeout=1).status == STATUS_OK
    finally:
        eng.close()
    order = [r["rid"] for r in log.read()
             if r["kind"] == "serve" and r.get("ev") == "result"]
    high_rids = {h.request.rid for h in high}
    assert set(order[:4]) == high_rids, order


@KERNELS
def test_warmup_then_traffic_compiles_nothing(params, compile_count, kernel):
    """warmup() pays every bucket's compile up front — the prefill/decode
    pair per bucket plus the shared page-copy program — and traffic
    afterwards adds ZERO XLA compiles (the promoted compile-bound guard
    from tests/conftest.py)."""
    with _engine(params, decode_kernel=kernel) as eng:
        assert eng.warmup() == len(BUCKETS)
        with compile_count() as c:
            hs = [eng.submit(Request(prompt=[1] * n, steps=2))
                  for n in (2, 5, 8, 12, 16)]
            for h in hs:
                assert h.result(timeout=60).status == STATUS_OK
        assert c.count == 0, \
            f"serving traffic recompiled after warmup ({c.count} compiles)"


def test_drain_idempotent_and_usable_from_context(params):
    with _engine(params) as eng:
        h = eng.submit(Request(prompt=[5, 6], steps=2))
        eng.drain()
        assert h.result(timeout=1).status == STATUS_OK
        eng.drain()   # terminal + idempotent
        r = eng.submit(Request(prompt=[5], steps=1)).result(timeout=1)
        assert r.status == STATUS_SHUTTING_DOWN and "draining" in r.reason


def test_drain_vs_concurrent_submit_race(params):
    """Regression (satellite): submits racing a drain must each get a
    deterministic terminal Result — completed if they made it in,
    ``shutting_down`` if they arrived after the gate shut — NEVER a
    silently-dropped request. Four submitter threads hammer while the main
    thread drains mid-burst."""
    eng = _engine(params, queue_depth=4096)
    handles, lock = [], threading.Lock()
    go = threading.Event()

    def submitter(seed):
        go.wait()
        for i in range(25):
            h = eng.submit(Request(prompt=[1 + (seed + i) % 8], steps=1))
            with lock:
                handles.append(h)

    threads = [threading.Thread(target=submitter, args=(i,))
               for i in range(4)]
    try:
        for t in threads:
            t.start()
        go.set()
        eng.drain()   # races the submitters by construction
        for t in threads:
            t.join()
        assert len(handles) == 100
        statuses = [h.result(timeout=60).status for h in handles]
    finally:
        eng.close()
    # every handle terminal, only the two deterministic outcomes
    assert set(statuses) <= {STATUS_OK, STATUS_SHUTTING_DOWN}, set(statuses)
    snap = eng.metrics.snapshot()
    assert snap["completed"] == statuses.count(STATUS_OK)
    assert eng.pending() == 0
    assert eng._queue.bytes_in_flight == 0


# ------------------------------------------------- row-level scheduling


@KERNELS
def test_rowlevel_step_events_and_slot_refill(params, tmp_path, kernel):
    """The row-level guarantee: a finished row's slot is refilled ON THE
    NEXT STEP. Asserted via the per-step occupancy
    event stream (no sleeps): 3 requests through a 2-slot pool complete in
    2 full-occupancy decode steps — only possible if the slot freed by the
    short row hosts the queued row immediately."""
    log = EventLog(str(tmp_path / "serve.jsonl"))
    eng = _engine(params, max_batch=2, log=log, start=False,
                  decode_kernel=kernel)
    try:
        a = eng.submit(Request(prompt=[1, 2, 3], steps=3))
        b = eng.submit(Request(prompt=[4, 5], steps=2))
        c = eng.submit(Request(prompt=[6, 7], steps=2))
        eng.start()
        eng.drain()
        for h in (a, b, c):
            assert h.result(timeout=60).status == STATUS_OK
    finally:
        eng.close()
    # b (slots with a) finishes on step 1; c takes its slot before step 2
    assert b.result().metrics["slot"] == c.result().metrics["slot"]
    steps = [r for r in log.read()
             if r["kind"] == "serve" and r.get("ev") == "step"]
    assert [s["rows"] for s in steps] == [2, 2]
    assert all(s["occupancy"] == 1.0 and s["new_tokens"] == s["rows"]
               and s["seconds"] >= 0.0 for s in steps)
    snap = eng.metrics.snapshot()
    assert snap["steps"] == 2 and snap["occupancy_mean"] == 1.0
    results = [r for r in log.read()
               if r["kind"] == "serve" and r.get("ev") == "result"]
    for r in results:
        assert r["ttft_s"] <= r["total_s"]


def test_rowlevel_eos_early_retirement_and_refill(params):
    """A row that emits its eos token retires early (fewer than ``steps``
    generated tokens, ending in the eos) and its slot frees for queued
    work; an eos VALUE sitting in the prompt or pad region never stops a
    row (detection looks only at generated tokens)."""
    prompt = [5, 3]
    ref = _reference_single(params, prompt, 4)
    gen = ref[len(prompt):].tolist()
    eos = gen[1]  # the second generated token
    with _engine(params, max_batch=1) as eng:
        h = eng.submit(Request(prompt=prompt, steps=4, eos=eos))
        # the 1-slot pool forces serial occupancy: tail only runs after h
        tail = eng.submit(Request(prompt=[9, 8], steps=2))
        res = h.result(timeout=60)
        assert res.status == STATUS_OK
        stop = gen.index(eos) + 1  # first eos emission wins
        assert res.tokens.tolist() == ref[: len(prompt) + stop].tolist()
        assert res.tokens[-1] == eos
        assert tail.result(timeout=60).status == STATUS_OK
        # pad-region immunity: prompt shorter than the bucket pads with 0s
        # and an eos VALUE may sit in prompt or pad — use an eos the greedy
        # continuation never emits (prompt token 5 unless generated too);
        # the row must run its full step budget
        unseen = next(v for v in range(32) if v not in gen)
        h2 = eng.submit(Request(prompt=prompt, steps=4, eos=unseen))
        res2 = h2.result(timeout=60)
    assert res2.status == STATUS_OK
    assert res2.tokens.tolist() == ref.tolist()


def test_rowlevel_mixed_sampling_knobs_share_steps(params, compile_count):
    """Per-row traced sampling knobs: a greedy row, a sampled row, and a
    top-p/top-k row share the same decode steps (no knob partitioning, no
    extra programs) and the greedy row stays bit-identical to
    lm_generate."""
    with _engine(params, max_batch=4) as eng:
        eng.warmup()
        with compile_count() as c:
            cold = eng.submit(Request(prompt=[1, 2], steps=3))
            hot = eng.submit(Request(prompt=[1, 2], steps=3,
                                     temperature=0.9, seed=7))
            nucl = eng.submit(Request(prompt=[3, 4], steps=3,
                                      temperature=0.8, top_p=0.9, top_k=5,
                                      seed=11))
            rs = [h.result(timeout=60) for h in (cold, hot, nucl)]
        assert c.count == 0, f"{c.count} compiles for a mixed-knob step"
        assert all(r.status == STATUS_OK for r in rs)
        assert rs[0].tokens.tolist() == \
            _reference_single(params, [1, 2], 3).tolist()
        for r in rs[1:]:
            assert r.tokens.size == 2 + 3
            assert np.all(r.tokens < 32) and np.all(r.tokens >= 0)
        snap = eng.metrics.snapshot()
        assert snap["steps"] >= 2  # row-level decode steps served them


def test_rowlevel_sampled_replay_is_composition_independent(params):
    """fold_in(key(seed), step) per row: the same sampled request replays
    the same tokens whether it rides alone or beside other rows."""
    req = dict(prompt=[2, 4, 6], steps=4, temperature=0.7, seed=13)
    with _engine(params, max_batch=4) as eng:
        alone = eng.submit(Request(**req)).result(timeout=60)
    with _engine(params, max_batch=4, start=False) as eng:
        crowd = [eng.submit(Request(prompt=[1] * n, steps=3))
                 for n in (2, 7, 3)]
        again = eng.submit(Request(**req))
        eng.start()
        eng.drain()
        assert all(h.result(timeout=60).status == STATUS_OK for h in crowd)
        again = again.result(timeout=60)
    assert alone.status == again.status == STATUS_OK
    assert alone.tokens.tolist() == again.tokens.tolist()


def test_rowlevel_gqa_bit_identical(params):
    """GQA (kv_heads < heads) through the row-level engine: the page shape
    derives kv_heads from the params, ragged lengths decode from their own
    positions, and per-row output stays bit-identical to lm_generate."""
    gqa = TransformerLM(vocab=32, d_model=16, heads=4, layers=2, kv_heads=2,
                        seed=3).init_params()
    rng = np.random.default_rng(8)
    with ServeEngine(gqa, 4, buckets=BUCKETS, max_batch=4, max_wait_ms=0.0,
                     queue_depth=64) as eng:
        reqs = [Request(prompt=rng.integers(0, 32, int(rng.integers(2, 17)))
                        .astype(np.int32), steps=int(rng.integers(1, 5)))
                for _ in range(8)]
        hs = [eng.submit(r) for r in reqs]
        for r, h in zip(reqs, hs):
            res = h.result(timeout=120)
            assert res.status == STATUS_OK, res.reason
            ref = _reference_single(gqa, r.prompt, r.steps, heads=4)
            assert res.tokens.tolist() == ref.tolist()


@KERNELS
def test_rowlevel_decode_step_fault_fails_only_live_rows(params, kernel):
    """Chaos: a serve.decode_step fault fails ONLY that step's live rows
    with error Results; queued requests still serve afterwards and the page
    pool stays consistent (all slots free, budget fully released)."""
    eng = _engine(params, max_batch=2, start=False, decode_kernel=kernel)
    try:
        live = [eng.submit(Request(prompt=[1, 2], steps=3))
                for _ in range(2)]
        queued = eng.submit(Request(prompt=[3, 4], steps=2))
        with faults.injected("serve.decode_step", RaiseFault(times=1)):
            eng.start()
            for h in live:
                r = h.result(timeout=60)
                assert r.status == STATUS_ERROR, r.status
                assert "FaultInjected" in r.reason
            assert queued.result(timeout=60).status == STATUS_OK
        after = eng.submit(Request(prompt=[5], steps=2))
        assert after.result(timeout=60).status == STATUS_OK
        snap = eng.metrics.snapshot()
        assert snap["errors"] == 2 and snap["completed"] == 2
    finally:
        eng.close()
    assert eng.pending() == 0
    assert eng._queue.bytes_in_flight == 0


@KERNELS
def test_expiring_burst_releases_admission_budget(params, kernel):
    """Regression (admission accounting): a burst of requests that all
    expire — some at submit, some at dispatch — must release every byte of
    the in-flight KV budget on retirement, or admission wedges forever.
    The charge is in PAGE units (request_pages x kv_page_bytes), at the page
    length the kernel's block shape rounds the engine's to."""
    from marlin_tpu.models.planner import kv_page_bytes, request_pages
    from marlin_tpu.ops.paged_attention import align_page_len

    clock = FakeClock()
    page_len = align_page_len(PAGE_LEN) if kernel == "pallas" else PAGE_LEN
    unit = (request_pages(2, 2, page_len)
            * kv_page_bytes(params, HEADS, page_len))
    eng = _engine(params, clock=clock, start=False, decode_kernel=kernel,
                  hbm_budget_bytes=10 * unit)
    try:
        at_submit = [eng.submit(Request(prompt=[1, 2], steps=2,
                                        deadline=-1.0)) for _ in range(3)]
        at_dispatch = [eng.submit(Request(prompt=[1, 2], steps=2,
                                          deadline=5.0)) for _ in range(6)]
        assert eng._page_len == page_len
        assert eng._queue.bytes_in_flight == 6 * unit
        clock.advance(10.0)
        eng.start()
        for h in at_submit + at_dispatch:
            r = h.result(timeout=60)
            assert r.status == STATUS_EXPIRED
        deadline = 50  # the worker releases asynchronously after _set
        import time as _t
        while eng._queue.bytes_in_flight and deadline:
            _t.sleep(0.01)
            deadline -= 1
        assert eng._queue.bytes_in_flight == 0
        assert eng.pending() == 0
        # admission is not wedged: a fresh request admits and completes
        ok = eng.submit(Request(prompt=[1, 2], steps=2))
        eng.drain()
        assert ok.result(timeout=60).status == STATUS_OK
    finally:
        eng.close()
    assert eng._queue.bytes_in_flight == 0


def test_crash_retry_releases_admission_budget_exactly_once(params):
    """Regression (admission accounting, the retry half of the expiring-
    burst guarantee): a request parked between attempts must hold EXACTLY
    its one admission reservation — never double-charged by the re-queue,
    and fully released on its final retirement whichever attempt serves
    it. Covers the decode-fault retry and the exhausted-budget error.
    The reservation under test is the page-unit charge carried across
    attempts."""
    from marlin_tpu.models.planner import kv_page_bytes, request_pages

    cost = (request_pages(2, 3, PAGE_LEN)
            * kv_page_bytes(params, HEADS, PAGE_LEN))
    eng = _engine(params, max_batch=2, start=False,
                  hbm_budget_bytes=10 * cost)
    try:
        eng.warmup()
        hs = [eng.submit(Request(prompt=[1, 2], steps=3, max_attempts=2))
              for _ in range(2)]
        assert eng._queue.bytes_in_flight == 2 * cost
        with faults.injected("serve.decode_step", RaiseFault(times=1)):
            eng.start()
            for h in hs:
                r = h.result(timeout=60)
                assert r.status == STATUS_OK, (r.status, r.reason)
                assert r.metrics["attempt"] == 2
        snap = eng.metrics.snapshot()
        assert snap["retries"] == 2 and snap["completed"] == 2
        # exhausting the budget errors exactly once, still one release
        with faults.injected("serve.decode_step", RaiseFault(times=2)):
            bad = eng.submit(Request(prompt=[3, 4], steps=3, max_attempts=2))
            r = bad.result(timeout=60)
            assert r.status == STATUS_ERROR and "FaultInjected" in r.reason
        deadline = 200   # worker releases asynchronously after _set
        import time as _t
        while eng._queue.bytes_in_flight and deadline:
            _t.sleep(0.01)
            deadline -= 1
        assert eng._queue.bytes_in_flight == 0
        assert eng.pending() == 0
    finally:
        eng.close()
    assert eng._queue.bytes_in_flight == 0


def test_percentile_helper():
    xs = list(range(1, 101))
    assert percentile(xs, 50) == 50
    assert percentile(xs, 99) == 99
    assert percentile([7.0], 99) == 7.0
    with pytest.raises(ValueError):
        percentile([], 50)


@pytest.mark.slow
def test_serving_soak_with_chaos(params):
    """Multi-minute-class soak: concurrent submitters, probabilistic
    prefill + decode-step chaos, ragged sizes — every request resolves,
    counters add up, nothing leaks (conftest checks threads + fault
    registry)."""
    rng = np.random.default_rng(11)
    n_threads, per_thread = 4, 40
    eng = _engine(params, queue_depth=n_threads * per_thread)
    handles, lock = [], threading.Lock()

    def submitter(seed):
        r = np.random.default_rng(seed)
        for _ in range(per_thread):
            req = Request(prompt=r.integers(0, 32, int(r.integers(1, 17))),
                          steps=int(r.integers(1, 5)),
                          priority=int(r.integers(0, 3)))
            h = eng.submit(req)
            with lock:
                handles.append(h)

    try:
        with faults.injected(
                "serve.prefill",
                RaiseFault(times=-1, schedule=Schedule(seed=3, rate=0.05))), \
             faults.injected(
                "serve.decode_step",
                RaiseFault(times=-1, schedule=Schedule(seed=4, rate=0.02))):
            threads = [threading.Thread(target=submitter, args=(100 + i,))
                       for i in range(n_threads)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            eng.drain()
        statuses = [h.result(timeout=300).status for h in handles]
    finally:
        eng.close()
    assert len(statuses) == n_threads * per_thread
    assert set(statuses) <= {STATUS_OK, STATUS_ERROR}
    snap = eng.metrics.snapshot()
    assert snap["completed"] == statuses.count(STATUS_OK)
    assert snap["errors"] == statuses.count(STATUS_ERROR)
    assert snap["completed"] + snap["errors"] == len(statuses)
    assert eng.pending() == 0
