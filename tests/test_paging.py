"""Paged KV-cache suite: pool mechanics, prefix sharing, chunked prefill.

Layers, bottom up: :class:`PagedKVPool` unit tests (free-list alloc,
refcounts, rolling-hash prefix cache with leaf-first LRU eviction,
copy-on-write splits with a real device page copy), the planner's page
arithmetic, and the paged :class:`ServeEngine` end to end — prefix-share
bit-identity (two requests sharing a system prompt produce outputs
identical to unshared runs), chunked-prefill interleaving and resumability
across injected ``serve.prefill`` faults, the ≤ 3-compiles-per-bucket
bound via the shared ``compile_count`` fixture, page-unit admission, and a
slow chaos soak with worker kills over a paged pool. The generic engine
contracts (exactly-once, drain/close, the acceptance scenario) live in
tests/test_serving.py.
"""

import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import marlin_tpu as mt
from marlin_tpu.models import TransformerLM
from marlin_tpu.models.planner import kv_page_bytes, request_pages
from marlin_tpu.models.transformer import (init_kv_pages, lm_decode_paged,
                                           lm_generate, lm_prefill_paged)
from marlin_tpu.obs import report as obs_report
from marlin_tpu.serving import (
    STATUS_ERROR,
    STATUS_EXPIRED,
    STATUS_OK,
    STATUS_REJECTED,
    PagedKVPool,
    PagePoolExhausted,
    Request,
    ServeEngine,
    Supervisor,
    auto_num_pages,
)
from marlin_tpu.utils import EventLog, faults
from marlin_tpu.utils.faults import RaiseFault, Schedule

HEADS = 2
BUCKETS = ((8, 4), (16, 4))
PAGE_LEN = 4
# the fork the platform chooses: the gather program (CPU) and the Pallas
# kernel (the chip; interpret mode here)
KERNELS = pytest.mark.parametrize("kernel", ["gather", "pallas"])


@pytest.fixture(scope="module")
def params():
    """One tiny LM for the whole module, so every engine shares the jit
    cache (compile-count assertions measure deltas, not absolutes)."""
    return TransformerLM(vocab=32, d_model=16, heads=HEADS, layers=2,
                         seed=9).init_params()


def _engine(params, **kw):
    kw.setdefault("buckets", BUCKETS)
    kw.setdefault("max_batch", 4)
    kw.setdefault("queue_depth", 64)
    kw.setdefault("page_len", PAGE_LEN)
    return ServeEngine(params, HEADS, **kw)


def _ref(params, prompt, steps, heads=HEADS):
    prompt = np.asarray(prompt, np.int32)
    return np.asarray(lm_generate(
        params, prompt, jax.random.key(0), heads=heads,
        max_len=len(prompt) + steps, steps=steps)).tolist()


# ------------------------------------------------------------ pool units


def test_alloc_free_refcount(params):
    pool = PagedKVPool(params, HEADS, num_pages=9, page_len=PAGE_LEN)
    assert pool.capacity == 8 and pool.free_count() == 8
    a = pool.alloc(3)
    b = pool.alloc(2)
    assert len(set(a) | set(b)) == 5 and 0 not in a + b  # dummy never leaves
    assert pool.used_count() == 5
    pool.retain(a)          # a second referent
    pool.release(a)
    assert pool.used_count() == 5  # still held by the second referent
    assert pool.shared_count() == 0
    pool.release(a)
    assert pool.used_count() == 2 and pool.free_count() == 6
    pool.release(b)
    assert pool.used_count() == 0
    with pytest.raises(PagePoolExhausted):
        pool.alloc(pool.capacity + 1)


def test_dummy_page_is_pinned(params):
    pool = PagedKVPool(params, HEADS, num_pages=4, page_len=PAGE_LEN)
    got = pool.alloc(3)
    assert 0 not in got
    pool.release([0, 0])  # table padding slices may include the dummy
    assert pool.free_count() == 0  # no-ops: the dummy never frees


def test_prefix_cache_match_insert_and_limit(params):
    pool = PagedKVPool(params, HEADS, num_pages=32, page_len=PAGE_LEN)
    prompt = np.arange(10, dtype=np.int32)  # share limit = (9//4)*4 = 8
    assert pool.match_prefix(prompt) == (0, [])
    assert pool.misses == 1
    pages = pool.alloc(3)
    assert pool.insert_prefix(prompt, pages) == 2  # 2 full pages cacheable
    sl, shared = pool.match_prefix(prompt)
    assert sl == 8 and shared == pages[:2] and pool.hits == 1
    # the page holding the prompt's LAST token is never shared — it must be
    # re-prefilled (first-token logits) and decode writes continue into it
    exact = np.arange(8, dtype=np.int32)  # page-aligned prompt
    sl, shared2 = pool.match_prefix(exact)
    assert sl == 4  # limit = (7//4)*4: only the first page shares
    pool.release(shared + shared2)
    # a diverging prefix only shares the common pages (rolling-hash chain)
    fork = np.concatenate([np.arange(4), [99, 98, 97, 96], [1, 2]])
    sl, shared3 = pool.match_prefix(fork.astype(np.int32))
    assert sl == 4 and shared3 == pages[:1]
    pool.release(shared3)
    # cache refs keep pages alive after the row's own release
    pool.release(pages)
    assert pool.used_count() == pool.cached_count() == 2


def test_prefix_cache_lru_eviction_is_leaf_first(params):
    pool = PagedKVPool(params, HEADS, num_pages=8, page_len=PAGE_LEN)
    long = np.arange(13, dtype=np.int32)   # 3 cacheable pages (chain r-m-l)
    pages = pool.alloc(4)
    pool.insert_prefix(long, pages)
    pool.release(pages)
    assert pool.cached_count() == 3 and pool.free_count() == 4
    # demand more than free: evicts a cached page LEAF-first (deepest chain
    # entry — evicting a parent would orphan unreachable children)
    got = pool.alloc(5)
    assert len(got) == 5 and pool.evictions == 1
    assert pool.cached_count() == 2
    pool.release(got)
    # leaf went first: the surviving chain still matches two pages
    sl, shared = pool.match_prefix(long)
    assert sl == 8 and len(shared) == 2
    # a cached page with a live reader is NOT evictable; the chain root
    # with a cached child is not either — so nothing can evict here
    with pytest.raises(PagePoolExhausted):
        pool.alloc(pool.free_count() + 1)
    pool.release(shared)
    pool.alloc(pool.free_count() + 1)  # readers gone: the next leaf evicts
    assert pool.evictions == 2 and pool.cached_count() == 1


def test_copy_on_write_splits_shared_page(params):
    pool = PagedKVPool(params, HEADS, num_pages=8, page_len=PAGE_LEN)
    page = pool.alloc(1)[0]
    # mark the page with recognizable contents
    k0 = pool.pages["l0"][0]
    pool.pages["l0"] = (k0.at[page].set(7.0), pool.pages["l0"][1])
    table = np.array([page], np.int32)
    assert not pool.ensure_writable(table, 0)  # sole owner: no copy
    pool.retain([page])                        # now shared
    assert pool.ensure_writable(table, 0)
    fresh = int(table[0])
    assert fresh != page and pool.cow_copies == 1
    # the device copy really happened, and the original kept its referent
    assert bool(jnp.all(pool.pages["l0"][0][fresh] == 7.0))
    assert pool.used_count() == 2 and pool.shared_count() == 0
    assert not pool.ensure_writable(table, 0)  # fresh page: sole owner


def test_page_arithmetic(params):
    # one page: layers(2) x k&v(2) x page_len x kv_heads(2) x dh(8) x f32(4)
    assert kv_page_bytes(params, HEADS, 4) == 2 * 2 * 4 * 2 * 8 * 4
    assert kv_page_bytes(params, HEADS, 4, "bfloat16") == \
        kv_page_bytes(params, HEADS, 4) // 2
    # positions written: [0, n + steps - 1)
    assert request_pages(1, 1, 4) == 1
    assert request_pages(4, 1, 4) == 1   # steps=1: prompt pages only
    assert request_pages(4, 2, 4) == 2   # first decode write opens page 2
    assert request_pages(10, 4, 4) == 4  # ceil(13/4)
    with pytest.raises(ValueError):
        request_pages(0, 1, 4)
    # auto pool sizing covers every bucket's full-width slab extent + slack
    assert auto_num_pages(((8, 4),), 2, 4) == 1 + 2 * (3 + 1)
    with pytest.raises(ValueError, match="num_pages"):
        init_kv_pages(params, 1, 4, HEADS)


# ------------------------------------------------- paged program contracts


def test_chunked_prefill_matches_one_shot(params):
    """Prefilling a prompt in page-aligned chunks writes the same pages —
    and yields the same first token — as one chunk covering everything."""
    prompt = np.arange(16, dtype=np.int32) % 32
    ref = _ref(params, prompt, 4)
    for C in (4, 8, 16):
        pages = init_kv_pages(params, 16, PAGE_LEN, HEADS)
        table = np.zeros(6 + C // PAGE_LEN, np.int32)
        table[:5] = range(1, 6)
        for cs in range(0, 16, C):
            pages, first = lm_prefill_paged(
                params, pages, table, prompt[cs:cs + C], cs, 16,
                heads=HEADS, page_len=PAGE_LEN)
        assert int(first) == ref[16]
        # decode the rest through the paged program
        out = [int(first)]
        positions = np.array([16], np.int32)
        cur = np.array([first], np.int32)
        done = np.array([1], np.int32)
        z = np.zeros(1, np.int32)
        for _ in range(3):
            pages, nxt = lm_decode_paged(
                params, pages, table[None, :5], positions, cur, done,
                z.astype(np.uint32), z.astype(np.float32),
                np.ones(1, np.float32), z, heads=HEADS, page_len=PAGE_LEN)
            out.append(int(np.asarray(nxt)[0]))
            positions += 1
            done += 1
            cur[0] = out[-1]
        assert out == ref[16:]


# --------------------------------------------------------- engine: sharing


def test_prefix_share_bit_identity(params, tmp_path):
    """Two requests sharing a system prompt produce outputs identical to
    unshared runs, the second admission is a prefix-cache hit, and its
    shared pages are counted. The COW/read-sharing invariant end to end."""
    log = EventLog(str(tmp_path / "serve.jsonl"))
    system = (np.arange(12) % 32).astype(np.int32)  # 3 full shared pages
    pa = np.concatenate([system, [7, 7]]).astype(np.int32)
    pb = np.concatenate([system, [9, 8]]).astype(np.int32)
    # unshared references (fresh engine per request: nothing cached)
    ref_a, ref_b = _ref(params, pa, 3), _ref(params, pb, 3)
    with _engine(params, max_batch=2, log=log) as eng:
        ra = eng.submit(Request(prompt=pa, steps=3)).result(timeout=60)
        rb = eng.submit(Request(prompt=pb, steps=3)).result(timeout=60)
        assert ra.status == rb.status == STATUS_OK
        assert ra.tokens.tolist() == ref_a
        assert rb.tokens.tolist() == ref_b
        assert ra.metrics["shared_pages"] == 0
        assert rb.metrics["shared_pages"] == 3  # the system prompt's pages
        snap = eng.metrics.snapshot()
        assert snap["prefix_hits"] == 1 and snap["prefix_misses"] == 1
        # the cache keeps the system prompt's pages alive across retires
        assert eng._kvpool.cached_count() >= 3
        # third sharer, co-resident with nothing: still identical
        rc = eng.submit(Request(prompt=pa, steps=2)).result(timeout=60)
        assert rc.tokens.tolist() == ref_a[:len(pa) + 2]
        assert rc.metrics["shared_pages"] == 3
    # the shared-page reuse skipped prefill work: b's prefill records cover
    # only the tail beyond the shared prefix
    chunks = [r["chunk"] for r in log.read()
              if r.get("kind") == "serve" and r.get("ev") == "prefill"]
    assert [0, 14] in chunks          # a: full prompt from position 0
    assert [12, 2] in chunks          # b: resumed at the shared boundary


def test_report_paging_line(params, tmp_path):
    """obs.report renders the prefix-hit-rate + page-occupancy line from
    the ev="page" stream alone."""
    log = EventLog(str(tmp_path / "serve.jsonl"))
    system = (np.arange(12) % 32).astype(np.int32)
    with _engine(params, log=log) as eng:
        for tail in ([1, 2], [3, 4]):
            h = eng.submit(Request(
                prompt=np.concatenate([system, tail]).astype(np.int32),
                steps=2))
            assert h.result(timeout=60).status == STATUS_OK
    events, skipped = obs_report.load_events(str(tmp_path / "serve.jsonl"))
    assert skipped == 0
    text = obs_report.analyze(events)
    assert "paging: prefix cache 1/2 admissions hit (50.0%" in text
    assert "page occupancy peak" in text


@pytest.mark.parametrize("selector", ["paged", "rowlevel", "serve_paged"])
def test_backend_selectors_are_removed(params, selector):
    """There is one scheduler and one KV backend, and nothing selects
    another: the two constructor kwargs are gone from the signature and the
    config field is unknown."""
    if selector == "serve_paged":
        with pytest.raises(AttributeError, match="serve_paged"):
            with mt.config_context(serve_paged=False):
                pass
    else:
        with pytest.raises(TypeError, match=selector):
            _engine(params, **{selector: False})


# ------------------------------------------------- engine: chunked prefill


def test_chunked_prefill_interleaves_decode(params, tmp_path):
    """The TTFT-under-load contract: a long prompt prefills across worker
    iterations in bounded chunks, and a co-resident short request's decode
    steps run BETWEEN those chunks instead of waiting out the whole
    prefill."""
    log = EventLog(str(tmp_path / "serve.jsonl"))
    eng = _engine(params, log=log, start=False, prefill_chunk=PAGE_LEN)
    try:
        short = eng.submit(Request(prompt=[1, 2], steps=4))
        long = eng.submit(Request(
            prompt=(np.arange(16) % 32).astype(np.int32), steps=2))
        eng.start()
        assert short.result(timeout=60).status == STATUS_OK
        assert long.result(timeout=60).status == STATUS_OK
        assert short.result().tokens.tolist() == _ref(params, [1, 2], 4)
    finally:
        eng.close()
    recs = [r for r in log.read() if r.get("kind") == "serve"
            and r.get("ev") in ("prefill", "step")]
    long_rid = long.request.rid
    chunk_idx = [i for i, r in enumerate(recs)
                 if r["ev"] == "prefill" and r.get("rid") == long_rid]
    assert len(chunk_idx) == 4  # 16 tokens / 4-token chunks, resumable
    starts = [recs[i]["chunk"][0] for i in chunk_idx]
    assert starts == [0, 4, 8, 12]
    between = [r["ev"] for r in recs[chunk_idx[0]:chunk_idx[-1]]]
    assert "step" in between, (
        "no decode step interleaved with the long prompt's chunks")


@KERNELS
def test_prefill_fault_retries_resumably(params, kernel):
    """Satellite: chunked prefill is resumable across injected
    serve.prefill faults — a mid-prefill fault frees the row's pages, the
    retry re-runs from its (re-matched) shared prefix, and the output
    stays bit-identical. Without attempt budget the request errors and
    the engine keeps serving."""
    prompt = (np.arange(16) % 32).astype(np.int32)
    eng = _engine(params, start=False, prefill_chunk=2 * PAGE_LEN,
                  decode_kernel=kernel)
    try:
        eng.warmup()
        with faults.injected("serve.prefill", RaiseFault(times=1)):
            h = eng.submit(Request(prompt=prompt, steps=3, max_attempts=2))
            eng.start()
            r = h.result(timeout=60)
        assert r.status == STATUS_OK, (r.status, r.reason)
        assert r.metrics["attempt"] == 2
        assert r.tokens.tolist() == _ref(params, prompt, 3)
        snap = eng.metrics.snapshot()
        assert snap["retries"] == 1
        # budget-exhausted path: error Result, engine unharmed
        with faults.injected("serve.prefill", RaiseFault(times=1)):
            bad = eng.submit(Request(prompt=[5, 6], steps=2))
            r = bad.result(timeout=60)
            assert r.status == STATUS_ERROR and "FaultInjected" in r.reason
        ok = eng.submit(Request(prompt=[5, 6], steps=2)).result(timeout=60)
        assert ok.status == STATUS_OK
        # every page reservation released exactly once: only cache-held
        # pages remain
        pool = eng._kvpool
        assert pool.used_count() == pool.cached_count()
    finally:
        eng.close()
    assert eng._queue.bytes_in_flight == 0


def test_fault_mid_chunk_stream_keeps_neighbors(params):
    """A serve.prefill fault on one row's LATER chunk leaves co-resident
    decoding rows untouched (the fault's blast radius is one row)."""
    eng = _engine(params, start=False, prefill_chunk=PAGE_LEN, max_batch=2)
    try:
        eng.warmup()
        neighbor = eng.submit(Request(prompt=[3, 1], steps=4))
        long = eng.submit(Request(
            prompt=(np.arange(16) % 32).astype(np.int32), steps=2))
        # fire on the long prompt's SECOND chunk (the first arrival is its
        # chunk 0; the neighbor's 2-token prefill is one dispatch earlier)
        with faults.injected("serve.prefill",
                             RaiseFault(schedule=Schedule(fire_on=[2]))):
            eng.start()
            rn = neighbor.result(timeout=60)
            rl = long.result(timeout=60)
        assert rn.status == STATUS_OK
        assert rn.tokens.tolist() == _ref(params, [3, 1], 4)
        assert rl.status == STATUS_ERROR and "FaultInjected" in rl.reason
    finally:
        eng.close()
    assert eng._queue.bytes_in_flight == 0


# ----------------------------------------------- compiles, admission units


def test_paged_compiles_bounded_three_per_bucket(params, compile_count):
    """≤ 3 compiled programs per bucket for ANY knob mix: the chunked
    prefill + decode pair per bucket plus ONE pool-wide page-copy program.
    warmup() pays them all; traffic — ragged lengths, shared prefixes,
    eos, mixed sampling knobs, multi-chunk prompts — adds ZERO."""
    from marlin_tpu.models.transformer import kv_page_copy

    probes = [f._cache_size
              for f in (lm_prefill_paged, lm_decode_paged, kv_page_copy)]
    before = sum(p() for p in probes)
    with _engine(params, prefill_chunk=2 * PAGE_LEN) as eng:
        assert eng.warmup() == len(BUCKETS)
        grew = sum(p() for p in probes) - before
        assert grew <= 2 * len(BUCKETS) + 1, \
            f"warmup compiled {grew} paged programs for {BUCKETS}"
        system = (np.arange(8) % 32).astype(np.int32)
        with compile_count() as c:
            hs = [eng.submit(Request(prompt=[1] * n, steps=2))
                  for n in (2, 5, 8, 12, 16)]
            hs.append(eng.submit(Request(
                prompt=np.concatenate([system, [4]]).astype(np.int32),
                steps=3, temperature=0.9, top_p=0.9, top_k=5, seed=11)))
            hs.append(eng.submit(Request(
                prompt=np.concatenate([system, [6]]).astype(np.int32),
                steps=3, eos=1)))
            for h in hs:
                assert h.result(timeout=60).status == STATUS_OK
        assert c.count == 0, \
            f"paged traffic recompiled after warmup ({c.count} compiles)"
    assert eng._queue.bytes_in_flight == 0


def test_unaligned_prefix_tail_compiles_nothing(params, compile_count,
                                                tmp_path):
    """Regression (review): a prefix hit whose shared_len is page- but not
    CHUNK-aligned resumes mid-chunk-grid; the final tail slice must be
    padded back to the compiled chunk width — a narrower chunk would
    compile a fresh program per residual width on the serving hot path."""
    log = EventLog(str(tmp_path / "serve.jsonl"))
    with _engine(params, prefill_chunk=2 * PAGE_LEN, log=log) as eng:
        eng.warmup()
        head = (np.arange(PAGE_LEN) % 32).astype(np.int32)  # one full page
        tail_a = (np.arange(12) % 7 + 20).astype(np.int32)
        tail_b = (np.arange(12) % 5 + 1).astype(np.int32)
        a = np.concatenate([head, tail_a]).astype(np.int32)  # n=16
        b = np.concatenate([head, tail_b]).astype(np.int32)  # n=16
        ra = eng.submit(Request(prompt=a, steps=2)).result(timeout=60)
        assert ra.status == STATUS_OK
        with compile_count() as c:
            # b shares exactly ONE page (shared_len=4, chunk=8): prefill
            # resumes at 4 and its FINAL slice [12:20) runs past the
            # 16-token padded prompt — the short-tail case the padding
            # restores to the compiled width
            rb = eng.submit(Request(prompt=b, steps=2)).result(timeout=60)
        assert rb.status == STATUS_OK
        # read the tally BEFORE the reference decode adds its own program
        assert c.count == 0, \
            f"unaligned prefix tail recompiled ({c.count} compiles)"
        assert rb.metrics["shared_pages"] == 1
        assert rb.tokens.tolist() == _ref(params, b, 2)
    # the chunk stream really took the unaligned path: resume at 4, then
    # a short final chunk from 12
    chunks = [r["chunk"] for r in log.read()
              if r.get("ev") == "prefill" and r.get("rid") == rb.rid]
    assert chunks == [[4, 8], [12, 4]], chunks


def test_second_bucket_failure_after_pool_drop_is_inert(params):
    """Regression (review): when one decode call's failure consumes the
    shared slab and drops the pool, a second call's failure landing in
    the same step loop must be a no-op — not a KeyError on the cleared
    pools map that masquerades as a worker crash."""
    eng = _engine(params, start=False)
    try:
        pools = {}
        from marlin_tpu.serving.kvpool import PagedGroup

        pool = eng._ensure_kvpool()
        for bucket in BUCKETS:
            pools[bucket] = PagedGroup(bucket, eng.max_batch, PAGE_LEN,
                                       eng._prefill_chunk)
        late = [(g, 0, object()) for g in pools.values()]
        eng._drop_paged_pool(pool, pools, "slab consumed (simulated)")
        assert pools == {} and eng._kvpool is None
        # the second call's handler observes the drop and returns
        eng._fail_paged_call(pool, pools, late, RuntimeError("late"))
        # a STALE generation's drop must not clear the live pool: rebind,
        # then drop with the old (dead) pool object — the live reference
        # survives
        live = eng._ensure_kvpool()
        eng._drop_paged_pool(pool, {}, "stale straggler")
        assert eng._kvpool is live
    finally:
        eng.close()


def test_page_unit_admission(params):
    """Admission charges actual pages, not the bucket worst case: a short
    request's reservation is its own extent, an impossible request rejects
    at submit, and the byte budget counts page units."""
    unit = kv_page_bytes(params, HEADS, PAGE_LEN)
    eng = _engine(params, start=False,
                  num_pages=1 + request_pages(2, 2, PAGE_LEN) * 3,
                  hbm_budget_bytes=0)
    try:
        # pool capacity (3 short requests' worth) gates impossible shapes
        r = eng.submit(Request(prompt=[1] * 16, steps=4)).result(timeout=1)
        assert r.status == STATUS_REJECTED and "KV pages" in r.reason
    finally:
        eng.close()
    eng = _engine(params, start=False, hbm_budget_bytes=10 * unit)
    try:
        # a (16, 4) bucket row would cost 5 pages' bytes under slab-era
        # worst-case accounting; paged charges this 2-token request 1 page
        h = eng.submit(Request(prompt=[1, 2], steps=2))
        assert eng._queue.bytes_in_flight == \
            request_pages(2, 2, PAGE_LEN) * unit
        for _ in range(9):  # nine more single-page requests fit
            eng.submit(Request(prompt=[1, 2], steps=2))
        r = eng.submit(Request(prompt=[1, 2], steps=2)).result(timeout=1)
        assert r.status == STATUS_REJECTED and "HBM" in r.reason
        eng.start()
        eng.drain()
        assert h.result(timeout=60).status == STATUS_OK
    finally:
        eng.close()
    assert eng._queue.bytes_in_flight == 0


@KERNELS
def test_every_retirement_path_frees_pages(params, kernel):
    """Satellite regression: eos / steps / submit-expiry / dispatch-expiry
    / prefill-fault / decode-fault / drain each release the row's pages
    exactly once — afterwards the pool holds only prefix-cache pages and
    the admission gate is fully drained (test_serving.py's expiring-burst
    test, per path)."""
    from tests.test_serving import FakeClock

    clock = FakeClock()
    eng = _engine(params, clock=clock, start=False, max_batch=2,
                  decode_kernel=kernel)
    try:
        eng.warmup()
        gen = _ref(params, [5, 3], 4)[2:]
        hs = [
            eng.submit(Request(prompt=[5, 3], steps=4, eos=gen[1])),  # eos
            eng.submit(Request(prompt=[1, 2], steps=2)),            # steps
            eng.submit(Request(prompt=[1, 2], steps=2, deadline=-1.0)),
            eng.submit(Request(prompt=[1, 2], steps=2, deadline=5.0)),
        ]
        clock.advance(10.0)  # expires the deadline=5.0 row at dispatch
        with faults.injected("serve.decode_step", RaiseFault(times=1)):
            eng.start()
            statuses = [h.result(timeout=60).status for h in hs]
        assert statuses[2] == statuses[3] == STATUS_EXPIRED
        assert set(statuses[:2]) <= {STATUS_OK, STATUS_ERROR}
        with faults.injected("serve.prefill", RaiseFault(times=1)):
            bad = eng.submit(Request(prompt=[9, 9], steps=2))
            assert bad.result(timeout=60).status == STATUS_ERROR
        tail = eng.submit(Request(prompt=[8, 8], steps=2))
        eng.drain()
        assert tail.result(timeout=60).status == STATUS_OK
        pool = eng._kvpool
        assert pool is not None
        assert pool.used_count() == pool.cached_count()  # rows all freed
        assert pool.shared_count() == 0
        audit = eng.kvpool_audit()  # every invariant, not just the counts
        assert audit["ok"], audit["errors"]
    finally:
        eng.close()
    assert eng.pending() == 0
    assert eng._queue.bytes_in_flight == 0


def test_crash_recovery_rebuilds_pool_and_preserves_identity(params):
    """Supervisor recovery over a paged pool: the pool (and its prefix
    cache) is dropped and rebuilt zeroed, live rows requeue with their
    page-unit reservations carried, and retried greedy output stays
    bit-identical — including a row that had prefix-shared pages in the
    dead pool."""
    system = (np.arange(12) % 32).astype(np.int32)
    pa = np.concatenate([system, [7]]).astype(np.int32)
    eng = _engine(params, max_batch=2)
    eng.warmup()
    sup = Supervisor(eng, backoff_s=0.005, poll_s=0.02)
    try:
        warm = eng.submit(Request(prompt=pa, steps=2)).result(timeout=60)
        assert warm.status == STATUS_OK  # seeds the prefix cache
        pool_before = eng._kvpool
        with faults.injected("serve.worker_crash", RaiseFault(times=1)):
            hs = [eng.submit(Request(prompt=pa, steps=3, max_attempts=3)),
                  eng.submit(Request(prompt=[4, 2], steps=3,
                                     max_attempts=3))]
            results = [h.result(timeout=120) for h in hs]
        assert all(r.status == STATUS_OK for r in results), \
            [(r.status, r.reason) for r in results]
        assert results[0].tokens.tolist() == _ref(params, pa, 3)
        assert results[1].tokens.tolist() == _ref(params, [4, 2], 3)
        assert sup.restart_count >= 1
        assert eng._kvpool is not pool_before  # rebuilt zeroed
    finally:
        sup.close()
        eng.close()
    assert eng._queue.bytes_in_flight == 0


# ------------------------------------------------------------- chaos soak


@pytest.mark.slow
def test_paging_soak_with_worker_kills(params):
    """Slow chaos soak: shared-prefix traffic over a paged pool while
    seeded serve.worker_crash kills the worker — every handle terminal
    exactly once, ok results bit-identical, pool accounting clean."""
    rng = np.random.default_rng(17)
    system = (np.arange(12) % 32).astype(np.int32)
    n_req = 160
    eng = _engine(params, queue_depth=n_req, num_pages=2048)
    eng.warmup()
    sup = Supervisor(eng, backoff_s=0.002, poll_s=0.01,
                     restart_max=1000, restart_window_s=1e6)
    handles, lock = [], threading.Lock()

    def submitter(seed):
        r = np.random.default_rng(seed)
        for _ in range(n_req // 4):
            if r.random() < 0.5:  # half the traffic shares the system prompt
                prompt = np.concatenate(
                    [system, r.integers(0, 32, int(r.integers(1, 4)))])
            else:
                prompt = r.integers(0, 32, int(r.integers(1, 17)))
            h = eng.submit(Request(prompt=prompt.astype(np.int32),
                                   steps=int(r.integers(1, 5)),
                                   max_attempts=8))
            with lock:
                handles.append(h)
            time.sleep(0.001)

    try:
        with faults.injected(
                "serve.worker_crash",
                RaiseFault(times=-1, schedule=Schedule(seed=5, rate=0.02))):
            threads = [threading.Thread(target=submitter, args=(40 + i,))
                       for i in range(4)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            eng.drain()
            audit = eng.kvpool_audit()  # chaos postcondition: pages leak
            assert audit["ok"], audit["errors"]  # nowhere, ever
        results = [h.result(timeout=600) for h in handles]
    finally:
        sup.close()
        eng.close()
    assert len(results) == n_req and all(h.done() for h in handles)
    statuses = [r.status for r in results]
    assert set(statuses) <= {STATUS_OK, STATUS_ERROR}
    assert statuses.count(STATUS_OK) >= n_req * 0.9
    for h, r in zip(handles, results):
        if r.status == STATUS_OK:
            steps = len(r.tokens) - len(h.request.prompt)
            ref = _ref(params, h.request.prompt, steps)
            assert r.tokens.tolist() == ref[:len(r.tokens)]
    snap = eng.metrics.snapshot()
    assert snap["completed"] == statuses.count(STATUS_OK)
    assert snap["prefix_hits"] > 0  # sharing really happened under chaos
    assert eng.pending() == 0
    assert eng._queue.bytes_in_flight == 0
