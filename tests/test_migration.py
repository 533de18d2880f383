"""Cross-replica KV migration suite (serving/kvpool.py export/import,
serving/engine.py freeze/adopt, serving/router.py migrate-then-restart;
docs/serving.md "Migration protocol", docs/robustness.md).

The acceptance bars, bottom up:

- **Wire format**: a flipped byte or truncated blob ALWAYS raises
  :class:`MigrationCorruptError` on import — a silently-corrupt KV page
  decodes garbage forever, so the CRC frame is load-bearing, not
  decorative.
- **Re-dedup**: importing rows whose prompts share a prefix re-allocates
  through the normal :meth:`match_prefix` path, so shared pages collapse
  again on the target instead of arriving duplicated.
- **Exactly-once + bit-identity**: rows frozen mid-decode on one engine
  and adopted by another resume from their exact cursor — final outputs
  byte-equal an uninterrupted :func:`lm_generate`, every handle reaches
  one terminal Result, the admission reservation travels exactly once
  (:meth:`AdmissionQueue.adopt` on the target, release on the source).
- **Rotation without work loss**: ``Router.rolling_restart`` under
  continuous load drops nothing AND restarts nothing from token 0
  (``retries == 0`` — the PR 7 retry counter is exactly the token-0
  restart counter).
- **Chaos**: a fault on any ``serve.migrate`` leg (export / import /
  adopt / warm) degrades to the PR 7 retry path — every request still
  reaches one ok Result and :meth:`PagedKVPool.audit` stays clean on
  every replica (pages leak nowhere).
"""

import random
import threading
import time

import numpy as np
import pytest

import jax

from marlin_tpu.models import TransformerLM
from marlin_tpu.models.transformer import lm_generate
from marlin_tpu.obs import memledger
from marlin_tpu.obs.exposition import (kvpool_payload,
                                       register_kvpool_provider,
                                       unregister_kvpool_provider)
from marlin_tpu.serving import (
    STATUS_OK,
    PagedKVPool,
    Request,
    Router,
    ServeEngine,
)
from marlin_tpu.serving.engine import MigrationError
from marlin_tpu.serving.kvpool import MigrationCorruptError
from marlin_tpu.serving.request import AdmissionQueue
from marlin_tpu.utils import faults
from marlin_tpu.utils.faults import DelayFault, RaiseFault, Schedule

HEADS = 2
BUCKETS = ((8, 8), (16, 8))
PAGE_LEN = 4


@pytest.fixture(scope="module")
def params():
    return TransformerLM(vocab=32, d_model=16, heads=HEADS, layers=2,
                         seed=9).init_params()


def _engine(params, **kw):
    kw.setdefault("buckets", BUCKETS)
    kw.setdefault("max_batch", 4)
    kw.setdefault("queue_depth", 64)
    kw.setdefault("page_len", PAGE_LEN)
    kw.setdefault("num_pages", 256)
    return ServeEngine(params, HEADS, **kw)


def _factory(params, **kw):
    def make():
        eng = _engine(params, **kw)
        # migration binds into live slots during rotation: an unwarmed
        # replacement would sit in first-traffic XLA compile for seconds
        # while the freeze deadline of the NEXT rotation ticks
        eng.warmup()
        return eng
    return make


def _ref(params, prompt, steps, heads=HEADS):
    prompt = np.asarray(prompt, np.int32)
    return np.asarray(lm_generate(
        params, prompt, jax.random.key(0), heads=heads,
        max_len=len(prompt) + steps, steps=steps)).tolist()


def _fill_pages(pool, pages, seed):
    """Write recognizable content into ``pages`` of ``pool``."""
    rng = np.random.default_rng(seed)
    host = pool._host_pages()
    for name in sorted(host):
        for half in (0, 1):
            arr = host[name][half]
            arr[pages] = rng.standard_normal(
                (len(pages),) + arr.shape[1:]).astype(arr.dtype)
    pool._flush_host(host)


def _page_bytes(pool, pages):
    host = pool._host_pages()
    return b"".join(np.ascontiguousarray(host[name][half][list(pages)])
                    .tobytes()
                    for name in sorted(host) for half in (0, 1))


# ------------------------------------------------------------- wire format


def test_export_import_roundtrip_and_corruption(params):
    """A row blob round-trips page contents exactly; a flipped byte or a
    truncation anywhere always raises MigrationCorruptError on import —
    and a failed import leaks no pages (audit stays clean)."""
    src = PagedKVPool(params, HEADS, num_pages=16, page_len=PAGE_LEN)
    prompt = np.arange(1, 13, dtype=np.int32)          # 12 toks = 3 pages
    pages = src.alloc(3)
    _fill_pages(src, pages, seed=42)
    row = {"rid": 7, "prompt": prompt.tolist(), "pages": pages,
           "pf_next": -1}
    blob = src.export_rows([row])

    dst = PagedKVPool(params, HEADS, num_pages=16, page_len=PAGE_LEN)
    out = dst.import_rows(blob)
    assert len(out) == 1 and out[0]["rid"] == 7
    assert _page_bytes(dst, out[0]["pages"]) == _page_bytes(src, pages)
    assert dst.audit()["ok"]

    # corruption: flip one byte in a page body, then in the meta chunk,
    # then truncate — every variant must raise, never import garbage
    fresh = PagedKVPool(params, HEADS, num_pages=16, page_len=PAGE_LEN)
    for cut in (len(blob) // 2, 40):
        bad = bytearray(blob)
        bad[cut] ^= 0xFF
        with pytest.raises(MigrationCorruptError):
            fresh.import_rows(bytes(bad))
    with pytest.raises(MigrationCorruptError):
        fresh.import_rows(blob[:-7])
    with pytest.raises(MigrationCorruptError):
        fresh.import_rows(b"")
    audit = fresh.audit()
    assert audit["ok"], audit["errors"]
    assert fresh.used_count() == 0      # failed imports released everything


def test_import_rededuplicates_shared_prefix(params):
    """Two exported rows sharing a prompt prefix collapse back onto shared
    pages on the target: the first row's completed prompt publishes its
    pages to the prefix cache, the second matches instead of importing."""
    src = PagedKVPool(params, HEADS, num_pages=32, page_len=PAGE_LEN)
    shared = list(range(1, 9))                          # 8 toks = 2 pages
    rows = []
    for rid, tail in enumerate(([9, 10, 11, 12], [13, 14, 15, 16])):
        pages = src.alloc(3)
        _fill_pages(src, pages, seed=rid)
        rows.append({"rid": rid, "prompt": shared + tail, "pages": pages,
                     "pf_next": -1})
    blob = src.export_rows(rows)

    dst = PagedKVPool(params, HEADS, num_pages=32, page_len=PAGE_LEN)
    out = dst.import_rows(blob)
    assert out[0]["n_shared"] == 0              # nothing cached yet
    assert out[1]["n_shared"] == 2              # the two full shared pages
    assert dst.hits > 0
    assert out[0]["pages"][:2] == out[1]["pages"][:2]
    assert out[0]["pages"][2] != out[1]["pages"][2]
    audit = dst.audit()
    assert audit["ok"], audit["errors"]


# ------------------------------------------------------- reservation unit


def test_admission_queue_adopt_carries_reservation():
    """adopt() charges a moved reservation unconditionally — depth, byte
    budget, and even closed state never bounce work that was ALREADY
    admitted on the source replica (rejecting it would drop it)."""
    q = AdmissionQueue(depth=1, budget_bytes=100)
    q.adopt(60)
    q.adopt(60)                 # over depth AND budget: still lands
    assert q.count == 2
    assert q.bytes_in_flight == 120
    q.close("draining")
    q.adopt(5)                  # closed: still lands
    assert q.count == 3
    q.release(60), q.release(60), q.release(5)
    assert q.count == 0 and q.bytes_in_flight == 0


# ------------------------------------------------------------------ audit


def test_audit_clean_and_seeded_violations(params):
    """audit() is quiet on a clean pool and names every seeded violation:
    the pinned dummy on the free list, a free page with a live refcount,
    and a leaked page (refcount 0, not on the free list)."""
    pool = PagedKVPool(params, HEADS, num_pages=8, page_len=PAGE_LEN)
    pages = pool.alloc(2)
    assert pool.audit()["ok"]

    pool._free.append(0)                              # dummy "freed"
    audit = pool.audit()
    assert not audit["ok"]
    assert any("dummy page 0" in e for e in audit["errors"])
    pool._free.remove(0)

    pool._free.append(pages[0])                       # freed but referenced
    audit = pool.audit()
    assert not audit["ok"]
    assert any(f"free page {pages[0]}" in e for e in audit["errors"])
    pool._free.remove(pages[0])

    pool._ref[pages[1]] = 0                           # leaked
    audit = pool.audit()
    assert not audit["ok"]
    assert any("leaked" in e for e in audit["errors"])
    pool._ref[pages[1]] = 1

    assert pool.audit()["ok"]


def test_debug_kvpool_endpoint(params):
    """A paged engine self-registers on /debug/kvpool: the payload is 200
    while its pool audits clean, 503 the moment any provider reports a
    violation, and the provider unregisters at close."""
    eng = _engine(params)
    try:
        code, payload = kvpool_payload()
        mine = [p for p in payload["pools"] if p["name"] == eng._name]
        assert code == 200 and payload["status"] == "ok"
        assert mine and mine[0]["ok"]

        register_kvpool_provider(
            "test-violated", lambda: {"ok": False, "errors": ["seeded"]})
        try:
            code, payload = kvpool_payload()
            assert code == 503 and payload["status"] == "violated"
        finally:
            unregister_kvpool_provider("test-violated")
    finally:
        eng.close()
    code, payload = kvpool_payload()
    assert all(p["name"] != eng._name for p in payload["pools"])


# ------------------------------------------------------- freeze and adopt


def test_freeze_adopt_midstream_bit_identical(params):
    """The tentpole invariant end to end: rows frozen MID-DECODE on engine
    A and adopted by engine B finish on B with outputs bit-identical to an
    uninterrupted reference decode, the queued backlog moves as-is, no
    request restarts from token 0 (retries == 0), and B's pool audits
    clean once drained."""
    memledger.reset_ledger()   # audit below must reflect THIS handoff only
    a, b = _engine(params), _engine(params)
    a.warmup(), b.warmup()
    steps = 8
    # hold A's worker 0.4s on its THIRD decode step: the freeze below
    # lands inside that window deterministically, with live rows that
    # have real decode progress behind them (a tiny warm model would
    # otherwise finish all 24 requests before any sleep-based freeze)
    with faults.injected("serve.decode_step",
                         DelayFault(seconds=0.4, times=1,
                                    schedule=Schedule(fire_on=[2]))):
        hs = [a.submit(Request(prompt=[3, 1 + i % 4, 2], steps=steps))
              for i in range(24)]
        time.sleep(0.1)
    try:
        frozen = a.freeze_rows()
        assert frozen is not None and frozen["blob"] is not None
        assert not frozen["fallback"]
        res = b.adopt_rows(frozen)
        assert not res["fallback"]         # B was idle: every row binds
        for rid in res["adopted"]:         # reservation travels exactly once
            a._queue.release(frozen["entries"][rid].cost)
        assert b.adopt_entries(frozen["queued"])
        for e in frozen["queued"]:
            a._queue.release(e.cost)
        a.close()
        for h in hs:
            r = h.result(timeout=120)
            assert r.status == STATUS_OK, (r.status, r.reason)
            assert r.tokens.tolist() == _ref(params, h.request.prompt,
                                             steps)
        snap = b.metrics.snapshot()
        assert snap["migrated_in"] == len(res["adopted"])
        assert snap["retries"] == 0        # nobody restarted from token 0
        assert a._queue.count == 0 and a._queue.bytes_in_flight == 0
        # the memory ledger followed the handoff: the frozen blob was
        # debited exactly once on adopt (no migration bytes linger), the
        # closed source swept everything it still owned, and B — still
        # serving — is the only engine with a resident slab
        led = memledger.get_ledger()
        mem_audit = led.audit()
        assert mem_audit["ok"], mem_audit["errors"]
        assert led.totals().get("migration", 0) == 0
        assert led.owner_bytes(a._name) == 0
        assert led.owner_bytes(b._name) > 0   # B's slab is still resident
        b.drain()
        audit = b.kvpool_audit()
        assert audit["ok"], audit["errors"]
        # drain is terminal: B's finalize swept its ledger entries too
        assert led.owner_bytes(b._name) == 0
        assert led.audit()["ok"]
    finally:
        a.close(), b.close()


def test_migration_trace_continuity_cross_process(params, tmp_path):
    """Migration-proof traces: rows frozen on A and adopted on B — with
    the live in-process span objects stripped, exactly as a cross-process
    hop would arrive — keep ONE trace_id from enqueue on A through adopt
    and result on B, and the adopt-side hop span (serve.migrate.<rid>)
    parents into the exact span the freeze manifest carried."""
    from marlin_tpu.utils.tracing import EventLog, set_default_event_log

    log = EventLog(str(tmp_path / "events.jsonl"))
    prev = set_default_event_log(log)
    a, b = _engine(params), _engine(params)
    a.warmup(), b.warmup()
    try:
        with faults.injected("serve.decode_step",
                             DelayFault(seconds=0.4, times=1,
                                        schedule=Schedule(fire_on=[2]))):
            hs = [a.submit(Request(prompt=[3, 1 + i % 4, 2], steps=8))
                  for i in range(8)]
            time.sleep(0.1)
        frozen = a.freeze_rows()
        assert frozen is not None and frozen["entries"]
        # every live row captured its submit-time span; remember it, then
        # strip the in-process objects so ONLY the manifest can carry the
        # trace across the hop (what a pickle/process boundary does)
        orig = {rid: e.trace for rid, e in frozen["entries"].items()}
        assert all(t is not None for t in orig.values())
        for e in frozen["entries"].values():
            e.trace = None
        res = b.adopt_rows(frozen)
        for rid in res["adopted"]:
            a._queue.release(frozen["entries"][rid].cost)
        assert b.adopt_entries(frozen["queued"] + res["fallback"])
        for e in frozen["queued"] + res["fallback"]:
            a._queue.release(e.cost)
        a.close()
        for h in hs:
            r = h.result(timeout=120)
            assert r.status == STATUS_OK, (r.status, r.reason)
        assert res["adopted"]  # the continuity claim needs real adoptions
        b.drain()
    finally:
        a.close(), b.close()
        set_default_event_log(prev)
        log.close()
    serve = [r for r in log.read() if r["kind"] == "serve"]
    by_rid = {}
    for rec in serve:
        if "rid" in rec and "trace_id" in rec:
            by_rid.setdefault(rec["rid"], []).append(rec)
    for rid in res["adopted"]:
        recs = by_rid.get(rid, [])
        assert recs, f"rid {rid} left no traced serve records"
        tids = {r["trace_id"] for r in recs}
        assert tids == {orig[rid].trace_id}, (rid, tids)
        evs = {r.get("ev") for r in recs}
        assert "result" in evs  # B retired it inside the SAME trace
        hops = [r for r in recs
                if r.get("ev") == "page" and r.get("action") == "adopt"]
        assert hops, f"rid {rid} adopt record missing"
        for rec in hops:
            assert rec.get("parent_id") == orig[rid].span_id, (rid, rec)


def test_adopt_rows_rejects_wrong_target(params):
    """adopt_rows on a non-running engine raises MigrationError instead of
    silently losing the frozen work (the router falls back to the retry
    path on that signal)."""
    a, b = _engine(params), _engine(params)
    a.warmup()
    try:
        with faults.injected("serve.decode_step",
                             DelayFault(seconds=0.4, times=1,
                                        schedule=Schedule(fire_on=[2]))):
            hs = [a.submit(Request(prompt=[3, 1], steps=8))
                  for _ in range(3)]
            time.sleep(0.1)               # rows live mid-decode
        frozen = a.freeze_rows()
        assert frozen["entries"]          # the raise below needs real work
        b.drain()
        with pytest.raises(MigrationError):
            b.adopt_rows(frozen)
        # the frozen work is still intact: a fresh engine can take it
        c = _engine(params)
        try:
            res = c.adopt_rows(frozen)
            for rid in res["adopted"]:
                a._queue.release(frozen["entries"][rid].cost)
            assert c.adopt_entries(frozen["queued"] + res["fallback"])
            for e in frozen["queued"] + res["fallback"]:
                a._queue.release(e.cost)
            for h in hs:
                assert h.result(timeout=120).status == STATUS_OK
        finally:
            c.close()
    finally:
        a.close(), b.close()


def test_prefix_cache_warm_transfer(params):
    """export_prefixes/import_prefixes move the hot cache entries: a
    freshly-warmed engine serves a shared-prefix prompt with cache hits
    its empty pool could never have had."""
    a, b = _engine(params), _engine(params)
    a.warmup(), b.warmup()
    shared = [7, 3, 5, 2, 6, 1, 4, 2]               # 2 full pages
    try:
        h = a.submit(Request(prompt=shared + [9, 8], steps=2))
        assert h.result(timeout=60).status == STATUS_OK
        blob = a.export_prefixes(8)
        assert blob is not None
        assert b.import_prefixes(blob) > 0
        h2 = b.submit(Request(prompt=shared + [11, 10], steps=2))
        r2 = h2.result(timeout=60)
        assert r2.status == STATUS_OK
        assert r2.tokens.tolist() == _ref(params, shared + [11, 10], 2)
        snap = b.metrics.snapshot()
        assert snap["prefix_hits"] > 0
        audit = b.kvpool_audit()
        assert audit["ok"], audit["errors"]
    finally:
        a.close(), b.close()


# ----------------------------------------------------------------- router


def test_rolling_restart_migrates_without_token0_restarts(params):
    """The rotation acceptance: a full fleet rotation under continuous
    offered load (~64 req/s) drops ZERO requests and restarts ZERO from
    token 0 — live rows migrate mid-stream (migrated_in > 0, retries ==
    0) and every output is bit-identical to the reference."""
    router = Router(_factory(params, max_batch=8, queue_depth=512,
                             num_pages=512),
                    replicas=2,
                    supervisor_kw=dict(backoff_s=0.005, poll_s=0.02),
                    rng=random.Random(7))
    handles, lock = [], threading.Lock()
    stop = threading.Event()

    def pump():
        i = 0
        while not stop.is_set():
            h = router.submit(Request(prompt=[5, 1 + i % 4], steps=4))
            with lock:
                handles.append(h)
            i += 1
            time.sleep(0.015)              # ~64 req/s

    thread = threading.Thread(target=pump)
    try:
        thread.start()
        time.sleep(0.1)
        # pin live mid-stream rows on the FIRST-rotated replica only: long
        # rows land in the (16, 8) bucket the pump never touches, and the
        # match-gated delay wedges just the worker decoding them — the
        # target replica keeps draining its own traffic at full speed, so
        # its slots are free when the frozen rows arrive (wedging both
        # workers would overload the target and force legitimate
        # fallbacks, which is the OTHER test's scenario)
        first = router._replicas[0].engine
        with faults.injected("serve.decode_step",
                             DelayFault(seconds=0.5, times=1,
                                        match="16x8")):
            with lock:
                handles.extend(first.submit(
                    Request(prompt=[2, 4, 6, 1, 3, 5, 2, 4, 6], steps=8))
                    for _ in range(4))
            time.sleep(0.05)
            rotated = router.rolling_restart()
        stop.set()
        thread.join()
        router.drain()
        assert set(rotated) == {0, 1}
        results = [h.result(timeout=120) for h in handles]
        assert len(results) >= 8
        for h, r in zip(handles, results):
            assert r.status == STATUS_OK, (r.status, r.reason)
            assert r.tokens.tolist() == _ref(params, h.request.prompt,
                                             h.request.steps)
        snap = router.snapshot()
        assert snap["migrated_in"] >= 1        # rows moved mid-stream...
        assert snap["retries"] == 0            # ...and none from token 0
        for rep in router._replicas:
            audit = rep.engine.kvpool_audit()
            assert audit["ok"], audit["errors"]
    finally:
        stop.set()
        router.close()


def test_prefix_affine_routing_concentrates(params):
    """Shared-prefix requests rendezvous onto ONE replica (whichever wins
    the hash), so its warm cache serves nearly every lookup — the bench
    acceptance's hit-parity bar, shrunk to suite scale. The very first
    request of the prefix routes load-aware (affinity engages on repeat),
    so at most one request may land off the rendezvous winner."""
    router = Router(_factory(params), replicas=2, supervise=False,
                    rng=random.Random(3))
    shared = [7, 3, 5, 2]                  # one full page: the route key
    try:
        for i in range(12):
            h = router.submit(Request(prompt=shared + [5 + i % 8, 1 + i],
                                      steps=2))
            assert h.result(timeout=60).status == STATUS_OK
        snap = router.snapshot()
        per = [s["submitted"] for s in snap["replicas"].values()]
        assert max(per) >= 11              # all but the first touch rode
        assert sorted(per)[0] <= 1         # the rendezvous winner
        assert snap["prefix_hit_rate"] >= 0.6
    finally:
        router.close()


def test_prefix_affinity_engages_only_on_repeat(params):
    """A first-page key's FIRST occurrence routes load-aware (power-of-two),
    not affine — a one-off prompt has no warm cache to win, and pinning it
    to a hash-chosen replica regardless of queue depth costs tail latency
    under unique-prompt load. The second occurrence engages rendezvous."""
    from marlin_tpu.serving.router import (_prefix_route_key,
                                           _rendezvous_score)

    router = Router(_factory(params), replicas=2, supervise=False,
                    rng=random.Random(7))
    try:
        ready = [r for r in router._replicas if r.ready()]
        # find a prompt whose rendezvous winner is replica 1, so the affine
        # order [1, 0] is distinguishable from the idle-fleet load order
        for salt in range(64):
            prompt = [salt, 3, 5, 2, 9]
            key = _prefix_route_key(Request(prompt=prompt, steps=2), ready)
            order = sorted(ready, reverse=True,
                           key=lambda r: _rendezvous_score(key, r.idx))
            if order[0].idx == 1:
                break
        else:  # pragma: no cover - blake2b would have to be pathological
            pytest.fail("no salt made replica 1 the rendezvous winner")
        req = Request(prompt=prompt, steps=2)
        first = [r.idx for r in router._candidates(req)]
        assert first == [0, 1]             # load order: both idle, idx ties
        second = [r.idx for r in router._candidates(req)]
        assert second == [1, 0]            # seen before: rendezvous order
        assert len(router._seen_prefixes) == 1
    finally:
        router.close()


def test_prefix_affinity_knob_off(params):
    """serve_prefix_affinity=False restores pure power-of-two routing —
    the knob exists precisely so a pathological prefix distribution can't
    pin a fleet to one replica with no escape hatch."""
    from marlin_tpu.config import config_context

    with config_context(serve_prefix_affinity=False):
        router = Router(_factory(params), replicas=2, supervise=False,
                        rng=random.Random(5))
        try:
            shared = [7, 3, 5, 2]
            hs = [router.submit(Request(prompt=shared + [1 + i % 8],
                                        steps=2)) for i in range(16)]
            for h in hs:
                assert h.result(timeout=60).status == STATUS_OK
            per = [s["submitted"]
                   for s in router.snapshot()["replicas"].values()]
            assert all(p > 0 for p in per)     # load spread, not pinned
        finally:
            router.close()


# ------------------------------------------------------------------ chaos


@pytest.mark.parametrize("leg", ["export:", "import@", "adopt:"])
def test_kill_mid_migration_falls_back_to_retry(params, leg):
    """A fault on any serve.migrate leg mid-rotation degrades to the PR 7
    retry path: every request still reaches exactly one ok Result
    (bit-identical — the twin restarts from token 0 by design), no page
    leaks on any replica, and the rotation itself completes."""
    memledger.reset_ledger()
    router = Router(_factory(params, max_batch=8, queue_depth=512,
                             num_pages=512),
                    replicas=2,
                    supervisor_kw=dict(backoff_s=0.005, poll_s=0.02),
                    rng=random.Random(11))
    try:
        # wedge both workers so the rotation finds live rows — the fault
        # legs under test only fire when there is real work to export
        with faults.injected("serve.decode_step",
                             DelayFault(seconds=0.5, times=2)):
            hs = [router.submit(Request(prompt=[3, 1 + i % 4], steps=8))
                  for i in range(6)]
            time.sleep(0.05)                   # rows live mid-decode
            with faults.injected("serve.migrate",
                                 RaiseFault(times=1, match=leg)):
                rotated = router.rolling_restart()
        assert set(rotated) == {0, 1}
        router.drain()
        for h in hs:
            r = h.result(timeout=120)
            assert r.status == STATUS_OK, (r.status, r.reason)
            assert r.tokens.tolist() == _ref(params, h.request.prompt, 8)
        for rep in router._replicas:
            audit = rep.engine.kvpool_audit()
            assert audit["ok"], audit["errors"]
        assert router.pending() == 0
        # ledger-audit-clean: the aborted leg's blob was swept by its
        # source's close — rotated-out engines own nothing anymore
        led = memledger.get_ledger()
        mem_audit = led.audit()
        assert mem_audit["ok"], mem_audit["errors"]
        assert led.totals().get("migration", 0) == 0
        live = {rep.engine._name for rep in router._replicas}
        for e in led.entries():
            if e["component"] in ("kvpool", "migration"):
                assert e["owner"] in live, e
    finally:
        router.close()


@pytest.mark.slow
def test_migration_chaos_soak(params):
    """Soak: repeated rotations under sustained load with a fault salted
    onto a random migration leg each round — exactly-once holds for every
    request ever accepted and every replica's pool audits clean at the
    end."""
    memledger.reset_ledger()
    rng = random.Random(0xC0FFEE)
    router = Router(_factory(params, max_batch=8, queue_depth=1024,
                             num_pages=512),
                    replicas=2,
                    supervisor_kw=dict(backoff_s=0.005, poll_s=0.02),
                    rng=random.Random(2))
    handles, lock = [], threading.Lock()
    stop = threading.Event()

    def pump():
        i = 0
        while not stop.is_set():
            h = router.submit(Request(prompt=[1 + i % 8, 3, 2],
                                      steps=2 + i % 6, max_attempts=3))
            with lock:
                handles.append(h)
            i += 1
            time.sleep(0.004)

    threads = [threading.Thread(target=pump) for _ in range(2)]
    try:
        for t in threads:
            t.start()
        for round_ in range(4):
            time.sleep(0.15)
            leg = rng.choice(["export:", "import@", "adopt:", "warm@",
                              None])
            if leg is None:
                router.rolling_restart()
            else:
                with faults.injected("serve.migrate",
                                     RaiseFault(times=1, match=leg)):
                    router.rolling_restart()
        stop.set()
        for t in threads:
            t.join()
        router.drain()
        results = [h.result(timeout=180) for h in handles]
        assert len(results) > 100
        bad = [(r.status, r.reason) for r in results
               if r.status != STATUS_OK]
        assert not bad, bad[:5]
        for h, r in zip(handles, results):
            assert r.tokens.tolist() == _ref(params, h.request.prompt,
                                             h.request.steps)
        for rep in router._replicas:
            audit = rep.engine.kvpool_audit()
            assert audit["ok"], audit["errors"]
        # after four chaos rotations the memory ledger still balances
        # exactly: no migration blob outlived its handoff, no rotated-out
        # engine left bytes behind
        led = memledger.get_ledger()
        mem_audit = led.audit()
        assert mem_audit["ok"], mem_audit["errors"]
        assert led.totals().get("migration", 0) == 0
        live = {rep.engine._name for rep in router._replicas}
        for e in led.entries():
            if e["component"] in ("kvpool", "migration"):
                assert e["owner"] in live, e
    finally:
        stop.set()
        router.close()
