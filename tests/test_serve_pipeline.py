"""The worker's pipeline, one call deep: a decode call lands only after the
call that follows it has been dispatched (``ServeEngine._run_paged``).

While the chip runs step t the host packs and dispatches step t+1, fed from
the device with the tokens it has not seen (``prev_tokens`` / ``prev_index``
of the decode programs, the first tokens of final prefill chunks written
into the same feed), and only then lands step t. Held here, on the CPU with
a tiny model: the order of dispatches and landings, every way out of the
loop, tokens against plain serial generation for rows that join and leave
mid-stream, an ``eos`` found one step late, a slot refilled under a stale
token, a call that fails with its successor in flight, a freeze with a step
in flight, and the spans' fields.
"""

import collections
import contextlib
import itertools
import time

import numpy as np
import pytest

import jax

from marlin_tpu.models import TransformerLM, transformer
from marlin_tpu.models.transformer import (init_kv_pages, lm_decode_paged,
                                           lm_generate, lm_prefill_paged)
from marlin_tpu.serving import (STATUS_EXPIRED, STATUS_OK, Request,
                                ServeEngine, Supervisor)
from marlin_tpu.serving import engine as engine_mod
from marlin_tpu.utils import faults
from marlin_tpu.utils.faults import RaiseFault, Schedule

HEADS = 2
PAGE_LEN = 4
BUCKETS = ((8, 8), (24, 8))


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
    kw.setdefault("decode_kernel", "gather")
    return ServeEngine(params, HEADS, **kw)


def _prompt(i, n):
    return [(7 * i + j) % 31 + 1 for j in range(n)]


def _greedy(params, prompt, steps):
    prompt = np.asarray(prompt, np.int32)
    return np.asarray(lm_generate(
        params, prompt, jax.random.key(0), heads=HEADS,
        max_len=len(prompt) + steps, steps=steps)).tolist()


def _serial(params, r: Request):
    """Plain serial generation through the paged programs, one row, every
    token brought to the host before the next step: what the engine did
    before it pipelined, and the reference for a sampled row (its stream is
    ``fold_in(key(seed), step)`` whatever it rides with)."""
    prompt = np.asarray(r.prompt, np.int32)
    n, C, W = len(prompt), 16, 8  # one chunk; a table of 8 + 4 spill pages
    pages = init_kv_pages(params, W + 1, PAGE_LEN, HEADS)
    table = np.zeros(W + C // PAGE_LEN, np.int32)
    table[:W] = np.arange(1, W + 1)
    chunk = np.zeros(C, np.int32)
    chunk[:n] = prompt
    pages, first = lm_prefill_paged(
        params, pages, table, chunk, 0, n, heads=HEADS, page_len=PAGE_LEN,
        seed=r.seed, temperature=r.temperature, top_p=r.top_p, top_k=r.top_k)
    out = [int(first)]
    for step in range(1, r.steps):
        pages, nxt = lm_decode_paged(
            params, pages, table[None, :W], [n + step - 1], [out[-1]],
            [step], np.asarray([r.seed], np.uint32), [r.temperature],
            [1.0 if r.top_p is None else r.top_p],
            [0 if r.top_k is None else r.top_k], heads=HEADS,
            page_len=PAGE_LEN, kernel="gather")
        out.append(int(nxt[0]))
    return prompt.tolist() + out


# --------------------------------------------------------------- the watchers


class _Tokens:
    """A decode call's tokens, telling when the host brings them over."""

    def __init__(self, real, log, n):
        self.real, self.log, self.n = real, log, n

    def __array__(self, dtype=None, copy=None):
        self.log.append(("land", self.n))
        return np.asarray(self.real)


def _unwrap(x):
    return x.real if isinstance(x, _Tokens) else x


@contextlib.contextmanager
def _watched(monkeypatch, fail=None):
    """Stand in for the decode program and the feed's write: the real ones
    run, and ``log`` gets ``("dispatch", n)`` as call ``n`` goes out and
    ``("land", n)`` as the host reads its tokens. ``fail(n, pages)`` may
    name another class for call ``n``'s tokens."""
    log, count = [], itertools.count(1)
    decode, feed = transformer.lm_decode_paged, transformer.feed_token

    def spy_decode(*args, **kw):
        n = next(count)
        kw["prev_tokens"] = _unwrap(kw["prev_tokens"])
        log.append(("dispatch", n))
        out = decode(*args, **kw)
        kind = fail(n, out[0]) if fail is not None else None
        return (out[0], (kind or _Tokens)(out[1], log, n), *out[2:])

    def spy_feed(feed_in, index, token):
        return feed(_unwrap(feed_in), index, token)

    monkeypatch.setattr(transformer, "lm_decode_paged", spy_decode)
    monkeypatch.setattr(transformer, "feed_token", spy_feed)
    yield log


class _Span:
    def __init__(self, log, name, fields):
        self.log, self.name, self.fields = log, name, dict(fields)

    def __enter__(self):
        self.log.append(self)
        return self

    def __exit__(self, *exc):
        return False

    def set_metadata(self, **fields):
        self.fields.update(fields)

    def is_enabled(self):
        return True


@pytest.fixture
def spans(monkeypatch):
    """The worker's spans in the order they open, with their fields (no
    profiler: ``annotate`` replaced where the engine looks it up)."""
    log = []

    def annotate(name, **fields):
        # (a caller's thread opens serve.submit: kept out of the worker's)
        return _Span([] if name == "serve.submit" else log, name, fields)

    monkeypatch.setattr(engine_mod, "annotate", annotate)
    return log


def _wait_steps(eng, n, timeout=120.0):
    """Return once the engine has announced ``n`` step records."""
    deadline = time.monotonic() + timeout
    while eng.metrics.snapshot()["steps"] < n:
        assert time.monotonic() < deadline, "the engine made no progress"
        time.sleep(0.001)


def _landed_once(log):
    """Every dispatched call was landed, once, in dispatch order."""
    out = [n for ev, n in log if ev == "dispatch"]
    assert [n for ev, n in log if ev == "land"] == out
    return out


# ------------------------------------------------------ (a) order and exits


def test_the_next_step_is_dispatched_before_the_last_one_lands(
        params, monkeypatch):
    eng = _engine(params, start=False)
    eng.warmup()
    with _watched(monkeypatch) as log:
        h = eng.submit(Request(prompt=_prompt(0, 5), steps=8))
        eng.start()
        res = h.result(timeout=120)
        eng.close()
    assert res.status == STATUS_OK
    assert res.tokens.tolist() == _greedy(params, _prompt(0, 5), 8)
    calls = _landed_once(log)
    assert calls == list(range(1, 8))  # seven decode steps after the first
    at = {ev: i for i, ev in enumerate(log)}
    for n in calls[:-1]:
        assert at["dispatch", n + 1] < at["land", n], log
    # nothing follows the last step: it lands as soon as it is found so
    assert log[-2:] == [("land", 6), ("land", 7)]


@pytest.mark.parametrize("leave", ["close", "drain", "freeze", "recover"])
def test_every_way_out_leaves_nothing_in_flight(params, monkeypatch, leave):
    """Rows mid-stream with a call in flight at every claim: ``close`` and
    ``drain`` finish them, ``freeze_rows`` parks the worker with every
    dispatched call landed and the rows' cursors the landed ones, and a
    recovery drops the dead generation's flight with its rows requeued."""
    eng = _engine(params, start=False)
    eng.warmup()
    sup = None
    reqs = [Request(prompt=_prompt(i, 3 + i % 6), steps=8, max_attempts=3)
            for i in range(12)]
    with _watched(monkeypatch) as log:
        handles = eng.submit_many(reqs)
        if leave == "recover":
            sup = Supervisor(eng, backoff_s=0.005, poll_s=0.01)
            crash = faults.injected(
                "serve.worker_crash",
                RaiseFault(times=1, schedule=Schedule(fire_on=[3])))
        else:
            crash = contextlib.nullcontext()
        with crash:
            eng.start()
            if leave == "freeze":
                _wait_steps(eng, 2)
                frozen = eng.freeze_rows()
                assert frozen is not None and not frozen["fallback"]
                _landed_once(log)
                live = 0
                for group in eng._pools.values():
                    for i in group.occupied_slots():
                        if group.pf_next[i] >= 0:
                            continue  # frozen mid-prefill
                        live += 1
                        emitted = group.emitted[i]
                        assert group.steps_done[i] == len(emitted) >= 1
                        assert (group.positions[i]
                                == group.lengths[i] + len(emitted) - 1)
                        assert group.cur_tok[i] == emitted[-1]
                assert live  # rows were mid-stream
                eng.close()
                return
            results = [h.result(timeout=120) for h in handles]
            eng.drain() if leave == "drain" else eng.close()
    if sup is not None:
        sup.close()
    for r, q in zip(results, reqs):
        assert r.status == STATUS_OK, r.reason
        assert r.tokens.tolist() == _greedy(params, q.prompt, q.steps)
    if leave == "recover":
        assert eng.metrics.snapshot()["retries"] >= 1
        # the dead generation's call in flight was dropped, not landed
        assert (len([1 for ev, _ in log if ev == "land"])
                < len([1 for ev, _ in log if ev == "dispatch"]))
    else:
        _landed_once(log)
    audit = eng.kvpool_audit()
    assert audit["ok"], audit["errors"]
    assert eng.pending() == 0 and eng._queue.bytes_in_flight == 0


# ------------------------------------------------------------ (b) the tokens


@pytest.mark.parametrize("kernel", ["gather", "pallas"])
@pytest.mark.parametrize("sampled", [False, True],
                         ids=["greedy", "sampled"])
def test_tokens_are_the_serial_reference_as_rows_join_and_leave(
        params, kernel, sampled):
    """Ten requests of both buckets through three slots: rows end and
    others join at every few steps, so a call's packing keeps changing
    under the index-fed tokens."""
    knobs = [dict(temperature=0.8, seed=3 + i, top_k=(5 if i % 2 else None),
                  top_p=(0.9 if i % 3 == 0 else None)) if sampled else {}
             for i in range(10)]
    reqs = [Request(prompt=_prompt(i, 2 + (5 * i) % 13), steps=2 + (3 * i) % 7,
                    **knobs[i]) for i in range(10)]
    with _engine(params, max_batch=3, decode_kernel=kernel,
                 start=False) as eng:
        handles = eng.submit_many(reqs)
        eng.start()
        results = [h.result(timeout=180) for h in handles]
        snap = eng.metrics.snapshot()
    for r, q in zip(results, reqs):
        assert r.status == STATUS_OK, r.reason
        if kernel == "gather":
            assert r.tokens.tolist() == _serial(params, q)
        if not sampled:
            assert r.tokens.tolist() == _greedy(params, q.prompt, q.steps)
        assert len(r.tokens) == len(q.prompt) + q.steps
    assert snap["new_tokens"] == sum(q.steps for q in reqs)
    assert snap["retries"] == 0 and snap["errors"] == 0


def test_a_row_of_one_step_never_rides_a_decode_call(params, monkeypatch):
    with _watched(monkeypatch) as log:
        with _engine(params) as eng:
            res = eng.submit(Request(prompt=_prompt(1, 6),
                                     steps=1)).result(timeout=120)
    assert res.tokens.tolist() == _greedy(params, _prompt(1, 6), 1)
    assert log == []


# --------------------------------------------------------------- (c) an eos


def test_an_eos_row_ends_at_the_eos_and_its_late_token_is_discarded(
        params, spans):
    """The ``eos`` is found when step t lands, with step t+1 of the row
    already on the device: the result ends at the ``eos`` as before, the
    extra token is discarded at its landing, and the pages are released
    once."""
    prompt, steps = _prompt(2, 5), 8
    want = _greedy(params, prompt, steps)
    generated = want[len(prompt):]
    # an eos that is first emitted mid-stream, by a decode step
    k = next(i for i in range(2, steps - 1)
             if generated[i] not in generated[:i])
    with _engine(params) as eng:
        res = eng.submit(Request(prompt=prompt, steps=steps,
                                 eos=generated[k])).result(timeout=120)
        other = eng.submit(Request(prompt=_prompt(3, 4),
                                   steps=3)).result(timeout=120)
        eng.drain()
        audit = eng.kvpool_audit()
    assert res.status == STATUS_OK
    assert res.tokens.tolist() == want[:len(prompt) + k + 1]
    assert other.tokens.tolist() == _greedy(params, _prompt(3, 4), 3)
    retires = [s.fields for s in spans if s.name == "serve.decode.retire"]
    assert sum(f["discarded"] for f in retires) == 1
    assert sum(f["retired"] for f in retires) == 2
    assert audit["ok"], audit["errors"]
    assert audit["used"] == audit["cached"]  # no row's pages left behind
    calls = [s for s in spans if s.name == "serve.decode.dispatch"]
    assert len(calls) >= k + 1


# ------------------------------------- (d) a slot refilled under a stale token


class _Hands:
    """The worker's iteration, driven by hand on a parked engine."""

    def __init__(self, eng):
        self.eng = eng
        self.pool = eng._ensure_kvpool()
        self.pools: dict = {}
        self.pf_queue: collections.deque = collections.deque()
        self.pipe = engine_mod._Pipeline(eng.max_batch)

    def admit(self):
        with self.eng._cond:
            claimed = self.eng._claim(self.pools)
        self.eng._admit_paged(self.pool, self.pools, claimed, self.pf_queue)

    def iterate(self):
        self.admit()
        self.eng._prefill_paged_chunk(self.pool, self.pools, self.pf_queue,
                                      self.pipe)
        self.eng._step_paged(self.pool, self.pools, self.pipe)


def test_a_slot_refilled_with_a_step_in_flight_never_sees_the_stale_token(
        params, monkeypatch, spans):
    """A row expires with a step of it in flight, and its slot is refilled
    before that step lands: the landing drops the token by entry, and the
    new occupant's stream is its own."""
    now = [0.0]
    eng = _engine(params, max_batch=1, start=False, clock=lambda: now[0])
    try:
        with _watched(monkeypatch) as log:
            hands = _Hands(eng)
            old = eng.submit(Request(prompt=_prompt(4, 5), steps=8,
                                     deadline=10.0))
            hands.iterate()   # prefill, call 1 out, first token landed
            hands.iterate()   # call 2 out, call 1 landed
            assert log == [("dispatch", 1), ("dispatch", 2), ("land", 1)]
            now[0] = 11.0
            eng._pack_paged(hands.pool, hands.pools)  # the deadline sweep
            assert old.result(timeout=1).status == STATUS_EXPIRED
            new = eng.submit(Request(prompt=_prompt(5, 6), steps=4))
            hands.admit()     # takes the slot, call 2 still in flight
            (group,) = [g for g in hands.pools.values()
                        if g.occupied_slots()]
            assert group.entries[0].request.rid == new.request.rid
            assert hands.pipe.call is not None
            while not new.done():
                hands.iterate()
        res = new.result(timeout=1)
    finally:
        eng.close()
    assert res.status == STATUS_OK
    assert res.tokens.tolist() == _greedy(params, _prompt(5, 6), 4)
    _landed_once(log)
    retires = [s.fields for s in spans if s.name == "serve.decode.retire"]
    assert sum(f["discarded"] for f in retires) == 1
    assert eng.kvpool_audit()["ok"]


# ------------------------------- (e) a call fails with its successor in flight


class _Poisoned(_Tokens):
    """Tokens of a call that failed on the device: good to dispatch on,
    an error for the host to read."""

    def __array__(self, dtype=None, copy=None):
        raise RuntimeError("the device lost this call")


@pytest.mark.parametrize("attempts", [1, 2])
def test_a_call_that_fails_with_its_successor_in_flight(params, monkeypatch,
                                                        attempts):
    """Call 2's tokens never arrive, and call 3 was dispatched on its slab
    (donated, now consumed): every resident row is failed or requeued once,
    whichever of the two calls carried it, and the pool is rebuilt once."""
    def fail(n, pages):
        if n == 2:
            return _Poisoned
        if n == 3:  # dispatched on the failed call's output: no slab left
            for leaf in jax.tree.leaves(pages):
                leaf.delete()
        return None

    sink = []
    eng = _engine(params, start=False,
                  log=type("S", (), {"event": lambda self, kind, **f:
                                     sink.append(f)})())
    eng.warmup()
    reqs = [Request(prompt=_prompt(i, 4 + i), steps=8, max_attempts=attempts)
            for i in range(3)]
    with _watched(monkeypatch, fail) as log:
        handles = eng.submit_many(reqs)
        eng.start()
        results = [h.result(timeout=120) for h in handles]
        after = eng.submit(Request(prompt=_prompt(9, 3), steps=3))
        assert after.result(timeout=120).status == STATUS_OK
        snap = eng.metrics.snapshot()
        eng.drain()
    assert ("dispatch", 3) in log and log.index(("dispatch", 3)) < len(log)
    lost = [f for f in sink if f.get("ev") == "page"
            and f.get("action") == "lost"]
    assert len(lost) == 1
    if attempts == 1:
        assert [r.status for r in results] == ["error"] * 3
        assert snap["errors"] == 3 and snap["retries"] == 0
    else:
        for r, q in zip(results, reqs):
            assert r.status == STATUS_OK, r.reason
            assert r.metrics["attempt"] == 2
            assert r.tokens.tolist() == _greedy(params, q.prompt, q.steps)
        assert snap["errors"] == 0 and snap["retries"] == 3
    assert len([f for f in sink if f.get("ev") == "retry"]) == (
        3 if attempts == 2 else 0)
    audit = eng.kvpool_audit()
    assert audit["ok"], audit["errors"]
    assert eng.pending() == 0 and eng._queue.bytes_in_flight == 0


# ----------------------------- (h) a row that ends by budget leaves at dispatch


def _leave_one(eng, hands, steps=3, **kw):
    """On a one-slot engine driven by hand: a row of ``steps`` tokens has
    its last step in flight and has left its slot. Returns its handle."""
    a = eng.submit(Request(prompt=_prompt(2, 5), steps=steps, **kw))
    for _ in range(steps - 1):
        hands.iterate()   # the last of them dispatches the row's last step
    assert hands.pipe.call is not None
    assert [e.request.rid for e in hands.pipe.leaving()] == [a.request.rid]
    assert not any(g.occupied_slots() for g in hands.pools.values())
    assert not a.done()
    return a


def test_a_row_whose_budget_ends_leaves_its_slot_with_its_last_step_in_flight(
        params, monkeypatch, spans):
    """The host knows at dispatch that the row ends: its slot (and its
    pages) are free for the next claim, the next request's chunk and first
    step go out behind the call in flight, and that call's landing answers
    the row from its landing record."""
    eng = _engine(params, max_batch=1, start=False)
    try:
        with _watched(monkeypatch) as log:
            hands = _Hands(eng)
            a = _leave_one(eng, hands)
            audit = eng.kvpool_audit()
            assert audit["ok"], audit["errors"]
            assert audit["used"] == audit["cached"]  # its pages are back
            b = eng.submit(Request(prompt=_prompt(3, 6), steps=4))
            hands.iterate()   # b takes the slot; call 3 out; call 2 landed
            assert log[-3:] == [("land", 1), ("dispatch", 3), ("land", 2)]
            assert a.done() and not hands.pipe.leaving()
            while not b.done():
                hands.iterate()
    finally:
        eng.close()
    for h, (i, n, steps) in ((a, (2, 5, 3)), (b, (3, 6, 4))):
        res = h.result(timeout=1)
        assert res.status == STATUS_OK, res.reason
        assert res.tokens.tolist() == _greedy(params, _prompt(i, n), steps)
    assert a.result().metrics["slot"] == b.result().metrics["slot"] == 0
    assert a.result().metrics["pages"] >= 1
    _landed_once(log)
    retires = [s.fields for s in spans if s.name == "serve.decode.retire"]
    assert sum(f["discarded"] for f in retires) == 0
    assert sum(f["retired"] for f in retires) == 2
    assert eng.kvpool_audit()["ok"]


@pytest.mark.parametrize("attempts", [1, 2])
def test_a_row_that_left_its_slot_fails_or_retries_once_with_its_call(
        params, monkeypatch, attempts):
    """The call that carries a row's last step never lands, and the row's
    slot already holds another request: the row is failed or requeued by
    entry, once, and the slot's new occupant is not touched."""
    eng = _engine(params, max_batch=1, start=False)
    try:
        with _watched(monkeypatch,
                      lambda n, pages: _Poisoned if n == 2 else None):
            hands = _Hands(eng)
            a = _leave_one(eng, hands, max_attempts=attempts)
            b = eng.submit(Request(prompt=_prompt(3, 6), steps=4))
            while not (a.done() and b.done()):
                hands.iterate()
        snap = eng.metrics.snapshot()
    finally:
        eng.close()
    got = b.result(timeout=1)
    assert got.status == STATUS_OK and "attempt" not in got.metrics
    assert got.tokens.tolist() == _greedy(params, _prompt(3, 6), 4)
    res = a.result(timeout=1)
    if attempts == 1:
        assert res.status == "error" and "decode step failed" in res.reason
        assert snap["errors"] == 1 and snap["retries"] == 0
    else:
        assert res.status == STATUS_OK and res.metrics["attempt"] == 2
        assert res.tokens.tolist() == _greedy(params, _prompt(2, 5), 3)
        assert snap["errors"] == 0 and snap["retries"] == 1
    assert eng.kvpool_audit()["ok"]
    assert eng.pending() == 0 and eng._queue.bytes_in_flight == 0


def test_a_recovery_finds_the_row_that_left_its_slot(params, monkeypatch):
    """A stuck generation's rows are gathered from its mirrors: a row in
    no group any more is in the flight's ``leaving``."""
    eng = _engine(params, max_batch=1, start=False)
    try:
        with _watched(monkeypatch):
            hands = _Hands(eng)
            a = _leave_one(eng, hands)
            eng._pools, eng._pipe = hands.pools, hands.pipe
            out = eng._recover("stuck", respawn=False)
        assert out["failed"] == 1
        res = a.result(timeout=1)
        assert res.status == "error" and "worker lost" in res.reason
    finally:
        eng.close()


def test_an_interval_is_a_programs_own_only_where_its_predecessor_landed():
    """A landing that follows a chunk nobody waited for holds that chunk's
    device time too: ``landed`` says so."""
    pipe = engine_mod._Pipeline(4)
    chunk, call, final, after = (pipe.dispatched() for _ in range(4))
    assert (chunk, call, final, after) == (1, 2, 3, 4)
    assert not pipe.landed(call)   # the chunk before it never landed
    assert pipe.landed(final)      # follows the call's landing
    assert pipe.landed(after)
    late = [pipe.dispatched() for _ in range(3)][-1]
    assert not pipe.landed(late)


def test_the_spans_seq_tells_the_calls_behind_a_chunk_nobody_waited_for(
        params, monkeypatch, spans):
    """A row decodes while a prompt of three chunks prefills beside it. By
    the dispatch spans' ``seq`` the two decode calls that went out right
    behind a chunk that is not final are the ones whose landing interval
    holds that chunk's device time too; every program but such a chunk is
    landed, once."""
    eng = _engine(params, max_batch=2, start=False)
    try:
        eng.warmup()
        del spans[:]
        with _watched(monkeypatch) as log:
            hands = _Hands(eng)
            short = eng.submit(Request(prompt=_prompt(1, 4), steps=8))
            hands.iterate()   # its one chunk, final; call 1
            long = eng.submit(Request(prompt=_prompt(6, 20), steps=2))
            while not (short.done() and long.done()):
                hands.iterate()
    finally:
        eng.close()
    assert _landed_once(log) == list(range(1, 8))
    chunks = {s.fields["seq"]: s.fields["final"] for s in spans
              if s.name == "serve.prefill.dispatch"}
    calls = [s.fields["seq"] for s in spans
             if s.name == "serve.decode.dispatch" and "seq" in s.fields]
    landed = [s.fields["seq"] for s in spans
              if s.name in ("serve.prefill.sync", "serve.decode.sync")]
    assert len(calls) == 7 and sorted(chunks.values()) == [0, 0, 1, 1]
    assert sorted([*chunks, *calls]) == list(range(1, 12))  # one numbering
    assert sorted(landed) == sorted(calls + [q for q, f in chunks.items()
                                             if f])
    # calls 2 and 3 went out behind the long prompt's first two chunks
    assert [n for n, q in enumerate(calls, 1)
            if chunks.get(q - 1) == 0] == [2, 3]


# ------------------------------------------ (f) a freeze with a step in flight


def test_freeze_then_adopt_with_a_step_in_flight_continues_token_for_token(
        params):
    a, b = _engine(params, num_pages=128), _engine(params, num_pages=128)
    a.warmup(), b.warmup()
    reqs = [Request(prompt=_prompt(i, 3 + i % 5), steps=8)
            for i in range(8)]
    try:
        handles = a.submit_many(reqs)
        _wait_steps(a, 3)
        frozen = a.freeze_rows()
        assert frozen is not None and frozen["blob"] is not None
        assert not frozen["fallback"] and frozen["entries"]
        res = b.adopt_rows(frozen)
        assert not res["fallback"]
        for rid in res["adopted"]:
            a._queue.release(frozen["entries"][rid].cost)
        assert b.adopt_entries(frozen["queued"])
        for e in frozen["queued"]:
            a._queue.release(e.cost)
        a.close()
        for h, q in zip(handles, reqs):
            r = h.result(timeout=120)
            assert r.status == STATUS_OK, (r.status, r.reason)
            assert r.tokens.tolist() == _greedy(params, q.prompt, q.steps)
        assert b.metrics.snapshot()["retries"] == 0
        b.drain()
        assert b.kvpool_audit()["ok"]
    finally:
        a.close(), b.close()


# ------------------------------------------------------------- (g) the spans


def test_only_a_final_chunk_is_waited_for_and_the_fields_say_it_engaged(
        params, spans):
    """A prompt of three chunks opens three ``serve.prefill.dispatch`` and
    ONE ``serve.prefill.sync`` (``final=1``, after the decode dispatch its
    row rides); every decode dispatch but a stream's first is ``ahead``,
    and its rows are fed from the device."""
    reqs = [Request(prompt=_prompt(6, 20), steps=6),
            Request(prompt=_prompt(7, 5), steps=6)]
    with _engine(params, start=False) as eng:
        handles = eng.submit_many(reqs)
        eng.start()
        for h, q in zip(handles, reqs):
            assert (h.result(timeout=120).tokens.tolist()
                    == _greedy(params, q.prompt, q.steps))
    names = [s.name for s in spans]
    chunks = [s.fields for s in spans if s.name == "serve.prefill.dispatch"]
    syncs = [s.fields for s in spans if s.name == "serve.prefill.sync"]
    assert len(chunks) == 3 + 1 and sum(c["final"] for c in chunks) == 2
    assert [s["final"] for s in syncs] == [1, 1]
    calls = [s.fields for s in spans if s.name == "serve.decode.dispatch"
             and s.fields["rows"]]
    assert {"ahead", "seq"} <= set(calls[0]) and "fed_rows" not in calls[0]
    assert calls[0]["ahead"] == 0 and all(c["ahead"] for c in calls[1:])
    # chunks and calls are numbered together, in the device's order, and
    # each landing names the number of the program whose result it brings
    numbered = [s.fields["seq"] for s in spans
                if s.name in ("serve.prefill.dispatch",
                              "serve.decode.dispatch") and "seq" in s.fields]
    assert numbered == list(range(numbered[0], numbered[0] + len(numbered)))
    assert ([s.fields["seq"] for s in spans if s.name == "serve.decode.sync"]
            == [c["seq"] for c in calls])
    assert ([s["seq"] for s in syncs]
            == [c["seq"] for c in chunks if c["final"]])
    retires = [s.fields for s in spans if s.name == "serve.decode.retire"]
    assert all(f["discarded"] == 0 for f in retires)
    # the first token's landing follows the dispatch of the call it rides
    first_sync = names.index("serve.prefill.sync")
    assert "serve.decode.dispatch" in names[:first_sync]
    # ... and call t's landing follows call t+1's dispatch
    order = [n for n in names
             if n in ("serve.decode.dispatch", "serve.decode.sync")]
    assert order[:3] == ["serve.decode.dispatch", "serve.decode.dispatch",
                         "serve.decode.sync"]
