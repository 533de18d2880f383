"""BucketProgram suite: non-LM request types riding the serving spine.

The acceptance scenario (test_mixed_traffic_exactly_once) drives a mixed
LM + ALS + PageRank + classify workload through one :class:`ServeEngine`
and asserts the subsystem's contracts: exactly one terminal Result per
request, LM greedy outputs bit-identical to the direct
:func:`lm_generate` call (program traffic must not perturb the LM lane),
ALS/classify values matching their NumPy oracles, and zero new compiles
after ``warmup()`` (the ``compile_count`` fixture — static program
buckets bound compiles exactly like LM shape buckets). Lifecycle
(drain/close), chaos (``serve.program_step`` + ``serve.worker_crash``
under a Supervisor), model hot-swap, and router placement for programs
live here too; the LM-only engine behaviors stay in tests/test_serving.py.
"""

import threading
import time

import numpy as np
import pytest

import jax

from marlin_tpu.models import TransformerLM
from marlin_tpu.models.transformer import lm_generate
from marlin_tpu.serving import (
    STATUS_ERROR,
    STATUS_OK,
    STATUS_REJECTED,
    STATUS_SHUTTING_DOWN,
    PROGRAM_REGISTRY,
    ALSScoreProgram,
    ClassifyProgram,
    PageRankQueryProgram,
    Request,
    Router,
    ServeEngine,
    Supervisor,
    available_programs,
)
from marlin_tpu.serving.router import _prefix_route_key
from marlin_tpu.utils import EventLog, faults
from marlin_tpu.utils.faults import RaiseFault

HEADS = 2
BUCKETS = ((8, 4),)

#: Edge list with real rank structure: node 3 has the highest in-degree,
#: node 0 the next — after a refresh the ranks are decisively non-uniform.
EDGES = [(0, 1), (0, 2), (0, 3), (1, 2), (1, 3), (2, 0), (2, 3), (3, 0),
         (3, 1), (4, 3), (4, 0)]


@pytest.fixture(scope="module")
def params():
    return TransformerLM(vocab=32, d_model=16, heads=HEADS, layers=2,
                         seed=9).init_params()


@pytest.fixture()
def factors():
    rng = np.random.default_rng(0)
    uf = rng.normal(size=(20, 4)).astype(np.float32)
    pf = rng.normal(size=(15, 4)).astype(np.float32)
    return uf, pf


def _engine(params, **kw):
    kw.setdefault("buckets", BUCKETS)
    kw.setdefault("max_batch", 4)
    kw.setdefault("max_wait_ms", 0.0)
    kw.setdefault("queue_depth", 64)
    kw.setdefault("page_len", 4)
    kw.setdefault("num_pages", 1024)
    return ServeEngine(params, HEADS, **kw)


def _ref_lm(params, prompt, steps):
    prompt = np.asarray(prompt, np.int32)
    return np.asarray(lm_generate(params, prompt, jax.random.key(0),
                                  heads=HEADS, max_len=len(prompt) + steps,
                                  steps=steps))


def _als_oracle(uf, pf, user, k):
    return set(np.argsort(-(uf[user] @ pf.T), kind="stable")[:k].tolist())


# ------------------------------------------------------------------ registry


def test_registry_lists_builtin_programs():
    names = available_programs()
    assert {"als", "classify", "lm", "pagerank"} <= set(names)
    for name in ("als", "classify", "pagerank"):
        assert PROGRAM_REGISTRY[name].name == name


def test_duplicate_program_name_rejected(params, factors):
    uf, pf = factors
    with pytest.raises(ValueError, match="duplicate program"):
        _engine(params, start=False,
                programs=[ALSScoreProgram((uf, pf)),
                          ALSScoreProgram((uf, pf))])


# ---------------------------------------------------------------- unit layer


def test_als_results_match_numpy_oracle(params, factors):
    uf, pf = factors
    eng = _engine(params, programs=[ALSScoreProgram((uf, pf))])
    try:
        eng.warmup()
        eng.start()
        hs = [(u, k, eng.submit(Request(program="als",
                                        payload={"user": u, "k": k})))
              for u in range(6) for k in (1, 3)]
        for u, k, h in hs:
            r = h.result(timeout=60)
            assert r.status == STATUS_OK, (u, k, r.status, r.reason)
            items = list(r.value["items"])
            assert len(items) == k
            assert set(items) == _als_oracle(uf, pf, u, k), (u, k)
            # scores ride along, sorted descending
            assert list(r.value["scores"]) == \
                sorted(r.value["scores"], reverse=True)
    finally:
        eng.close()


def test_program_rejections_are_clean(params, factors):
    uf, pf = factors
    eng = _engine(params, programs=[ALSScoreProgram((uf, pf)),
                                    ClassifyProgram(np.ones(6, np.float32))])
    try:
        eng.start()
        cases = [
            (Request(program="nosuch", payload={}), "unknown program"),
            (Request(program="als", payload={"user": 999}), "out of range"),
            (Request(program="als", payload={"user": 0, "k": 999}),
             "no bucket fits"),
            (Request(program="classify", payload={"x": np.ones(3)}),
             "feature vector has 3 dims"),
        ]
        for req, needle in cases:
            r = eng.submit(req).result(timeout=30)
            assert r.status == STATUS_REJECTED, (needle, r.status, r.reason)
            assert needle in r.reason, (needle, r.reason)
    finally:
        eng.close()


def test_classify_logreg_matches_sigmoid_oracle(params):
    rng = np.random.default_rng(3)
    w = rng.normal(size=(6,)).astype(np.float32)   # intercept + 5 features
    eng = _engine(params, programs=[ClassifyProgram(w)])
    try:
        eng.warmup()
        eng.start()
        xs = rng.normal(size=(5, 5)).astype(np.float32)
        hs = [eng.submit(Request(program="classify", payload={"x": x}))
              for x in xs]
        for x, h in zip(xs, hs):
            r = h.result(timeout=60)
            assert r.status == STATUS_OK, (r.status, r.reason)
            want = 1.0 / (1.0 + np.exp(-(w[0] + x @ w[1:])))
            assert abs(r.value["proba"] - want) < 1e-5
            assert r.value["label"] == int(want >= 0.5)
    finally:
        eng.close()


def test_classify_mlp_multiclass(params):
    from marlin_tpu.ml.neural_network import mlp_init

    mlp = mlp_init(jax.random.key(1), (4, 8, 3))
    eng = _engine(params, programs=[ClassifyProgram(mlp, activation="relu")])
    try:
        eng.warmup()
        eng.start()
        rng = np.random.default_rng(4)
        hs = [eng.submit(Request(program="classify",
                                 payload={"x": rng.normal(size=4)}))
              for _ in range(4)]
        for h in hs:
            r = h.result(timeout=60)
            assert r.status == STATUS_OK, (r.status, r.reason)
            proba = np.asarray(r.value["proba"])
            assert proba.shape == (3,)
            assert r.value["label"] == int(np.argmax(proba))
    finally:
        eng.close()
    # a typo'd dict or unknown activation fails at construction, not traced
    with pytest.raises(ValueError, match="w0"):
        ClassifyProgram({"w1": np.ones((4, 3), np.float32)})


def test_pagerank_refresh_changes_rankings(params):
    pr = PageRankQueryProgram(EDGES, n=5)
    eng = _engine(params, programs=[pr])
    try:
        eng.warmup()
        eng.start()

        def top2_of_node0():
            r = eng.submit(Request(program="pagerank",
                                   payload={"node": 0, "k": 2})) \
                   .result(timeout=60)
            assert r.status == STATUS_OK, (r.status, r.reason)
            return list(r.value["items"])

        before = top2_of_node0()
        assert set(before) <= {1, 2, 3}
        r0 = pr.ranks()
        np.testing.assert_allclose(r0, np.full(5, 0.2), atol=1e-6)

        ranks = pr.refresh(iterations=20)
        assert pr.refresh_count == 1
        assert not np.allclose(ranks, r0)          # converged ≠ uniform
        # node 3 (highest in-degree) now decisively outranks node 1
        assert ranks[3] > ranks[1]
        after = top2_of_node0()
        # the query reads the LIVE vector: 3 and 0's other top neighbor
        assert after[0] == 3
        assert set(after) == {3, int(np.argmax(np.where(
            np.isin(np.arange(5), [1, 2]), ranks, -np.inf)))}
    finally:
        eng.close()


# ------------------------------------------------------------- mixed traffic


def test_mixed_traffic_exactly_once_and_lm_bit_identical(
        params, factors, compile_count, tmp_path):
    """The acceptance scenario: four request types through one engine —
    every handle reaches exactly one ok Result, LM greedy output is
    bit-identical to lm_generate (programs never perturb the LM lane),
    program values match their oracles, zero compiles after warmup, and
    the event stream / metrics carry the program labels."""
    rng = np.random.default_rng(7)
    uf, pf = factors
    log = EventLog(str(tmp_path / "serve.jsonl"))
    eng = _engine(params, log=log,
                  programs=[ALSScoreProgram((uf, pf)),
                            PageRankQueryProgram(EDGES, n=5),
                            ClassifyProgram(rng.normal(
                                size=(6,)).astype(np.float32))])
    try:
        eng.warmup()
        eng.start()
        with compile_count() as c:
            handles, prompts = [], {}
            for i in range(4):
                p = rng.integers(1, 30, size=5).astype(np.int32)
                prompts[i] = p
                handles.append(("lm", i, eng.submit(
                    Request(prompt=p, steps=3))))
            for i in range(6):
                handles.append(("als", i, eng.submit(
                    Request(program="als", payload={"user": i, "k": 3}))))
            for i in range(4):
                handles.append(("pagerank", i, eng.submit(
                    Request(program="pagerank",
                            payload={"node": i, "k": 2}))))
            for i in range(4):
                handles.append(("classify", i, eng.submit(
                    Request(program="classify",
                            payload={"x": rng.normal(size=5)}))))
            results = [(kind, i, h.result(timeout=120))
                       for kind, i, h in handles]
            assert c.count == 0   # warmup paid every program's compiles
        for kind, i, r in results:
            assert r.status == STATUS_OK, (kind, i, r.status, r.reason)
        for kind, i, r in results:
            if kind == "lm":
                assert np.array_equal(np.asarray(r.tokens),
                                      _ref_lm(params, prompts[i], 3))
            elif kind == "als":
                assert set(r.value["items"]) == _als_oracle(uf, pf, i, 3)
            elif kind == "pagerank":
                assert len(r.value["items"]) == 2
            else:
                assert 0.0 <= r.value["proba"] <= 1.0
        snap = eng.metrics.snapshot()
        assert snap["completed"] == len(handles)
        assert snap["program_steps"] >= 3      # one-shot batches ran
        assert snap["program_rows"] == 14      # 6 als + 4 pr + 4 classify
    finally:
        eng.close()
    recs = [r for r in log.read() if r["kind"] == "serve"]
    # program labels: every non-LM result carries one, LM records never do
    by_rid = {}
    for r in recs:
        if r.get("ev") == "result":
            by_rid[r["rid"]] = r
    progs = [r.get("program") for r in by_rid.values()]
    assert progs.count(None) == 4                       # the LM rows
    assert sorted(p for p in progs if p) == \
        ["als"] * 6 + ["classify"] * 4 + ["pagerank"] * 4
    steps = [r for r in recs if r.get("ev") == "step" and r.get("program")]
    assert steps and all(r["new_tokens"] == 0 for r in steps)


def test_mixed_concurrent_submitters_exactly_once(params, factors):
    """Concurrency bar: parallel submitter threads racing LM and ALS
    traffic onto one engine — every request exactly one ok Result."""
    uf, pf = factors
    eng = _engine(params, programs=[ALSScoreProgram((uf, pf))])
    eng.warmup()
    handles, lock = [], threading.Lock()

    def pump_lm():
        for i in range(8):
            h = eng.submit(Request(prompt=[3, 1 + i % 4], steps=2))
            with lock:
                handles.append(("lm", [3, 1 + i % 4], h))

    def pump_als():
        for i in range(8):
            h = eng.submit(Request(program="als",
                                   payload={"user": i % 5, "k": 3}))
            with lock:
                handles.append(("als", i % 5, h))

    try:
        eng.start()
        threads = [threading.Thread(target=pump_lm),
                   threading.Thread(target=pump_als)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for kind, arg, h in handles:
            r = h.result(timeout=120)
            assert r.status == STATUS_OK, (kind, r.status, r.reason)
            if kind == "lm":
                assert r.tokens.tolist() == _ref_lm(params, arg, 2).tolist()
            else:
                assert set(r.value["items"]) == _als_oracle(uf, pf, arg, 3)
    finally:
        eng.close()
    assert eng.pending() == 0
    assert eng.kvpool_audit().get("leaked_pages", 0) == 0


# -------------------------------------------------------------------- swap


def test_swap_model_atomic_no_recompile(params, factors, compile_count):
    uf, pf = factors
    als = ALSScoreProgram((uf, pf))
    eng = _engine(params, programs=[als])
    try:
        eng.warmup()
        eng.start()
        before = eng.submit(Request(program="als",
                                    payload={"user": 0, "k": 3}))
        assert set(before.result(timeout=60).value["items"]) == \
            _als_oracle(uf, pf, 0, 3)
        with compile_count() as c:
            eng.swap_model("als", (uf * -1.0, pf))
            after = eng.submit(Request(program="als",
                                       payload={"user": 0, "k": 3}))
            r = after.result(timeout=60)
            assert c.count == 0      # same shapes → same compiled kernel
        assert set(r.value["items"]) == _als_oracle(-uf, pf, 0, 3)
        assert als.swap_count == 1
        assert eng.metrics.snapshot()["swaps"] == 1
        # the contract's failure modes are loud ValueErrors
        with pytest.raises(ValueError, match="unknown program"):
            eng.swap_model("nosuch", (uf, pf))
        with pytest.raises(ValueError, match="no swap_model hook"):
            eng.swap_model("lm", params)
        with pytest.raises(ValueError, match="shape"):
            eng.swap_model("als", (uf[:3], pf))
    finally:
        eng.close()


# --------------------------------------------------------------- lifecycle


def test_close_retires_queued_program_rows_shutting_down(params, factors):
    uf, pf = factors
    eng = _engine(params, start=False, programs=[ALSScoreProgram((uf, pf))])
    hs = [eng.submit(Request(program="als", payload={"user": i, "k": 3}))
          for i in range(3)]
    eng.close()
    for h in hs:
        r = h.result(timeout=5)
        assert r.status == STATUS_SHUTTING_DOWN and "closed" in r.reason
    assert eng.pending() == 0
    r = eng.submit(Request(program="als",
                           payload={"user": 0, "k": 3})).result(timeout=5)
    assert r.status == STATUS_SHUTTING_DOWN


def test_drain_completes_accepted_program_rows(params, factors):
    uf, pf = factors
    eng = _engine(params, programs=[ALSScoreProgram((uf, pf))])
    try:
        eng.warmup()
        eng.start()
        hs = [eng.submit(Request(program="als", payload={"user": i, "k": 3}))
              for i in range(5)]
        eng.drain()
        for i, h in enumerate(hs):
            r = h.result(timeout=60)
            assert r.status == STATUS_OK, (i, r.status, r.reason)
            assert set(r.value["items"]) == _als_oracle(uf, pf, i, 3)
        # drained engines refuse new work deterministically
        r = eng.submit(Request(program="als",
                               payload={"user": 0, "k": 3})).result(timeout=5)
        assert r.status == STATUS_SHUTTING_DOWN and "draining" in r.reason
    finally:
        eng.close()


# -------------------------------------------------------------------- chaos


def test_program_step_fault_retries_within_budget(params, factors):
    """serve.program_step chaos: the batch's rows re-queue transparently
    within max_attempts and complete ok — LM rows in flight untouched."""
    uf, pf = factors
    eng = _engine(params, programs=[ALSScoreProgram((uf, pf))])
    try:
        eng.warmup()
        with faults.injected("serve.program_step", RaiseFault(times=1)):
            hs = [eng.submit(Request(program="als", max_attempts=3,
                                     payload={"user": i, "k": 3}))
                  for i in range(3)]
            lm = eng.submit(Request(prompt=[3, 1], steps=2))
            eng.start()
            for i, h in enumerate(hs):
                r = h.result(timeout=120)
                assert r.status == STATUS_OK, (i, r.status, r.reason)
                assert set(r.value["items"]) == _als_oracle(uf, pf, i, 3)
            assert lm.result(timeout=120).status == STATUS_OK
        assert eng.metrics.snapshot()["retries"] >= 1
    finally:
        eng.close()
    assert eng.kvpool_audit().get("leaked_pages", 0) == 0


def test_program_step_fault_exhausted_budget_is_clean_error(params, factors):
    uf, pf = factors
    eng = _engine(params, programs=[ALSScoreProgram((uf, pf))])
    try:
        eng.warmup()
        with faults.injected("serve.program_step", RaiseFault(times=8)):
            h = eng.submit(Request(program="als", max_attempts=1,
                                   payload={"user": 0, "k": 3}))
            eng.start()
            r = h.result(timeout=120)
        assert r.status == STATUS_ERROR
        assert "program step failed" in r.reason
        # the engine keeps serving after the chaos window closes
        ok = eng.submit(Request(program="als", payload={"user": 1, "k": 3}))
        assert ok.result(timeout=60).status == STATUS_OK
    finally:
        eng.close()


def test_supervisor_recovers_worker_crash_under_mixed_load(
        params, factors, tmp_path):
    """The ISSUE chaos parity bar: serve.worker_crash under mixed LM+ALS
    load with a Supervisor — zero dropped, exactly-once, bit-identical LM,
    clean audit after recovery."""
    uf, pf = factors
    log = EventLog(str(tmp_path / "serve.jsonl"))
    eng = _engine(params, log=log, programs=[ALSScoreProgram((uf, pf))])
    eng.warmup()
    sup = Supervisor(eng, backoff_s=0.005, poll_s=0.02, log=log)
    try:
        with faults.injected("serve.worker_crash", RaiseFault(times=1)):
            hs = []
            for i in range(4):
                hs.append(("lm", [3, 1 + i % 4], eng.submit(
                    Request(prompt=[3, 1 + i % 4], steps=3,
                            max_attempts=3))))
                hs.append(("als", i, eng.submit(
                    Request(program="als", max_attempts=3,
                            payload={"user": i, "k": 3}))))
            for kind, arg, h in hs:
                r = h.result(timeout=120)
                assert r.status == STATUS_OK, (kind, r.status, r.reason)
                if kind == "lm":
                    assert r.tokens.tolist() == \
                        _ref_lm(params, arg, 3).tolist()
                else:
                    assert set(r.value["items"]) == \
                        _als_oracle(uf, pf, arg, 3)
        assert sup.restart_count >= 1
        assert not sup.breaker_open
    finally:
        sup.close()
        eng.close()
    assert eng.pending() == 0
    assert eng.kvpool_audit().get("leaked_pages", 0) == 0


# -------------------------------------------------------------------- router


def test_router_program_requests_skip_prefix_affinity(params, factors):
    """Satellite: non-LM requests have no KV prefix — _prefix_route_key
    must return None (power-of-two fallback) even when LM traffic with the
    same router is being prefix-pinned."""
    uf, pf = factors
    import random
    router = Router(lambda: _engine(params,
                                    programs=[ALSScoreProgram((uf, pf))]),
                    replicas=2, supervise=False, rng=random.Random(7))
    try:
        ready = router._replicas
        lm_req = Request(prompt=list(range(1, 9)), steps=2)
        als_req = Request(program="als", payload={"user": 0, "k": 3})
        assert _prefix_route_key(lm_req, ready) is not None
        assert _prefix_route_key(als_req, ready) is None
        # end to end: mixed traffic through the router, exactly once each
        hs = [router.submit(Request(prompt=list(range(1, 9)), steps=2))
              for _ in range(4)]
        hs += [router.submit(Request(program="als",
                                     payload={"user": u, "k": 3}))
               for u in range(4)]
        for h in hs:
            assert h.result(timeout=120).status == STATUS_OK
        snap = router.snapshot()
        assert snap["program_rows"] >= 4   # folded program counters
    finally:
        router.close()


def test_router_rolling_restart_mixed_load_zero_dropped(params, factors):
    """Rolling restart under continuous mixed LM+ALS offered load: every
    handle reaches exactly one ok Result — program rows migrate or retry
    through the rotation like LM rows do."""
    uf, pf = factors
    import random
    router = Router(lambda: _engine(params,
                                    programs=[ALSScoreProgram((uf, pf))]),
                    replicas=2,
                    supervisor_kw=dict(backoff_s=0.005, poll_s=0.02),
                    rng=random.Random(7))
    handles, lock = [], threading.Lock()
    stop = threading.Event()

    def pump():
        i = 0
        while not stop.is_set():
            if i % 2:
                h = router.submit(Request(program="als", max_attempts=3,
                                          payload={"user": i % 5, "k": 3}))
                kind, arg = "als", i % 5
            else:
                h = router.submit(Request(prompt=[5, 1 + i % 4], steps=2,
                                          max_attempts=3))
                kind, arg = "lm", [5, 1 + i % 4]
            with lock:
                handles.append((kind, arg, h))
            i += 1
            time.sleep(0.002)

    threads = [threading.Thread(target=pump) for _ in range(2)]
    try:
        for t in threads:
            t.start()
        time.sleep(0.1)
        rotated = router.rolling_restart()
        time.sleep(0.1)
        stop.set()
        for t in threads:
            t.join()
        router.drain()
        assert set(rotated) == {0, 1}
        results = [(kind, arg, h.result(timeout=120))
                   for kind, arg, h in handles]
    finally:
        stop.set()
        router.close()
    assert len(results) >= 20
    assert any(kind == "als" for kind, _, _ in results)
    for kind, arg, r in results:
        assert r.status == STATUS_OK, (kind, r.status, r.reason)
        if kind == "lm":
            assert r.tokens.tolist() == _ref_lm(params, arg, 2).tolist()
        else:
            assert set(r.value["items"]) == _als_oracle(uf, pf, arg, 3)
