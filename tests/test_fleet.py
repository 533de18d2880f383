"""Elastic fleet suite (serving/fleet.py, the router's elastic membership,
the supervisor's warmup exemption; docs/serving.md "Elastic fleet",
docs/robustness.md).

The acceptance bars, bottom up:

- **Routing math**: weighted rendezvous at weight 1.0 orders replicas
  identically to the classic digest score (the prefix-affinity tests'
  invariant survives), removing a replica moves ONLY the keys it owned,
  and deweighting one replica re-places only (a fraction of) its own keys
  — minimal-churn membership changes by construction.
- **Membership**: a retired replica leaves every rendezvous score list
  and readiness snapshot BEFORE its rows move; a scale-out replica joins
  warmed, with a brand-new supervisor (fresh restart-breaker window) and
  a never-reused index; a warmup in progress is exempt from the watchdog
  while crash detection stays on.
- **Controller state machine** (FakeRouter + injected clock, fully
  deterministic): hysteresis, cooldown, flap damping, min/max bounds,
  single-flight actions with timeout accounting, error degradation to
  no-op, and the /debug/fleet payload.
- **Chaos**: a fault on any ``serve.fleet`` leg (spawn/join/retire/shed)
  aborts the scale event atomically — fleet unchanged, zero dropped or
  double-delivered requests, zero token-0 restarts; the slow soak drives
  >= 6 real scale events (out, in, rebalance, one killed mid-event each,
  plus a controller killed and rebuilt mid-fleet) under ~64 req/s.
"""

import itertools
import random
import threading
import time

import numpy as np
import pytest

import jax

from marlin_tpu.models import TransformerLM
from marlin_tpu.models.transformer import lm_generate
from marlin_tpu.obs import memledger
from marlin_tpu.obs.exposition import fleet_payload
from marlin_tpu.serving import (
    STATUS_OK,
    FleetController,
    Request,
    Router,
    ServeEngine,
)
from marlin_tpu.serving.router import _rendezvous_score, _weighted_score
from marlin_tpu.serving.supervisor import Supervisor
from marlin_tpu.utils import faults
from marlin_tpu.utils.faults import DelayFault, RaiseFault

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
        # a scale-out replica binds live traffic the moment it joins the
        # ring: an unwarmed one would sit in first-traffic XLA compile
        eng.warmup()
        return eng
    return make


def _ref(params, prompt, steps, heads=HEADS):
    prompt = np.asarray(prompt, np.int32)
    return np.asarray(lm_generate(
        params, prompt, jax.random.key(0), heads=heads,
        max_len=len(prompt) + steps, steps=steps)).tolist()


class FakeClock:
    """Injectable monotonic clock for deterministic controller tests."""

    def __init__(self, t=0.0):
        self.t = float(t)

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


_fake_ids = itertools.count()


class FakeRouter:
    """The controller's full contract surface, with scripted burn/loads."""

    def __init__(self, n=1, burn=0.0, loads=None):
        self._name = f"fake-router-{next(_fake_ids)}"
        self.n = n
        self.burn = burn
        self.loads = loads
        self.weights = {}
        self.calls = []
        self.fail = None

    def replica_count(self):
        return self.n

    def replica_view(self):
        loads = self.loads if self.loads is not None else [0] * self.n
        return [{"replica": i, "state": "accepting", "load": loads[i],
                 "weight": self.weights.get(i, 1.0), "restarts": 0}
                for i in range(self.n)]

    def _fleet_slo(self):
        return {"objectives": [{"burn_rate": self.burn}]}

    def add_replica(self):
        self.calls.append("scale_out")
        if self.fail is not None:
            raise self.fail
        self.n += 1
        return self.n - 1

    def retire_replica(self, idx=None):
        self.calls.append("scale_in")
        if self.fail is not None:
            raise self.fail
        self.n -= 1
        return self.n

    def shed_weight(self, idx=None, frac=0.5):
        self.calls.append("rebalance")
        if self.fail is not None:
            raise self.fail
        i = 0 if idx is None else idx
        self.weights[i] = self.weights.get(i, 1.0) * (1.0 - frac)
        return i, self.weights[i]


def _ctl(router, clock, **kw):
    kw.setdefault("min_replicas", 1)
    kw.setdefault("max_replicas", 4)
    kw.setdefault("eval_interval_s", 1.0)
    kw.setdefault("out_burn", 1.0)
    kw.setdefault("in_burn", 0.1)
    kw.setdefault("hysteresis", 2)
    kw.setdefault("cooldown_s", 0.0)
    kw.setdefault("flap_window_s", 0.0)
    kw.setdefault("threaded", False)
    return FleetController(router, clock=clock, **kw)


class _CaptureLog:
    def __init__(self):
        self.records = []

    def event(self, kind, **fields):
        self.records.append(dict(fields, kind=kind))


# --------------------------------------------------------- routing math


def _owner(key, members, weights=None):
    weights = weights or {}
    return max(members,
               key=lambda i: _weighted_score(key, i, weights.get(i, 1.0)))


def test_weighted_hrw_matches_classic_at_weight_one():
    """At weight 1.0 the weighted transform is order-preserving over the
    digest score — every existing affinity placement is unchanged by the
    elastic-membership refactor."""
    members = [0, 1, 2, 3, 7]
    for i in range(60):
        key = f"prefix-{i}".encode()
        classic = max(members, key=lambda m: _rendezvous_score(key, m))
        assert _owner(key, members) == classic


def test_remove_and_deweight_move_only_owned_keys():
    """Minimal re-placement churn: dropping replica 2 moves ONLY keys it
    owned; halving its weight moves only a fraction of its own keys and
    nobody else's — the satellite-1 churn bar."""
    keys = [f"prefix-{i}".encode() for i in range(400)]
    members = [0, 1, 2, 3]
    before = {k: _owner(k, members) for k in keys}
    owned = [k for k in keys if before[k] == 2]
    assert owned  # the fixture must actually exercise replica 2

    after = {k: _owner(k, [0, 1, 3]) for k in keys}
    for k in keys:
        if before[k] != 2:
            assert after[k] == before[k], "non-owned key moved on removal"

    shed = {k: _owner(k, members, {2: 0.5}) for k in keys}
    moved = [k for k in keys if shed[k] != before[k]]
    assert moved, "halving a weight must re-place some of its keys"
    assert all(before[k] == 2 for k in moved), \
        "deweighting replica 2 moved a key it never owned"
    frac = len(moved) / len(owned)
    assert 0.2 < frac < 0.8, frac  # ~1 - 1/(2 - w): a share, not all


# ----------------------------------------------------- router membership


def test_retired_replica_leaves_candidates_before_rows_move(params):
    """retire_replica pulls the replica out of every rendezvous/readiness
    list BEFORE migration starts (observed from inside the migrate hook),
    removes it from the fleet, and never reuses its index."""
    router = Router(_factory(params), replicas=3, supervise=False,
                    rng=random.Random(5))
    try:
        seen = {}
        orig = router._migrate_out

        def spy(rep):
            seen["candidates"] = [r.idx for r in router._candidates()]
            return orig(rep)

        router._migrate_out = spy
        retired = router.retire_replica(1)
        assert retired == 1
        assert 1 not in seen["candidates"], \
            "retiring replica still routable while its rows moved"
        assert router.replica_count() == 2
        assert [v["replica"] for v in router.replica_view()] == [0, 2]
        # stable indices: the next spawn gets a NEVER-used index, not 1 —
        # rendezvous keys the index, and reuse would inherit dead affinity
        idx = router.add_replica()
        assert idx == 3
    finally:
        router.close()


def test_retire_refuses_last_replica(params):
    router = Router(_factory(params), replicas=1, supervise=False)
    try:
        with pytest.raises(RuntimeError, match="last replica"):
            router.retire_replica()
        assert router.replica_count() == 1
    finally:
        router.close()


def test_scale_out_replica_fresh_breaker_window(params):
    """A scaled-out replica must NOT inherit a struggling peer's restart
    history: it gets its own supervisor with an empty sliding window and
    a closed breaker — the satellite-2 regression bar."""
    router = Router(_factory(params), replicas=1,
                    supervisor_kw=dict(backoff_s=0.005, poll_s=0.02))
    try:
        sup0 = router._replicas[0].supervisor
        # salt the incumbent's window as if it had been crash-looping
        sup0._restarts.extend([time.monotonic()] * 2)
        sup0.restart_count = 2
        idx = router.add_replica()
        rep = next(r for r in router._replicas if r.idx == idx)
        assert rep.supervisor is not None and rep.supervisor is not sup0
        assert len(rep.supervisor._restarts) == 0
        assert rep.supervisor.restart_count == 0
        assert rep.supervisor.breaker_open is False
        assert rep.restarts == 0
        assert rep.engine._started is False or rep.ready()
        # the fresh replica serves for real
        h = rep.engine.submit(Request(prompt=[1, 2, 3], steps=4))
        assert h.result(timeout=60).status == STATUS_OK
    finally:
        router.close()


def test_watchdog_exempts_warmup_but_keeps_crash_detection(params):
    """A stale heartbeat with pending work is 'stuck' — UNLESS a warmup
    is in progress (first-compile latency outlasts any sane watchdog).
    The same staleness recovers the moment the warmup flag drops."""
    eng = _engine(params)
    sup = Supervisor(eng, watchdog_s=0.05, start=False, poll_s=0.01,
                     backoff_s=0.0)
    try:
        eng.warmup()
        assert eng._warming is False  # the flag never leaks past warmup
        eng.start()
        time.sleep(0.05)  # worker parks idle (heartbeat stays put)
        eng.pending = lambda: 1                      # stuck-looking state:
        eng._heartbeat = time.monotonic() - 99.0     # pending + stale pulse
        eng._warming = True
        assert sup.check()  # mid-warmup: watchdog holds fire
        assert sup.restart_count == 0
        eng._warming = False
        assert sup.check()  # warmup over: same staleness recovers
        assert sup.restart_count == 1
    finally:
        del eng.pending
        sup.close()
        eng.close()


def test_shed_weight_reroutes_and_floors(params):
    """shed_weight shrinks exactly one replica's weight (visible in the
    replica view), repeated sheds floor instead of hitting zero, and the
    replica stays in the candidate list as a failover target."""
    router = Router(_factory(params), replicas=2, supervise=False,
                    rng=random.Random(1))
    try:
        idx, w = router.shed_weight(idx=0, frac=0.5)
        assert idx == 0 and w == pytest.approx(0.5)
        view = {v["replica"]: v["weight"] for v in router.replica_view()}
        assert view[0] == pytest.approx(0.5) and view[1] == 1.0
        for _ in range(20):
            _, w = router.shed_weight(idx=0, frac=0.9)
        assert w >= 0.05  # the floor keeps it scoreable
        assert 0 in [r.idx for r in router._candidates()]
    finally:
        router.close()


def test_scale_events_emit_and_merge_counters(params):
    """replica_add / replica_retire / rebalance land in the EventLog and
    a retired replica's counters fold into the router snapshot (work it
    served is not forgotten with it)."""
    log = _CaptureLog()
    router = Router(_factory(params), replicas=2, supervise=False,
                    rng=random.Random(2), log=log)
    try:
        hs = [router.submit(Request(prompt=[3, i % 4 + 1], steps=3))
              for i in range(6)]
        router.drain()
        assert all(h.result(timeout=60).status == STATUS_OK for h in hs)
        steps_before = router.snapshot()["steps"]
        router.add_replica()
        router.shed_weight(idx=0, frac=0.25)
        router.retire_replica(0)
        assert router.snapshot()["steps"] >= steps_before
        evs = [r.get("ev") for r in log.records if r["kind"] == "serve"]
        assert "replica_add" in evs
        assert "rebalance" in evs
        assert "replica_retire" in evs
    finally:
        router.close()


# ----------------------------------------- scale events under live load


def test_scale_in_lossless_under_load(params):
    """The scale-in acceptance: retiring a replica with live mid-stream
    rows and a queued backlog drops NOTHING and restarts NOTHING from
    token 0 — rows migrate mid-decode (migrated_in > 0, retries == 0)
    and every output is bit-identical to the reference."""
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
            time.sleep(0.015)

    thread = threading.Thread(target=pump)
    try:
        thread.start()
        time.sleep(0.1)
        # pin live mid-stream rows on the retiring replica: long rows land
        # in the (16, 8) bucket the pump never touches, and the match-gated
        # delay wedges just the worker decoding them
        first = router._replicas[0].engine
        with faults.injected("serve.decode_step",
                             DelayFault(seconds=0.5, times=1,
                                        match="16x8")):
            with lock:
                handles.extend(first.submit(
                    Request(prompt=[2, 4, 6, 1, 3, 5, 2, 4, 6], steps=8))
                    for _ in range(4))
            time.sleep(0.05)
            retired = router.retire_replica(0)
        stop.set()
        thread.join()
        router.drain()
        assert retired == 0 and router.replica_count() == 1
        results = [h.result(timeout=120) for h in handles]
        assert len(results) >= 8
        for h, r in zip(handles, results):
            assert r.status == STATUS_OK, (r.status, r.reason)
            assert r.tokens.tolist() == _ref(params, h.request.prompt,
                                             h.request.steps)
        snap = router.snapshot()
        assert snap["migrated_in"] >= 1  # rows moved mid-stream...
        assert snap["retries"] == 0      # ...and none restarted at token 0
        for rep in router._replicas:
            audit = rep.engine.kvpool_audit()
            assert audit["ok"], audit["errors"]
    finally:
        stop.set()
        router.close()


def test_scale_out_serves_immediately(params):
    """A scaled-out replica joins warmed and takes traffic: after the
    join, submits spread across both replicas and everything completes."""
    router = Router(_factory(params), replicas=1,
                    supervisor_kw=dict(backoff_s=0.005, poll_s=0.02),
                    rng=random.Random(4))
    try:
        hs = [router.submit(Request(prompt=[1 + i % 6, 2], steps=3))
              for i in range(4)]
        idx = router.add_replica()
        assert idx == 1 and router.replica_count() == 2
        assert all(r.ready() for r in router._replicas)
        hs += [router.submit(Request(prompt=[2, 1 + i % 6], steps=3))
               for i in range(12)]
        router.drain()
        for h in hs:
            r = h.result(timeout=120)
            assert r.status == STATUS_OK, (r.status, r.reason)
            assert r.tokens.tolist() == _ref(params, h.request.prompt,
                                             h.request.steps)
        for rep in router._replicas:
            audit = rep.engine.kvpool_audit()
            assert audit["ok"], audit["errors"]
    finally:
        router.close()


# --------------------------------------------- controller state machine


def test_hysteresis_gates_scale_out():
    r = FakeRouter(n=1, burn=5.0)
    clk = FakeClock()
    ctl = _ctl(r, clk, hysteresis=3)
    try:
        for _ in range(2):
            d = ctl.tick()
            assert d["action"] is None and d["reason"] == "steady"
            clk.advance(1.0)
        d = ctl.tick()
        assert d["action"] == "scale_out"
        assert r.calls == ["scale_out"] and r.n == 2
        assert ctl._last_action["outcome"] == "ok"
    finally:
        ctl.close()


def test_burn_dip_resets_the_streak():
    r = FakeRouter(n=1, burn=5.0)
    clk = FakeClock()
    ctl = _ctl(r, clk, hysteresis=2)
    try:
        ctl.tick()
        clk.advance(1.0)
        r.burn = 0.5  # between in_burn and out_burn: streaks reset
        d = ctl.tick()
        assert d["reason"] == "steady"
        clk.advance(1.0)
        r.burn = 5.0
        d = ctl.tick()  # streak restarts at 1, not 2
        assert d["action"] is None
        assert r.calls == []
    finally:
        ctl.close()


def test_bounds_at_max_and_at_min():
    clk = FakeClock()
    r = FakeRouter(n=4, burn=5.0)
    ctl = _ctl(r, clk, hysteresis=1, max_replicas=4)
    try:
        d = ctl.tick()
        assert d["action"] is None and d["reason"] == "at-max"
        assert r.calls == []
    finally:
        ctl.close()
    r2 = FakeRouter(n=2, burn=0.0)
    ctl2 = _ctl(r2, clk, hysteresis=1, min_replicas=2)
    try:
        clk.advance(1.0)
        d = ctl2.tick()
        assert d["action"] is None and d["reason"] == "at-min"
        assert r2.calls == []
    finally:
        ctl2.close()


def test_cooldown_spaces_actions():
    r = FakeRouter(n=1, burn=5.0)
    clk = FakeClock()
    ctl = _ctl(r, clk, hysteresis=1, cooldown_s=5.0)
    try:
        assert ctl.tick()["action"] == "scale_out"
        clk.advance(1.0)
        d = ctl.tick()
        assert d["action"] is None and d["reason"] == "cooldown"
        clk.advance(5.0)
        assert ctl.tick()["action"] == "scale_out"
        assert r.calls == ["scale_out", "scale_out"]
    finally:
        ctl.close()


def test_flap_damping_suppresses_reversal():
    """A scale-in right after a scale-out (inside the flap window) is an
    oscillating signal, not a trend: the reversal is suppressed and
    recorded, the fleet does not thrash; past the window it proceeds."""
    r = FakeRouter(n=1, burn=5.0)
    clk = FakeClock()
    ctl = _ctl(r, clk, hysteresis=1, flap_window_s=30.0)
    try:
        assert ctl.tick()["action"] == "scale_out"
        clk.advance(1.0)
        r.burn = 0.0  # immediate slack: wants to reverse
        d = ctl.tick()
        assert d["action"] == "scale_in" and d["outcome"] == "damped"
        assert r.calls == ["scale_out"]  # nothing actually retired
        clk.advance(60.0)  # past the flap window: the trend is real now
        d = ctl.tick()
        assert d["action"] == "scale_in" and d["outcome"] is None
        assert r.calls == ["scale_out", "scale_in"]
    finally:
        ctl.close()


def test_rebalance_targets_the_hot_spot():
    r = FakeRouter(n=3, burn=0.5, loads=[20, 1, 1])
    clk = FakeClock()
    ctl = _ctl(r, clk, hysteresis=2, shed_frac=0.5)
    try:
        d = ctl.tick()
        assert d["action"] is None  # imbalance streak 1 < hysteresis
        clk.advance(1.0)
        d = ctl.tick()
        assert d["action"] == "rebalance" and d["replica"] == 0
        assert r.calls == ["rebalance"]
        assert r.weights[0] == pytest.approx(0.5)
    finally:
        ctl.close()


def test_balanced_or_trivial_load_never_rebalances():
    clk = FakeClock()
    r = FakeRouter(n=3, burn=0.5, loads=[3, 0, 0])  # top below the floor
    ctl = _ctl(r, clk, hysteresis=1)
    try:
        assert ctl.tick()["reason"] == "steady"
        assert r.calls == []
    finally:
        ctl.close()


def test_action_error_degrades_to_noop_and_retries():
    r = FakeRouter(n=1, burn=5.0)
    r.fail = RuntimeError("spawn exploded")
    clk = FakeClock()
    ctl = _ctl(r, clk, hysteresis=1)
    try:
        d = ctl.tick()
        assert d["action"] == "scale_out"
        assert r.n == 1  # nothing changed
        assert ctl._last_action["outcome"] == "error"
        assert "spawn exploded" in ctl._last_action["error"]
        r.fail = None
        clk.advance(1.0)
        assert ctl.tick()["action"] == "scale_out"
        assert r.n == 2
    finally:
        ctl.close()


def test_single_flight_busy_then_timeout_accounting():
    """A second decision while an action runs is a no-op ('busy'); past
    action_timeout_s the in-flight action is declared timed out (the
    controller degrades to doing nothing) and its eventual completion is
    recorded as outcome='timeout', after which control resumes."""
    r = FakeRouter(n=1, burn=5.0)
    release = threading.Event()

    def slow_add():
        release.wait(10.0)
        r.n += 1
        return r.n - 1

    r.add_replica = slow_add
    clk = FakeClock()
    ctl = _ctl(r, clk, hysteresis=1, threaded=True, action_timeout_s=5.0)
    try:
        d = ctl.tick()
        assert d["action"] == "scale_out"
        clk.advance(1.0)
        d = ctl.tick()
        assert d["action"] is None and d["reason"] == "busy"
        clk.advance(10.0)  # past the timeout while still in flight
        d = ctl.tick()
        assert d["reason"] == "busy"
        assert ctl._action is not None and ctl._action["timed_out"]
        release.set()
        deadline = time.monotonic() + 5.0
        while ctl._action is not None and time.monotonic() < deadline:
            time.sleep(0.01)
        assert ctl._action is None
        assert ctl._last_action["outcome"] == "timeout"
        clk.advance(1.0)
        d = ctl.tick()  # single-flight slot is free again
        assert d["action"] == "scale_out"
    finally:
        release.set()
        ctl.close()


def test_no_slo_means_no_scale_out():
    """A burn-less fleet (no SLOs configured) never scales out and reads
    as permanent slack — min_replicas floors the shrink."""
    r = FakeRouter(n=2, burn=0.0)
    r._fleet_slo = lambda: None
    clk = FakeClock()
    ctl = _ctl(r, clk, hysteresis=1, min_replicas=1)
    try:
        d = ctl.tick()
        assert d["action"] == "scale_in"
        assert r.n == 1
        clk.advance(1.0)
        assert ctl.tick()["reason"] == "at-min"
    finally:
        ctl.close()


def test_payload_and_debug_fleet_endpoint():
    """payload() exposes bounds/burn/streaks/history/view; the provider
    registry serves it on /debug/fleet and prunes at close."""
    r = FakeRouter(n=2, burn=0.7, loads=[1, 2])
    clk = FakeClock()
    ctl = _ctl(r, clk, hysteresis=2)
    try:
        ctl.tick()
        body = ctl.payload()
        assert body["replicas"] == 2
        assert body["bounds"] == {"min": 1, "max": 4}
        assert body["burn"] == pytest.approx(0.7)
        assert body["streaks"] == {"hot": 0, "slack": 0, "imbalance": 0}
        assert [v["replica"] for v in body["view"]] == [0, 1]
        assert body["replica_seconds"] >= 0.0
        code, payload = fleet_payload()
        assert code == 200 and payload["status"] == "ok"
        mine = [f for f in payload["fleets"]
                if f.get("controller") == ctl._name]
        assert mine and mine[0]["replicas"] == 2
    finally:
        ctl.close()
    code, payload = fleet_payload()
    assert all(f.get("controller") != ctl._name
               for f in payload["fleets"])


def test_console_renders_fleet_panel():
    """The ops console's elastic-fleet panel renders bounds/streaks and
    recent actions from the /debug/fleet payload — and old servers
    (fleet=None) render without the panel or its separator."""
    from marlin_tpu.obs import console

    r = FakeRouter(n=1, burn=5.0)
    clk = FakeClock()
    ctl = _ctl(r, clk, hysteresis=1)
    try:
        ctl.tick()
        frame = console.render({}, {"scopes": []},
                               fleet={"fleets": [ctl.payload()]})
        assert f"fleet {r._name}" in frame
        assert "replicas=2 [1..4]" in frame
        assert "scale_out  -> ok" in frame
        bare = console.render({}, {"scopes": []}, fleet=None)
        assert "fleet " not in bare and len(bare) < len(frame)
    finally:
        ctl.close()


def test_controller_emits_fleet_events():
    log = _CaptureLog()
    r = FakeRouter(n=1, burn=5.0)
    clk = FakeClock()
    ctl = FleetController(r, clock=clk, log=log, min_replicas=1,
                          max_replicas=4, eval_interval_s=1.0,
                          out_burn=1.0, in_burn=0.1, hysteresis=1,
                          cooldown_s=0.0, flap_window_s=0.0,
                          threaded=False)
    try:
        ctl.tick()
        recs = [x for x in log.records if x["kind"] == "fleet"]
        assert any(x.get("action") == "scale_out"
                   and x.get("outcome") == "ok" for x in recs)
    finally:
        ctl.close()


def test_replica_seconds_accumulate_on_the_injected_clock():
    r = FakeRouter(n=3, burn=0.5)
    clk = FakeClock()
    ctl = _ctl(r, clk, hysteresis=99)
    try:
        start = ctl.replica_seconds()
        ctl.tick()
        clk.advance(10.0)
        ctl.tick()
        assert ctl.replica_seconds() - start == pytest.approx(30.0)
    finally:
        ctl.close()


# ----------------------------------------------------------------- chaos


@pytest.mark.parametrize("leg", ["spawn-", "join-"])
def test_kill_fresh_replica_before_join(params, leg):
    """A spawn that dies before the ring join is discarded whole: the
    fleet is untouched, in-flight traffic unaffected (no work existed on
    the orphan to lose), and the next scale-out succeeds."""
    router = Router(_factory(params), replicas=1, supervise=False,
                    rng=random.Random(3))
    try:
        hs = [router.submit(Request(prompt=[1 + i % 5, 2], steps=3))
              for i in range(4)]
        with faults.injected("serve.fleet", RaiseFault(times=1, match=leg)):
            with pytest.raises(Exception):
                router.add_replica()
        assert router.replica_count() == 1
        hs += [router.submit(Request(prompt=[2, 1 + i % 5], steps=3))
               for i in range(4)]
        router.drain()
        for h in hs:
            r = h.result(timeout=120)
            assert r.status == STATUS_OK, (r.status, r.reason)
        idx = router.add_replica()  # the fault was one-shot
        assert router.replica_count() == 2 and idx >= 1
        for rep in router._replicas:
            audit = rep.engine.kvpool_audit()
            assert audit["ok"], audit["errors"]
    finally:
        router.close()


def test_kill_retire_leg_aborts_atomically(params):
    """A fault on the retire leg fires BEFORE any state moves: the
    replica returns to rotation (routable again), nothing migrated, and
    a later retire completes normally."""
    router = Router(_factory(params), replicas=2, supervise=False,
                    rng=random.Random(6))
    try:
        with faults.injected("serve.fleet",
                             RaiseFault(times=1, match="retire-")):
            with pytest.raises(Exception):
                router.retire_replica(0)
        assert router.replica_count() == 2
        assert sorted(r.idx for r in router._candidates()) == [0, 1]
        hs = [router.submit(Request(prompt=[4, 1 + i % 4], steps=3))
              for i in range(6)]
        router.drain()
        assert all(h.result(timeout=120).status == STATUS_OK for h in hs)
        assert router.retire_replica(0) == 0
        assert router.replica_count() == 1
    finally:
        router.close()


def test_kill_shed_leg_leaves_weights(params):
    router = Router(_factory(params), replicas=2, supervise=False,
                    rng=random.Random(8))
    try:
        with faults.injected("serve.fleet",
                             RaiseFault(times=1, match="shed-")):
            with pytest.raises(Exception):
                router.shed_weight(idx=0, frac=0.5)
        assert all(v["weight"] == 1.0 for v in router.replica_view())
        idx, w = router.shed_weight(idx=0, frac=0.5)
        assert idx == 0 and w == pytest.approx(0.5)
    finally:
        router.close()


def test_kill_donor_mid_scale_in_stays_lossless(params):
    """The donor dying mid-migration (export/adopt legs) during a
    scale-in degrades to the retry path: every request still reaches
    exactly one ok Result (bit-identical) and no page leaks anywhere —
    the PR 12 guarantee carried onto the retire path."""
    memledger.reset_ledger()
    for leg in ("export:", "adopt:"):
        router = Router(_factory(params, max_batch=8, queue_depth=512,
                                 num_pages=512),
                        replicas=2,
                        supervisor_kw=dict(backoff_s=0.005, poll_s=0.02),
                        rng=random.Random(11))
        try:
            # wedge both workers so the retire finds real rows to export
            with faults.injected("serve.decode_step",
                                 DelayFault(seconds=0.5, times=2)):
                hs = [router.submit(Request(prompt=[3, 1 + i % 4],
                                            steps=8))
                      for i in range(6)]
                time.sleep(0.05)  # rows live mid-decode
                with faults.injected("serve.migrate",
                                     RaiseFault(times=1, match=leg)):
                    retired = router.retire_replica(0)
            assert retired == 0 and router.replica_count() == 1
            router.drain()
            for h in hs:
                r = h.result(timeout=120)
                assert r.status == STATUS_OK, (leg, r.status, r.reason)
                assert r.tokens.tolist() == _ref(params, h.request.prompt,
                                                 8)
            for rep in router._replicas:
                audit = rep.engine.kvpool_audit()
                assert audit["ok"], (leg, audit["errors"])
            assert router.pending() == 0
            # the ledger balances after the faulted retire: the donor's
            # bytes and any in-flight migration blob were debited exactly
            # once, and only the survivor still owns device memory
            led = memledger.get_ledger()
            mem_audit = led.audit()
            assert mem_audit["ok"], (leg, mem_audit["errors"])
            assert led.totals().get("migration", 0) == 0
            live = {rep.engine._name for rep in router._replicas}
            for e in led.entries():
                if e["component"] in ("kvpool", "migration"):
                    assert e["owner"] in live, (leg, e)
        finally:
            router.close()


def test_controller_rebuild_mid_fleet_resumes_from_router(params):
    """Killing the controller loses only streak counters: a new one on
    the same router reconstructs the fleet from replica_view/count alone
    and keeps controlling correctly."""
    router = Router(_factory(params), replicas=2, supervise=False,
                    rng=random.Random(9))
    clk = FakeClock()
    ctl = _ctl(router, clk, hysteresis=1, max_replicas=3)
    try:
        ctl._burn_signal = lambda: 9.0
        assert ctl.tick()["action"] == "scale_out"
        assert router.replica_count() == 3
        ctl.close()  # controller dies; the fleet stays at its size
        assert router.replica_count() == 3
        ctl2 = _ctl(router, clk, hysteresis=1, min_replicas=1,
                    max_replicas=3)
        try:
            ctl2._burn_signal = lambda: 0.0
            clk.advance(1.0)
            d = ctl2.tick()  # re-derives scale-in purely from router truth
            assert d["action"] == "scale_in" and d["replicas"] == 3
            assert router.replica_count() == 2
        finally:
            ctl2.close()
        hs = [router.submit(Request(prompt=[2, 1 + i % 4], steps=3))
              for i in range(6)]
        router.drain()
        assert all(h.result(timeout=120).status == STATUS_OK for h in hs)
    finally:
        ctl.close()
        router.close()


@pytest.mark.slow
def test_fleet_chaos_soak(params):
    """The acceptance soak: >= 6 real scale events under ~64 req/s — out,
    in, and rebalance, each also killed once mid-event on a serve.fleet
    leg, plus a controller killed and rebuilt mid-fleet. Every request
    ever accepted reaches exactly one ok Result (bit-identical), ZERO
    restart from token 0 (the killed legs abort before any state moves),
    and every surviving pool audits clean. The burn signal is scripted
    (test_slo.py owns the SLO windows); the actions are entirely real."""
    memledger.reset_ledger()
    router = Router(_factory(params, max_batch=8, queue_depth=1024,
                             num_pages=512),
                    replicas=1,
                    supervisor_kw=dict(backoff_s=0.005, poll_s=0.02),
                    rng=random.Random(13))
    clk = FakeClock()
    burn = {"v": 0.0}

    def make_ctl():
        c = FleetController(router, clock=clk, min_replicas=1,
                            max_replicas=3, eval_interval_s=0.0,
                            out_burn=1.0, in_burn=0.1, hysteresis=1,
                            cooldown_s=0.0, flap_window_s=0.0,
                            action_timeout_s=60.0, threaded=False)
        c._burn_signal = lambda: burn["v"]
        return c

    ctl = make_ctl()
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
            time.sleep(0.015)  # 2 pumps x ~32 req/s

    def step(burn_v, fault_leg=None):
        time.sleep(0.25)  # dwell: traffic keeps flowing between events
        burn["v"] = burn_v
        clk.advance(1.0)
        if fault_leg is None:
            return ctl.tick()
        with faults.injected("serve.fleet",
                             RaiseFault(times=1, match=fault_leg)):
            return ctl.tick()

    threads = [threading.Thread(target=pump) for _ in range(2)]
    try:
        for t in threads:
            t.start()
        time.sleep(0.1)
        # 1. scale-out killed before the ring join -> degraded to no-op
        d = step(9.0, fault_leg="join-")
        assert d["action"] == "scale_out"
        assert router.replica_count() == 1
        assert ctl._last_action["outcome"] == "error"
        # 2-3. scale out for real, 1 -> 3
        assert step(9.0)["action"] == "scale_out"
        assert step(9.0)["action"] == "scale_out"
        assert router.replica_count() == 3
        time.sleep(0.2)  # let traffic spread across the grown fleet
        # 4. rebalance killed mid-shed -> weights untouched
        hot = router._replicas[0].idx
        ctl._hot_spot = lambda view: hot
        d = step(0.5, fault_leg="shed-")
        assert d["action"] == "rebalance"
        assert ctl._last_action["outcome"] == "error"
        assert all(v["weight"] == 1.0 for v in router.replica_view())
        # 5. rebalance for real
        assert step(0.5)["action"] == "rebalance"
        assert any(v["weight"] < 1.0 for v in router.replica_view())
        ctl._hot_spot = lambda view: None
        # 6. scale-in killed on the retire leg -> fleet unchanged
        d = step(0.0, fault_leg="retire-")
        assert d["action"] == "scale_in"
        assert ctl._last_action["outcome"] == "error"
        assert router.replica_count() == 3
        # 7-8. scale in for real, 3 -> 1: live rows migrate losslessly
        assert step(0.0)["action"] == "scale_in"
        assert step(0.0)["action"] == "scale_in"
        assert router.replica_count() == 1
        # 9. controller killed and rebuilt mid-fleet, still under load
        ctl.close()
        ctl = make_ctl()
        d = step(9.0)
        assert d["action"] == "scale_out"
        assert router.replica_count() == 2
        stop.set()
        for t in threads:
            t.join()
        router.drain()
        results = [h.result(timeout=180) for h in handles]
        assert len(results) > 100
        bad = [(r.status, r.reason) for r in results
               if r.status != STATUS_OK]
        assert not bad, bad[:5]  # zero dropped or double-terminal
        for h, r in zip(handles, results):
            assert r.tokens.tolist() == _ref(params, h.request.prompt,
                                             h.request.steps)
        snap = router.snapshot()
        assert snap["retries"] == 0  # zero token-0 restarts, all events
        ok_events = [x for x in ctl.payload()["history"]
                     if x["outcome"] == "ok"]
        assert len(ok_events) >= 1  # the rebuilt controller's own event
        for rep in router._replicas:
            audit = rep.engine.kvpool_audit()
            assert audit["ok"], audit["errors"]
        # nine scale events later the ledger still balances exactly: no
        # retired replica or aborted scale event left bytes behind
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
        ctl.close()
        router.close()
