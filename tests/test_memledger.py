"""HBM-ledger suite (obs/memledger.py; docs/observability.md "Memory
attribution").

The acceptance bars, bottom up:

- **Exact accounting**: register/free debit exactly once, flow entries
  clamp at zero, transfer moves ownership without moving the process
  total, and every anomaly shape (double register, strict free of an
  unknown name, a flow driven negative, an unknown component) lands in
  the audit as an error — with the total still exact.
- **Reconciler edge cases**: a backend with no ``memory_stats`` renders
  "n/a", NEVER zero (zero reads as "nothing resident", the opposite of
  "unknown"); a pool rebuild that frees-then-registers the same name
  does not double-count; a negative unattributed remainder is reported,
  not clamped.
- **Concurrency**: 8 threads of register/free against a concurrent
  gauge render keep the audit exact (the scrape-stress bar from the
  module docstring).
- **Alarms**: the leak detector arms only above ``min_bytes`` with a
  live baseline, resolves on a real live-byte drop, and alerts after
  ``windows`` samples; OOM forensics dump one parseable JSONL artifact,
  rate-limited and pruned to the newest 16.
"""

import json
import os
import threading

import pytest

from marlin_tpu.config import config_context
from marlin_tpu.obs import memledger
from marlin_tpu.obs.console import render as console_render
from marlin_tpu.obs.memledger import (
    KNOWN_COMPONENTS,
    LeakDetector,
    MemoryLedger,
    dump_oom_forensics,
    emit_snapshot,
    install_memledger_gauges,
    is_oom_error,
    memory_payload,
    reconcile,
)
from marlin_tpu.obs.metrics import MetricsRegistry
from marlin_tpu.obs.report import _memory_attribution_section, load_events
from marlin_tpu.utils.tracing import EventLog, set_default_event_log

# install_memledger_gauges is idempotent per id(registry); pin every test
# registry for the module's lifetime so CPython can never hand a later
# test a recycled id (which would silently skip the install)
_PINNED: list = []


def _fresh_registry() -> MetricsRegistry:
    reg = MetricsRegistry()
    _PINNED.append(reg)
    return reg


@pytest.fixture(autouse=True)
def _clean_ledger():
    # the process ledger is a singleton; these tests deliberately seed
    # anomalies, so isolate every test (and leave the ledger clean for
    # the migration/fleet suites' audit assertions in the same process)
    memledger.reset_ledger()
    yield
    memledger.reset_ledger()


@pytest.fixture()
def default_log(tmp_path):
    log = EventLog(str(tmp_path / "events.jsonl"))
    prev = set_default_event_log(log)
    yield log
    set_default_event_log(prev)
    log.close()


# --------------------------------------------------------- exact accounting


def test_register_free_exact():
    led = MemoryLedger()
    led.register("kvpool:a", 1000, "kvpool", owner="e1")
    led.register("program:als", 500, "program", owner="e1")
    assert led.total_bytes() == 1500
    assert led.totals() == {"kvpool": 1000, "program": 500}
    assert led.owner_bytes("e1") == 1500
    assert led.free("kvpool:a") == 1000
    assert led.total_bytes() == 500
    audit = led.audit()
    assert audit["ok"], audit["errors"]
    assert audit["registered_bytes"] == 500
    assert audit["entries"] == 1


def test_entries_sorted_and_shaped():
    led = MemoryLedger()
    led.register("b", 2, "kvpool", owner="y")
    led.register("a", 1, "program", owner="x")
    es = led.entries()
    assert [e["name"] for e in es] == ["a", "b"]
    assert es[0] == {"name": "a", "component": "program", "bytes": 1,
                     "owner": "x"}


def test_double_register_is_anomaly_but_total_stays_exact():
    led = MemoryLedger()
    led.register("x", 100, "kvpool")
    led.register("x", 250, "kvpool")  # replaced, not summed
    assert led.total_bytes() == 250
    audit = led.audit()
    assert not audit["ok"]
    assert any("double register" in e for e in audit["errors"])
    # the total invariant held through the anomaly
    assert audit["registered_bytes"] == 250


def test_strict_free_of_unknown_is_anomaly_lenient_is_noop():
    led = MemoryLedger()
    assert led.free("ghost", strict=False) == 0
    assert led.audit()["ok"]
    assert led.free("ghost") == 0
    audit = led.audit()
    assert not audit["ok"]
    assert any("not registered" in e for e in audit["errors"])


def test_negative_register_and_unknown_component_are_anomalies():
    led = MemoryLedger()
    led.register("neg", -64, "kvpool")
    led.register("odd", 10, "bogus")
    assert led.total_bytes() == 10  # negative clamped to 0
    audit = led.audit()
    assert not audit["ok"]
    assert any("negative size" in e for e in audit["errors"])
    assert any("unknown component" in e for e in audit["errors"])


def test_flow_entries_clamp_and_stay_registered_at_zero():
    led = MemoryLedger()
    led.add("prefetch:inflight", 300, "prefetch")
    led.add("prefetch:inflight", -100, "prefetch")
    assert led.total_bytes() == 200
    led.add("prefetch:inflight", -200, "prefetch")
    assert led.total_bytes() == 0
    # a drained flow is a live series at zero, not a freed slab
    assert led.audit()["entries"] == 1
    assert led.audit()["ok"]
    led.add("prefetch:inflight", -50, "prefetch")  # driven negative
    assert led.total_bytes() == 0
    audit = led.audit()
    assert not audit["ok"]
    assert any("driven" in e for e in audit["errors"])


def test_transfer_moves_owner_not_total():
    led = MemoryLedger()
    led.register("mig:1", 4096, "migration", owner="src")
    assert led.transfer("mig:1", "dst")
    assert led.owner_bytes("src") == 0
    assert led.owner_bytes("dst") == 4096
    assert led.total_bytes() == 4096
    # a second transfer of a consumed name is idempotent, not an anomaly
    led.free("mig:1")
    assert led.transfer("mig:1", "elsewhere") is False
    assert led.audit()["ok"]


def test_free_owner_sweeps_everything_the_owner_holds():
    led = MemoryLedger()
    led.register("kvpool:a", 100, "kvpool", owner="e1")
    led.register("mig:a", 50, "migration", owner="e1")
    led.register("kvpool:b", 70, "kvpool", owner="e2")
    assert led.free_owner("e1") == 150
    assert led.total_bytes() == 70
    assert led.owner_bytes("e1") == 0
    assert led.audit()["ok"]


def test_free_listener_fires_once_per_debit_and_swallows_errors():
    led = MemoryLedger()
    calls = []

    def listener(component, nbytes):
        calls.append((component, nbytes))
        raise RuntimeError("listener bug must not break the free")

    led.add_free_listener(listener)
    led.add_free_listener(listener)  # idempotent per callable
    led.register("x", 123, "kvpool")
    assert led.free("x") == 123
    assert calls == [("kvpool", 123)]
    led.add("f", 100, "prefetch")
    led.add("f", -40, "prefetch")  # flow debits feed the listener too
    assert calls == [("kvpool", 123), ("prefetch", 40)]


# ------------------------------------------------------ reconciler edge cases


def test_reconcile_without_live_view_is_na_not_zero(monkeypatch):
    monkeypatch.setattr(memledger, "live_device_bytes", lambda: None)
    memledger.get_ledger().register("kvpool:x", 512, "kvpool")
    rec = reconcile()
    assert rec["registered_bytes"] == 512
    assert rec["live_bytes"] is None
    assert rec["unattributed_bytes"] is None
    assert rec["unattributed_frac"] is None
    status, body = memory_payload()
    assert status == 200 and body["status"] == "ok"
    # "n/a", NEVER 0 — zero would read as "nothing resident"
    assert body["live_bytes"] == "n/a"
    assert body["unattributed_bytes"] == "n/a"
    assert body["unattributed_frac"] == "n/a"


def test_reconcile_with_live_view(monkeypatch):
    monkeypatch.setattr(memledger, "live_device_bytes", lambda: 1000)
    memledger.get_ledger().register("kvpool:x", 600, "kvpool")
    rec = reconcile()
    assert rec["live_bytes"] == 1000
    assert rec["unattributed_bytes"] == 400
    assert rec["unattributed_frac"] == 0.4


def test_reconcile_overcount_reported_not_clamped(monkeypatch):
    # ledger above live = the ledger over-counts; that asymmetry is the
    # finding, so the signed remainder must survive
    monkeypatch.setattr(memledger, "live_device_bytes", lambda: 500)
    memledger.get_ledger().register("kvpool:x", 600, "kvpool")
    rec = reconcile()
    assert rec["unattributed_bytes"] == -100
    assert rec["unattributed_frac"] == 0.0


def test_pool_rebuild_free_then_register_no_double_count():
    # the engine's _ensure_kvpool idiom: recover tears the slab down and
    # rebuilds under the SAME ledger name — free-then-register keeps the
    # account exact with zero anomalies, unlike a bare re-register
    led = memledger.get_ledger()
    for rebuild, nbytes in enumerate((1 << 20, 2 << 20, 1 << 19)):
        led.free("kvpool:eng", strict=False)
        led.register("kvpool:eng", nbytes, "kvpool", owner="eng")
        assert led.total_bytes() == nbytes, rebuild
    audit = led.audit()
    assert audit["ok"], audit["errors"]
    assert audit["entries"] == 1


def test_concurrent_register_free_under_scrape_stress(monkeypatch):
    # 8 writer threads vs a continuous render of the memledger collector:
    # every op atomic, the final audit exact, no render ever raises
    monkeypatch.setattr(memledger, "live_device_bytes", lambda: 1 << 30)
    led = memledger.get_ledger()
    reg = _fresh_registry()
    install_memledger_gauges(reg)
    stop = threading.Event()
    errors = []

    def writer(tid):
        try:
            for i in range(300):
                name = f"t{tid}:{i}"
                led.register(name, 4096 + tid, "kvpool", owner=f"th{tid}")
                led.add(f"flow{tid}", 128, "prefetch")
                led.add(f"flow{tid}", -128, "prefetch")
                led.free(name)
        except Exception as e:  # pragma: no cover - the failure we hunt
            errors.append(e)

    def scraper():
        while not stop.is_set():
            text = reg.render()
            assert "# TYPE marlin_mem_registered_bytes gauge" in text

    threads = [threading.Thread(target=writer, args=(t,)) for t in range(8)]
    s = threading.Thread(target=scraper)
    s.start()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    stop.set()
    s.join()
    assert not errors, errors
    audit = led.audit()
    assert audit["ok"], audit["errors"]
    assert led.total_bytes() == 0  # flows drained to zero, slabs freed


# ------------------------------------------------------------ gauge families


def test_gauges_render_all_three_families(monkeypatch):
    monkeypatch.setattr(memledger, "live_device_bytes", lambda: 5000)
    led = memledger.get_ledger()
    led.register("kvpool:x", 3000, "kvpool")
    reg = _fresh_registry()
    install_memledger_gauges(reg)
    install_memledger_gauges(reg)  # idempotent per registry
    text = reg.render()
    for fam in ("marlin_mem_registered_bytes", "marlin_mem_live_bytes",
                "marlin_mem_unattributed_bytes"):
        assert f"# TYPE {fam} gauge" in text
    assert 'marlin_mem_registered_bytes{component="kvpool"} 3000' in text
    assert 'marlin_mem_registered_bytes{component="total"} 3000' in text
    # every known component exports a series even at zero
    for comp in KNOWN_COMPONENTS:
        assert f'component="{comp}"' in text
    assert 'marlin_mem_live_bytes{component="total"} 5000' in text
    assert 'marlin_mem_unattributed_bytes{component="total"} 2000' in text
    # each scrape doubles as a leak-detector observation window
    assert memledger.get_leak_detector()._last_live == 5000


def test_gauges_without_live_view_omit_live_samples(monkeypatch):
    monkeypatch.setattr(memledger, "live_device_bytes", lambda: None)
    reg = _fresh_registry()
    install_memledger_gauges(reg)
    text = reg.render()
    # the families exist (scrapers see the TYPE line) but carry no bogus
    # zero samples when the backend has no live view
    assert "# TYPE marlin_mem_live_bytes gauge" in text
    assert 'marlin_mem_live_bytes{component="total"}' not in text
    assert 'marlin_mem_unattributed_bytes{component="total"}' not in text


# -------------------------------------------------------------- /debug/memory


def test_memory_payload_503_on_audit_violation():
    led = memledger.get_ledger()
    led.register("x", 10, "kvpool")
    led.register("x", 10, "kvpool")  # seed the anomaly
    status, body = memory_payload()
    assert status == 503
    assert body["status"] == "violated"
    assert body["audit"]["errors"]


def test_memory_payload_ok_shape(monkeypatch):
    monkeypatch.setattr(memledger, "live_device_bytes", lambda: 2000)
    memledger.get_ledger().register("program:als", 800, "program",
                                    owner="eng")
    status, body = memory_payload()
    assert status == 200 and body["status"] == "ok"
    assert body["registered_bytes"] == 800
    assert body["components"] == {"program": 800}
    assert body["entries"][0]["owner"] == "eng"
    assert body["unattributed_bytes"] == 1200
    assert "planner_ratios" not in body  # admission charges one number
    assert body["leak_alerts"] == []


# ------------------------------------------------------------- leak detector


def test_leak_detector_inert_without_live_baseline():
    clock = [0.0]
    det = LeakDetector(windows=2, min_bytes=1024,
                       clock=lambda: clock[0])
    det.note_free("kvpool", 1 << 20)  # no observe yet: CPU shape
    assert det.pending_count() == 0
    assert det.observe(10_000_000) == []


def test_leak_detector_min_bytes_filter():
    det = LeakDetector(windows=2, min_bytes=4096, clock=lambda: 0.0)
    det.observe(10_000_000)
    det.note_free("kvpool", 4095)
    assert det.pending_count() == 0
    det.note_free("kvpool", 4096)
    assert det.pending_count() == 1


def test_leak_detector_resolves_on_live_drop():
    det = LeakDetector(windows=2, min_bytes=1024, clock=lambda: 0.0)
    det.observe(10_000_000)
    det.note_free("kvpool", 4096)
    # live dropped by at least half the freed size: watch resolved
    assert det.observe(10_000_000 - 2048) == []
    assert det.pending_count() == 0
    assert det.alerts == []


def test_leak_detector_alerts_after_windows(default_log):
    hooks = []
    det = LeakDetector(windows=2, min_bytes=1024, clock=lambda: 7.0)
    det.add_hook(hooks.append)
    det.add_hook(hooks.append)  # idempotent
    det.observe(10_000_000)
    det.note_free("kvpool", 4096)
    assert det.observe(10_000_000) == []  # window 1 of 2
    fired = det.observe(10_000_000)       # window 2: verdict
    assert len(fired) == 1
    alert = fired[0]
    assert alert["component"] == "kvpool"
    assert alert["freed_bytes"] == 4096
    assert alert["windows"] == 2
    assert alert["t"] == 7.0
    assert hooks == [alert]
    assert det.alerts == [alert]
    assert det.pending_count() == 0
    default_log.close()
    events, _ = load_events(default_log.path)
    leaks = [r for r in events
             if r.get("kind") == "mem" and r.get("ev") == "leak"]
    assert len(leaks) == 1 and leaks[0]["component"] == "kvpool"


def test_global_detector_wired_to_ledger_frees(monkeypatch):
    det = memledger.get_leak_detector()
    det.observe(100 << 20)  # give the detector a live baseline
    led = memledger.get_ledger()
    led.register("kvpool:big", 64 << 20, "kvpool")
    led.free("kvpool:big")
    assert det.pending_count() == 1


# -------------------------------------------------------------- OOM forensics


def test_is_oom_error_classifier():
    assert is_oom_error(RuntimeError("RESOURCE_EXHAUSTED: ..."))
    assert is_oom_error(RuntimeError("Out of memory allocating 1GB"))
    assert is_oom_error(RuntimeError("backend OOM"))
    assert not is_oom_error(ValueError("bad bucket"))

    class PagePoolExhausted(Exception):
        pass

    assert is_oom_error(PagePoolExhausted("pool dry"))


def test_oom_dump_writes_parseable_artifact(tmp_path, default_log):
    led = memledger.get_ledger()
    led.register("kvpool:eng", 1 << 20, "kvpool", owner="eng")
    led.register("program:als", 1 << 18, "program", owner="eng")
    with config_context(obs_profile_dir=str(tmp_path)):
        path = dump_oom_forensics("PagePoolExhausted: pool dry",
                                  extra={"bucket": "16x8"})
        assert path and os.path.exists(path)
        # rate limited: a second dump inside the window is skipped
        assert dump_oom_forensics("again") is None
    lines = [json.loads(ln) for ln in
             open(path).read().splitlines() if ln]
    head = lines[0]
    assert head["kind"] == "mem" and head["ev"] == "oom"
    assert head["reason"].startswith("PagePoolExhausted")
    assert head["bucket"] == "16x8"
    assert head["audit"]["ok"]
    assert "components" not in head["reconcile"]  # entries carry the detail
    entries = [r for r in lines if r.get("ev") == "entry"]
    assert {e["name"] for e in entries} == {"kvpool:eng", "program:als"}
    default_log.close()
    events, _ = load_events(default_log.path)
    dumps = [r for r in events
             if r.get("kind") == "mem" and r.get("ev") == "oom_dump"]
    assert len(dumps) == 1 and dumps[0]["path"] == path


def test_oom_dump_prunes_to_newest_16(tmp_path):
    with config_context(obs_profile_dir=str(tmp_path)):
        for i in range(20):
            assert dump_oom_forensics(f"oom {i}", min_interval_s=0.0)
    mine = [n for n in os.listdir(tmp_path)
            if n.startswith("marlin_oom_") and n.endswith(".jsonl")]
    assert len(mine) <= 16


# ----------------------------------------------------- snapshots and reports


def test_emit_snapshot_to_explicit_log(tmp_path):
    led = memledger.get_ledger()
    led.register("kvpool:x", 2048, "kvpool")
    log = EventLog(str(tmp_path / "snap.jsonl"))
    emit_snapshot(log=log)
    log.close()
    events, _ = load_events(str(tmp_path / "snap.jsonl"))
    assert len(events) == 1
    rec = events[0]
    assert rec["kind"] == "mem" and rec["ev"] == "snapshot"
    assert rec["components"] == {"kvpool": 2048}
    assert rec["total_bytes"] == 2048


def test_report_memory_section_renders_from_mem_records():
    events = [
        {"kind": "serve", "ev": "x", "t": 1.0},
        {"kind": "mem", "ev": "snapshot", "t": 2.0,
         "components": {"kvpool": 800, "program": 200},
         "total_bytes": 1000},
        {"kind": "mem", "ev": "leak", "t": 3.0, "component": "kvpool",
         "freed_bytes": 4096, "live_drop_bytes": 0, "windows": 3},
        {"kind": "mem", "ev": "oom_dump", "t": 4.0, "reason": "oom",
         "path": "/tmp/marlin_oom_1_0.jsonl"},
    ]
    out = _memory_attribution_section(events)
    text = "\n".join(out)
    assert out[0] == "== memory attribution =="
    assert "last attribution (1000 bytes registered)" in text
    assert "kvpool" in text and "80.0%" in text
    assert "leak alerts: 1" in text
    assert "OOM forensics dumps: 1" in text
    # pre-ledger logs carry no mem records: the section must vanish so
    # old goldens stay byte-identical
    assert _memory_attribution_section([{"kind": "serve", "t": 1.0}]) == []


def test_console_memory_panel(monkeypatch):
    monkeypatch.setattr(memledger, "live_device_bytes", lambda: None)
    led = memledger.get_ledger()
    led.register("kvpool:x", 700, "kvpool")
    led.register("program:y", 300, "program")
    _, body = memory_payload()
    body["leak_alerts"] = [{"component": "kvpool", "freed_bytes": 4096,
                            "live_drop_bytes": 0, "windows": 3, "t": 0.0}]
    frame = console_render({}, {"scopes": []}, memory=body)
    assert "memory: registered=1000 live=n/a unattributed=n/a" in frame
    assert "kvpool" in frame and "program" in frame
    assert "LEAK kvpool: freed 4096 B" in frame
    assert "LEDGER AUDIT VIOLATED" not in frame
    # the violated frame is the one an operator most needs to see
    led.register("kvpool:x", 700, "kvpool")
    _, body = memory_payload()
    frame = console_render({}, {"scopes": []}, memory=body)
    assert "LEDGER AUDIT VIOLATED" in frame
    # a memory-less server renders the pre-ledger layout
    frame = console_render({}, {"scopes": []})
    assert "memory:" not in frame
