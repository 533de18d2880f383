"""The static-analysis suite analyzed: every check fires on its seeded
fixture and stays silent on the clean one, the suppression baseline
round-trips, the JSON schema holds, and the real repo passes its own gate.

Stdlib-only on purpose (no jax import): the analyzer must run on a box
that cannot import the package it analyzes, and so must its tests.
"""

import json
import subprocess
import sys
from pathlib import Path

import pytest

from tools.analyze.__main__ import FIXTURES, _selftest, main
from tools.analyze.checks import CHECKS, run_checks
from tools.analyze.core import (Finding, Repo, load_baseline, save_baseline,
                                split_by_baseline)

ROOT = Path(__file__).resolve().parents[1]
FDIR = ROOT / "tests" / "fixtures" / "analyze"
TREE = FDIR / "consistency_tree"


def _run_fixture(check: str, fixture: str):
    repo = Repo(FDIR, explicit_files=[FDIR / fixture])
    return run_checks(repo, names=[check])


# ------------------------------------------------------------ per-check

@pytest.mark.parametrize("check,fixture", sorted(FIXTURES.items()))
def test_check_fires_on_seeded_fixture(check, fixture):
    findings = [f for f in _run_fixture(check, fixture) if f.check == check]
    assert findings, f"{check} must fire on {fixture}"
    for f in findings:
        assert f.path == fixture
        assert f.line > 0 and f.message and f.hint and f.key
        assert f.severity == "error"


@pytest.mark.parametrize("check", sorted(FIXTURES))
def test_check_silent_on_clean_fixture(check):
    findings = [f for f in _run_fixture(check, "clean.py")
                if f.check == check]
    assert findings == [], f"{check} must stay silent on clean.py"


def test_lock_discipline_flags_both_unlocked_domains():
    keys = {f.key for f in _run_fixture("lock-discipline", "bad_locks.py")}
    assert keys == {
        "lock-discipline:bad_locks.py:ServeEngine.counter@submit",
        "lock-discipline:bad_locks.py:ServeEngine.counter@_run",
    }


def test_recompile_flags_all_three_hazard_shapes():
    keys = {f.key for f in _run_fixture("recompile", "bad_recompile.py")}
    assert "recompile:bad_recompile.py:make_program@closure" in keys
    assert any(k.startswith("recompile:bad_recompile.py:loop@")
               for k in keys)
    assert "recompile:bad_recompile.py:scaled@traced-knob" in keys


def test_donation_names_the_donated_chain():
    (f,) = _run_fixture("donation", "bad_donation.py")
    assert f.key == "donation:bad_donation.py:train.state@_step"


def test_consistency_tree_finds_every_seeded_drift():
    repo = Repo(TREE)
    keys = {f.key for f in run_checks(repo,
                                      names=["doc-sync", "test-hygiene"])}
    assert keys == {
        "doc-sync:faults:net.flaky@undocumented",
        "doc-sync:faults:fs.phantom@ghost",
        "doc-sync:config:retry_max@default-drift",
        "doc-sync:config:unused_knob@undocumented",
        "doc-sync:config:unused_knob@dead-knob",
        "doc-sync:config:ghost_knob@ghost",
        "doc-sync:config:pagelen@unknown-read:marlin_tpu/engine.py:"
        "configure",
        "doc-sync:metrics:marlin_mini_depth@undocumented",
        "doc-sync:metrics:marlin_mini_ghost@ghost",
        "doc-sync:memory:mystery_comp@undocumented",
        "doc-sync:memory:phantom_comp@ghost",
        "doc-sync:events:kind:mystery@unknown",
        "doc-sync:events:ev:surprise@unknown",
        "doc-sync:events:ev:stale_ev@stale",
        "test-hygiene:marlin_tpu/utils/faults.py:net.flaky@untested",
    }


# ------------------------------------------------------------ annotations

def test_ignore_annotation_suppresses_a_finding(tmp_path):
    (tmp_path / "mod.py").write_text(
        "def decode_step(tokens):\n"
        "    # analyze: ignore[host-sync] — the one intentional pull\n"
        "    return tokens.item()\n")
    repo = Repo(tmp_path, explicit_files=[tmp_path / "mod.py"])
    assert run_checks(repo, names=["host-sync"]) == []


def test_single_writer_annotation_exempts_the_field(tmp_path):
    (tmp_path / "mod.py").write_text(
        "import threading\n\n\n"
        "class ServeEngine:\n"
        "    def __init__(self):\n"
        "        self.hb = 0\n"
        "        self._t = threading.Thread(target=self._run)\n\n"
        "    def poke(self):\n"
        "        # analyze: single-writer — generation-guarded stamp\n"
        "        self.hb = 1\n\n"
        "    def _run(self):\n"
        "        self.hb = 2\n")
    repo = Repo(tmp_path, explicit_files=[tmp_path / "mod.py"])
    assert run_checks(repo, names=["lock-discipline"]) == []


def test_parse_error_is_a_finding_not_a_crash(tmp_path):
    (tmp_path / "broken.py").write_text("def f(:\n")
    repo = Repo(tmp_path, explicit_files=[tmp_path / "broken.py"])
    findings = run_checks(repo, names=["host-sync"])
    assert [f.check for f in findings] == ["parse"]


# ------------------------------------------------------------- baseline

def test_baseline_roundtrip_suppresses_and_prunes(tmp_path):
    findings = _run_fixture("lock-discipline", "bad_locks.py")
    bpath = tmp_path / "baseline.json"
    save_baseline(bpath, findings, "fixture: seeded on purpose")
    baseline = load_baseline(bpath)
    assert all(baseline[f.key] == "fixture: seeded on purpose"
               for f in findings)
    new, suppressed, stale = split_by_baseline(findings, baseline)
    assert new == [] and len(suppressed) == len(findings) and stale == []
    # a baseline key nothing matches any more is reported stale
    baseline["lock-discipline:gone.py:X.y@z"] = "obsolete"
    _, _, stale = split_by_baseline(findings, baseline)
    assert stale == ["lock-discipline:gone.py:X.y@z"]


def test_baseline_missing_file_is_empty(tmp_path):
    assert load_baseline(tmp_path / "nope.json") == {}


# ------------------------------------------------------------------ CLI

def test_cli_exit_one_on_findings_zero_with_baseline(tmp_path, capsys):
    fixture = str(FDIR / "bad_locks.py")
    bpath = tmp_path / "baseline.json"
    assert main([fixture, "--baseline", str(bpath)]) == 1
    assert main([fixture, "--baseline", str(bpath),
                 "--update-baseline", "--reason", "seeded"]) == 0
    assert main([fixture, "--baseline", str(bpath)]) == 0
    capsys.readouterr()


def test_cli_update_baseline_requires_reason(tmp_path, capsys):
    fixture = str(FDIR / "bad_locks.py")
    bpath = tmp_path / "baseline.json"
    assert main([fixture, "--baseline", str(bpath),
                 "--update-baseline"]) == 2
    capsys.readouterr()


def test_cli_json_schema(tmp_path, capsys):
    # bad_locks, not bad_hostsync: host-sync skips files under tests/
    # when resolved repo-relative, lock-discipline runs everywhere
    fixture = str(FDIR / "bad_locks.py")
    main([fixture, "--no-baseline", "--json"])
    payload = json.loads(capsys.readouterr().out)
    assert payload["version"] == 1
    assert payload["findings"], "seeded fixture must produce findings"
    for f in payload["findings"]:
        assert set(f) >= {"check", "path", "line", "message", "hint",
                          "severity", "key"}
    assert "suppressed" in payload and "stale_baseline_keys" in payload


def test_selftest_green():
    assert _selftest(ROOT) == 0


def test_repo_gate_is_green():
    """The shipped tree passes its own strict gate: no non-baselined
    error findings. Run as a subprocess so the gate's real entry point
    (python -m tools.analyze) is what's exercised."""
    proc = subprocess.run(
        [sys.executable, "-m", "tools.analyze"], cwd=ROOT,
        capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_every_check_registered_and_listed(capsys):
    assert set(CHECKS) == {"lock-discipline", "donation", "recompile",
                           "host-sync", "doc-sync", "test-hygiene"}
    assert main(["--list"]) == 0
    out = capsys.readouterr().out
    for name in CHECKS:
        assert name in out
