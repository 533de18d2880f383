"""Empirical multiply-strategy autotuner (programmatic RMMcompare)."""

import numpy as np
import pytest

import marlin_tpu as mt
from marlin_tpu.parallel import autotune


@pytest.fixture(autouse=True)
def _fresh_cache(tmp_path):
    # point the disk layer at a per-test path so clear_cache() (which clears
    # BOTH layers) never touches a developer's real ~/.cache file
    with mt.config_context(autotune_cache_path=str(tmp_path / "autotune.json")):
        autotune.clear_cache()
        yield
        autotune.clear_cache()


def test_tune_multiply_times_candidates(mesh):
    a = mt.DenseVecMatrix.random(0, 64, 48, mesh=mesh)
    b = mt.DenseVecMatrix.random(1, 48, 32, mesh=mesh)
    results = mt.tune_multiply(a, b, reps=1)
    assert len(results) >= 2
    assert all(sec > 0 for _, sec in results)
    # sorted fastest-first
    secs = [sec for _, sec in results]
    assert secs == sorted(secs)


def test_tuned_strategy_matches_oracle(mesh):
    a = mt.DenseVecMatrix.random(2, 40, 24, mesh=mesh)
    b = mt.DenseVecMatrix.random(3, 24, 16, mesh=mesh)
    c = a.multiply(b, strategy="tuned")
    np.testing.assert_allclose(c.to_numpy(), a.to_numpy() @ b.to_numpy(),
                               rtol=2e-4, atol=2e-4)


def test_tuned_uses_cache(mesh):
    a = mt.DenseVecMatrix.random(4, 32, 32, mesh=mesh)
    b = mt.DenseVecMatrix.random(5, 32, 32, mesh=mesh)
    a.multiply(b, strategy="tuned")
    assert len(autotune._CACHE) == 1
    calls = {"n": 0}
    orig = autotune.tune_multiply

    def spy(*args, **kw):
        calls["n"] += 1
        return orig(*args, **kw)

    autotune.tune_multiply = spy
    try:
        a.multiply(b, strategy="tuned")  # same config -> no re-tune
    finally:
        autotune.tune_multiply = orig
    assert calls["n"] == 0


def test_explicit_strategy_list_does_not_pin_cache(mesh):
    a = mt.DenseVecMatrix.random(6, 32, 32, mesh=mesh)
    b = mt.DenseVecMatrix.random(7, 32, 32, mesh=mesh)
    results = mt.tune_multiply(a, b, strategies=["gspmd", "broadcast"], reps=1)
    assert {s for s, _ in results} <= {"gspmd", "broadcast"}
    # a restricted benchmark must never pin strategy="tuned" dispatch
    assert len(autotune._CACHE) == 0


def test_no_viable_strategy_raises(mesh):
    a = mt.DenseVecMatrix.random(8, 16, 16, mesh=mesh)
    b = mt.DenseVecMatrix.random(9, 16, 16, mesh=mesh)
    with pytest.raises(ValueError):
        mt.tune_multiply(a, b, strategies=["not_a_strategy"])


def test_shape_mismatch_error_is_clear(mesh):
    a = mt.DenseVecMatrix.random(10, 64, 48, mesh=mesh)
    b = mt.DenseVecMatrix.random(11, 32, 32, mesh=mesh)
    with pytest.raises(ValueError, match="inner dim mismatch"):
        mt.tune_multiply(a, b)


def test_cache_keyed_on_layout(mesh):
    # same shapes, different matrix classes/layouts -> distinct cache entries
    a1 = mt.DenseVecMatrix.random(12, 32, 32, mesh=mesh)
    b1 = mt.DenseVecMatrix.random(13, 32, 32, mesh=mesh)
    a2 = mt.BlockMatrix.random(12, 32, 32, mesh=mesh)
    b2 = mt.BlockMatrix.random(13, 32, 32, mesh=mesh)
    a1.multiply(b1, strategy="tuned")
    a2.multiply(b2, strategy="tuned")
    assert len(autotune._CACHE) == 2


def test_vector_operand_rejected_clearly(mesh):
    a = mt.DenseVecMatrix.random(20, 32, 32, mesh=mesh)
    v = np.ones((32,), np.float32)
    with pytest.raises(ValueError, match="2-D right operand"):
        mt.tune_multiply(a, v)


def _seed_cache_entry(mesh, strategy="gspmd", seed=40):
    """A (key, operands) pair with the winner planted in both cache layers —
    persistence tests must not depend on real multiplies succeeding."""
    a = mt.DenseVecMatrix.random(seed, 32, 32, mesh=mesh)
    b = mt.DenseVecMatrix.random(seed + 1, 32, 32, mesh=mesh)
    key = autotune._cache_key(a, b, None)
    autotune._CACHE[key] = strategy
    autotune._persist(key, strategy)
    return key, a, b


def _simulate_restart():
    autotune._CACHE.clear()
    autotune._disk = None  # force a reload from the file


def test_disk_cache_survives_restart(mesh, monkeypatch):
    import os

    key, a, b = _seed_cache_entry(mesh)
    path = mt.get_config().autotune_cache_path
    assert os.path.exists(path)
    _simulate_restart()

    def boom(*args, **kw):
        raise AssertionError("re-tuned despite a persisted winner")

    monkeypatch.setattr(autotune, "tune_multiply", boom)
    assert autotune.best_strategy(a, b) == "gspmd"
    assert len(autotune._CACHE) == 1  # promoted back into the memory layer


def test_clear_cache_clears_both_layers(mesh):
    import os

    _seed_cache_entry(mesh)
    path = mt.get_config().autotune_cache_path
    assert os.path.exists(path)
    autotune.clear_cache()
    assert len(autotune._CACHE) == 0
    assert not os.path.exists(path)


def test_disk_layer_disabled_by_empty_path(mesh, tmp_path):
    import os

    with mt.config_context(autotune_cache_path=""):
        key, a, b = _seed_cache_entry(mesh, seed=44)
    assert not os.path.exists(os.path.join(str(tmp_path), "autotune.json"))


def test_corrupt_disk_file_degrades_to_retune(mesh, monkeypatch):
    key, a, b = _seed_cache_entry(mesh, seed=48)
    path = mt.get_config().autotune_cache_path
    with open(path, "w") as f:
        f.write("{ not json")
    _simulate_restart()
    tuned = {"n": 0}

    def fake_tune(mat, other, **kw):
        tuned["n"] += 1
        autotune._CACHE[autotune._cache_key(mat, other, None)] = "rmm"
        return [("rmm", 0.001)]

    monkeypatch.setattr(autotune, "tune_multiply", fake_tune)
    # corrupt file must not crash; it just loses the persisted winners
    assert autotune.best_strategy(a, b) == "rmm"
    assert tuned["n"] == 1


def test_stale_persisted_strategy_triggers_retune(mesh, monkeypatch):
    """A winner persisted by an older version whose engine was renamed must
    degrade to a retune, never poison every tuned multiply."""
    key, a, b = _seed_cache_entry(mesh, seed=56, strategy="engine_v0_name")
    _simulate_restart()
    tuned = {"n": 0}

    def fake_tune(mat, other, **kw):
        tuned["n"] += 1
        autotune._CACHE[autotune._cache_key(mat, other, None)] = "gspmd"
        return [("gspmd", 0.001)]

    monkeypatch.setattr(autotune, "tune_multiply", fake_tune)
    assert autotune.best_strategy(a, b) == "gspmd"
    assert tuned["n"] == 1  # the stale name was ignored


def test_persist_merges_with_concurrent_writes(mesh):
    """Merge-on-write: a winner another process wrote between our load and
    our persist survives in the file (no lost update)."""
    import json

    key1, a, b = _seed_cache_entry(mesh, seed=60)
    path = mt.get_config().autotune_cache_path
    # simulate another process adding a winner behind our back
    other = json.load(open(path))
    other["('OtherProc', (1, 1))"] = "rmm"
    json.dump(other, open(path, "w"))
    key2 = autotune._cache_key(a, b, "highest")
    autotune._persist(key2, "ring")
    merged = json.load(open(path))
    assert merged["('OtherProc', (1, 1))"] == "rmm"
    assert merged[repr(key1)] == "gspmd"
    assert merged[repr(key2)] == "ring"


def test_disk_key_distinguishes_precision(mesh):
    _, a, b = _seed_cache_entry(mesh, seed=52)
    _simulate_restart()
    key_high = autotune._cache_key(a, b, "highest")
    with autotune._DISK_LOCK:
        assert repr(key_high) not in autotune._disk_layer()


def test_unknown_candidate_skipped_not_fatal(mesh):
    """An unsupported candidate mixed into an explicit set is skipped via
    UnknownStrategyError (no message-text matching, ADVICE r3); the viable
    one still gets timed."""
    a = mt.DenseVecMatrix.random(30, 32, 32, mesh=mesh)
    b = mt.DenseVecMatrix.random(31, 32, 32, mesh=mesh)
    results = mt.tune_multiply(a, b, strategies=["gspmd", "not_a_strategy"])
    assert [s for s, _ in results] == ["gspmd"]


def test_cache_key_includes_device_kind(mesh):
    """Winners are hardware-specific: a v5e tiling loses on a v4. The key's
    tail must carry (platform, device_kind) so a cache file that moves
    between machines re-tunes instead of importing the wrong winner."""
    a = mt.DenseVecMatrix.random(70, 32, 32, mesh=mesh)
    b = mt.DenseVecMatrix.random(71, 32, 32, mesh=mesh)
    key = autotune._cache_key(a, b, None)
    assert key[-2:] == autotune._device_sig()


def test_unversioned_disk_file_ignored(mesh, monkeypatch):
    """A pre-versioning cache file (no __version__, or the wrong one) is
    keyed by the old device-blind scheme — the loader must drop it whole
    and let every configuration re-tune."""
    import json

    key, a, b = _seed_cache_entry(mesh, seed=64)
    path = mt.get_config().autotune_cache_path
    data = json.load(open(path))
    assert data["__version__"] == autotune._DISK_VERSION
    del data["__version__"]  # simulate a v1-era file
    json.dump(data, open(path, "w"))
    _simulate_restart()
    with autotune._DISK_LOCK:
        assert autotune._disk_layer() == {}
    tuned = {"n": 0}

    def fake_tune(mat, other, **kw):
        tuned["n"] += 1
        autotune._CACHE[autotune._cache_key(mat, other, None)] = "gspmd"
        return [("gspmd", 0.001)]

    monkeypatch.setattr(autotune, "tune_multiply", fake_tune)
    assert autotune.best_strategy(a, b) == "gspmd"
    assert tuned["n"] == 1


def test_version_key_survives_merge(mesh):
    """_persist's merge-on-write keeps the file loadable: the version key
    is re-stamped on every write, never dropped by the merge."""
    import json

    _seed_cache_entry(mesh, seed=68)
    key2 = ("Other", (2, 2))
    autotune._persist(key2, "rmm")
    data = json.load(open(mt.get_config().autotune_cache_path))
    assert data["__version__"] == autotune._DISK_VERSION
    assert data[repr(key2)] == "rmm"


# ------------------------------------------- ranking is by the measured time


class _FakeTime:
    """The module's clock, advanced only by the fake engines below."""

    def __init__(self):
        self.now = 0.0

    def perf_counter(self):
        return self.now


@pytest.mark.parametrize("fastest", ["gspmd", "ring", "broadcast"])
def test_the_fastest_timed_strategy_wins_is_cached_and_persisted(
        mesh, monkeypatch, fastest):
    """Three engines with set durations and one that refuses its name: the
    ranking is the measured seconds a multiply and nothing else (no cost
    model stands between a timing and the choice), the refused candidate is
    skipped, and the winner reaches both cache layers, from where a
    restarted process dispatches it without timing anything."""
    from marlin_tpu.parallel.matmul import UnknownStrategyError

    a = mt.DenseVecMatrix.random(80, 32, 32, mesh=mesh)
    b = mt.DenseVecMatrix.random(81, 32, 32, mesh=mesh)
    cost = {"gspmd": 0.004, "ring": 0.003, "broadcast": 0.002}
    cost[fastest] = 0.001
    clock, timed = _FakeTime(), []

    def multiply(self, other, strategy=None, precision=None):
        if strategy == "rmm":
            raise UnknownStrategyError(strategy)
        timed.append(strategy)
        clock.now += cost[strategy]
        return self.data

    monkeypatch.setattr(autotune, "time", clock)
    monkeypatch.setattr(autotune, "_candidates",
                        lambda *_: ["gspmd", "rmm", "ring", "broadcast"])
    monkeypatch.setattr(mt.DenseVecMatrix, "multiply", multiply)
    results = autotune.tune_multiply(a, b, reps=4)
    # one compile call and four timed ones a candidate; seconds a multiply
    assert timed == [s for s in ("gspmd", "ring", "broadcast")
                     for _ in range(5)]
    assert [s for s, _ in results] == sorted(cost, key=cost.get)
    assert dict(results) == pytest.approx(cost)
    key = autotune._cache_key(a, b, None)
    assert autotune._CACHE == {key: fastest}
    _simulate_restart()
    del timed[:]
    assert autotune.best_strategy(a, b) == fastest and not timed
