"""ML workload tests: ALS (untested in the reference — SURVEY.md §4), plus the
CARMA split heuristic properties."""

import os

import numpy as np
import pytest

import marlin_tpu as mt
from marlin_tpu.parallel.carma import near_square_split, split_method


def test_als_reduces_rmse(mesh):
    rng = np.random.default_rng(0)
    n_users, n_items, rank = 30, 20, 4
    u_true = rng.standard_normal((n_users, rank)).astype(np.float32)
    v_true = rng.standard_normal((n_items, rank)).astype(np.float32)
    full = u_true @ v_true.T
    # observe 50% of entries
    mask = rng.random((n_users, n_items)) < 0.5
    ui, ii = np.nonzero(mask)
    coo = mt.CoordinateMatrix(ui, ii, full[mask], shape=(n_users, n_items), mesh=mesh)
    model = coo.als(rank=rank, iterations=12, lam=0.05)
    rmse = model.rmse(coo)
    assert rmse < 0.3, f"ALS failed to fit: rmse={rmse}"
    assert model.user_features.shape == (n_users, rank)
    assert model.product_features.shape == (n_items, rank)


def test_als_predict_shape(mesh):
    coo = mt.CoordinateMatrix.from_entries(
        [(0, 0, 5.0), (0, 1, 3.0), (1, 0, 4.0), (2, 1, 1.0)], mesh=mesh
    )
    model = coo.als(rank=2, iterations=5, lam=0.1)
    preds = model.predict([0, 1], [0, 0])
    assert preds.shape == (2,)


def _rating_fixture(seed, n_users, n_items, rank, density, mesh):
    rng = np.random.default_rng(seed)
    u_true = rng.standard_normal((n_users, rank)).astype(np.float32)
    v_true = rng.standard_normal((n_items, rank)).astype(np.float32)
    full = u_true @ v_true.T
    mask = rng.random((n_users, n_items)) < density
    ui, ii = np.nonzero(mask)
    return mt.CoordinateMatrix(ui, ii, full[mask], shape=(n_users, n_items),
                               mesh=mesh)


def test_als_sharded_matches_replicated(mesh):
    # same init, same data: the mesh-sharded solver must agree with the
    # replicated one up to FP summation order
    coo = _rating_fixture(2, 60, 40, 4, 0.5, mesh)
    rep = coo.als(rank=4, iterations=6, lam=0.05, shard=False)
    sh = coo.als(rank=4, iterations=6, lam=0.05, shard=True, segment_block=8)
    np.testing.assert_allclose(sh.user_features.to_numpy(),
                               rep.user_features.to_numpy(),
                               rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(sh.product_features.to_numpy(),
                               rep.product_features.to_numpy(),
                               rtol=2e-3, atol=2e-3)
    assert sh.rmse(coo) < 0.3


def test_als_blocked_single_device(mesh):
    # shard=True on a ONE-device mesh is the bounded-memory blocked mode (the
    # single-chip ALS bench config OOMs through the unsharded path: 31 GB of
    # (num_users, rank, rank) stats vs 16 GB HBM); it must agree with the
    # unsharded solver
    import jax

    mesh1 = mt.create_mesh((1, 1), devices=jax.devices()[:1])
    coo = _rating_fixture(7, 50, 30, 4, 0.5, mesh1)
    rep = coo.als(rank=4, iterations=5, lam=0.05, shard=False)
    blk = coo.als(rank=4, iterations=5, lam=0.05, shard=True, segment_block=8)
    np.testing.assert_allclose(blk.user_features.to_numpy(),
                               rep.user_features.to_numpy(),
                               rtol=2e-3, atol=2e-3)
    assert blk.rmse(coo) < 0.3


def test_als_auto_shard_threshold(mesh):
    # the auto heuristic keys on stat-tensor size alone (device count no
    # longer gates it): big segment side -> blocked mode even on 1 device
    from marlin_tpu.ml import als as als_mod

    calls = {}
    orig = als_mod._als_sharded

    def spy(*a, **k):
        calls["sharded"] = True
        return orig(*a, **k)

    als_mod._als_sharded = spy
    try:
        # 300k users x rank 16: stats 4*16*16*300k = 307 MB > 256 MB
        rng = np.random.default_rng(8)
        ui = rng.integers(0, 300_000, 500).astype(np.int32)
        ii = rng.integers(0, 20, 500).astype(np.int32)
        coo = mt.CoordinateMatrix(ui, ii, rng.standard_normal(500).astype(np.float32),
                                  shape=(300_000, 20), mesh=mesh)
        coo.als(rank=16, iterations=1, lam=0.1)
    finally:
        als_mod._als_sharded = orig
    assert calls.get("sharded"), "auto shard heuristic did not engage"


def test_als_sharded_implicit_matches_replicated(mesh):
    rng = np.random.default_rng(3)
    n_users, n_items = 40, 24
    mask = rng.random((n_users, n_items)) < 0.3
    ui, ii = np.nonzero(mask)
    counts = rng.integers(1, 10, len(ui)).astype(np.float32)
    coo = mt.CoordinateMatrix(ui, ii, counts, shape=(n_users, n_items), mesh=mesh)
    rep = coo.als(rank=4, iterations=8, lam=0.1, implicit_prefs=True,
                  alpha=10.0, shard=False)
    sh = coo.als(rank=4, iterations=8, lam=0.1, implicit_prefs=True,
                 alpha=10.0, shard=True, segment_block=8)
    np.testing.assert_allclose(sh.user_features.to_numpy(),
                               rep.user_features.to_numpy(),
                               rtol=5e-3, atol=5e-3)


@pytest.mark.skipif(not os.environ.get("MARLIN_SCALE_TESTS"),
                    reason="multi-GB scale run; set MARLIN_SCALE_TESTS=1")
def test_als_sharded_scale(mesh):
    # VERDICT round-1 #4 done criterion: 2M users × 200k items × rank 64 on
    # the 8-device CPU mesh, per-device stat memory bounded by segment_block
    # (4096·64·64·4 ≈ 67 MB vs the 32 GB full stat tensor), RMSE decreasing.
    rng = np.random.default_rng(0)
    n_users, n_items, rank, nnz = 2_000_000, 200_000, 64, 4_000_000
    ui = rng.integers(0, n_users, nnz).astype(np.int32)
    ii = rng.integers(0, n_items, nnz).astype(np.int32)
    vals = rng.standard_normal(nnz).astype(np.float32)
    coo = mt.CoordinateMatrix(ui, ii, vals, shape=(n_users, n_items), mesh=mesh)
    model = coo.als(rank=rank, iterations=1, lam=0.1, shard=True)
    rmse = model.rmse(coo)
    assert np.isfinite(rmse)
    # one sweep on pure-noise ratings still must beat the unit-sphere init
    from marlin_tpu.ml.als import ALSModel
    init = ALSModel(
        mt.DenseVecMatrix.from_array(np.ones((n_users, rank), np.float32) / rank, mesh),
        mt.DenseVecMatrix.from_array(np.ones((n_items, rank), np.float32) / rank, mesh),
    )
    assert rmse < init.rmse(coo)


def test_carma_split_budget():
    for m, k, n, p in [(100, 100, 100, 8), (10000, 100, 100, 8), (64, 4096, 64, 16)]:
        ms, ks, ns = split_method(m, k, n, p)
        assert ms * ks * ns <= p
        assert ms >= 1 and ks >= 1 and ns >= 1


def test_carma_prefers_long_dim():
    # k is dominant -> k gets the splits (contraction-parallel, psum over k)
    ms, ks, ns = split_method(64, 65536, 64, 8)
    assert ks == 8 and ms == 1 and ns == 1
    # m dominant -> row-parallel: A stays where it lies
    ms, ks, ns = split_method(65536, 64, 64, 8)
    assert ms == 8


_CUBE = 36864


@pytest.mark.parametrize("mkn, chips, want", [
    # a cube: ties go to m, then n; the contraction only once both are halved
    ((_CUBE, _CUBE, _CUBE), 1, (1, 1, 1)),
    ((_CUBE, _CUBE, _CUBE), 2, (2, 1, 1)),
    ((_CUBE, _CUBE, _CUBE), 4, (2, 1, 2)),
    ((_CUBE, _CUBE, _CUBE), 8, (2, 2, 2)),
    ((_CUBE, _CUBE, _CUBE), 16, (4, 2, 2)),
    # a budget that is no power of two is not exceeded
    ((_CUBE, _CUBE, _CUBE), 6, (2, 1, 2)),
    # k strictly the longest still gets k, m the longest m, n the longest n
    ((64, 65536, 64), 8, (1, 8, 1)),
    ((100, 101, 100), 2, (1, 2, 1)),
    ((65536, 64, 64), 8, (8, 1, 1)),
    ((64, 64, 65536), 8, (1, 1, 8)),
    # n ties with k: n
    ((64, 4096, 4096), 2, (1, 1, 2)),
    # a Gramian's shape (A^T A of a tall A): the contraction, then m before n
    ((512, 1 << 20, 512), 16, (1, 16, 1)),
])
def test_carma_tie_break_spares_the_contraction(mkn, chips, want):
    """A tie goes to m, then n, then k: an m- or n-split moves operand bytes,
    known before the dot; a k-split moves partial products, which are not."""
    got = split_method(*mkn, chips)
    assert got == want
    assert got[0] * got[1] * got[2] <= chips


def test_near_square_split():
    assert near_square_split(9) == 3
    assert near_square_split(1) >= 1


def test_als_implicit_prefs(mesh):
    # implicit feedback: observed entries should score higher than unobserved
    rng = np.random.default_rng(1)
    n_users, n_items = 25, 15
    mask = rng.random((n_users, n_items)) < 0.3
    ui, ii = np.nonzero(mask)
    counts = rng.integers(1, 10, len(ui)).astype(np.float32)  # interaction counts
    coo = mt.CoordinateMatrix(ui, ii, counts, shape=(n_users, n_items), mesh=mesh)
    model = coo.als(rank=4, iterations=10, lam=0.1, implicit_prefs=True, alpha=10.0)
    u = model.user_features.to_numpy()
    v = model.product_features.to_numpy()
    scores = u @ v.T
    obs_mean = scores[mask].mean()
    unobs_mean = scores[~mask].mean()
    assert obs_mean > unobs_mean + 0.1, (obs_mean, unobs_mean)
