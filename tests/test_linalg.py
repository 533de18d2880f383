"""Factorization tests vs NumPy oracles. The reference only exercises LU via
its example (SURVEY.md §4 "not covered by tests"); here every factorization is
covered in both local and dist (blocked, sharded) modes."""

import numpy as np
import pytest

import marlin_tpu as mt



def _spd(n, seed):
    rng = np.random.default_rng(seed)
    a = rng.standard_normal((n, n)).astype(np.float32)
    return a @ a.T + n * np.eye(n, dtype=np.float32)


def _well_conditioned(n, seed):
    rng = np.random.default_rng(seed)
    a = rng.standard_normal((n, n)).astype(np.float32)
    return a + n * np.eye(n, dtype=np.float32)


@pytest.mark.parametrize("mode,block", [("local", None), ("dist", 8), ("dist", 5)])
def test_lu(mesh, mode, block):
    n = 24
    a = _well_conditioned(n, 0)
    m = mt.BlockMatrix.from_array(a, mesh)
    l, u, p = m.lu_decompose(mode=mode) if block is None else mt.linalg.lu_decompose(
        m, mode=mode, block_size=block
    )
    lnp, unp = l.to_numpy(), u.to_numpy()
    # A[perm] == L @ U
    np.testing.assert_allclose(a[p], lnp @ unp, rtol=1e-3, atol=1e-3)
    assert np.allclose(lnp, np.tril(lnp))
    assert np.allclose(unp, np.triu(unp))


def test_lu_pivoting_needed(mesh):
    # leading zero forces a row swap inside the pivot block
    a = np.array([[0.0, 1.0], [1.0, 0.0]], np.float32)
    m = mt.BlockMatrix.from_array(a, mesh)
    l, u, p = m.lu_decompose(mode="local")
    np.testing.assert_allclose(a[p], l.to_numpy() @ u.to_numpy(), atol=1e-6)


@pytest.mark.parametrize("mode,block", [("local", None), ("dist", 8), ("dist", 7)])
def test_cholesky(mesh, mode, block):
    n = 21
    a = _spd(n, 1)
    m = mt.BlockMatrix.from_array(a, mesh)
    l = m.cholesky_decompose(mode=mode) if block is None else mt.linalg.cholesky_decompose(
        m, mode=mode, block_size=block
    )
    lnp = l.to_numpy()
    np.testing.assert_allclose(lnp @ lnp.T, a, rtol=1e-3, atol=1e-2)
    assert np.allclose(lnp, np.tril(lnp))


@pytest.mark.parametrize("mode,block", [("local", None), ("dist", 8)])
def test_inverse(mesh, mode, block):
    n = 16
    a = _well_conditioned(n, 2)
    m = mt.BlockMatrix.from_array(a, mesh)
    inv = m.inverse(mode=mode) if block is None else mt.linalg.inverse(
        m, mode=mode, block_size=block
    )
    np.testing.assert_allclose(inv.to_numpy() @ a, np.eye(n), atol=1e-2)


def test_lu_schedules_agree(mesh):
    # shrinking (unrolled true-extent) and masked (fori_loop full-width) are
    # the same algorithm scheduled differently — identical pivots, so results
    # agree to FP reassociation
    n = 24
    a = _well_conditioned(n, 4)
    m = mt.BlockMatrix.from_array(a, mesh)
    outs = {}
    for sched in ("shrinking", "masked"):
        l, u, p = mt.linalg.lu_decompose(m, mode="dist", block_size=8,
                                         schedule=sched)
        np.testing.assert_allclose(a[p], l.to_numpy() @ u.to_numpy(),
                                   rtol=1e-3, atol=1e-3)
        outs[sched] = (l.to_numpy(), u.to_numpy(), p)
    np.testing.assert_allclose(outs["shrinking"][0], outs["masked"][0],
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_array_equal(outs["shrinking"][2], outs["masked"][2])


def test_lu_shrinking_pivots_inside_blocks(mesh):
    # tiny leading diagonal entries force genuine row swaps inside each pivot
    # block; the shrinking schedule must carry them across the full stripe
    # (including the already-written L columns left of the panel)
    n = 24
    rng = np.random.default_rng(9)
    a = rng.standard_normal((n, n)).astype(np.float32)
    a[np.arange(n), np.arange(n)] = 1e-8  # every block pivots
    m = mt.BlockMatrix.from_array(a, mesh)
    l, u, p = mt.linalg.lu_decompose(m, mode="dist", block_size=8,
                                     schedule="shrinking")
    p = np.asarray(p)
    assert not np.array_equal(p, np.arange(n)), "expected non-trivial pivoting"
    np.testing.assert_allclose(a[p], l.to_numpy() @ u.to_numpy(),
                               rtol=2e-3, atol=2e-3)


def test_cholesky_schedules_agree(mesh):
    n = 21
    a = _spd(n, 5)
    m = mt.BlockMatrix.from_array(a, mesh)
    ls = [mt.linalg.cholesky_decompose(m, mode="dist", block_size=7,
                                       schedule=s).to_numpy()
          for s in ("shrinking", "masked")]
    np.testing.assert_allclose(ls[0], ls[1], rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(ls[0] @ ls[0].T, a, rtol=1e-3, atol=1e-2)


def test_auto_schedule_is_op_aware():
    # r5 on-chip shoot-out (PERF.md's r5 table, 8192²): shrinking wins for LU,
    # masked wins for Cholesky — "auto" must resolve per op
    from marlin_tpu.linalg.factorizations import _resolve_schedule

    assert _resolve_schedule("auto", 16) == "shrinking"
    assert _resolve_schedule("auto", 100) == "masked"  # past unroll cap
    assert _resolve_schedule("auto", 16, pivot="panel") == "masked"
    assert _resolve_schedule("auto", 16, op="cholesky") == "masked"
    assert _resolve_schedule("auto", 100, op="cholesky") == "masked"
    # explicit choice always wins over the op-aware default
    assert _resolve_schedule("shrinking", 100, op="cholesky") == "shrinking"
    assert _resolve_schedule("masked", 16) == "masked"


def test_inverse_schedules_agree(mesh):
    n = 16
    a = _well_conditioned(n, 6)
    m = mt.BlockMatrix.from_array(a, mesh)
    invs = [mt.linalg.inverse(m, mode="dist", block_size=8,
                              schedule=s).to_numpy()
            for s in ("shrinking", "masked")]
    np.testing.assert_allclose(invs[0], invs[1], rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(invs[0] @ a, np.eye(n), atol=1e-2)


def test_shrinking_schedule_rejects_panel_pivot(mesh):
    m = mt.BlockMatrix.from_array(_well_conditioned(16, 7), mesh)
    with pytest.raises(ValueError):
        mt.linalg.lu_decompose(m, mode="dist", block_size=8, pivot="panel",
                               schedule="shrinking")
    with pytest.raises(ValueError):
        mt.linalg.lu_decompose(m, mode="dist", block_size=8, schedule="eager")
    # arg validation must not depend on the mode taken (local short-circuits
    # before the dist machinery)
    with pytest.raises(ValueError):
        mt.linalg.lu_decompose(m, mode="local", schedule="eager")
    with pytest.raises(ValueError):
        mt.linalg.cholesky_decompose(m, mode="local", schedule="eager")
    with pytest.raises(ValueError):
        mt.linalg.inverse(m, mode="local", schedule="eager")
    with pytest.raises(ValueError):
        mt.linalg.inverse(m, mode="local", pivot="bogus")


@pytest.mark.parametrize("mode", ["local-svd", "local-eigs", "dist-eigs"])
def test_svd(mesh, mode):
    rng = np.random.default_rng(3)
    a = (rng.standard_normal((40, 12)) @ np.diag(np.linspace(10, 0.1, 12))).astype(np.float32)
    m = mt.DenseVecMatrix.from_array(a, mesh)
    k = 4
    res = m.compute_svd(k, mode=mode)
    s_true = np.linalg.svd(a, compute_uv=False)[:k]
    np.testing.assert_allclose(res.s, s_true, rtol=2e-2)
    # reconstruction on the top-k subspace
    u = res.u.to_numpy()
    recon = u @ np.diag(res.s) @ res.v.T
    a_k = None
    uu, ss, vv = np.linalg.svd(a, full_matrices=False)
    a_k = (uu[:, :k] * ss[:k]) @ vv[:k]
    np.testing.assert_allclose(recon, a_k, atol=0.2)


def test_svd_no_u(mesh):
    rng = np.random.default_rng(4)
    a = rng.standard_normal((30, 10)).astype(np.float32)
    res = mt.DenseVecMatrix.from_array(a, mesh).compute_svd(3, mode="local-eigs",
                                                            compute_u=False)
    assert res.u is None
    np.testing.assert_allclose(res.s, np.linalg.svd(a, compute_uv=False)[:3], rtol=2e-2)


@pytest.mark.parametrize("block", [8, 5])
def test_lu_panel_pivot(mesh, block):
    n = 24
    a = _well_conditioned(n, 7)
    m = mt.BlockMatrix.from_array(a, mesh)
    l, u, p = mt.linalg.lu_decompose(m, mode="dist", block_size=block, pivot="panel")
    np.testing.assert_allclose(a[p], l.to_numpy() @ u.to_numpy(), rtol=1e-3, atol=1e-3)
    assert np.allclose(l.to_numpy(), np.tril(l.to_numpy()))
    # multipliers bounded by 1 — the signature of true partial pivoting
    assert np.abs(np.tril(l.to_numpy(), -1)).max() <= 1.0 + 1e-5


def test_lu_panel_pivot_beats_block_pivot(mesh):
    # pivot block entirely zero, good pivots below it: block-local pivoting
    # cannot factor this; full-height panel pivoting must
    n, b = 8, 4
    a = np.zeros((n, n), np.float32)
    a[:b, b:] = np.eye(b)        # upper-right identity
    a[b:, :b] = np.eye(b)        # lower-left identity
    a[b:, b:] = 0.5 * np.eye(b)
    m = mt.BlockMatrix.from_array(a, mesh)
    l, u, p = mt.linalg.lu_decompose(m, mode="dist", block_size=b, pivot="panel")
    np.testing.assert_allclose(a[p], l.to_numpy() @ u.to_numpy(), atol=1e-5)


def test_lu_bad_pivot_arg(mesh):
    m = mt.BlockMatrix.from_array(np.eye(8, dtype=np.float32), mesh)
    with pytest.raises(ValueError):
        mt.linalg.lu_decompose(m, mode="dist", block_size=4, pivot="bogus")


@pytest.mark.parametrize("mode", ["local", "dist"])
def test_solve(mesh, mode):
    n = 20
    a = _well_conditioned(n, 9)
    m = mt.BlockMatrix.from_array(a, mesh)
    rng = np.random.default_rng(10)
    b_vec = rng.standard_normal(n).astype(np.float32)
    b_mat = rng.standard_normal((n, 3)).astype(np.float32)
    x = mt.linalg.solve(m, b_vec, mode=mode)
    np.testing.assert_allclose(a @ np.asarray(x), b_vec, rtol=1e-2, atol=1e-3)
    xm = mt.linalg.solve(m, b_mat, mode=mode)
    np.testing.assert_allclose(a @ np.asarray(xm), b_mat, rtol=1e-2, atol=1e-3)


def test_lu_solve_reuses_factorization(mesh):
    n = 16
    a = _well_conditioned(n, 11)
    m = mt.BlockMatrix.from_array(a, mesh)
    l, u, p = mt.linalg.lu_decompose(m, mode="dist", block_size=8)
    for seed in (0, 1):
        b = np.random.default_rng(seed).standard_normal(n).astype(np.float32)
        x = mt.linalg.lu_solve(l, u, p, b)
        np.testing.assert_allclose(a @ np.asarray(x), b, rtol=1e-2, atol=1e-3)
    with pytest.raises(ValueError):
        mt.linalg.lu_solve(l, u, p, np.ones(5, np.float32))


def test_solve_validates_pivot_early(mesh):
    m = mt.BlockMatrix.from_array(np.eye(8, dtype=np.float32), mesh)
    with pytest.raises(ValueError):
        mt.linalg.solve(m, np.ones(8, np.float32), mode="local", pivot="bogus")
    # block_size forwarded to the dist factorization
    a = _well_conditioned(16, 13)
    x = mt.linalg.solve(mt.BlockMatrix.from_array(a, mesh),
                        np.ones(16, np.float32), mode="dist", block_size=4)
    np.testing.assert_allclose(a @ np.asarray(x), np.ones(16), rtol=1e-2, atol=1e-3)


def test_cholesky_solve(mesh):
    n = 18
    a = _spd(n, 15)
    m = mt.BlockMatrix.from_array(a, mesh)
    l = m.cholesky_decompose(mode="dist", )
    rng = np.random.default_rng(16)
    b = rng.standard_normal(n).astype(np.float32)
    x = mt.linalg.cholesky_solve(l, b)
    np.testing.assert_allclose(a @ np.asarray(x), b, rtol=1e-2, atol=1e-2)
    bm = rng.standard_normal((n, 2)).astype(np.float32)
    xm = mt.linalg.cholesky_solve(l, bm)
    np.testing.assert_allclose(a @ np.asarray(xm), bm, rtol=1e-2, atol=1e-2)
    with pytest.raises(ValueError):
        mt.linalg.cholesky_solve(l, np.ones(3, np.float32))


def test_matrix_solve_method(mesh):
    n = 12
    a = _well_conditioned(n, 17)
    m = mt.BlockMatrix.from_array(a, mesh)
    b = np.random.default_rng(18).standard_normal(n).astype(np.float32)
    x = m.solve(b)
    np.testing.assert_allclose(a @ np.asarray(x), b, rtol=1e-2, atol=1e-3)


def test_inverse_panel_pivot(mesh):
    # zero pivot block with good pivots below it: block-local pivoting cannot
    # factor this, so the pivot= plumb-through to inverse() is load-bearing
    n, b = 8, 4
    a = np.zeros((n, n), np.float32)
    a[:b, b:] = np.eye(b)
    a[b:, :b] = np.eye(b)
    a[b:, b:] = 0.5 * np.eye(b)
    m = mt.BlockMatrix.from_array(a, mesh)
    inv = mt.linalg.inverse(m, mode="dist", block_size=b, pivot="panel")
    np.testing.assert_allclose(inv.to_numpy() @ a, np.eye(n), atol=1e-4)
    with pytest.raises(ValueError):
        mt.linalg.inverse(m, mode="dist", block_size=b, pivot="bogus")


def test_factorization_sharding_always_applied(mesh):
    """A padded size that doesn't divide the row-shard count used to silently
    drop the sharding constraint; now the pad covers lcm(block, shards) and
    the dist-mode LU output carries the expected sharding."""
    import jax.numpy as jnp

    from marlin_tpu.linalg.factorizations import (
        _blocked_lu,
        _pad_and_sharding,
        _pad_with_identity,
    )

    n, b = 21, 7  # pad-to-block alone gives 21, not divisible by 2 mesh rows
    a = _well_conditioned(n, 11)
    m = mt.BlockMatrix.from_array(a, mesh)
    n_pad, sharding = _pad_and_sharding(m, n, b)
    assert sharding is not None
    assert n_pad % b == 0 and n_pad % mesh.shape["rows"] == 0

    lu_pad, _ = _blocked_lu(_pad_with_identity(jnp.asarray(a), n_pad), b, sharding)
    assert lu_pad.sharding.is_equivalent_to(sharding, lu_pad.ndim)

    # and the public API stays correct at the awkward size
    l, u, p = mt.linalg.lu_decompose(m, mode="dist", block_size=b)
    np.testing.assert_allclose(a[p], l.to_numpy() @ u.to_numpy(), rtol=1e-3, atol=1e-3)
