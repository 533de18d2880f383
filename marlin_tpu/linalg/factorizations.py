"""Blocked dense factorizations on sharded global arrays.

The reference implements block LU / Cholesky / inverse as driver-orchestrated
panel+trailing-update loops: each iteration filters the pivot block out of the
RDD, *collects it to the driver*, factorizes it with Breeze there, broadcasts
the factors back, applies panel updates, and shuffle-multiplies the trailing
submatrix (DenseVecMatrix.scala:283-466 LU, 475-561 Cholesky, 568-764 inverse).
The per-iteration driver round-trip is its scalability bottleneck (SURVEY.md §3.3).

TPU-first, the whole factorization is ONE jitted XLA program with the pivot
block factorized *on-device* (``jax.lax.linalg.lu`` / ``jnp.linalg.cholesky``
on a b×b slice — the "collect+broadcast" disappears into XLA's implicit data
movement). Two schedules exist (``schedule=`` on the public functions):

- ``"shrinking"`` (LU default up to 64 block steps): the Python loop over
  block columns unrolls at trace time, so every step's panel/trailing slices
  have their true static shrinking shapes — the ideal 2n³/3 FLOPs, at the
  cost of one compiled GEMM shape per step.
- ``"masked"`` (Cholesky default): a single ``lax.fori_loop`` body reused for
  every step — full-width panels with masked operands (zero contribution
  outside the trailing region), one compiled shape total but ~3× the ideal
  FLOPs. This is the scalable-step-count form and the only one for
  ``pivot="panel"``.

``"auto"`` resolves per op from the r5 on-chip shoot-out (8192²): LU
shrinking 2758 vs masked 2069 GFLOP/s, but Cholesky masked 1480 vs
shrinking 1319 — see ``_resolve_schedule``.

Pivoting: the default (``pivot="block"``) matches the reference's choice —
partial pivoting *within the pivot block only* (the reference LUs just the
collected pivot block, DenseVecMatrix.scala:345-349) — with row swaps applied
across the full width and the global permutation accumulated.
``pivot="panel"`` upgrades to LAPACK getrf-style full-height panel pivoting
(pivot search over the entire trailing column), which handles singular or
ill-conditioned pivot blocks the block-local strategy cannot, at the cost of a
serial per-column panel phase.

Numerical trade-off, stated: panel updates multiply by the explicitly inverted
b×b pivot triangles (one small solve per step, then MXU GEMMs across the
panel) instead of running n-wide triangular solves. For an ill-conditioned
pivot block (κ ≈ 1/eps) the inverse carries κ·eps relative error into the
panel, where backward-stable solves would not — the same trade the reference
makes by broadcasting pivot inverses (DenseVecMatrix.scala:370-387), and
consistent with block-local pivoting already bounding stability. Accuracy-
critical callers with adversarial inputs should use mode="local" (LAPACK-style
full factorization).

Square inputs are padded with an identity tail so the padded problem stays
nonsingular; block size comes from the config knobs that mirror
``marlin.lu.basesize``/``marlin.cholesky.basesize``/``marlin.inverse.basesize``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding

from ..config import get_config
from ..mesh import pad_to_multiple

__all__ = ["lu_decompose", "cholesky_decompose", "inverse", "PIVOT_STRATEGIES",
           "SCHEDULES"]

PIVOT_STRATEGIES = ("block", "panel")
SCHEDULES = ("auto", "shrinking", "masked")

# above this many block steps the unrolled shrinking schedule's per-step
# compilation cost outweighs its 3x FLOP saving; fall back to the single
# fori_loop program
_MAX_UNROLL_STEPS = 64


def _require_pivot(pivot: str) -> None:
    if pivot not in PIVOT_STRATEGIES:
        raise ValueError(
            f"unknown pivot strategy: {pivot!r} (one of {PIVOT_STRATEGIES})"
        )


def _resolve_schedule(schedule: str, nb: int, pivot: str = "block",
                      op: str = "lu") -> str:
    if schedule not in SCHEDULES:
        raise ValueError(f"unknown schedule: {schedule!r} (one of {SCHEDULES})")
    if schedule == "shrinking" and pivot == "panel":
        raise ValueError('schedule="shrinking" supports pivot="block" only '
                         '(panel pivoting keeps the masked full-width loop)')
    if schedule == "auto":
        # Measured on the v5e (PERF.md's r5 table, 8192²): LU shrinking beats
        # masked 2758 vs 2069 GFLOP/s, but Cholesky masked beats shrinking
        # 1480 vs 1319 — Cholesky's symmetric trailing update keeps the MXU
        # busier in the single fori_loop program than LU's, so the unrolled
        # schedule's per-step compile cost is not repaid there.
        if op == "cholesky":
            return "masked"
        return ("shrinking" if pivot == "block" and nb <= _MAX_UNROLL_STEPS
                else "masked")
    return schedule


def _pad_with_identity(a: jax.Array, n_pad: int) -> jax.Array:
    """Embed the n×n matrix in an n_pad×n_pad one with an identity tail block,
    so factorizations of the padded matrix restrict to the original."""
    n = a.shape[0]
    if n_pad == n:
        return a
    out = jnp.zeros((n_pad, n_pad), a.dtype)
    out = out.at[:n, :n].set(a)
    pad_diag = jnp.arange(n, n_pad)
    return out.at[pad_diag, pad_diag].set(jnp.ones((), a.dtype))


def _trailing_update(a: jax.Array, o, block: int, u12: jax.Array):
    """Shared epilogue of both LU variants: write U12 right of the panel and
    subtract the masked rank-b outer product — zero outside the trailing
    region, so the full-size GEMM only touches A22. Expects the column panel
    of ``a`` to already hold L21 below the diagonal block."""
    n = a.shape[0]
    col_idx = jnp.arange(n)
    row_idx = jnp.arange(n)[:, None]
    right = col_idx[None, :] >= o + block
    rpan = jax.lax.dynamic_slice(a, (o, 0), (block, n))
    a = jax.lax.dynamic_update_slice(a, jnp.where(right, u12, rpan), (o, 0))
    cpan = jax.lax.dynamic_slice(a, (0, o), (n, block))
    below = row_idx >= o + block
    l21_m = jnp.where(below, cpan, jnp.zeros((), a.dtype))
    u12_m = jnp.where(right, u12, jnp.zeros((), a.dtype))
    return a - jnp.dot(l21_m, u12_m, precision="highest")


@functools.partial(jax.jit, static_argnames=("block", "sharding"))
def _blocked_lu(a: jax.Array, block: int, sharding=None):
    """Right-looking blocked LU with block-local partial pivoting.
    Returns (LU-combined, global permutation vector)."""
    n = a.shape[0]
    nb = n // block
    solve = jax.scipy.linalg.solve_triangular
    perm0 = jnp.arange(n, dtype=jnp.int32)
    col_idx = jnp.arange(n)
    row_idx = jnp.arange(n)[:, None]

    eye_b = jnp.eye(block)

    def body(i, carry):
        a, gperm = carry
        o = i * block
        piv = jax.lax.dynamic_slice(a, (o, o), (block, block))
        lu, _, p = jax.lax.linalg.lu(piv)
        l11 = jnp.tril(lu, -1) + jnp.eye(block, dtype=a.dtype)
        u11 = jnp.triu(lu)
        # invert the small triangles once (b×b solves), so the full-width
        # panel updates become GEMMs on the MXU instead of n-wide triangular
        # solves — the same trick the reference's panel updates use
        # (broadcast pivot inverse, DenseVecMatrix.scala:370-387)
        l11_inv = solve(l11, eye_b.astype(a.dtype), lower=True, unit_diagonal=True)
        u11_inv = solve(u11.T, eye_b.astype(a.dtype), lower=True).T

        # Row panel (rows o:o+b): permute rows, keep the permuted L-part left
        # of the panel, write the combined lu block into the diagonal; the
        # right part (U12) is handled by the shared epilogue.
        rpan = jax.lax.dynamic_slice(a, (o, 0), (block, n))
        rpan = rpan[p, :]
        in_block = (col_idx[None, :] >= o) & (col_idx[None, :] < o + block)
        lu_wide = jax.lax.dynamic_update_slice(jnp.zeros_like(rpan), lu, (0, o))
        a = jax.lax.dynamic_update_slice(
            a, jnp.where(in_block, lu_wide, rpan), (o, 0)
        )

        # Column panel (full height, cols o:o+b): rows >= o+b get
        # L21 = A21 U11^{-1}; rows above keep what's already written.
        cpan = jax.lax.dynamic_slice(a, (0, o), (n, block))
        l21 = jnp.dot(cpan, u11_inv, precision="highest")
        below = row_idx >= o + block
        a = jax.lax.dynamic_update_slice(a, jnp.where(below, l21, cpan), (0, o))

        u12 = jnp.dot(l11_inv, rpan, precision="highest")
        a = _trailing_update(a, o, block, u12)

        # Accumulate the global permutation.
        gseg = jax.lax.dynamic_slice(gperm, (o,), (block,))
        gperm = jax.lax.dynamic_update_slice(gperm, gseg[p], (o,))
        if sharding is not None:
            a = jax.lax.with_sharding_constraint(a, sharding)
        return a, gperm

    return jax.lax.fori_loop(0, nb, body, (a, perm0))


@functools.partial(jax.jit, static_argnames=("block", "sharding"))
def _blocked_lu_panel_pivot(a: jax.Array, block: int, sharding=None):
    """Right-looking blocked LU with *full-height panel pivoting* (LAPACK
    getrf-style): each elimination column selects its pivot over the entire
    trailing column, not just the b×b pivot block — the stability the
    reference gives up by factorizing only the collected pivot block.

    The sequential elimination runs on the (n × b) panel buffer only
    (O(n·b) work per column); the chosen swaps are then replayed across the
    full width in one O(n·b) pass (LAPACK's laswp), and the trailing update
    is the shared masked rank-b GEMM. Returns (LU-combined, permutation)."""
    n = a.shape[0]
    nb = n // block
    perm0 = jnp.arange(n, dtype=jnp.int32)
    row_idx = jnp.arange(n)
    eye_b = jnp.eye(block)
    solve = jax.scipy.linalg.solve_triangular
    panel_col_idx = jnp.arange(block)

    def swap_rows(x, r1, r2):
        row1 = x[r1]
        row2 = x[r2]
        x = x.at[r1].set(row2)
        return x.at[r2].set(row1)

    def body(i, carry):
        a, gperm = carry
        o = i * block
        cpan0 = jax.lax.dynamic_slice(a, (0, o), (n, block))

        # --- panel factorization with full-height pivoting, column by column,
        # entirely within the (n, b) panel buffer
        def col_step(j, carry_p):
            pan, pivots = carry_p
            c = o + j
            col = jax.lax.dynamic_slice(pan, (0, j), (n, 1))[:, 0]
            mag = jnp.where(row_idx >= c, jnp.abs(col), -1.0)
            piv = jnp.argmax(mag)
            pan = swap_rows(pan, c, piv)
            pivots = pivots.at[j].set(piv)
            col = jax.lax.dynamic_slice(pan, (0, j), (n, 1))[:, 0]
            pivot_val = col[c]
            safe = jnp.where(jnp.abs(pivot_val) > 0, pivot_val, 1.0)
            factor = jnp.where(row_idx > c, col / safe, 0.0)
            pivot_row = jax.lax.dynamic_slice(pan, (c, 0), (1, block))[0]
            update = factor[:, None] * jnp.where(panel_col_idx > j, pivot_row,
                                                 0.0)[None, :]
            pan = pan - update
            newcol = jnp.where(row_idx > c, factor, col)
            pan = jax.lax.dynamic_update_slice(pan, newcol[:, None], (0, j))
            return pan, pivots

        pan, pivots = jax.lax.fori_loop(
            0, block, col_step, (cpan0, jnp.zeros((block,), jnp.int32))
        )

        # --- replay the swaps across the full matrix + permutation (laswp);
        # columns outside the panel are untouched by the elimination, so
        # applying the same swap sequence afterwards is equivalent
        def apply_swap(j, carry_s):
            a, gperm = carry_s
            c = o + j
            piv = pivots[j]
            return swap_rows(a, c, piv), swap_rows(gperm, c, piv)

        a, gperm = jax.lax.fori_loop(0, block, apply_swap, (a, gperm))
        a = jax.lax.dynamic_update_slice(a, pan, (0, o))

        # --- shared epilogue: U12 from the panel's unit-lower triangle
        lu_blk = jax.lax.dynamic_slice(a, (o, o), (block, block))
        l11 = jnp.tril(lu_blk, -1) + jnp.eye(block, dtype=a.dtype)
        l11_inv = solve(l11, eye_b.astype(a.dtype), lower=True, unit_diagonal=True)
        rpan = jax.lax.dynamic_slice(a, (o, 0), (block, n))
        u12 = jnp.dot(l11_inv, rpan, precision="highest")
        a = _trailing_update(a, o, block, u12)
        if sharding is not None:
            a = jax.lax.with_sharding_constraint(a, sharding)
        return a, gperm

    a, gperm = jax.lax.fori_loop(0, nb, body, (a, perm0))
    return a, gperm


@functools.partial(jax.jit, static_argnames=("block", "sharding"))
def _blocked_lu_shrinking(a: jax.Array, block: int, sharding=None):
    """Right-looking blocked LU, block-local pivoting, *shrinking-extent*
    schedule: the step offsets are static, so the Python loop unrolls at trace
    time and every panel/trailing slice has its true (shrinking) static shape —
    no masks, no wasted work. The masked ``_blocked_lu`` executes ~3× the
    ideal 2n³/3 FLOPs (full-width rank-b GEMMs with zero-masked operands);
    this schedule executes the ideal count at the cost of one compiled GEMM
    shape per block step (fine for the tens of steps real sizes produce)."""
    n = a.shape[0]
    nb = n // block
    solve = jax.scipy.linalg.solve_triangular
    gperm = jnp.arange(n, dtype=jnp.int32)
    eye_b = jnp.eye(block, dtype=a.dtype)

    for i in range(nb):
        o = i * block
        piv = jax.lax.slice(a, (o, o), (o + block, o + block))
        lu, _, p = jax.lax.linalg.lu(piv)
        l11 = jnp.tril(lu, -1) + eye_b
        u11 = jnp.triu(lu)
        l11_inv = solve(l11, eye_b, lower=True, unit_diagonal=True)
        u11_inv = solve(u11.T, eye_b, lower=True).T

        # permute the whole row stripe (columns left of the panel carry
        # already-final L entries and must swap with it, like laswp)
        stripe = jax.lax.slice(a, (o, 0), (o + block, n))[p]
        gseg = jax.lax.dynamic_slice(gperm, (o,), (block,))
        gperm = jax.lax.dynamic_update_slice(gperm, gseg[p], (o,))

        a = jax.lax.dynamic_update_slice(a, stripe, (o, 0))
        a = jax.lax.dynamic_update_slice(a, lu, (o, o))
        if o + block < n:
            right = stripe[:, o + block:]
            u12 = jnp.dot(l11_inv, right, precision="highest")
            below = jax.lax.slice(a, (o + block, o), (n, o + block))
            l21 = jnp.dot(below, u11_inv, precision="highest")
            trail = jax.lax.slice(a, (o + block, o + block), (n, n))
            trail = trail - jnp.dot(l21, u12, precision="highest")
            a = jax.lax.dynamic_update_slice(a, u12, (o, o + block))
            a = jax.lax.dynamic_update_slice(a, l21, (o + block, o))
            a = jax.lax.dynamic_update_slice(a, trail, (o + block, o + block))
        if sharding is not None:
            a = jax.lax.with_sharding_constraint(a, sharding)
    return a, gperm


@functools.partial(jax.jit, static_argnames=("block", "sharding"))
def _blocked_cholesky_shrinking(a: jax.Array, block: int, sharding=None):
    """Shrinking-extent blocked Cholesky (lower) — same schedule trade as
    :func:`_blocked_lu_shrinking`."""
    n = a.shape[0]
    nb = n // block
    solve = jax.scipy.linalg.solve_triangular
    eye_b = jnp.eye(block, dtype=a.dtype)

    for i in range(nb):
        o = i * block
        piv = jax.lax.slice(a, (o, o), (o + block, o + block))
        l11 = jnp.linalg.cholesky(piv)
        a = jax.lax.dynamic_update_slice(a, l11, (o, o))
        if o + block < n:
            l11_inv = solve(l11, eye_b, lower=True)
            below = jax.lax.slice(a, (o + block, o), (n, o + block))
            l21 = jnp.dot(below, l11_inv.T, precision="highest")
            trail = jax.lax.slice(a, (o + block, o + block), (n, n))
            trail = trail - jnp.dot(l21, l21.T, precision="highest")
            a = jax.lax.dynamic_update_slice(a, l21, (o + block, o))
            a = jax.lax.dynamic_update_slice(a, trail, (o + block, o + block))
        if sharding is not None:
            a = jax.lax.with_sharding_constraint(a, sharding)
    return jnp.tril(a)


@functools.partial(jax.jit, static_argnames=("block", "sharding"))
def _blocked_cholesky(a: jax.Array, block: int, sharding=None):
    """Right-looking blocked Cholesky (lower). No pivoting (SPD input)."""
    n = a.shape[0]
    nb = n // block
    solve = jax.scipy.linalg.solve_triangular
    row_idx = jnp.arange(n)[:, None]

    eye_b = jnp.eye(block)

    def body(i, a):
        o = i * block
        piv = jax.lax.dynamic_slice(a, (o, o), (block, block))
        l11 = jnp.linalg.cholesky(piv)
        l11_inv = solve(l11, eye_b.astype(a.dtype), lower=True)

        cpan = jax.lax.dynamic_slice(a, (0, o), (n, block))
        l21 = jnp.dot(cpan, l11_inv.T, precision="highest")
        below = row_idx >= o + block
        at_block = (row_idx >= o) & (row_idx < o + block)
        l11_tall = jax.lax.dynamic_update_slice(jnp.zeros_like(cpan), l11, (o, 0))
        cpan_new = jnp.where(below, l21, jnp.where(at_block, l11_tall, cpan))
        a = jax.lax.dynamic_update_slice(a, cpan_new, (0, o))

        l21_m = jnp.where(below, l21, jnp.zeros((), a.dtype))
        a = a - jnp.dot(l21_m, l21_m.T, precision="highest")
        # restore the block column (the rank-b update also touched it)
        a = jax.lax.dynamic_update_slice(a, cpan_new, (0, o))
        if sharding is not None:
            a = jax.lax.with_sharding_constraint(a, sharding)
        return a

    a = jax.lax.fori_loop(0, nb, body, a)
    return jnp.tril(a)


def _require_square(mat):
    if mat.num_rows() != mat.num_cols():
        raise ValueError(f"factorization needs a square matrix, got {mat.shape}")


def _mode_to_local(mode: str, n: int) -> bool:
    cfg = get_config()
    if mode in ("local", "breeze"):  # "breeze" kept as a parity alias
        return True
    if mode in ("dist", "distspark"):
        return False
    if mode == "auto":  # reference: n > 6000 -> dist (DenseVecMatrix.scala:289-298)
        return n <= cfg.local_fallback_dim
    raise ValueError(f"unknown factorization mode: {mode}")


def lu_decompose(mat, mode: str = "auto", block_size: int | None = None,
                 pivot: str = "block", schedule: str = "auto"):
    """Block LU with partial pivoting (DenseVecMatrix.luDecompose,
    DenseVecMatrix.scala:283-466). Returns ``(L, U, perm)`` where ``perm`` is
    the row-permutation vector: ``A[perm] == L @ U``. ``perm`` stays a device
    array — forcing it to host here would insert a blocking sync into every
    call (dispatch is async; fetch when you need the values).

    ``pivot``: "block" restricts pivot search to the b×b pivot block (the
    reference's choice — fast, weaker on adversarial inputs); "panel" searches
    the full trailing column per elimination step (LAPACK getrf behavior —
    handles e.g. a singular pivot block with good pivots below it).

    ``schedule``: "shrinking" unrolls the block steps with true shrinking
    trailing extents (ideal 2n³/3 FLOPs, one compiled GEMM shape per step);
    "masked" is the single fori_loop program with full-width masked updates
    (~3× the FLOPs, one compiled shape total). "auto" picks shrinking for
    block-pivot factorizations up to 64 steps."""
    _require_square(mat)
    _require_pivot(pivot)
    _resolve_schedule(schedule, 1, pivot)  # arg validation in EVERY mode
    n = mat.num_rows()
    a = mat.logical()
    if _mode_to_local(mode, n):
        lu, _, p = jax.lax.linalg.lu(a)
        l = jnp.tril(lu, -1) + jnp.eye(n, dtype=a.dtype)
        u = jnp.triu(lu)
        return mat._wrap(l), mat._wrap(u), p

    b = block_size or get_config().lu_base_size
    b = min(b, n)
    n_pad, sharding = _pad_and_sharding(mat, n, b)
    a_pad = _pad_with_identity(a, n_pad)
    sched = _resolve_schedule(schedule, n_pad // b, pivot)
    if pivot == "panel":
        factor = _blocked_lu_panel_pivot
    else:
        factor = _blocked_lu_shrinking if sched == "shrinking" else _blocked_lu
    lu_pad, perm = factor(a_pad, b, sharding)
    lu_log = lu_pad[:n, :n]
    l = jnp.tril(lu_log, -1) + jnp.eye(n, dtype=a.dtype)
    u = jnp.triu(lu_log)
    return mat._wrap(l), mat._wrap(u), perm[:n]


def _grid(mat) -> int:
    """LCM-ish divisor check helper: the row-axis shard count of the matrix."""
    ax = mat.spec[0] if len(mat.spec) > 0 else None
    return mat.mesh.shape[ax] if ax is not None else 1


def _pad_and_sharding(mat, n: int, block: int):
    """Padded size + sharding constraint for a blocked factorization.

    Pads to lcm(block, row-shard-count) so the distributed-mode sharding
    constraint ALWAYS applies — previously a non-dividing padded size silently
    dropped the constraint and let GSPMD place the loop however it pleased."""
    n_pad = pad_to_multiple(n, math.lcm(block, _grid(mat)))
    return n_pad, NamedSharding(mat.mesh, mat.spec)


def cholesky_decompose(mat, mode: str = "auto", block_size: int | None = None,
                       schedule: str = "auto"):
    """Block Cholesky, lower factor (DenseVecMatrix.choleskyDecompose,
    DenseVecMatrix.scala:475-561). Returns L with ``A == L @ Lᵀ``.
    ``schedule`` as in :func:`lu_decompose`, except ``"auto"`` resolves to
    ``"masked"`` here: measured on chip (r5, 8192²) the single fori_loop
    program beats the unrolled shrinking schedule for Cholesky (1480 vs
    1319 GFLOP/s) even though the reverse holds for LU."""
    _require_square(mat)
    _resolve_schedule(schedule, 1, op="cholesky")  # arg validation in EVERY mode
    n = mat.num_rows()
    a = mat.logical()
    if _mode_to_local(mode, n):
        return mat._wrap(jnp.linalg.cholesky(a))
    b = block_size or get_config().cholesky_base_size
    b = min(b, n)
    n_pad, sharding = _pad_and_sharding(mat, n, b)
    a_pad = _pad_with_identity(a, n_pad)
    sched = _resolve_schedule(schedule, n_pad // b, op="cholesky")
    chol = (_blocked_cholesky_shrinking if sched == "shrinking"
            else _blocked_cholesky)
    l_pad = chol(a_pad, b, sharding)
    return mat._wrap(l_pad[:n, :n])


@functools.partial(jax.jit,
                   static_argnames=("block", "pivot", "sharding", "schedule"))
def _inverse_via_lu(a: jax.Array, block: int, pivot: str = "block",
                    sharding=None, schedule: str = "masked"):
    if pivot == "panel":
        factor = _blocked_lu_panel_pivot
    else:
        factor = (_blocked_lu_shrinking if schedule == "shrinking"
                  else _blocked_lu)
    lu_pad, perm = factor(a, block, sharding)
    n = a.shape[0]
    solve = jax.scipy.linalg.solve_triangular
    l = jnp.tril(lu_pad, -1) + jnp.eye(n, dtype=a.dtype)
    u = jnp.triu(lu_pad)
    # A[perm] = L U  =>  A^{-1} = (U^{-1} L^{-1}) P  where P x = x[perm]
    pa_inv = solve(u, solve(l, jnp.eye(n, dtype=a.dtype), lower=True, unit_diagonal=True))
    return pa_inv[:, jnp.argsort(perm)][:, :n]  # apply P on the right


def inverse(mat, mode: str = "auto", block_size: int | None = None,
            pivot: str = "block", schedule: str = "auto"):
    """Matrix inverse (DenseVecMatrix.inverse, DenseVecMatrix.scala:568-764).
    The reference runs a blocked Gauss-Jordan-style forward + backward sweep
    with driver-factorized pivots; here it is blocked LU + two sharded
    triangular solves in one XLA program.

    ``pivot`` mirrors :func:`lu_decompose`: "panel" routes through the
    full-height panel-pivoted LU for ill-conditioned pivot blocks.
    ``schedule`` as in :func:`lu_decompose` (applies to the LU stage)."""
    _require_square(mat)
    _require_pivot(pivot)
    _resolve_schedule(schedule, 1, pivot)  # arg validation in EVERY mode
    n = mat.num_rows()
    a = mat.logical()
    if _mode_to_local(mode, n):
        return mat._wrap(jnp.linalg.inv(a))
    b = block_size or get_config().inverse_base_size
    b = min(b, n)
    n_pad, sharding = _pad_and_sharding(mat, n, b)
    a_pad = _pad_with_identity(a, n_pad)
    sched = _resolve_schedule(schedule, n_pad // b, pivot)
    inv_pad = _inverse_via_lu(a_pad, b, pivot, sharding, sched)
    return mat._wrap(inv_pad[:n, :n])
