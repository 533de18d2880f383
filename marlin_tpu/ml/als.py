"""Alternating least squares on explicit ratings.

The reference ports the old MLlib blocked ALS (ml/ALSHelp.scala): user/product
factor blocks with in/out link tables, a message-passing shuffle per half-
iteration (outlinks → messages → join inlinks, ALSHelp.scala:263-286), per-user
normal equations accumulated with BLAS dspr (:236-254), solved via an explicit
``inv(AᵀA)`` (:388-392 — a numerical weakness SURVEY.md §7 flags to fix).

TPU-first there are no link tables and no shuffles: factors are dense sharded
(num_users × rank) / (num_items × rank) arrays; for each half-step the rated
items' factors are *gathered* by index (XLA turns cross-shard gathers into
collectives), per-rating outer products ``v vᵀ`` are accumulated per user with
``segment_sum`` (the dspr loop, vectorized), and the per-user rank×rank normal
equations are solved batched with ``jnp.linalg.solve`` — not an explicit
inverse. One whole ALS sweep is a single jitted program.

Supports the regularization modes of the reference: plain λ and
weighted-λ (``alpha``-free explicit ALS-WR scaling by each user's rating count,
ALSHelp.scala:57-60 implicitPrefs=false path).
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P


__all__ = ["als_run", "ALSModel"]


@dataclasses.dataclass
class ALSModel:
    user_features: object  # DenseVecMatrix (num_users × rank)
    product_features: object  # DenseVecMatrix (num_items × rank)

    def predict(self, users, items) -> jax.Array:
        u = self.user_features.logical()
        v = self.product_features.logical()
        return jnp.sum(u[jnp.asarray(users)] * v[jnp.asarray(items)], axis=1)

    def rmse(self, coo) -> float:
        pred = self.predict(coo.row_indices, coo.col_indices)
        err = pred - coo.values
        return float(jnp.sqrt(jnp.mean(err * err)))


def _chunked_segment_stats(factors_other, seg_ids, other_ids, ratings,
                           num_segments, weight=None, chunk: int | None = None):
    """Accumulate per-segment XᵀX / Xᵀy / counts over nnz in bounded chunks:
    the (chunk, rank, rank) outer-product tensor never materializes beyond a
    fixed element budget, so huge rating sets (the MEMORY_AND_DISK link tables
    of the reference, ALSHelp.scala:32) stay in HBM."""
    nnz = ratings.shape[0]
    rank = factors_other.shape[1]
    if chunk is None:
        # ~64 MB f32 of outer-product tensor per chunk regardless of rank
        chunk = max(1, (1 << 24) // (rank * rank))
    chunk = max(1, min(chunk, nnz))
    n_chunks = max(1, -(-nnz // chunk))
    pad = n_chunks * chunk - nnz
    if pad:
        seg_ids = jnp.pad(seg_ids, (0, pad), constant_values=num_segments)
        other_ids = jnp.pad(other_ids, (0, pad))
        ratings = jnp.pad(ratings, (0, pad))
        if weight is not None:
            weight = jnp.pad(weight, (0, pad))
    dt = factors_other.dtype

    def body(carry, idx):
        xtx, xty, counts = carry
        s = seg_ids[idx]
        vt = factors_other[other_ids[idx]]
        r = ratings[idx]
        w = weight[idx] if weight is not None else jnp.ones_like(r)
        outer = vt[:, :, None] * vt[:, None, :] * w[:, None, None]
        # the extra segment (num_segments) swallows the padding entries
        xtx = xtx + jax.ops.segment_sum(outer, s, num_segments + 1)
        xty = xty + jax.ops.segment_sum(vt * r[:, None], s, num_segments + 1)
        counts = counts + jax.ops.segment_sum(jnp.ones_like(r), s, num_segments + 1)
        return (xtx, xty, counts), None

    init = (
        jnp.zeros((num_segments + 1, rank, rank), dt),
        jnp.zeros((num_segments + 1, rank), dt),
        jnp.zeros((num_segments + 1,), dt),
    )
    # inside shard_map the data is varying over the mesh axes; the scan carry
    # init must carry the same varying-manual-axes type
    vma = tuple(jax.typeof(ratings).vma)
    if vma:
        init = tuple(jax.lax.pcast(x, vma, to="varying") for x in init)
    idxs = jnp.arange(n_chunks * chunk).reshape(n_chunks, chunk)
    (xtx, xty, counts), _ = jax.lax.scan(body, init, idxs)
    return xtx[:num_segments], xty[:num_segments], counts[:num_segments]


def _solve_explicit_stats(xtx, xty, counts, lam, weighted):
    """Batched regularized normal-equation solve from accumulated stats —
    ``jnp.linalg.solve``, not the reference's explicit ``inv(AᵀA)``
    (ALSHelp.scala:388-392)."""
    reg = lam * (counts[:, None] if weighted else jnp.ones_like(counts)[:, None])
    eye = jnp.eye(xtx.shape[-1], dtype=xtx.dtype)
    a = xtx + reg[:, :, None] * eye
    # rows with no ratings keep a well-posed system (identity) and get 0
    sol = jnp.linalg.solve(a, xty[..., None])[..., 0]
    return jnp.where(counts[:, None] > 0, sol, jnp.zeros_like(sol))


def _solve_implicit_stats(yty, corr, rhs, counts, lam):
    eye = jnp.eye(yty.shape[0], dtype=yty.dtype)
    a = yty[None] + corr + lam * eye[None]
    sol = jnp.linalg.solve(a, rhs[..., None])[..., 0]
    return jnp.where(counts[:, None] > 0, sol, jnp.zeros_like(sol))


@functools.partial(jax.jit, static_argnames=("num_segments", "weighted"))
def _solve_side(factors_other, seg_ids, other_ids, ratings, rank, lam,
                num_segments, weighted):
    """One explicit half-step: recompute `num_segments` factor rows from the
    fixed other side. seg_ids: which row each rating belongs to; other_ids:
    which fixed factor it references. Normal-equation stats accumulate in
    nnz chunks (the vectorized dspr loop, ALSHelp.scala:292-382)."""
    xtx, xty, counts = _chunked_segment_stats(
        factors_other, seg_ids, other_ids, ratings, num_segments
    )
    return _solve_explicit_stats(xtx, xty, counts, lam, weighted)


@functools.partial(jax.jit, static_argnames=("num_segments",))
def _solve_side_implicit(factors_other, seg_ids, other_ids, ratings, lam, alpha,
                         num_segments):
    """One implicit-feedback half-step (Hu/Koren/Volinsky; the role of the
    reference's implicitPrefs path with its computeYtY precompute,
    ALSHelp.scala:188-200, 292-382): solve
    (YᵀY + Yᵀ(C−I)Y + λI) x = Yᵀ C p  per row, with the dense YᵀY computed
    once globally and only the (c−1)-weighted corrections segment-summed."""
    yty = jnp.dot(factors_other.T, factors_other, precision="highest")
    conf_minus_1 = alpha * ratings  # c = 1 + alpha*r
    # chunked accumulation: corr = Σ (c−1)·v vᵀ, rhs = Σ c·p·v (p = 1 observed)
    corr, rhs, counts = _chunked_segment_stats(
        factors_other, seg_ids, other_ids, 1.0 + conf_minus_1,
        num_segments, weight=conf_minus_1,
    )
    return _solve_implicit_stats(yty, corr, rhs, counts, lam)


def _block_ratings_by_segment(seg_ids, other_ids, vals, num_segments,
                              n_dev: int, block: int):
    """Host-side prep for the sharded path: sort ratings by owning segment and
    pack them into a dense ``(total_blocks, max_nnz)`` layout where block ``b``
    holds exactly the ratings of segments ``[b·block, (b+1)·block)``. Device
    ``d`` then owns a contiguous run of blocks — this replaces the reference's
    in/out link tables + HashPartitioner shuffle (ALSHelp.scala:101-165) with a
    static layout XLA can scan without any data-dependent control flow.

    Padding entries carry segment id ``block`` (the swallow segment of
    ``_chunked_segment_stats``) and rating 0. The packed size is
    ``total_blocks · max_nnz`` where ``max_nnz`` is the fullest block — fine
    for near-uniform rating distributions; a pathologically hot segment block
    inflates padding, in which case lower ``segment_block``."""
    seg = np.asarray(seg_ids)
    oth = np.asarray(other_ids)
    val = np.asarray(vals)
    segs_per_dev = -(-num_segments // (n_dev * block)) * block
    padded_segments = segs_per_dev * n_dev
    total_blocks = padded_segments // block
    order = np.argsort(seg, kind="stable")
    seg, oth, val = seg[order], oth[order], val[order]
    blk = seg // block
    counts = np.bincount(blk, minlength=total_blocks).astype(np.int64)
    max_nnz = -(-max(int(counts.max()), 8) // 8) * 8
    starts = np.cumsum(counts) - counts
    pos = np.arange(seg.shape[0]) - starts[blk]
    sid = np.full((total_blocks, max_nnz), block, np.int32)
    oid = np.zeros((total_blocks, max_nnz), np.int32)
    v = np.zeros((total_blocks, max_nnz), np.float32)
    sid[blk, pos] = (seg % block).astype(np.int32)
    oid[blk, pos] = oth.astype(np.int32)
    v[blk, pos] = val.astype(np.float32)
    return sid, oid, v, padded_segments


@functools.partial(jax.jit,
                   static_argnames=("mesh", "block", "weighted", "implicit"))
def _solve_side_sharded(factors_other, blk_sid, blk_oid, blk_val, lam, alpha,
                        *, mesh, block, weighted, implicit):
    """One sharded half-step. The updated side's segment axis is sharded over
    *all* mesh devices (each device owns a contiguous run of segment blocks and
    solves only those), so the ``(segments, rank, rank)`` stat tensor never
    materializes beyond one ``segment_block`` per device. The fixed other side
    arrives replicated — the shard_map in_spec ``P()`` makes GSPMD insert the
    all-gather, which is this design's entire communication (the analog of the
    reference's outlinks→messages shuffle, ALSHelp.scala:263-286)."""
    axes = tuple(mesh.axis_names)
    spec_b = P(axes, None)

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(P(), spec_b, spec_b, spec_b, P(), P()),
        out_specs=spec_b,
    )
    def run(other, sid, oid, val, lam_, alpha_):
        yty = (jnp.dot(other.T, other, precision="highest")
               if implicit else None)

        def body(_, xs):
            s, o, r = xs
            if implicit:
                cm1 = alpha_ * r
                corr, rhs, counts = _chunked_segment_stats(
                    other, s, o, 1.0 + cm1, block, weight=cm1)
                sol = _solve_implicit_stats(yty, corr, rhs, counts, lam_)
            else:
                xtx, xty, counts = _chunked_segment_stats(other, s, o, r, block)
                sol = _solve_explicit_stats(xtx, xty, counts, lam_, weighted)
            return None, sol

        _, out = jax.lax.scan(body, None, (sid, oid, val))
        return out.reshape(-1, out.shape[-1])

    return run(factors_other, blk_sid, blk_oid, blk_val, lam, alpha)


def als_run(ratings, rank: int, iterations: int = 10, lam: float = 0.01,
            seed: int = 0, weighted_lambda: bool = True, mesh=None,
            implicit_prefs: bool = False, alpha: float = 1.0,
            num_user_blocks: int = -1, num_product_blocks: int = -1,
            shard: bool | None = None, segment_block: int = 4096) -> ALSModel:
    """Run blocked ALS (ALSHelp.ALSRun, ml/ALSHelp.scala:34-96).

    ``ratings`` is a CoordinateMatrix of (user, product, rating). Factors are
    initialized on the unit sphere like ``randomFactor`` (ALSHelp.scala:170-179).
    ``implicit_prefs``/``alpha`` select the implicit-feedback formulation, the
    same switch ALSRun takes (ALSHelp.scala:33-34). ``num_user_blocks``/
    ``num_product_blocks`` are accepted for signature parity but ignored:
    blocking was the reference's shuffle-partitioning knob, and factor layout
    here is governed by the mesh sharding instead.

    ``shard`` selects the blocked solver (segment axes of the factor matrices
    and stat accumulators sharded over all devices, the fixed side
    all-gathered per half-step) — the scale path matching the reference's
    MEMORY_AND_DISK blocked design (ALSHelp.scala:32, 263-286). On a single
    device it is the bounded-memory mode: stats materialize one
    ``segment_block`` at a time instead of ``(num_segments, rank, rank)`` at
    once, which is what lets reference-scale rating sets (10⁶+ users) fit one
    chip's HBM. ``None`` auto-enables it when the full stat tensor of either
    side would exceed 256 MB. ``segment_block`` is the per-device solve
    granularity.
    """
    del num_user_blocks, num_product_blocks
    from ..matrix.dense import DenseVecMatrix

    mesh = mesh or ratings.mesh
    num_users, num_items = ratings.shape
    # jit-produced ratings may carry BCOO padding (indices == shape); padded
    # entries would be clip-gathered into wrong segments. Detect with two
    # device-side scalar reduces so the clean (reference-scale) case never
    # pays a host round-trip of the full entry arrays
    if ratings.nnz and (int(jnp.max(ratings.row_indices)) >= num_users
                        or int(jnp.max(ratings.col_indices)) >= num_items):
        ratings = ratings.compact()
    users = jnp.asarray(ratings.row_indices, jnp.int32)
    items = jnp.asarray(ratings.col_indices, jnp.int32)
    vals = jnp.asarray(ratings.values, jnp.float32)

    key_u, key_v = jax.random.split(jax.random.key(seed))
    u = jax.random.normal(key_u, (num_users, rank), jnp.float32)
    u = jnp.abs(u) / jnp.linalg.norm(u, axis=1, keepdims=True)
    v = jax.random.normal(key_v, (num_items, rank), jnp.float32)
    v = jnp.abs(v) / jnp.linalg.norm(v, axis=1, keepdims=True)

    n_dev = int(np.prod(list(mesh.shape.values())))
    if shard is None:
        # blocked mode whenever the full stat tensor is HBM-hostile — on ANY
        # device count (the single-chip ALS bench config needs 31 GB of stats
        # through the unsharded path; blocked, it needs one segment block)
        stat_bytes = 4 * rank * rank * max(num_users, num_items)
        shard = stat_bytes > (1 << 28)

    if shard:
        u, v = _als_sharded(mesh, u, v, users, items, vals, num_users,
                            num_items, iterations, lam, alpha, weighted_lambda,
                            implicit_prefs, segment_block, n_dev)
    else:
        for _ in range(iterations):
            # products fixed -> update users, then users fixed -> update products
            if implicit_prefs:
                u = _solve_side_implicit(v, users, items, vals, lam, alpha, num_users)
                v = _solve_side_implicit(u, items, users, vals, lam, alpha, num_items)
            else:
                u = _solve_side(v, users, items, vals, rank, lam, num_users, weighted_lambda)
                v = _solve_side(u, items, users, vals, rank, lam, num_items, weighted_lambda)

    return ALSModel(
        DenseVecMatrix.from_array(u, mesh),
        DenseVecMatrix.from_array(v, mesh),
    )


def _als_sharded(mesh, u, v, users, items, vals, num_users, num_items,
                 iterations, lam, alpha, weighted_lambda, implicit_prefs,
                 segment_block, n_dev):
    """Drive the sharded half-steps: pack both rating orientations once
    (user-sorted for the user update, item-sorted for the item update), place
    the packed blocks and the factor matrices sharded over the whole mesh, and
    alternate jitted half-steps. Factors stay padded/sharded across the loop;
    the slice back to logical size happens once at the end."""
    axes = tuple(mesh.axis_names)
    spec_b = NamedSharding(mesh, P(axes, None))
    block = max(8, min(segment_block, -(-max(num_users, num_items) // n_dev)))

    users_np, items_np, vals_np = (np.asarray(users), np.asarray(items),
                                   np.asarray(vals))
    u_sid, u_oid, u_val, pad_users = _block_ratings_by_segment(
        users_np, items_np, vals_np, num_users, n_dev, block)
    v_sid, v_oid, v_val, pad_items = _block_ratings_by_segment(
        items_np, users_np, vals_np, num_items, n_dev, block)
    u_sid, u_oid, u_val, v_sid, v_oid, v_val = (
        jax.device_put(x, spec_b)
        for x in (u_sid, u_oid, u_val, v_sid, v_oid, v_val))

    u = jax.device_put(jnp.pad(u, ((0, pad_users - num_users), (0, 0))), spec_b)
    v = jax.device_put(jnp.pad(v, ((0, pad_items - num_items), (0, 0))), spec_b)
    lam = jnp.float32(lam)
    alpha = jnp.float32(alpha)
    for _ in range(iterations):
        u = _solve_side_sharded(v, u_sid, u_oid, u_val, lam, alpha, mesh=mesh,
                                block=block, weighted=weighted_lambda,
                                implicit=implicit_prefs)
        v = _solve_side_sharded(u, v_sid, v_oid, v_val, lam, alpha, mesh=mesh,
                                block=block, weighted=weighted_lambda,
                                implicit=implicit_prefs)
    return u[:num_users], v[:num_items]
