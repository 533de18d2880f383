"""PageRank as iterated distributed mat-vec.

The reference example (examples/PageRank.scala) builds a link matrix and
multiplies it against the rank vector per iteration (:46-58), one Spark job per
step. Here the link matrix is a (sparse or dense) sharded operand, the rank
vector is replicated, and the full power iteration runs as one jitted
``lax.fori_loop`` with XLA collectives inside — plus an optional convergence
threshold via ``lax.while_loop``.

Graph-scale input never densifies: :func:`build_transition_operator` keeps the
graph as (src, dst) edge arrays plus an out-degree table (the reference builds
its link matrix distributed from the edge file, examples/PageRank.scala:46-58),
and the iteration is gather + ``segment_sum`` over edges — the TPU-shaped SpMV
for unstructured graphs, optionally sharded over the edge axis of the mesh.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P


__all__ = ["pagerank", "build_transition_matrix", "build_transition_operator",
           "TransitionOperator"]


def build_transition_matrix(edges, n: int | None = None) -> np.ndarray:
    """Column-stochastic transition matrix from (src, dst) edge pairs.
    Dangling nodes get uniform columns."""
    edges = np.asarray(list(edges), dtype=np.int64)
    if edges.size == 0:
        raise ValueError("empty edge list")
    if n is None:
        n = int(edges.max()) + 1
    m = np.zeros((n, n), np.float32)
    np.add.at(m, (edges[:, 1], edges[:, 0]), 1.0)
    colsum = m.sum(axis=0)
    dangling = colsum == 0
    m[:, ~dangling] /= colsum[~dangling]
    m[:, dangling] = 1.0 / n
    return m


@dataclasses.dataclass
class TransitionOperator:
    """Column-stochastic link operator held in edge-list form: applying it to a
    rank vector is ``segment_sum(r[src]/outdeg[src], dst)`` plus the dangling
    mass spread uniformly — identical math to the dense
    :func:`build_transition_matrix` without the n×n materialization."""

    src: jax.Array  # (E,) int32
    dst: jax.Array  # (E,) int32
    inv_deg: jax.Array  # (n,) f32, 1/outdegree, 0 at dangling nodes
    dangling: jax.Array  # (n,) f32, 1.0 at dangling nodes
    n: int
    mesh: object | None = None
    weight: jax.Array | None = None  # (E,) f32 edge validity (sharded padding)

    @property
    def shape(self):
        return (self.n, self.n)

    @property
    def nnz(self):
        return int(self.src.shape[0])


def build_transition_operator(edges, n: int | None = None,
                              mesh=None) -> TransitionOperator:
    """Edge-list transition operator from (src, dst) pairs — the graph-scale
    input path (reference: examples/PageRank.scala:46-58 builds the link
    matrix distributed from the edge file). O(E + n) memory; duplicate edges
    weight like the dense builder (each contributes one out-link).

    ``edges`` is an (E, 2) array-like or iterable of pairs. With ``mesh`` the
    edge arrays are sharded over all mesh devices and the per-iteration
    scatter-reduce runs edge-parallel with a psum."""
    edges = np.asarray(edges if hasattr(edges, "ndim") else list(edges),
                       dtype=np.int64)
    if edges.size == 0:
        raise ValueError("empty edge list")
    edges = edges.reshape(-1, 2)
    if n is None:
        n = int(edges.max()) + 1
    deg = np.bincount(edges[:, 0], minlength=n).astype(np.float32)
    dangling = (deg == 0).astype(np.float32)
    inv_deg = np.where(deg > 0, 1.0 / np.maximum(deg, 1.0), 0.0).astype(np.float32)
    # sort by destination once at build time: the per-iteration scatter-reduce
    # then runs with indices_are_sorted=True — on TPU an unsorted 10^8-update
    # scatter-add is pathologically slow, a sorted one is a linear pass
    order = np.argsort(edges[:, 1], kind="stable")
    src = edges[order, 0].astype(np.int32)
    dst = edges[order, 1].astype(np.int32)
    if mesh is not None:
        axes = tuple(mesh.axis_names)
        n_dev = int(np.prod([mesh.shape[a] for a in axes]))
        # pad the edge axis to the device count; padding edges carry weight 0
        # so they contribute nothing, and dst = n-1 keeps the axis dst-sorted
        pad = (-len(src)) % n_dev
        weight = np.ones(len(src) + pad, np.float32)
        if pad:
            src = np.concatenate([src, np.zeros(pad, np.int32)])
            dst = np.concatenate([dst, np.full(pad, n - 1, np.int32)])
            weight[-pad:] = 0.0
        espec = NamedSharding(mesh, P(axes))
        return TransitionOperator(
            jax.device_put(src, espec), jax.device_put(dst, espec),
            jnp.asarray(inv_deg), jnp.asarray(dangling), n, mesh,
            jax.device_put(weight, espec))
    return TransitionOperator(jnp.asarray(src), jnp.asarray(dst),
                              jnp.asarray(inv_deg), jnp.asarray(dangling), n)


def _pagerank_step(r, src, dst, weight, inv_deg, dangling, damping, n,
                   psum_axes=None):
    """One power-iteration step in edge form: gather per-edge contributions,
    scatter-reduce into destinations (segment_sum — the reduceByKey of
    examples/PageRank.scala:52), spread dangling mass uniformly."""
    contrib = (r * inv_deg)[src]
    if weight is not None:
        contrib = contrib * weight
    acc = jax.ops.segment_sum(contrib, dst, n, indices_are_sorted=True)
    if psum_axes:
        acc = jax.lax.psum(acc, psum_axes)
    d_mass = jnp.sum(r * dangling)
    r = damping * (acc + d_mass / n) + (1.0 - damping) / n
    return r / jnp.sum(r)


@functools.partial(jax.jit, static_argnames=("n", "iterations", "mesh"))
def _pagerank_edges(src, dst, weight, inv_deg, dangling, damping, n: int,
                    iterations: int, mesh=None):
    r0 = jnp.full((n,), 1.0 / n, jnp.float32)

    if mesh is None:
        def body(_, r):
            return _pagerank_step(r, src, dst, weight, inv_deg, dangling,
                                  damping, n)
        return jax.lax.fori_loop(0, iterations, body, r0)

    axes = tuple(mesh.axis_names)

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(P(axes), P(axes), P(axes), P(), P(), P()),
        out_specs=P(),
    )
    def run(src_, dst_, w_, inv_deg_, dangling_, damping_):
        def body(_, r):
            return _pagerank_step(r, src_, dst_, w_, inv_deg_, dangling_,
                                  damping_, n, psum_axes=axes)
        # psum returns a mesh-invariant value, so the whole carry stays
        # invariant and the replicated out_spec holds by construction
        return jax.lax.fori_loop(0, iterations, body, r0)

    return run(src, dst, weight, inv_deg, dangling, damping)


@functools.partial(jax.jit, static_argnames=("iterations",))
def _pagerank_fori(m, damping, iterations: int):
    n = m.shape[0]
    r0 = jnp.full((n,), 1.0 / n, jnp.result_type(m.dtype, jnp.float32))

    def body(_, r):
        r = damping * (m @ r) + (1.0 - damping) / n
        return r / jnp.sum(r)

    return jax.lax.fori_loop(0, iterations, body, r0)


def pagerank(link_matrix, damping: float = 0.85, iterations: int = 20) -> np.ndarray:
    """Run power iteration. ``link_matrix`` is a DenseMatrix/SparseVecMatrix/
    array holding a column-stochastic transition matrix (use
    :func:`build_transition_matrix` to build one from an edge list), or a
    :class:`TransitionOperator` from :func:`build_transition_operator` for
    graph-scale edge lists that must never densify. Sparse operands stay
    sparse: the mat-vec inside the loop is a BCOO contraction / edge-parallel
    scatter-reduce."""
    from ..matrix.sparse import SparseVecMatrix

    if isinstance(link_matrix, TransitionOperator):
        op = link_matrix
        r = _pagerank_edges(op.src, op.dst, op.weight, op.inv_deg, op.dangling,
                            jnp.asarray(damping, jnp.float32), op.n,
                            int(iterations), op.mesh)
        return np.asarray(jax.device_get(r))
    if isinstance(link_matrix, SparseVecMatrix):
        arr = link_matrix.bcoo
    else:
        arr = link_matrix.logical() if hasattr(link_matrix, "logical") else jnp.asarray(link_matrix)
    r = _pagerank_fori(arr, jnp.asarray(damping, jnp.float32), int(iterations))
    return np.asarray(jax.device_get(r))
