"""Ring matmul: blockwise accumulation with compute/communication overlap.

The reference's only mechanism for "a contraction dimension too big for one
node" is its k-split shuffle with reduceByKey (SURVEY.md §5.7); the all-at-once
analog here is :func:`marlin_tpu.parallel.rmm_matmul`'s psum. This module adds
the *ring* formulation — the same pattern ring attention uses for long
sequences: every device keeps its A-rows stationary, while B-panels rotate
around the ring via ``lax.ppermute``; each step multiplies the resident panel
while the next one is already in flight over ICI, so the collective cost hides
behind the MXU instead of serializing after it.

Layout: A row-sharded ``P(axis, None)`` (each device: m/p × k), B row-sharded
``P(axis, None)`` (each device: k/p × n), C row-sharded — i.e. both operands
and the result stay in the natural DenseVecMatrix layout; no reshard of B into
a column layout is needed at all (contrast BlockMatrix.multiply's full
replicate-shuffle, BlockMatrix.scala:149-220).

One body, two uses (:func:`ring_local`): ``strategy="ring"`` runs it over one
axis (:func:`ring_matmul`, C row-sharded); the adaptive multiply of two
row-sharded operands on a full 2-D mesh runs it along ``rows`` with B cut into
column panels by ``cols`` (``parallel/matmul.py``, program ``ring2d``: the
CARMA (rows, 1, cols) split, C block-sharded), so a chip sends and receives
one panel of B at a time and nothing else.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..config import get_config
from ..mesh import ROWS, default_mesh, pad_to_multiple

__all__ = ["ring_local", "ring_matmul"]


def ring_local(axis: str, precision: str, accum_dtype, col_axis: str | None = None):
    """The ring's body for ``shard_map``: ``local(a_blk, b_blk)`` with
    ``a_blk`` the chip's rows of A (m/p x k, stationary) and ``b_blk`` the
    rows of B it holds (k/p x n), both ``P(axis, None)``.

    The chip multiplies the column chunk of ``a_blk`` that meets the panel it
    holds while that panel already travels to its neighbour along ``axis``,
    then takes the arrived panel as resident: p dots of a p-th of the
    contraction, summed in float32 in the order own panel first, and p - 1
    transfers, each under the dot before it (the steps are unrolled: every
    transfer is one ``collective-permute`` the scheduler starts ahead of the
    dot it rides under, and the last step sends nothing).

    ``col_axis``: a second mesh axis over which the operands are replicated.
    The chip then rotates only ITS column panel of B (``n / cols`` wide, cut
    out of the held rows by ``axis_index(col_axis)``) and produces the block
    ``P(axis, col_axis)`` of the result: an m x n split of the product whose
    only traffic is that panel, a ``cols``-th of the 1-D ring's bytes."""

    def local(a_blk, b_blk):
        p = jax.lax.axis_size(axis)
        perm = [(j, (j + 1) % p) for j in range(p)]
        kp = b_blk.shape[0]
        idx = jax.lax.axis_index(axis)
        b_cur = b_blk
        if col_axis is not None:
            nc = b_blk.shape[1] // jax.lax.axis_size(col_axis)
            b_cur = jax.lax.dynamic_slice(
                b_blk, (0, jax.lax.axis_index(col_axis) * nc), (kp, nc)
            )
        acc = None
        for i in range(p):
            owner = (idx - i) % p  # whose B-panel we currently hold
            a_chunk = jax.lax.dynamic_slice(
                a_blk, (0, owner * kp), (a_blk.shape[0], kp)
            )
            # kick off the rotation, then multiply the resident panel — XLA
            # overlaps the ppermute DMA with the dot.
            b_next = jax.lax.ppermute(b_cur, axis, perm) if i + 1 < p else None
            part = jnp.dot(
                a_chunk, b_cur, precision=precision, preferred_element_type=accum_dtype
            )
            acc = part if acc is None else acc + part
            b_cur = b_next
        return acc

    return local


@functools.lru_cache(maxsize=64)
def _ring_fn(mesh: Mesh, axis: str, precision: str, accum_dtype):
    @jax.jit
    def f(a, b):
        return jax.shard_map(
            ring_local(axis, precision, accum_dtype),
            mesh=mesh,
            in_specs=(P(axis, None), P(axis, None)),
            out_specs=P(axis, None),
        )(a, b)

    return f


def ring_matmul(
    a: jax.Array,
    b: jax.Array,
    mesh: Mesh | None = None,
    axis: str = ROWS,
    precision: str | None = None,
    accum_dtype=None,
) -> jax.Array:
    """``a @ b`` with B-panels rotating around the mesh ring. Logical in/out."""
    m, k = a.shape
    k2, n = b.shape
    if k != k2:
        raise ValueError(f"inner dimensions mismatch: {a.shape} @ {b.shape}")
    mesh = mesh or default_mesh()
    p = mesh.shape[axis]
    mp, kp = pad_to_multiple(m, p), pad_to_multiple(k, p)
    if (mp, kp) != (m, k):
        a = jnp.pad(a, ((0, mp - m), (0, kp - k)))
    if kp != k:
        b = jnp.pad(b, ((0, kp - k), (0, 0)))
    sh = NamedSharding(mesh, P(axis, None))
    a = jax.device_put(a, sh)
    b = jax.device_put(b, sh)
    precision = precision or get_config().matmul_precision
    c = _ring_fn(mesh, axis, precision, accum_dtype or a.dtype)(a, b)
    return c[:m] if mp != m else c
