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
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..config import get_config
from ..mesh import ROWS, default_mesh, pad_to_multiple

__all__ = ["ring_matmul"]


@functools.lru_cache(maxsize=64)
def _ring_fn(mesh: Mesh, axis: str, precision: str, accum_dtype):
    p = mesh.shape[axis]
    perm = [(j, (j + 1) % p) for j in range(p)]

    def local(a_blk, b_blk):
        # a_blk: (m/p, k) stationary; b_blk: (k/p, n) rotating
        kp = b_blk.shape[0]
        idx = jax.lax.axis_index(axis)

        def step(i, carry):
            b_cur, acc = carry
            owner = (idx - i) % p  # whose B-panel we currently hold
            a_chunk = jax.lax.dynamic_slice(
                a_blk, (0, owner * kp), (a_blk.shape[0], kp)
            )
            # kick off the rotation, then multiply the resident panel — XLA
            # overlaps the ppermute DMA with the dot.
            b_next = jax.lax.ppermute(b_cur, axis, perm)
            acc = acc + jnp.dot(
                a_chunk, b_cur, precision=precision, preferred_element_type=accum_dtype
            )
            return b_next, acc

        acc0 = jax.lax.pcast(
            jnp.zeros((a_blk.shape[0], b_blk.shape[1]), accum_dtype),
            (axis,), to="varying",
        )
        _, acc = jax.lax.fori_loop(0, p, step, (b_blk, acc0))
        return acc

    @jax.jit
    def f(a, b):
        return jax.shard_map(
            local,
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
