"""Distributed matrix multiply strategies.

This is the TPU-native replacement for the reference's flagship path — the
replication matrix multiply ("RMM") and its adaptive dispatch:

- ``BlockMatrix.multiply`` replicates A-blocks n×, B-blocks m×, routes each
  (i, j, l) pair to its own shuffle partition via ``BlockID.seq`` +
  ``MatrixMultPartitioner``, joins, GEMMs per pair, then reduces over k
  (matrix/BlockMatrix.scala:149-220, rdd/MatrixMultPartitioner.scala:6-33).
- ``DenseVecMatrix.multiply(other, cores, broadcastThreshold)`` picks between a
  broadcast multiply for small operands and a CARMA-split shuffle multiply
  (matrix/DenseVecMatrix.scala:196-231).

Here the same three strategies exist, but as *static SPMD programs* instead of
dynamic shuffles:

- :func:`rmm_matmul` — the (m, k, n) task grid becomes a 3-D device mesh
  ``("m", "k", "n")``; "replicate A n times" is simply A's sharding being
  replicated along the ``n`` axis (zero-copy on ICI until XLA decides to move
  bytes), the per-pair GEMM is the per-device ``jnp.dot``, and ``reduceByKey``
  over k is ``lax.psum`` over the ``k`` axis.
- :func:`broadcast_matmul` — the small operand gets a fully-replicated
  sharding (the analog of ``sc.broadcast``, DenseVecMatrix.scala:1660-1680).
- :func:`gspmd_matmul` — hands the sharded contraction to XLA's SPMD
  partitioner, which chooses the collective schedule itself; this is the
  "RMMv2 vs RMMv3" competition (examples/RMMcompare.scala:13-16) resolved by
  the compiler per shape.

What an RMM split moves, for the row-sharded operands a ``DenseVecMatrix``
holds (``P("rows", None)``: chip (r, c) has A[r, :] and B[k_r, :]): an m-split
moves nothing of A; an n-split fetches the rows of B[:, n_c] the chip lacks;
a k-split re-places an operand AND sums result-sized partials after the dot.
Operand bytes can travel under the dot, partial products cannot (see
``parallel/carma.py``). So where the split is (rows, 1, cols), the caller's
whole mesh, and both operands arrive row-sharded, the fused program is a ring
along ``rows`` over B's column panels (``ring2d``,
:func:`marlin_tpu.parallel.ring.ring_local`): no reshard, no reduction, one
panel of B in flight under the dot of the panel before it. Every other
layout or split (a ``BlockMatrix`` operand, a k-split, a subset mesh) runs
the 3-D mesh program with its ``psum``.

All functions take/return *logical* (unpadded) arrays; shard-divisibility
padding happens inside the jitted program and is sliced off before returning.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..config import get_config
from ..mesh import default_mesh, pad_to_multiple
from .carma import split_method
from .ring import ring_local, ring_matmul

_M, _K, _N = "m", "k", "n"


class UnknownStrategyError(ValueError):
    """Raised when a matmul ``strategy`` name is not one the engine knows.

    A dedicated type so the autotuner can skip unsupported candidates without
    matching on message text (any other ``ValueError`` from an engine is a
    genuinely broken run and must surface)."""


def _resolve_precision(precision):
    return precision or get_config().matmul_precision


def build_rmm_mesh(split: tuple[int, int, int], devices=None) -> Mesh:
    """Arrange devices into the (m_split, k_split, n_split) grid chosen by the
    CARMA heuristic — the mesh-shaped descendant of ``MatrixMultPartitioner``'s
    m·k·n partition space."""
    devs = list(devices) if devices is not None else jax.devices()
    pm, pk, pn = split
    need = pm * pk * pn
    if need > len(devs):
        raise ValueError(f"split {split} needs {need} devices, have {len(devs)}")
    return Mesh(np.array(devs[:need]).reshape(pm, pk, pn), (_M, _K, _N))


@functools.lru_cache(maxsize=64)
def _rmm_fn(mesh3: Mesh, precision: str, accum_dtype):
    def local(ab, bb):
        c = jnp.dot(ab, bb, precision=precision, preferred_element_type=accum_dtype)
        return jax.lax.psum(c, _K)

    @jax.jit
    def f(a, b):
        return jax.shard_map(
            local,
            mesh=mesh3,
            in_specs=(P(_M, _K), P(_K, _N)),
            out_specs=P(_M, _N),
        )(a, b)

    return f


def rmm_matmul(
    a: jax.Array,
    b: jax.Array,
    split: tuple[int, int, int] | None = None,
    devices=None,
    precision: str | None = None,
    accum_dtype=None,
) -> jax.Array:
    """3-D replicated matmul over an (m, k, n) device mesh.

    ``split=None`` runs the CARMA heuristic over the actual shapes and device
    count (the ``multiply(other, cores)`` auto path, DenseVecMatrix.scala:214-218);
    an explicit split mirrors ``multiply(other, (m, k, n))``
    (DenseVecMatrix.scala:109-141).
    """
    m, k = a.shape
    k2, n = b.shape
    if k != k2:
        raise ValueError(f"inner dimensions mismatch: {a.shape} @ {b.shape}")
    devs = list(devices) if devices is not None else jax.devices()
    if split is None:
        # CARMA device budget: with no explicit device list, the
        # default_parallelism knob caps the heuristic (the reference's
        # spark.default.parallelism hint, MTUtils.scala:496-502) — the
        # mesh then uses a device subset, never more than exist
        budget = len(devs)
        if devices is None:
            hint = get_config().default_parallelism
            if hint:
                budget = max(1, min(int(hint), budget))
        split = split_method(m, k, n, budget)
    mesh3 = build_rmm_mesh(split, devs)
    pm, pk, pn = split
    mp, kp, np_ = pad_to_multiple(m, pm), pad_to_multiple(k, pk), pad_to_multiple(n, pn)
    if (mp, kp) != (m, k):
        a = jnp.pad(a, ((0, mp - m), (0, kp - k)))
    if (kp, np_) != (k, n):
        b = jnp.pad(b, ((0, kp - k), (0, np_ - n)))
    # place operands on the 3-D mesh (may be a device subset when the CARMA
    # split doesn't fill the device count): the placement moves what of its
    # blocks a device lacks; shard_map then psums along k.
    a = jax.device_put(a, NamedSharding(mesh3, P(_M, _K)))
    b = jax.device_put(b, NamedSharding(mesh3, P(_K, _N)))
    fn = _rmm_fn(mesh3, _resolve_precision(precision), accum_dtype or a.dtype)
    c = fn(a, b)
    return c[:m, :n] if (mp, np_) != (m, n) else c


@functools.lru_cache(maxsize=64)
def _broadcast_fn(out_sharding, replicate_which: str, precision: str, accum_dtype):
    repl = NamedSharding(out_sharding.mesh, P())

    @jax.jit
    def f(a, b):
        if replicate_which == "b":
            b_ = jax.lax.with_sharding_constraint(b, repl)
            c = jnp.dot(a, b_, precision=precision, preferred_element_type=accum_dtype)
        else:
            a_ = jax.lax.with_sharding_constraint(a, repl)
            c = jnp.dot(a_, b, precision=precision, preferred_element_type=accum_dtype)
        return jax.lax.with_sharding_constraint(c, out_sharding)

    return f


def broadcast_matmul(
    a: jax.Array,
    b: jax.Array,
    out_sharding: NamedSharding,
    replicate: str = "b",
    precision: str | None = None,
    accum_dtype=None,
) -> jax.Array:
    """Small-operand multiply: fully replicate one side (the analog of
    collect-to-driver + ``sc.broadcast``, DenseVecMatrix.scala:196-207 and
    1660-1680; BlockMatrix.scala:280-335) and keep the big side sharded. No
    inter-device communication happens on the big operand at all."""
    fn = _broadcast_fn(
        out_sharding, replicate, _resolve_precision(precision), accum_dtype or a.dtype
    )
    return fn(a, b)


@functools.lru_cache(maxsize=64)
def _gspmd_fn(out_sharding, precision: str, accum_dtype):
    @jax.jit
    def f(a, b):
        c = jnp.dot(a, b, precision=precision, preferred_element_type=accum_dtype)
        return jax.lax.with_sharding_constraint(c, out_sharding)

    return f


def gspmd_matmul(
    a: jax.Array,
    b: jax.Array,
    out_sharding: NamedSharding,
    precision: str | None = None,
    accum_dtype=None,
) -> jax.Array:
    """Sharded contraction scheduled by XLA's SPMD partitioner: the inputs keep
    whatever shardings they carry and the compiler inserts the collective
    schedule. Competes with :func:`rmm_matmul` in examples/rmm_compare."""
    fn = _gspmd_fn(out_sharding, _resolve_precision(precision), accum_dtype or a.dtype)
    return fn(a, b)


def _size_mb(x: jax.Array) -> float:
    return x.size * x.dtype.itemsize / 1e6


_STRATEGIES = ("auto", "broadcast", "broadcast_a", "rmm", "gspmd", "ring")


def _resolve_strategy(
    mkn: tuple[int, int, int],
    itemsize: int,
    strategy: str,
    broadcast_threshold_mb: float | None,
) -> str:
    """Shared auto-dispatch (DenseVecMatrix.scala:196-231): broadcast when one
    operand is under the threshold, else CARMA RMM. Used by both the fused and
    the legacy entry points so the dispatch can't drift between them."""
    if strategy not in _STRATEGIES:
        raise UnknownStrategyError(
            f"unknown matmul strategy: {strategy!r} (one of {_STRATEGIES})"
        )
    if strategy != "auto":
        return strategy
    m, k, n = mkn
    threshold = (
        broadcast_threshold_mb
        if broadcast_threshold_mb is not None
        else get_config().broadcast_threshold_mb
    )
    if k * n * itemsize / 1e6 <= threshold:
        return "broadcast"
    if m * k * itemsize / 1e6 <= threshold:
        return "broadcast_a"
    return "rmm"


@functools.lru_cache(maxsize=128)
def _fused_fn(
    program: str,
    mkn: tuple[int, int, int],
    out_pad: tuple[int, int],
    out_sharding: NamedSharding,
    precision: str,
    accum_dtype,
    mesh3: Mesh | None,
    replicate_which: str,
):
    """One jitted program for the whole multiply: slice the padded operands to
    their logical extents, reshard/contract, and emit the result already padded
    to the OUTPUT matrix's grid and constrained to its sharding.

    This is the round-2 fix for the per-call dispatch overhead the mid-size
    bench exposed (pads + device_puts outside jit on every call, then a
    ``from_array`` round-trip on the result): everything between the two padded
    buffers now lives inside XLA, where resharding is a collective the
    scheduler can overlap instead of a blocking host-side placement."""
    m, k, n = mkn
    mp_out, np_out = out_pad

    def _finish(c):
        c = jnp.pad(c, ((0, mp_out - m), (0, np_out - n)))
        return jax.lax.with_sharding_constraint(c, out_sharding)

    def _placed(x_pad, logical, padded, sharding):
        """The logical extent of ``x_pad``, zero-padded to the program's own
        grid and laid out as the program takes it."""
        (r, c), (rp, cp) = logical, padded
        x = jnp.pad(x_pad[:r, :c], ((0, rp - r), (0, cp - c)))
        return jax.lax.with_sharding_constraint(x, sharding)

    if program == "ring2d":
        # the (rows, 1, cols) split on the caller's own mesh: both operands
        # stay where they lie and B's column panels ride the ring along rows
        mesh = out_sharding.mesh
        rows, cols = mesh.axis_names
        pm, pn = mesh.shape[rows], mesh.shape[cols]
        mp_r, kp_r, np_r = (
            pad_to_multiple(m, pm), pad_to_multiple(k, pm), pad_to_multiple(n, pn)
        )
        held = NamedSharding(mesh, P(rows, None))
        local = ring_local(rows, precision, accum_dtype, col_axis=cols)

        @jax.jit
        def f(a_pad, b_pad):
            a = _placed(a_pad, (m, k), (mp_r, kp_r), held)
            b = _placed(b_pad, (k, n), (kp_r, np_r), held)
            c = jax.shard_map(
                local, mesh=mesh,
                in_specs=(P(rows, None), P(rows, None)),
                out_specs=P(rows, cols),
            )(a, b)
            return _finish(c[:m, :n])

        return f

    if program == "rmm":
        pm, pk, pn = (mesh3.shape[_M], mesh3.shape[_K], mesh3.shape[_N])
        mp_r, kp_r, np_r = (
            pad_to_multiple(m, pm), pad_to_multiple(k, pk), pad_to_multiple(n, pn)
        )
        sh_a = NamedSharding(mesh3, P(_M, _K))
        sh_b = NamedSharding(mesh3, P(_K, _N))

        def local(ab, bb):
            cb = jnp.dot(ab, bb, precision=precision,
                         preferred_element_type=accum_dtype)
            return jax.lax.psum(cb, _K)

        @jax.jit
        def f(a_pad, b_pad):
            a = _placed(a_pad, (m, k), (mp_r, kp_r), sh_a)
            b = _placed(b_pad, (k, n), (kp_r, np_r), sh_b)
            c = jax.shard_map(
                local, mesh=mesh3,
                in_specs=(P(_M, _K), P(_K, _N)), out_specs=P(_M, _N),
            )(a, b)
            return _finish(c[:m, :n])

        return f

    if program == "broadcast":
        repl = NamedSharding(out_sharding.mesh, P())

        @jax.jit
        def f(a_pad, b_pad):
            a, b = a_pad[:m, :k], b_pad[:k, :n]
            if replicate_which == "b":
                b = jax.lax.with_sharding_constraint(b, repl)
            else:
                a = jax.lax.with_sharding_constraint(a, repl)
            c = jnp.dot(a, b, precision=precision,
                        preferred_element_type=accum_dtype)
            return _finish(c)

        return f

    # gspmd: let the SPMD partitioner pick the schedule
    @jax.jit
    def f(a_pad, b_pad):
        c = jnp.dot(a_pad[:m, :k], b_pad[:k, :n], precision=precision,
                    preferred_element_type=accum_dtype)
        return _finish(c)

    return f


@dataclasses.dataclass(eq=False)
class FusedPlan:
    """What the dispatch decided for one multiply: the resolved ``strategy``,
    the (m, k, n) ``split`` (``None`` where the strategy has none), the fused
    ``program`` that runs it (``ring2d`` / ``rmm`` / ``broadcast`` /
    ``gspmd``), ``moved_bytes`` (what the plan sends the chip that receives
    most: the blocks it needs and lacks, and its share of a sum over k; -1
    for ``gspmd``, whose schedule is the compiler's) and the jitted program.
    ``dispatched`` is set by ``DenseMatrix.multiply`` once it has run the
    plan: the first dispatch of a cached plan is where its program is traced,
    lowered and compiled or loaded (the ``matmul.first_dispatch`` span)."""

    strategy: str
    split: tuple[int, int, int] | None
    program: str
    moved_bytes: int
    fn: Callable
    dispatched: bool = False


def _layout(x, mesh: Mesh):
    """``(sharding, shape)`` as ``x`` arrives. A traced operand shows no
    sharding: it is taken as row-sharded over the caller's mesh (its rows
    padded to divide it), which is what the program it gets then asks for by
    its own sharding constraint."""
    sh = getattr(x, "sharding", None)
    if sh is not None:
        return sh, tuple(x.shape)
    rows = mesh.axis_names[0]
    return (NamedSharding(mesh, P(rows, None)),
            (pad_to_multiple(x.shape[0], mesh.shape[rows]), x.shape[1]))


def _held_rows(have, mesh: Mesh) -> bool:
    """``P(rows, None)`` over the caller's mesh: what a ``DenseVecMatrix`` holds."""
    return have[0].is_equivalent_to(
        NamedSharding(mesh, P(mesh.axis_names[0], None)), 2)


def _lacking_bytes(have, want: NamedSharding, want_shape, itemsize) -> int:
    """Bytes of its block under ``want`` that the worst-off device does not
    hold of an operand lying as ``have`` (index rectangles intersected)."""
    have, shape = have
    haves = have.devices_indices_map(shape)
    worst = 0
    nothing = (slice(0, 0),) * len(shape)  # a device the operand is not on
    for dev, w in want.devices_indices_map(tuple(want_shape)).items():
        h = haves.get(dev, nothing)
        block = held = 1
        for d, size in enumerate(want_shape):
            lo, hi, _ = w[d].indices(size)
            hlo, hhi, _ = h[d].indices(shape[d])
            block *= hi - lo
            held *= max(0, min(hi, hhi) - max(lo, hlo))
        worst = max(worst, block - held)
    return worst * itemsize


@functools.lru_cache(maxsize=128)
def _plan(strategy, split, mkn, out_pad, out_sharding, precision, accum_dtype,
          itemsize, a_have, b_have) -> FusedPlan | None:
    m, k, n = mkn
    mesh = out_sharding.mesh
    devs = list(mesh.devices.flat)
    mesh3, program, which = None, strategy, "b"
    if strategy == "rmm":
        if split is None:
            split = split_method(m, k, n, len(devs))
        pm, pk, pn = split
        if pm * pk * pn != len(devs):
            return None  # subset mesh — not expressible in one executable
        mp_r, kp_r, np_r = (
            pad_to_multiple(m, pm), pad_to_multiple(k, pk), pad_to_multiple(n, pn)
        )
        if (
            len(devs) > 1
            and len(mesh.axis_names) == 2
            and split == (mesh.devices.shape[0], 1, mesh.devices.shape[1])
            and _held_rows(a_have, mesh)
            and _held_rows(b_have, mesh)
        ):
            program = "ring2d"
            moved = (pm - 1) * (pad_to_multiple(k, pm) // pm) * (np_r // pn) * itemsize
        else:
            mesh3 = build_rmm_mesh(split, devs)
            moved = (
                _lacking_bytes(a_have, NamedSharding(mesh3, P(_M, _K)),
                               (mp_r, kp_r), itemsize)
                + _lacking_bytes(b_have, NamedSharding(mesh3, P(_K, _N)),
                                 (kp_r, np_r), itemsize)
                # the least a sum over k sends: all but its own share of the
                # chip's partial block
                + (mp_r // pm) * (np_r // pn) * (pk - 1) // pk
                * jnp.dtype(accum_dtype).itemsize
            )
    elif strategy in ("broadcast", "broadcast_a"):
        program, which = "broadcast", "a" if strategy == "broadcast_a" else "b"
        have = a_have if which == "a" else b_have
        moved = _lacking_bytes(have, NamedSharding(mesh, P()), have[1], itemsize)
    else:
        moved = -1
    fn = _fused_fn(program, mkn, out_pad, out_sharding, precision, accum_dtype,
                   mesh3, which)
    return FusedPlan(strategy, split, program, moved, fn)


def plan_padded(
    a_pad: jax.Array,
    b_pad: jax.Array,
    mkn: tuple[int, int, int],
    out_sharding: NamedSharding,
    out_pad: tuple[int, int],
    strategy: str = "auto",
    split: tuple[int, int, int] | None = None,
    broadcast_threshold_mb: float | None = None,
    precision: str | None = None,
    accum_dtype=None,
) -> FusedPlan | None:
    """Choose the fused program of one padded-in / padded-out multiply from
    what can be seen of it: the shapes, the caller's mesh and the layouts the
    two operands arrive in (see the module docstring).

    Returns ``None`` when the requested configuration has no fused program
    (an RMM split that doesn't fill the mesh — one XLA executable cannot span
    two different device sets — or the ring strategy, which manages its own
    placement); callers fall back to the legacy logical-array path."""
    strategy = _resolve_strategy(
        mkn, jnp.dtype(b_pad.dtype).itemsize, strategy, broadcast_threshold_mb
    )
    if strategy == "ring":
        return None
    mesh = out_sharding.mesh
    return _plan(
        strategy,
        None if split is None else tuple(split),
        tuple(mkn),
        tuple(out_pad),
        out_sharding,
        _resolve_precision(precision),
        accum_dtype or a_pad.dtype,
        jnp.dtype(b_pad.dtype).itemsize,
        _layout(a_pad, mesh),
        _layout(b_pad, mesh),
    )


def matmul_padded(
    a_pad: jax.Array,
    b_pad: jax.Array,
    mkn: tuple[int, int, int],
    out_sharding: NamedSharding,
    out_pad: tuple[int, int],
    strategy: str = "auto",
    split: tuple[int, int, int] | None = None,
    broadcast_threshold_mb: float | None = None,
    precision: str | None = None,
    accum_dtype=None,
) -> jax.Array | None:
    """Padded-in / padded-out multiply in ONE dispatch (see :func:`_fused_fn`).

    ``a_pad``/``b_pad`` carry their matrices' zero-padded layouts; ``mkn`` is
    the logical (m, k, n). Returns the result already padded to ``out_pad`` and
    sharded as ``out_sharding`` — the caller can construct the result matrix
    around it directly, with no further placement — or ``None`` where
    :func:`plan_padded` has no fused program."""
    plan = plan_padded(a_pad, b_pad, mkn, out_sharding, out_pad, strategy, split,
                       broadcast_threshold_mb, precision, accum_dtype)
    return None if plan is None else plan.fn(a_pad, b_pad)


def matmul(
    a: jax.Array,
    b: jax.Array,
    out_sharding: NamedSharding | None = None,
    strategy: str = "auto",
    split: tuple[int, int, int] | None = None,
    broadcast_threshold_mb: float | None = None,
    precision: str | None = None,
    accum_dtype=None,
) -> jax.Array:
    """Adaptive distributed matmul — the dispatch logic of
    ``DenseVecMatrix.multiply(other, cores, broadcastThreshold)``
    (DenseVecMatrix.scala:196-231): broadcast when one operand is small,
    otherwise CARMA-split RMM over the mesh.
    """
    if out_sharding is None:
        mesh = default_mesh()
        out_sharding = NamedSharding(mesh, P(mesh.axis_names[0], mesh.axis_names[1]))

    strategy = _resolve_strategy(
        (a.shape[0], a.shape[1], b.shape[1]),
        jnp.dtype(b.dtype).itemsize,
        strategy,
        broadcast_threshold_mb,
    )

    if strategy == "broadcast":
        return broadcast_matmul(a, b, out_sharding, "b", precision, accum_dtype)
    if strategy == "broadcast_a":
        return broadcast_matmul(a, b, out_sharding, "a", precision, accum_dtype)
    if strategy == "rmm":
        # the caller re-places the logical result onto its own sharding
        return rmm_matmul(
            a, b, split, list(out_sharding.mesh.devices.flat), precision, accum_dtype
        )
    if strategy == "gspmd":
        return gspmd_matmul(a, b, out_sharding, precision, accum_dtype)
    if strategy == "ring":
        return ring_matmul(
            a, b, out_sharding.mesh, out_sharding.mesh.axis_names[0],
            precision, accum_dtype,
        )
    raise UnknownStrategyError(f"unknown matmul strategy: {strategy}")
