"""GPipe-style pipeline parallelism over a mesh axis.

The reference scales its one DNN workload by data-parallel row partitioning
only (SURVEY.md §2.7; NeuralNetwork.scala's minibatch sampling) — there is no
pipeline dimension anywhere in it. This module adds the canonical third
parallelism family the TPU way: the model's stages live on successive devices
of a mesh axis, microbatches stream through them, and the activations hop
stage-to-stage over ICI with ``jax.lax.ppermute`` — no parameter server, no
NCCL send/recv loops, one jitted SPMD program.

Design notes (TPU-first):

- **Schedule as a ``lax.scan``**: the pipeline runs ``M + S - 1`` ticks
  (M microbatches, S stages). Each tick every device applies its stage to its
  current activation and passes the result to the next device. ``scan`` (not
  ``fori_loop``) so the whole pipeline is reverse-mode differentiable — the
  backward pass is the mirrored pipeline XLA derives automatically.
- **Static shapes / predication**: bubble ticks (device s idle while
  ``t - s`` is outside ``[0, M)``) compute the stage anyway and mask the
  result with ``jnp.where`` — branch-free SPMD, the standard TPU trade of a
  little wasted MXU work for a single fused program.
- **Per-stage params via sharding, not scatter**: every leaf of
  ``stage_params`` carries a leading ``S`` axis sharded over ``axis``; inside
  ``shard_map`` each device sees exactly its own stage's slice. Placement is
  data placement, the way everything else in this package ships work.
- **Output collection by masked psum**: only the last stage produces real
  outputs; they're scattered into a per-device ``(M, mb, d)`` buffer and one
  ``psum`` at the end both collects and replicates them (every other
  device's buffer is zero).

The activation shape must be invariant across stage boundaries (uniform
residual width — true of the MLP trunk and of transformer blocks); the
first/last stages may widen/narrow internally.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..mesh import ROWS, default_mesh

__all__ = ["pipeline_apply", "stack_stage_params", "split_microbatches"]


def stack_stage_params(per_stage: list, mesh: Mesh | None = None,
                       axis: str = ROWS):
    """Stack a list of per-stage param pytrees along a new leading axis and
    shard that axis over ``axis`` — stage ``s``'s params land on the devices
    of mesh coordinate ``s``. The result is what :func:`pipeline_apply`
    expects as ``stage_params``."""
    mesh = mesh or default_mesh()
    n = mesh.shape[axis]
    if len(per_stage) != n:
        raise ValueError(
            f"{len(per_stage)} stage param sets for a {n}-stage axis {axis!r}")
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *per_stage)
    return jax.tree.map(
        lambda x: jax.device_put(
            x, NamedSharding(mesh, P(axis, *(None,) * (x.ndim - 1)))),
        stacked)


def split_microbatches(x, microbatch: int):
    """(batch, ...) -> (M, microbatch, ...). The batch must divide evenly —
    pipelining resizes no data; pad upstream if needed."""
    b = x.shape[0]
    if microbatch < 1 or b % microbatch:
        raise ValueError(
            f"batch {b} must be a multiple of microbatch {microbatch}")
    return x.reshape(b // microbatch, microbatch, *x.shape[1:])


def pipeline_apply(stage_params, stage_fn, x, mesh: Mesh | None = None,
                   axis: str = ROWS, microbatch: int | None = None):
    """Run ``x`` through ``S = mesh.shape[axis]`` pipeline stages.

    ``stage_params``: pytree whose every leaf has leading axis ``S`` (stage
    ``s``'s slice is that stage's parameters) — see
    :func:`stack_stage_params`. ``stage_fn(params_s, xs) -> ys`` maps one
    stage over one microbatch; ``ys`` must have ``xs``'s shape.

    ``x``: ``(batch, ...)``; ``microbatch`` divides ``batch`` (default: one
    microbatch per stage, the smallest count that fills the pipeline).
    Returns ``stage_{S-1}(... stage_0(x))`` with ``x``'s shape, replicated
    over the mesh. Differentiable end-to-end (scan-based schedule).

    **Requirement on** ``stage_fn``: bubble ticks evaluate it on *all-zero*
    activations (the branch-free schedule computes every tick and masks dead
    results out of the primal), so ``stage_fn`` must produce finite outputs —
    and finite VJPs — on zero-valued inputs. A stage that divides by a norm,
    takes a log, or otherwise blows up at 0 yields inf/NaN whose backward
    products can poison gradients even though the primal is masked (0 · NaN
    is NaN). Guard such ops with an epsilon (the built-in LM blocks' rmsnorm
    uses ``+ 1e-6``).
    """
    mesh = mesh or default_mesh()
    n_stages = mesh.shape[axis]
    if microbatch is None:
        # largest divisor of the batch that still yields >= n_stages
        # microbatches (falls back to 1): a working default for ANY batch,
        # not just multiples of the stage count
        microbatch = max(1, x.shape[0] // n_stages)
        while x.shape[0] % microbatch:
            microbatch -= 1
    xm = split_microbatches(x, microbatch)
    n_micro = xm.shape[0]

    def spec(a):
        return P(axis, *(None,) * (a.ndim - 1))

    pspecs = jax.tree.map(spec, stage_params)

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(pspecs, P(*(None,) * xm.ndim)),
        out_specs=P(*(None,) * xm.ndim),
        # manualize ONLY the pipeline axis: every other mesh axis stays Auto
        # inside, so a stage_fn can itself be tensor-parallel (weights
        # sharded over e.g. "cols") with GSPMD inserting the activation
        # collectives — pp composes with tp on one mesh instead of
        # replicating non-pipeline-sharded params at this boundary
        axis_names={axis},
    )
    def run(params, xin):
        # inside shard_map each leaf's stage axis is length 1: this device's
        # own stage
        p_s = jax.tree.map(lambda a: jnp.squeeze(a, 0), params)
        s = jax.lax.axis_index(axis)
        last = n_stages - 1
        fwd = [(i, i + 1) for i in range(n_stages - 1)]

        def tick(carry, t):
            recv, out_buf = carry
            mb = t - s  # microbatch this stage works on this tick
            live = jnp.logical_and(mb >= 0, mb < n_micro)
            x_t = jax.lax.dynamic_index_in_dim(
                xin, jnp.clip(t, 0, n_micro - 1), 0, keepdims=False)
            act_in = jnp.where(s == 0, x_t, recv)
            y = stage_fn(p_s, act_in)
            # last stage banks its (live) result at position mb
            idx = jnp.clip(mb, 0, n_micro - 1)
            prev = jax.lax.dynamic_index_in_dim(out_buf, idx, 0,
                                                keepdims=False)
            write = jnp.logical_and(live, s == last)
            out_buf = jax.lax.dynamic_update_index_in_dim(
                out_buf, jnp.where(write, y, prev), idx, 0)
            # hop to the next stage (stage 0 receives nothing; its input
            # always comes from xin)
            recv = jax.lax.ppermute(y, axis, fwd) if fwd else y
            return (recv, out_buf), None

        # zeros built from the LOCAL view's shape/dtype — zeros_like of the
        # outer (sharded) xm would smuggle an Auto-mesh sharding into this
        # Manual context, which the TPU lowering rejects
        init = (jnp.zeros(xin.shape[1:], xin.dtype),
                jnp.zeros(xin.shape, xin.dtype))
        # the tick output is device-varying (axis_index / ppermute); the
        # zero init must carry the same varying-manual-axes type
        init = jax.tree.map(
            lambda a: jax.lax.pcast(a, (axis,), to="varying"), init)
        (_, out), _ = jax.lax.scan(
            tick, init, jnp.arange(n_micro + n_stages - 1))
        # every device but the last holds zeros: psum collects AND replicates
        return jax.lax.psum(jnp.where(s == last, out, jnp.zeros_like(out)),
                            axis)

    out = run(stage_params, xm)
    return out.reshape(x.shape)
