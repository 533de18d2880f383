"""AOT compile-only evidence channel: compile for TPU without a chip.

The build/test environment has the TPU *compiler* (libtpu) even when no chip
is reachable, so every TPU claim that is really a claim about what Mosaic/XLA
accepts and schedules can be proven ahead of time:

- Pallas kernels (flash attention fwd/bwd, the BSR manual-DMA kernel) are
  lowered by the real Mosaic compiler — interpret-mode correctness on the CPU
  mesh says nothing about whether Mosaic accepts scalar-prefetch grids,
  ``pl.ANY`` HBM refs or manual ``make_async_copy`` double-buffering; this
  does.
- ``Compiled.memory_analysis()`` of a TPU lowering gives the compiler's HBM
  accounting (argument/output/temp/generated-code bytes) for long-context
  configurations that cannot run on the CPU mesh at all — the predicted-HBM
  column of docs/parallelism.md's budget table.

No reference analog: the reference compiles JVM bytecode and finds out about
memory at runtime (SURVEY.md §5.7 is the rebuild's long-context story).

Usage is deliberately plain ``jax.jit(...).trace(...).lower().compile()`` —
this module only supplies the topology plumbing, so the artifact proven is
the same jitted program the runtime path executes.
"""

from __future__ import annotations

import functools
import os

import jax
import numpy as np
from jax.sharding import Mesh

__all__ = ["tpu_topology", "topology_mesh", "trace_lm_train_step",
           "parse_hbm_oom"]


@functools.lru_cache(maxsize=None)
def tpu_topology(topology_name: str = "v5e:2x2"):
    """A compile-only TPU topology (never touches hardware).

    Requires libtpu (the compiler) to be importable; raises RuntimeError with
    the underlying cause otherwise. Loading libtpu is exclusive to one
    process at a time, so never call this while a module is being imported
    (tests/test_aot_tpu.py describes the topology inside a fixture).

    The probe runs with ``TPU_SKIP_MDS_QUERY=1`` (restored afterwards unless
    the caller already set it): a compile-only topology needs no instance
    metadata, and on hosts without a TPU runtime libtpu's PJRT plugin init
    otherwise blocks the process — GIL held — retrying GCP metadata fetches
    (30 tries per variable), which hangs any caller."""
    from jax.experimental import topologies

    had = "TPU_SKIP_MDS_QUERY" in os.environ
    os.environ.setdefault("TPU_SKIP_MDS_QUERY", "1")
    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name=topology_name)
    except Exception as e:  # pragma: no cover - env without libtpu
        raise RuntimeError(
            f"compile-only TPU topology {topology_name!r} unavailable: {e}"
        ) from e
    finally:
        if not had:
            os.environ.pop("TPU_SKIP_MDS_QUERY", None)


def topology_mesh(axis_names: tuple[str, ...], shape: tuple[int, ...],
                  topology_name: str = "v5e:2x2") -> Mesh:
    """A Mesh over compile-only topology devices, for AOT-compiling the same
    sharded programs the runtime builds over real chips."""
    topo = tpu_topology(topology_name)
    n = int(np.prod(shape))
    devs = np.asarray(topo.devices)
    if n > devs.size:
        raise ValueError(
            f"mesh shape {shape} needs {n} devices; topology "
            f"{topology_name!r} has {devs.size}")
    return Mesh(devs[:n].reshape(shape), axis_names)


def trace_lm_train_step(model, seq: int, mesh):
    """Trace the REAL ``lm_train_step`` for AOT compilation: replicated
    ``ShapeDtypeStruct`` args over ``mesh`` for a ``TransformerLM`` at
    ``seq`` tokens — the one arg-plumbing shared by the context planner,
    ``tools/aot_report.py`` and the compile-only tests (callers ``.lower()
    .compile()`` the result, usually under
    ``config_context(pallas_interpret=False)``)."""
    import jax.numpy as jnp
    import optax
    from jax.sharding import NamedSharding, PartitionSpec

    from ..mesh import ROWS
    from ..models.transformer import lm_train_step

    rep = NamedSharding(mesh, PartitionSpec())
    # expert tensors carry the RUNTIME placement (shard_moe_params shards
    # their leading expert axis over rows) — replicating them here would
    # overstate per-chip expert + Adam memory by the axis size, making the
    # planner's multi-chip MoE evidence diverge from the program that runs
    exp = NamedSharding(mesh, PartitionSpec(ROWS, None, None))
    rows = mesh.shape.get(ROWS, 1)

    def leaf_sharding(path, x):
        in_moe = any(getattr(k, "key", None) == "moe" for k in path)
        if (in_moe and jnp.ndim(x) == 3
                and jnp.shape(x)[0] % max(rows, 1) == 0):
            return exp
        return rep

    def sds(tree):
        return jax.tree_util.tree_map_with_path(
            lambda p, x: jax.ShapeDtypeStruct(jnp.shape(x), x.dtype,
                                              sharding=leaf_sharding(p, x)),
            tree)

    params = jax.eval_shape(model.init_params)
    opt_state = jax.eval_shape(optax.adam(model.learning_rate).init, params)
    tokens = jax.ShapeDtypeStruct((seq,), jnp.int32, sharding=rep)
    return lm_train_step.trace(
        sds(params), sds(opt_state), tokens, mesh, model.heads, model.attn,
        model.remat, model.precision, model.learning_rate, model.loss_chunk,
        model.compute_dtype, model.mlp_chunk, model.offload_residuals,
        model._moe(), model.moe_aux_weight)


def parse_hbm_oom(exc) -> int | None:
    """Bytes the TPU compiler says it needed, parsed from an over-HBM
    rejection ("Ran out of memory in hbm ... Used X of Y hbm") — None when
    the exception is not that rejection. An OOM'd compile is a *result* (the
    compiler locating the cliff), which is why both the planner and
    aot_report record it instead of crashing."""
    import re

    m = re.search(r"Used ([0-9.]+)([GMK]) of [0-9.]+[GMK] hbm", str(exc))
    if not m:
        return None
    mult = {"K": 1024, "M": 1024 ** 2, "G": 1024 ** 3}[m.group(2)]
    return int(float(m.group(1)) * mult)
