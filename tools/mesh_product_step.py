"""One product of the flagship square multiply on a 2x2 mesh, three ways,
alone on the chips.

    chiprun --chips 4 -- python3 tools/mesh_product_step.py [--n 36864]

Two row-sharded operands (``P("rows", None)``: what a ``DenseVecMatrix``
holds and the cell ``matmul.square-mesh4`` multiplies), float32 at
``precision="high"``, made on the device from a seed. Three programs, each
the fused program ``DenseVecMatrix.multiply`` would dispatch:

- ``k_split_2x2x1``: the CARMA split until PR 50 (an explicit
  ``split=(2, 2, 1)`` still runs it): B's half is swapped between the
  mesh's diagonal chips BEFORE the dot and the partial products are
  reduce-scattered AFTER it;
- ``mn_split_gathered``: the (2, 1, 2) split through the same 3-D mesh
  program (``_fused_fn("rmm")``, which no public call reaches any more for
  row-sharded operands): no reduction, XLA fetches the block of B's column
  panel the chip lacks first and multiplies then;
- ``mn_split_ring``: ``strategy="auto"``, the program ``ring2d``: the chip
  multiplies A[r, k_r] by the half of the panel it holds while the other
  half arrives along ``rows``.

One JSON line a form: ``product_ms`` (host clock, the median of
``--products`` products, each ended by ``block_until_ready``) and, from a
device trace of two more, a product's milliseconds on the chip where the
collectives are most exposed: ``dot_ms`` (the matrix-multiply operations
``gemm_roofline_pct`` counts), ``permute_ms`` (every collective operation,
its asynchronous span included, as a union), ``exposed_ms`` (of that, what
ran while no compute operation did: ``collective_exposed_pct``'s numerator),
``other_ms`` (compute that is no dot: slices, adds), the compiler's
``peak_gb`` a chip, the plan's ``moved_bytes``, and the largest difference
of a 256 x 256 corner from the first program's. The first line names the
devices in mesh order with their ``coords``: a panel travels along ``rows``,
between mesh[r][c] and mesh[r +- 1][c].

Ends with ``{"ok": true, "device": ...}``; needs four TPU chips (``--tiny``
runs the control flow at n = 512 on any four devices, e.g.
``XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu``,
and ends non-zero: a time from the CPU says nothing)."""

from __future__ import annotations

import argparse
import json
import pathlib
import shutil
import statistics
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

PRECISION = "high"


def programs(mesh, n):
    """[(name, plan fields, jitted program)] for operands of ``(n, n)``."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from marlin_tpu.parallel.matmul import (_fused_fn, build_rmm_mesh,
                                            plan_padded)

    rows = NamedSharding(mesh, P("rows", None))
    out = NamedSharding(mesh, P("rows", "cols"))
    x = jax.ShapeDtypeStruct((n, n), jnp.float32, sharding=rows)

    def plan(**kw):
        return plan_padded(x, x, (n, n, n), out, (n, n), precision=PRECISION,
                           broadcast_threshold_mb=0, **kw)

    k_split, ring = plan(strategy="rmm", split=(2, 2, 1)), plan()
    if (k_split.program, ring.program) != ("rmm", "ring2d"):
        raise SystemExit(f"unexpected plans: {k_split} {ring}")
    gathered = _fused_fn(
        "rmm", (n, n, n), (n, n), out, PRECISION, jnp.dtype(jnp.float32),
        build_rmm_mesh((2, 1, 2), list(mesh.devices.flat)), "b")
    block = (n // 2) * (n // 2) * 4
    return [
        ("k_split_2x2x1", dict(split="2x2x1", program=k_split.program,
                               moved_bytes=k_split.moved_bytes), k_split.fn),
        ("mn_split_gathered", dict(split="2x1x2", program="rmm",
                                   moved_bytes=block), gathered),
        ("mn_split_ring", dict(split="2x1x2", program=ring.program,
                               moved_bytes=ring.moved_bytes), ring.fn),
    ]


def device_parts(trace_dir, products):
    """A product's milliseconds on the chip whose collectives are most
    exposed, from the profiler's files; ``{}`` where no TPU was traced."""
    from benchmarks import trace_reduce as tr
    from benchmarks.layer_metrics.gemm_roofline_pct import is_gemm

    trace = tr.load(tr.find_xplane(str(trace_dir)))
    if not trace.devices:
        return {}
    lo, hi = tr.window_of(trace)
    worst = max(trace.devices,
                key=lambda d: tr.exposed_collective_seconds(d, lo, hi))

    def ms(intervals):
        return 1e3 * tr.total(tr.union(intervals)) / products

    moving = [(e.start, e.end) for e in worst.ops + worst.async_ops
              if tr.is_collective(e)]
    return {
        "chip": worst.name,
        "dot_ms": ms(tr.op_intervals(worst, is_gemm)),
        "permute_ms": ms(moving),
        "exposed_ms": 1e3 * tr.exposed_collective_seconds(worst, lo, hi)
        / products,
        "other_ms": ms(tr.op_intervals(
            worst, lambda e: not is_gemm(e) and not tr.is_collective(e)
            and e.category not in ("while", "conditional", "call"))),
    }


def measure(mesh, n, products, seed):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    rows = NamedSharding(mesh, P("rows", None))
    make = jax.jit(lambda key: jax.random.uniform(key, (n, n), jnp.float32,
                                                  -1.0, 1.0),
                   out_shardings=rows)
    root = jax.random.key(seed)
    a, b = make(jax.random.fold_in(root, 0)), make(jax.random.fold_in(root, 1))
    jax.block_until_ready((a, b))
    trace_dir = ROOT / ".bench_trace" / "mesh_product_step"
    first = None
    for name, fields, fn in programs(mesh, n):
        run = fn.lower(a, b).compile()
        for _ in range(2):
            jax.block_until_ready(run(a, b))
        took = []
        for _ in range(products):
            t0 = time.perf_counter()
            jax.block_until_ready(run(a, b))
            took.append(time.perf_counter() - t0)
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
        for _ in range(2):
            c = None  # a caller's C = A x B: the old product is dropped
            c = run(a, b)
            jax.block_until_ready(c)
        jax.profiler.stop_trace()
        # the corner off the one shard that holds it (an index into the
        # sharded array would gather the whole product)
        shard = next(sh for sh in c.addressable_shards
                     if all(ix.start in (0, None) for ix in sh.index))
        corner = np.asarray(shard.data[:256, :256])
        first = corner if first is None else first
        product_s = statistics.median(took)
        print(json.dumps({
            "form": name, **fields, "n": n,
            "product_ms": 1e3 * product_s,
            "tflops": 2.0 * n ** 3 / product_s / 1e12,
            **device_parts(trace_dir, 2),
            "peak_gb": run.memory_analysis().peak_memory_in_bytes / 1e9,
            "max_abs_diff_from_k_split": float(np.abs(corner - first).max()),
        }), flush=True)
        c = None
    shutil.rmtree(trace_dir, ignore_errors=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--n", type=int, default=36864)
    ap.add_argument("--products", type=int, default=5)
    ap.add_argument("--seed", type=int, default=50)
    ap.add_argument("--tiny", action="store_true",
                    help="n = 512: the control flow, on any four devices")
    args = ap.parse_args(argv)
    import jax

    import marlin_tpu as mt

    dev = jax.devices()[0]
    on_chip = dev.platform == "tpu"
    if jax.device_count() < 4 or not (on_chip or args.tiny):
        print(json.dumps({"ok": False, "error": "needs four TPU chips",
                          "device": dev.platform,
                          "count": jax.device_count()}))
        return 1
    mesh = mt.create_mesh((2, 2))
    print(json.dumps({"mesh": [[{"id": d.id,
                                 "coords": getattr(d, "coords", None)}
                                for d in row] for row in mesh.devices]}),
          flush=True)
    measure(mesh, 512 if args.tiny else args.n, args.products, args.seed)
    print(json.dumps({"ok": on_chip, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": jax.device_count()}}))
    return 0 if on_chip else 1


if __name__ == "__main__":
    sys.exit(main())
