"""Headline benchmark: dense N×N distributed matmul GFLOP/s on TPU vs the
CPU-BLAS baseline (the reference's netlib-java dgemm analog — BASELINE.md
configs; north star = dense multiply beating the CPU baseline on GFLOP/s).

Needs a TPU: measures in this process and prints ONE JSON line
  {"metric": ..., "value": N, "unit": "GFLOP/s", "vs_baseline": N,
   "device": {"platform": "tpu", "kind": ..., "count": N}}
or, when JAX finds no TPU, prints no result and exits non-zero.
Extra detail goes to stderr.

Timing notes: device dispatch is async, so the measurement enqueues REPS
multiplies back-to-back and forces completion once with a scalar fetch — the
same discipline MTUtils.evaluate exists for in the reference
(MTUtils.scala:218-220).
"""

import json
import os
import sys
import time

import numpy as np

# BASELINE's north star names the 20000×20000 multiply (config 3); config 2
# (4000) is available via MARLIN_BENCH_N=4000.
N = int(os.environ.get("MARLIN_BENCH_N", "20000"))
REPS = int(os.environ.get("MARLIN_BENCH_REPS", "5" if N >= 10000 else "30"))
PRECISION = os.environ.get("MARLIN_BENCH_PRECISION", "high")  # f32-class accuracy


def log(*args):
    print(*args, file=sys.stderr, flush=True)


def cpu_baseline_gflops() -> float:
    """NumPy (OpenBLAS) float64 GEMM — the netlib-java-BLAS-on-CPU baseline the
    reference's README compares against (README.md:29)."""
    n = min(N, 2000)  # keep the CPU run bounded; GFLOP/s is ~size-invariant here
    a = np.random.default_rng(0).random((n, n))
    b = np.random.default_rng(1).random((n, n))
    a @ b  # warm-up
    t0 = time.perf_counter()
    a @ b
    dt = time.perf_counter() - t0
    return 2 * n**3 / dt / 1e9


def tpu_gflops() -> float:
    import jax
    import jax.numpy as jnp

    import marlin_tpu as mt

    log(f"devices: {jax.devices()}")
    mesh = mt.create_mesh()
    a = mt.DenseVecMatrix.random(0, N, N, mesh=mesh)
    b = mt.DenseVecMatrix.random(1, N, N, mesh=mesh)
    float(jnp.sum(a.data) + jnp.sum(b.data))  # materialize inputs

    c = a.multiply(b, precision=PRECISION)  # compile
    float(jnp.sum(c.data))
    # correctness anchor on a row slice: f64 numpy for small N; for large N
    # (3.2 GB of operands at 20000) compare against an independent on-device
    # f32-highest contraction instead of fetching the operands
    rows = np.asarray(c.data[:8]).astype(np.float64)[:, :N]
    if N <= 4096:
        ref = a.to_numpy()[:8].astype(np.float64) @ b.to_numpy().astype(np.float64)
        anchor = "f64 numpy"
    else:
        ref = np.asarray(
            jnp.dot(a.data[:8], b.data, precision="highest")
        ).astype(np.float64)[:, :N]
        anchor = "on-device f32-highest"
    rel_err = np.abs(rows - ref).max() / np.abs(ref).max()
    log(f"matmul rel err vs {anchor} (precision={PRECISION}): {rel_err:.2e}")

    # enqueue REPS multiplies, force completion once with a scalar fetch
    t0 = time.perf_counter()
    for _ in range(REPS):
        c = a.multiply(b, precision=PRECISION)
    float(jnp.sum(c.data))
    dt = (time.perf_counter() - t0) / REPS
    log(f"N={N}: {dt * 1e3:.2f} ms/multiply over {REPS} reps (precision={PRECISION})")
    return 2 * N**3 / dt / 1e9


def main():
    from marlin_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"bench.py needs a TPU; JAX found {dev.platform!r}")
    baseline = cpu_baseline_gflops()
    log(f"CPU f64 BLAS baseline: {baseline:.1f} GFLOP/s")
    value = tpu_gflops()
    print(
        json.dumps(
            {
                "metric": f"dense_matmul_{N}x{N}_gflops",
                "value": round(value, 1),
                "unit": "GFLOP/s",
                "vs_baseline": round(value / baseline, 2),
                "device": {"platform": dev.platform,
                           "kind": dev.device_kind,
                           "count": len(jax.devices())},
            }
        )
    )


if __name__ == "__main__":
    main()
