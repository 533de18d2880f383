"""Driver of the matrix cells: ``DenseVecMatrix.multiply`` on operands the
benchmark makes on the device from the seed.

Configuration keys read: ``n``, ``dtype``, ``precision``, ``strategy``,
``mesh`` ([rows, cols] of chips), ``entries``, ``check``.
"""

from __future__ import annotations

import time

import numpy as np

from benchmarks import costs
from benchmarks.seeds import seed_key
from benchmarks.reference import matmul as reference

_ENTRIES = {"uniform_pm1": (-1.0, 1.0), "uniform_01": (0.0, 1.0)}


def setup(run, plan) -> dict:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    import marlin_tpu as mt

    cfg = run.config
    n = int(cfg["n"])
    mesh = mt.create_mesh(tuple(cfg["mesh"]), devices=run.devices)
    rows = NamedSharding(mesh, P("rows", None))
    lo, hi = _ENTRIES[cfg["entries"]]
    dtype = jnp.dtype(cfg["dtype"])

    def operand(key):
        return jax.random.uniform(key, (n, n), dtype, lo, hi)

    make = jax.jit(operand, out_shardings=rows)
    root = seed_key(run.seed)
    a = mt.DenseVecMatrix(make(jax.random.fold_in(root, 0)), (n, n), mesh,
                          P("rows", None))
    b = mt.DenseVecMatrix(make(jax.random.fold_in(root, 1)), (n, n), mesh,
                          P("rows", None))
    state = {"a": a, "b": b, "n": n, "c": None}
    jax.block_until_ready((a.data, b.data))
    run.phase("operands")
    for _ in range(2):  # the first compiles (or loads), the second is warm
        state["c"] = None
        state["c"] = multiply(state, cfg["precision"])
        jax.block_until_ready(state["c"].data)
    run.phase("warm_products")
    run.facts.update(n=n, precision=cfg["precision"], chips=run.chips,
                     itemsize=dtype.itemsize)
    return state


def multiply(state, precision: str):
    """The timed path: one call of the program's entry point."""
    return state["a"].multiply(state["b"], strategy="auto",
                               precision=precision)


def measure(run, state, plan, seconds: float) -> dict:
    import jax

    precision = run.config["precision"]
    ends = []
    t0 = run.open_window()
    while True:
        state["c"] = None  # a caller's C = A x B: the old product is dropped
        with run.span("product"):
            state["c"] = multiply(state, precision)
        with run.span("block_until_ready"):
            jax.block_until_ready(state["c"].data)
        ends.append(time.perf_counter())
        if ends[-1] - t0 >= seconds:
            break
    t1 = run.close_window()
    run.counters.update(products=len(ends))
    return {"products": len(ends), "window_s": t1 - t0,
            "product_s": np.diff([t0] + ends).tolist()}


def attempted_failed(samples) -> tuple:
    return samples["products"], 0


def end_to_end(run, samples) -> dict:
    n = run.facts["n"]
    done = samples["products"] * costs.matmul_flops(n, n, n)
    return {"tflops": done / samples["window_s"] / 1e12}


def _sample_error(run, state, c) -> float:
    import jax.numpy as jnp

    n = state["n"]
    rows, cols = reference.sample_indices(run.seed, n, n,
                                          run.config["check"]["sample"])
    a_rows = np.asarray(jnp.take(state["a"].data, jnp.asarray(rows), axis=0))
    b_cols = np.asarray(jnp.take(state["b"].data, jnp.asarray(cols), axis=1))
    got = np.asarray(jnp.take(jnp.take(c.data, jnp.asarray(rows), axis=0),
                              jnp.asarray(cols), axis=1))
    return reference.rel_err(got, reference.product_sample(a_rows, b_cols))


def verify(run, state, plan, samples) -> list:
    """The window's last product, on a seeded sample of its entries, against
    host float64 of the same rows of A and columns of B."""
    import jax

    limit = run.config["check"]["limits"]["rel_err_vs_float64"]
    c = state["c"]
    shape_ok = c is not None and tuple(c.shape) == (state["n"], state["n"])
    err = _sample_error(run, state, c) if shape_ok else float("inf")
    out = [{"name": "rel_err_vs_float64", "value": err, "limit": limit,
            "ok": bool(err < limit)}]
    if run.control:
        # the program's own lower-precision path: one bf16 pass
        state["c"] = c = None
        low = multiply(state, "default")
        jax.block_until_ready(low.data)
        out.append({"name": "control_rel_err_vs_float64",
                    "value": _sample_error(run, state, low), "limit": limit,
                    "ok": True})
    return out
