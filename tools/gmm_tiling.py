"""What the grouped expert matmuls of an expert layer cost at a list of
tilings, alone on the chip.

    chiprun --chips 1 -- python3 tools/gmm_tiling.py [--rows 384] \
        [--experts 32] [--hidden 2048] [--width 1792] [--top-k 4] \
        [--tilings 128,1024,1024 16,1024,896 ...]

The three grouped matmuls of ``moe_experts_ffn`` (gate and up: ``(rows,
hidden) x (experts, hidden, width)``; down: ``(rows, width) x (experts,
width, hidden)``) over ``rows`` assignment rows sorted by expert, the rows an
expert has drawn as a router's would fall (multinomial over the experts:
``rows`` = tokens x picks). Every line times eight layers' worth chained in
ONE program (a call of 100-300 us dispatched from the host reads the host),
at one tiling ``rows,k,n`` (the package's :func:`~marlin_tpu.models.moe
._gmm_tiling` where none is given), beside the time of the touched experts'
bytes at 819 GB/s and of the assignments' flops at 197 TFLOP/s. Prints one
JSON line a tiling and ends with ``{"ok": true, "device": ...}``; needs a TPU.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

import numpy as np

_LAYERS = 8  # layers' worth of the three matmuls in one timed program


def _time(fn, args, calls: int = 4, repeats: int = 5) -> float:
    fn(*args).block_until_ready()
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        for _ in range(calls):
            out = fn(*args)
        out.block_until_ready()
        best = min(best, (time.perf_counter() - t0) / calls)
    return best


def measure(rows, experts, hidden, width, tiling, seed=0) -> dict:
    import jax
    import jax.numpy as jnp

    from marlin_tpu.models import moe

    rng = np.random.default_rng(seed)
    sizes = jnp.asarray(rng.multinomial(rows, np.ones(experts) / experts),
                        jnp.int32)
    ks = jax.random.split(jax.random.key(seed), 4)
    dt = jnp.bfloat16
    xs = jax.random.normal(ks[0], (rows, hidden), jnp.float32).astype(dt)
    gate = (jax.random.normal(ks[1], (experts, hidden, width), jnp.float32)
            * hidden ** -0.5).astype(dt)
    up = (jax.random.normal(ks[2], (experts, hidden, width), jnp.float32)
          * hidden ** -0.5).astype(dt)
    down = (jax.random.normal(ks[3], (experts, width, hidden), jnp.float32)
            * width ** -0.5).astype(dt)

    def tile(k, n):   # a tile no wider than the matrix it cuts
        if tiling is None:
            return moe._gmm_tiling(k, n)
        return (tiling[0], min(tiling[1], k), min(tiling[2], n))

    @jax.jit
    def layers(xs, gate, up, down, sizes):
        def one(_, x):
            a = (jax.nn.silu(moe._grouped_matmul(
                x, gate, sizes, dt, tiling=tile(hidden, width)))
                * moe._grouped_matmul(x, up, sizes, dt,
                                      tiling=tile(hidden, width)))
            y = moe._grouped_matmul(a, down, sizes, jnp.float32,
                                    tiling=tile(width, hidden))
            return (x + 1e-3 * y.astype(dt)).astype(dt)
        return jax.lax.fori_loop(0, _LAYERS, one, xs)

    touched = int(jnp.sum(sizes > 0))
    line = {"rows": rows, "experts": experts, "hidden": hidden,
            "width": width, "touched": touched,
            "rows_an_expert_max": int(jnp.max(sizes)),
            "tiling_gate_up": list(tile(hidden, width)),
            "tiling_down": list(tile(width, hidden)),
            "bytes_us_a_layer": touched * 3 * hidden * width * 2 / 819e9 * 1e6,
            "flops_us_a_layer": rows * 6 * hidden * width / 197e12 * 1e6}
    try:
        t = _time(layers, (xs, gate, up, down, sizes))
        line["us_a_layer"] = t / _LAYERS * 1e6
    except Exception as e:  # e.g. scoped VMEM at a large tile
        line["error"] = str(e).split(". ")[0][:300]
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rows", type=int, nargs="*", default=[384, 2048],
                    help="assignment rows (tokens x picks): a decode call's "
                         "and a prefill chunk's")
    ap.add_argument("--experts", type=int, default=32)
    ap.add_argument("--hidden", type=int, default=2048)
    ap.add_argument("--width", type=int, default=1792)
    ap.add_argument("--tilings", nargs="*", default=[
        "auto", "128,1024,1024", "128,1024,896", "128,2048,1024",
        "128,2048,896", "128,2048,1792", "128,1024,1792", "128,512,1024",
        "256,1024,1024", "64,1024,1024", "16,1024,896"])
    args = ap.parse_args(argv)
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(json.dumps({"ok": False, "error": "not a TPU",
                          "device": dev.platform}))
        return 1
    for rows in args.rows:
        for t in args.tilings:
            tiling = None if t == "auto" else tuple(
                int(v) for v in t.split(","))
            print(json.dumps(measure(rows, args.experts, args.hidden,
                                     args.width, tiling)), flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": jax.device_count()}}))
    return 0


if __name__ == "__main__":
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))
    sys.exit(main())
