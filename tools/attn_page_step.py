"""What one live page costs the (K, V) decode-attention kernel, alone on the chip.

    chiprun --chips 1 -- python3 tools/attn_page_step.py [--f32]

At the three calls the spec cells make (Falcon-H1's global call, Laguna's
global and window calls) it times ``paged_decode_attention`` over the SAME
random pages held both ways: ``(num_pages, page_len, kv_heads, head_dim)``
(the dense model's layout: every head in one einsum batched over the block's
middle axis) and ``(num_pages, page_len, kv_heads * head_dim)`` (a spec
model's: a head's keys a lane slice, contracted head by head). Two calls a
layout, one at the cell's lengths and one with every row at length 1, give
the two unknowns: microseconds a LIVE grid step and a DEAD one. Prints one
JSON line a shape and ends with ``{"ok": true, "device": ...}``; needs a TPU
(a time from the CPU's interpreter says nothing). No engine, no model."""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

import numpy as np

# (rows, table width, kv heads, group, pages of the slab, live pages a call,
# window): the cells' decode calls as PERF.md section 5 reads them
SHAPES = {
    "falconh1.global": (64, 20, 4, 5, 641, 250, False),
    "laguna.global": (32, 32, 8, 6, 769, 190, False),
    "laguna.window": (32, 4, 8, 9, 145, 85, True),
}
PAGE_LEN, HEAD_DIM = 256, 128


def _lengths(rng, rows: int, width: int, live_pages: int,
             window: bool) -> np.ndarray:
    """Row lengths (in tokens) whose pages sum to about ``live_pages``: a
    long tail like the cells' (most rows a page or two, a few nearly the
    table), each row at least one token. Under a window a row's length is
    its position in a long context (the ring holds the last 512 of it)."""
    if window:
        return np.clip(rng.lognormal(np.log(1500.0), 1.0, rows), 1,
                       8000).astype(np.int32)
    raw = rng.lognormal(0.0, 1.0, rows)
    pages = np.clip(np.round(raw * live_pages / raw.sum()), 1, width)
    return (pages * PAGE_LEN - rng.integers(0, PAGE_LEN, rows)).astype(
        np.int32)


def _time_call(fn, args, calls: int = 30, repeats: int = 5) -> float:
    """Seconds a call: the least mean over ``repeats`` trains of ``calls``
    back-to-back dispatches, each train ended by ``block_until_ready``."""
    fn(*args).block_until_ready()
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        for _ in range(calls):
            out = fn(*args)
        out.block_until_ready()
        best = min(best, (time.perf_counter() - t0) / calls)
    return best


def measure(name: str, dtype, seed: int = 0) -> dict:
    import jax
    import jax.numpy as jnp

    from marlin_tpu.ops.paged_attention import paged_decode_attention

    rows, width, kvh, group, pages, live_pages, window = SHAPES[name]
    rng = np.random.default_rng(seed)
    key = jax.random.key(seed)
    kq, kk, kv = jax.random.split(key, 3)
    slab4 = (pages, PAGE_LEN, kvh, HEAD_DIM)
    k4 = jax.random.normal(kk, slab4, jnp.float32).astype(dtype)
    v4 = jax.random.normal(kv, slab4, jnp.float32).astype(dtype)
    q = jax.random.normal(kq, (rows, kvh, group, HEAD_DIM),
                          jnp.float32).astype(dtype)
    tables = jnp.asarray(rng.integers(1, pages, (rows, width)), jnp.int32)
    lengths = _lengths(rng, rows, width, live_pages, window)
    out = {"shape": name, "dtype": str(jnp.dtype(dtype)), "rows": rows,
           "table_width": width, "kv_heads": kvh, "group": group,
           "page_bytes": 2 * PAGE_LEN * kvh * HEAD_DIM
           * jnp.dtype(dtype).itemsize}
    results = {}
    for layout, (k, v) in {
            "tkd": (k4, v4),
            "t_kd": (k4.reshape(pages, PAGE_LEN, -1),
                     v4.reshape(pages, PAGE_LEN, -1))}.items():
        times = {}
        for case, lens in (("cell", lengths),
                           ("ones", np.ones((rows,), np.int32))):
            lens = jnp.asarray(lens)
            if window:
                # the ring's first page and lowest visible position, as the
                # decode program derives them from a row's position
                lower = jnp.maximum(lens - 512, 0)
                extra = dict(first_page=lower // PAGE_LEN, lower=lower)
                live = int(jnp.sum((lens - 1) // PAGE_LEN
                                   - lower // PAGE_LEN + 1))
            else:
                extra = {}
                live = int(jnp.sum((lens - 1) // PAGE_LEN + 1))
            fn = jax.jit(lambda q, k, v, t, n, extra=extra:
                         paged_decode_attention(q, k, v, t, n, **extra))
            try:
                times[case] = (_time_call(fn, (q, k, v, tables, lens)), live)
            except jax.errors.JaxRuntimeError as e:  # e.g. scoped VMEM
                out[layout] = {"error": str(e).split(". ")[0][:300]}
                break
            if case == "cell":
                results[layout] = np.asarray(
                    fn(q, k, v, tables, lens).astype(jnp.float32))
        if layout in out:
            continue
        (t1, l1), (t0, l0) = times["cell"], times["ones"]
        steps = rows * width
        # t = live * a + (steps - live) * d at both points
        a, d = np.linalg.solve([[l1, steps - l1], [l0, steps - l0]], [t1, t0])
        out[layout] = {"call_us": t1 * 1e6, "live_pages": l1,
                       "call_us_all_rows_length_1": t0 * 1e6,
                       "live_step_us": a * 1e6, "dead_step_us": d * 1e6}
    out["bytes_us_a_page"] = out["page_bytes"] / 819e9 * 1e6
    if len(results) == 2:
        out["max_abs_diff_between_layouts"] = float(
            np.abs(results["tkd"] - results["t_kd"]).max())
        out["live_step_ratio"] = (out["tkd"]["live_step_us"]
                                  / out["t_kd"]["live_step_us"])
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--f32", action="store_true",
                    help="float32 pages and queries (the f32 checks' blocks)")
    ap.add_argument("--shapes", nargs="*", default=list(SHAPES))
    args = ap.parse_args(argv)
    import jax
    import jax.numpy as jnp

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(json.dumps({"ok": False, "error": "not a TPU",
                          "device": dev.platform}))
        return 1
    for name in args.shapes:
        print(json.dumps(measure(
            name, jnp.float32 if args.f32 else jnp.bfloat16)), flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": jax.device_count()}}))
    return 0


if __name__ == "__main__":
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))
    sys.exit(main())
