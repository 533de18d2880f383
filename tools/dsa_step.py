"""What the token selection of a DeepSeek-V3.2 layer costs, part by part, alone
on the chip.

    chiprun --chips 1 -- python3 tools/dsa_step.py [--tiny] [--aot]

The ``serve.deepseekv32-longctx32`` cell's shapes, ONE layer (a call runs
five): a prefill chunk of ``T`` queries against ``L`` keys (64 index heads of
128, ``index_topk`` 2048, cache entries 640 wide), and a decode call of 32
rows at ~66 k. One JSON line a part, milliseconds a call (the least of a few
timed runs of several calls; every part takes a millisecond or more, so the
host's dispatch is small beside it):

- ``index.chunk``: :func:`marlin_tpu.ops.dsa.index_scores_chunk` (the MXU
  kernel), with ``least_ms`` (the causal pairs' 16,384 operations over the
  peak) at three chunk starts;
- ``index.paged``: :func:`~marlin_tpu.ops.dsa.index_scores_paged` (the walk of
  a row's index-key pages), ``least_ms`` its bytes over the HBM peak;
- ``select.mask``: :func:`~marlin_tpu.ops.dsa.selection_mask` for the chunk's
  T rows at once, for one tile of ``--tile`` of them (what the prefill
  program runs, a tile at a time) and for the 32 decode rows;
  ``select.top_k``: ``lax.top_k`` of the same scores (what it replaces);
- ``select.kernel``: :func:`~marlin_tpu.ops.dsa.select_tokens` as the ONE
  kernel the programs run (PR 57: the k-th score, the mask and the list with
  a group of 8 rows' scores resident in VMEM), milliseconds a TILE of
  ``--tile`` queries (the chunk's T rows in one call, a grid step a group,
  over its tiles: a call of one tile is shorter than the host's dispatch)
  and a decode call's 32 rows (eight calls' rows in one), with
  ``equals_top_k`` (every row's list ``lax.top_k``'s set) and
  ``equals_xla`` (the first tile's lists XLA's form's, to the bit): to be
  read beside ``select.mask`` + ``select.compact`` of a tile, and beside
  ``select.xla``: XLA's form in a loop over the chunk's tiles, as a program
  runs it (XLA keeps a tile's scores in VMEM there, which a call of
  ``select.mask`` alone cannot show);
- ``select.compact``, ``attend.gather``, ``attend.list``: one tile of
  ``--tile`` queries: the list from the mask, XLA's gather of its entries
  (``attend.gather.u32``: the same out of a 32-bit view of the context, its
  conversion included), the absorbed attention over them.

``--aot`` compiles everything for a described v5e and runs nothing (no chip
needed; ``JAX_PLATFORMS=cpu``). ``--tiny`` runs the control flow on a CPU at
a toy size and ends non-zero. No engine, no model."""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

import numpy as np

CELL = dict(T=1024, L=67584, J=64, D=128, k=2048, E=640, H=128, B=32,
            page_len=256, value_dim=512)
TINY = dict(T=32, L=512, J=4, D=16, k=128, E=128, H=4, B=8, page_len=16,
            value_dim=64)
PEAK_FLOPS, PEAK_BYTES = 197e12, 819e9


def _time_call(fn, args, calls: int = 3, repeats: int = 3) -> float:
    import jax

    jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        for _ in range(calls):
            out = fn(*args)
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - t0) / calls)
    return best * 1e3


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--aot", action="store_true")
    ap.add_argument("--tile", type=int, default=32)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))
    import jax
    import jax.numpy as jnp

    from marlin_tpu.ops import dsa

    c = TINY if args.tiny else CELL
    T, L, J, D, k, E, H, B = (c[n] for n in "T L J D k E H B".split())
    page_len, tile = c["page_len"], min(args.tile, c["T"])
    cd = jnp.float32 if args.tiny else jnp.bfloat16
    interpret = bool(args.tiny)
    W = L // page_len
    shapes = {
        "qi": ((T, J, D), cd), "w": ((T, J), jnp.float32),
        "keys": ((L, D), cd), "scores": ((T, L), jnp.float32),
        "ctx": ((L, E), cd), "q": ((tile, H, E), cd),
        "slab": ((B * W + 1, page_len, D), cd)}

    def chunk(qi, w, keys, start):
        return dsa.index_scores_chunk(qi, w, keys, start,
                                      interpret=interpret)

    def paged(qi, w, slab, tables, lengths):
        return dsa.index_scores_paged(qi, w, slab, tables, lengths,
                                      interpret=interpret)

    def mask(scores, n_valid):
        return dsa.selection_mask(scores, n_valid, k)

    def top_k(scores):
        return jax.lax.top_k(scores, k)

    def compact(m, count):
        return dsa.compact(m, count, k)

    def select(scores, n_valid):
        assert dsa.select_kernel_supported(*scores.shape, k)
        return dsa.select_tokens(scores, n_valid, k, interpret=interpret)

    def attend(q, entries, count):
        return dsa.attend_list(q, entries, count, c["value_dim"])

    if args.aot:
        import os
        os.environ.setdefault("TPU_LOG_DIR", "disabled")
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding

        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
        one = SingleDeviceSharding(topo.devices[0])

        def sds(shape, dt):
            return jax.ShapeDtypeStruct(shape, dt, sharding=one)

        s = {n: sds(*v) for n, v in shapes.items()}
        i32 = jnp.int32
        for name, fn, a in (
                ("index.chunk", chunk, (s["qi"], s["w"], s["keys"],
                                        sds((), i32))),
                ("index.paged", paged, (sds((B, J, D), cd),
                                        sds((B, J), jnp.float32), s["slab"],
                                        sds((B, W), i32), sds((B,), i32))),
                ("select.mask", mask, (s["scores"], sds((T,), i32))),
                ("select.mask.decode", mask, (sds((B, L), jnp.float32),
                                              sds((B,), i32))),
                ("select.compact", compact, (sds((tile, L), jnp.bool_),
                                             sds((tile,), i32))),
                ("select.kernel", select, (sds((tile, L), jnp.float32),
                                           sds((tile,), i32))),
                ("select.kernel.decode", select, (sds((B, L), jnp.float32),
                                                  sds((B,), i32))),
                ("attend.gather", dsa.gather_entries,
                 (s["ctx"], sds((tile, k), i32))),
                ("attend.list", attend, (s["q"], sds((tile, k, E), cd),
                                         sds((tile,), i32)))):
            t0 = time.perf_counter()
            m = jax.jit(fn).lower(*a).compile().memory_analysis()
            print(json.dumps({
                "part": name, "compiled": True,
                "temp_bytes": int(m.temp_size_in_bytes),
                "seconds": round(time.perf_counter() - t0, 1)}), flush=True)
        return 0

    rng = np.random.default_rng(0)

    def draw(name):
        shape, dt = shapes[name]
        return jnp.asarray(rng.standard_normal(shape, np.float32), dt)

    qi, w, keys, ctx, q, slab = (draw(n) for n in
                                 ("qi", "w", "keys", "ctx", "q", "slab"))
    jchunk = jax.jit(chunk)
    for start in (0, (L - T) // 2 // T * T, L - T):
        ms = _time_call(jchunk, (qi, w, keys, jnp.int32(start)))
        pairs = T * start + T * (T + 1) // 2
        print(json.dumps({"part": "index.chunk", "start": start, "ms": ms,
                          "least_ms": pairs * 2 * J * D / PEAK_FLOPS * 1e3}),
              flush=True)
    start = L - T
    scores = jchunk(qi, w, keys, jnp.int32(start))
    ref = dsa.index_scores(qi[:8], w[:8], keys, start + jnp.arange(8))
    fin = np.isfinite(np.asarray(ref))
    print(json.dumps({"part": "index.chunk", "max_abs_diff_from_einsum": float(
        np.abs(np.asarray(scores[:8])[fin] - np.asarray(ref)[fin]).max())}),
        flush=True)
    tables = jnp.asarray(1 + rng.permutation(B * W).reshape(B, W), jnp.int32)
    lengths = jnp.asarray(rng.integers(L - 2 * T, L, B), jnp.int32)
    jpaged = jax.jit(paged)
    ms = _time_call(jpaged, (qi[:B], w[:B], slab, tables, lengths))
    print(json.dumps({
        "part": "index.paged", "ms": ms, "least_ms": float(
            np.asarray(lengths).sum()) * D * slab.dtype.itemsize
        / PEAK_BYTES * 1e3}), flush=True)
    dscores = jpaged(qi[:B], w[:B], slab, tables, lengths)
    n_valid = start + 1 + jnp.arange(T, dtype=jnp.int32)
    jmask = jax.jit(mask)
    for name, sc, nv in (("chunk", scores, n_valid),
                         ("tile", scores[:tile], n_valid[:tile]),
                         ("decode", dscores, lengths)):
        print(json.dumps({"part": "select.mask", "rows": name,
                          "ms": _time_call(jmask, (sc, nv))}), flush=True)
        print(json.dumps({"part": "select.top_k", "rows": name,
                          "ms": _time_call(jax.jit(top_k), (sc,), calls=1,
                                           repeats=2)}), flush=True)
    m, count = jmask(scores, n_valid)
    jcompact = jax.jit(compact)
    idx = jcompact(m[:tile], count[:tile])

    def sets_equal(lists, counts, sc):
        _, want = jax.lax.top_k(sc, k)
        return all(sorted(a[:c]) == sorted(b[:c]) and not any(a[c:])
                   for a, b, c in zip(np.asarray(lists).tolist(),
                                      np.asarray(want).tolist(),
                                      np.asarray(counts).tolist()))

    print(json.dumps({"part": "select.compact", "tile": tile,
                      "ms": _time_call(jcompact, (m[:tile], count[:tile])),
                      "equals_top_k": sets_equal(idx, count[:tile],
                                                 scores[:tile])}), flush=True)
    # the kernel: one call over the chunk's rows is T / tile tiles of the
    # program's lax.map, a grid step a group of 8 rows either way
    jselect = jax.jit(select)
    got, gcount = jselect(scores, n_valid)
    print(json.dumps({
        "part": "select.kernel", "rows": "tile", "tile": tile,
        "ms": _time_call(jselect, (scores, n_valid)) / (T // tile),
        "equals_top_k": sets_equal(got, gcount, scores),
        "equals_xla": bool(jnp.array_equal(got[:tile], idx)
                           & jnp.array_equal(gcount, count))}), flush=True)
    # XLA's form the way a PROGRAM runs it: a loop over the chunk's tiles,
    # where XLA keeps a tile's 8.65 MB of scores in VMEM over the sixteen
    # passes (0.33 ms a tile in the parent's prefill program, PERF.md
    # section 6 PR 57); `select.mask` above reads its tile out of HBM
    def in_a_loop(scores, n_valid):
        def one(a):
            m, count = dsa.selection_mask(*a, k)
            return dsa.compact(m, count, k)
        return jax.lax.map(one, (scores.reshape(T // tile, tile, L),
                                 n_valid.reshape(T // tile, tile)))

    jloop = jax.jit(in_a_loop)
    print(json.dumps({
        "part": "select.xla", "rows": "tile, in a loop", "tile": tile,
        "ms": _time_call(jloop, (scores, n_valid)) / (T // tile),
        "equals_kernel": bool(jnp.array_equal(
            jloop(scores, n_valid).reshape(T, k), got))}), flush=True)
    eight = jnp.tile(dscores, (8, 1)), jnp.tile(lengths, 8)
    got, gcount = jselect(*eight)
    print(json.dumps({
        "part": "select.kernel", "rows": "decode",
        "ms": _time_call(jselect, eight) / 8,
        "equals_top_k": sets_equal(got[:B], gcount[:B], dscores)}),
        flush=True)
    jgather = jax.jit(dsa.gather_entries)
    entries = jgather(ctx, idx)
    print(json.dumps({
        "part": "attend.gather", "tile": tile,
        "ms": _time_call(jgather, (ctx, idx)),
        "least_ms": 2 * entries.size * entries.dtype.itemsize / PEAK_BYTES
        * 1e3}), flush=True)
    if not args.tiny:
        # the same rows out of a 32-bit view of the context (two columns a
        # word): what XLA's gather reads where a row is whole sublane words
        def gather32(ctx, idx):
            words = jax.lax.bitcast_convert_type(
                ctx.reshape(L, E // 2, 2), jnp.uint32)
            got = dsa.gather_entries(words, idx)
            return jax.lax.bitcast_convert_type(got, cd).reshape(
                *idx.shape, E)

        j32 = jax.jit(gather32)
        same = bool(jnp.array_equal(j32(ctx, idx), entries))
        print(json.dumps({"part": "attend.gather.u32", "tile": tile,
                          "ms": _time_call(j32, (ctx, idx)),
                          "equals_gather": same}), flush=True)
    print(json.dumps({
        "part": "attend.list", "tile": tile,
        "ms": _time_call(jax.jit(attend), (q, entries, count[:tile])),
        "least_ms": tile * k * H * 2 * (E + c["value_dim"]) / PEAK_FLOPS
        * 1e3}), flush=True)
    dev = jax.devices()[0]
    print(json.dumps({"ok": dev.platform == "tpu",
                      "device": {"platform": dev.platform,
                                 "kind": dev.device_kind}}), flush=True)
    return 0 if dev.platform == "tpu" else 1


if __name__ == "__main__":
    sys.exit(main())
