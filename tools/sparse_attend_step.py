"""What a sparse layer's attention costs a prefill chunk, alone on the chip.

    chiprun --chips 1 -- python3 tools/sparse_attend_step.py [--tq 8 16 32]

The ``serve.minicpm-sala-longdoc16`` cell's chunk: 512 query rows of which
273 hold a token, at positions 33 024.. of a context of 34 816 keys (544
blocks of 64), 2 KV heads x 16 query heads x 128, bfloat16. One sparse layer
(a chunk runs four). Three SELECTIONS of 64 blocks a token (block 0, the 32
that end with its own, 31 of the ~510 between), the same for both KV heads'
shapes but drawn apart:

- ``seeded``: :func:`~marlin_tpu.ops.sparse_attention.select_blocks` over
  seeded queries and keys with the family's QK-norm gain (scores spread by
  3), as a model of random weights chooses: neighbours share little;
- ``alike``: every token the same 31 free blocks: what a tile's union costs
  at its least (a trained model's neighbouring positions choose nearly so);
- ``independent``: every token 31 free blocks of its own, drawn uniformly:
  the worst case the issue sized (a tile of 16 tokens meets ~356 blocks).

A line a selection and form, every time the SLOPE between programs of
``--calls`` (2 and 8) calls chained in one program (each call's positions
read off the previous call's output): what a call more adds, free of the
host's dispatch.

- ``<selection>.tq<n>``: the package's kernel
  (:func:`~marlin_tpu.ops.paged_attention.sparse_prefill_attention`) with
  tiles of ``n`` tokens, lists from
  :func:`~marlin_tpu.ops.sparse_attention.tile_lists` built OUTSIDE the timed
  program: ``us_a_call``; ``blocks_met`` / ``blocks_taken`` (the union the
  tiles copy and meet, and what their tokens took: the two counters the
  engine sets on ``serve.prefill.sync``); ``us_a_block_met``; ``copy_only_us``
  (the same walk with the matmuls taken out: the copies' own rate; the tool
  swaps the meeting step for one that reads a row of each buffer);
  ``least_us`` (the selected pairs' flops at 197 TFLOP/s, what
  ``sparse_prefill_roofline_pct`` prices); ``max_abs_diff_from_mask``
  against ``attend_selected`` over the rows that hold a token; ``lists_us``
  the list building alone;
- ``<selection>.mask``: :func:`~marlin_tpu.ops.sparse_attention
  .attend_selected`, the form the programs held until PR 49 (a mask inside a
  key loop over every key), both KV heads.
- ``decode.walk``: the decode list walk at the cell's call (16 rows x 2 KV
  heads x 64 blocks), whose meeting step the prefill kernel shares.

Prints one JSON line a form and ends with ``{"ok": true, "device": ...}``;
needs a TPU (a time from the CPU's interpreter says nothing; ``--tiny`` runs
the control flow there at a toy size and ends non-zero). No engine, no
model."""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

import numpy as np

CELL = dict(T=512, tokens=273, start=33024, keys=34816, kvh=2, group=16,
            dh=128)
TINY = dict(T=32, tokens=21, start=1100, keys=1280, kvh=2, group=2, dh=16)
#: the MiniCPM4 family's published ``sparse_config`` (the cell's)
SPARSE = dict(stride=16, block=64, topk=64, init_blocks=1, window=2048,
              dense_len=8192)
PEAK_FLOPS = 197e12


def _time_call(fn, args, calls: int = 3, repeats: int = 4) -> float:
    import jax

    jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        for _ in range(calls):
            out = fn(*args)
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - t0) / calls)
    return best


def _chained(fn, inner: int):
    """``inner`` calls of ``fn(q_pos, *arrays)`` in ONE program, each call's
    positions read off the previous call's output (they never change: no
    output is that large), so that the calls run back to back on the chip."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def many(q_pos, *arrays):
        def one(_, carry):
            pos, total = carry
            first = sum(x.ravel()[0].astype(jnp.float32)
                        for x in jax.tree.leaves(fn(pos, *arrays)))
            return pos + (first > 1e30).astype(pos.dtype), total + first

        return jax.lax.fori_loop(0, inner, one, (q_pos, jnp.float32(0)))[1]

    return many


def _slope(fn, args, calls=(2, 8)) -> float:
    """Microseconds a call more adds to a program."""
    few, many = (_time_call(_chained(fn, n), args) for n in calls)
    return (many - few) / (calls[1] - calls[0]) * 1e6


def selections(shape: dict, sp, seed: int):
    """``{name: mask (kvh, T, NB) bool}`` and the queries, keys and values the
    ``seeded`` one was chosen from."""
    import jax
    import jax.numpy as jnp

    from marlin_tpu.ops import sparse_attention as sa

    T, L, kvh, g, dh = (shape[k] for k in ("T", "keys", "kvh", "group", "dh"))
    nb = L // sp.block
    kq, kk, kv = jax.random.split(jax.random.key(seed), 3)
    gain = np.sqrt(3.0)   # hybrid._SPARSE_QK_GAIN: unit rows times sqrt(3)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True) \
        * np.sqrt(dh) * gain  # noqa: E731
    q = unit(jax.random.normal(kq, (T, kvh, g, dh), jnp.float32))
    k = unit(jax.random.normal(kk, (L, kvh, dh), jnp.float32))
    v = jax.random.normal(kv, (L, kvh, dh), jnp.float32)
    q_pos = shape["start"] + jnp.arange(T)

    @jax.jit
    def seeded(q, k):
        def one(args):
            qh, kh = args
            ext = jnp.concatenate([jnp.zeros((sp.stride, dh)), kh])
            idx, taken = sa.select_blocks(
                qh, sa.compress_keys(ext, sp.stride), q_pos, sp)
            return sa.block_mask(idx, taken, nb)
        return jax.lax.map(one, (q.transpose(1, 0, 2, 3),
                                 k.transpose(1, 0, 2)))

    rng = np.random.default_rng(seed)
    own = np.asarray(q_pos) // sp.block
    b = np.arange(nb)
    forced = (b[None] < sp.init_blocks) | (
        (b[None] > own[:, None] - sp.window_blocks) & (b[None] <= own[:, None]))
    free = np.flatnonzero(~forced.any(0))
    n_free = sp.topk - sp.init_blocks - sp.window_blocks

    def drawn(alike: bool):
        out = np.repeat(forced[None], kvh, 0)
        for h in range(kvh):
            same = rng.choice(free, n_free, replace=False)
            for t in range(T):
                out[h, t, same if alike
                    else rng.choice(free, n_free, replace=False)] = True
        return jnp.asarray(out)

    masks = {"seeded": seeded(q, k), "alike": drawn(True),
             "independent": drawn(False)}
    return masks, q, k.reshape(L, kvh * dh), v.reshape(L, kvh * dh), q_pos


def _copy_only(q, keys, values, seen, carry):
    """In the meeting step's place: a row of each buffer read, no matmul."""
    m, l, acc = carry
    return m, l + 1.0, acc + (keys[:1, :] + values[:1, :]).astype(acc.dtype)


def measure(shape: dict, tqs, dtype, seed: int = 0) -> list[dict]:
    import jax
    import jax.numpy as jnp

    from marlin_tpu.ops import paged_attention as pa, sparse_attention as sa

    sp = sa.SparseSpec(**(SPARSE if shape is CELL else dict(
        stride=2, block=8, topk=6, init_blocks=1, window=16, dense_len=40)))
    T, L, kvh, g, dh = (shape[k] for k in ("T", "keys", "kvh", "group", "dh"))
    masks, q, k, v, q_pos = selections(shape, sp, seed)
    q, k, v = (x.astype(dtype) for x in (q, k, v))
    valid = jnp.arange(T) < shape["tokens"]
    rows = np.asarray(valid)
    heads = lambda x: x.reshape(L, kvh, dh)  # noqa: E731
    base = {"dtype": str(jnp.dtype(dtype)), "rows": T,
            "tokens": shape["tokens"], "keys": L, "kv_heads": kvh,
            "group": g, "head_dim": dh}
    lines = []
    for name, mask in masks.items():
        def masked(pos, q, k, v, mask):
            return jnp.stack([sa.attend_selected(
                q[:, h], heads(k)[:, h], heads(v)[:, h], pos, mask[h],
                sp.block, T) for h in range(kvh)], axis=1)

        want = np.asarray(jax.jit(masked)(q_pos, q, k, v, mask)
                          .astype(jnp.float32))
        lines.append({"shape": f"{name}.mask", **base,
                      "us_a_call": _slope(masked, (q_pos, q, k, v, mask))})
        print(json.dumps(lines[-1]), flush=True)
        for tq in tqs:
            def build(pos, m):
                return sa.tile_lists(m, pos, valid, sp.block, tq)

            lists, rounds, words, met, taken = jax.jit(build)(q_pos, mask)
            met, taken = int(met), int(taken)

            def walk(pos, q, k, v, lists, rounds, words):
                return pa.sparse_prefill_attention(q, k, v, pos, lists,
                                                   rounds, words, sp.block)

            args = (q_pos, q, k, v, lists, rounds, words)
            line = {"shape": f"{name}.tq{tq}", **base, "tiles": T // tq,
                    "blocks_met": met, "blocks_taken": taken,
                    "tokens_a_block_met": taken / met}
            lines.append(line)
            try:
                got = np.asarray(jax.jit(walk)(*args).astype(jnp.float32))
                line["max_abs_diff_from_mask"] = float(
                    np.abs(got - want)[rows].max())
                line["us_a_call"] = _slope(walk, args)
                line["us_a_block_met"] = line["us_a_call"] / met
                # the selected pairs' flops (scores and values) at the peak
                pairs = float(jnp.sum(jnp.where(
                    mask & valid[None, :, None], jnp.clip(
                        q_pos[None, :, None] + 1 - jnp.arange(
                            L // sp.block)[None, None, :] * sp.block,
                        0, sp.block), 0)))
                line["least_us"] = pairs * g * 4 * dh / PEAK_FLOPS * 1e6
                line["lists_us"] = _slope(build, (q_pos, mask))
                real, pa._meet_keys = pa._meet_keys, _copy_only
                try:
                    pa._sparse_prefill_attention_call.clear_cache()
                    line["copy_only_us"] = _slope(walk, args)
                finally:
                    pa._meet_keys = real
                    pa._sparse_prefill_attention_call.clear_cache()
            except Exception as e:  # e.g. scoped VMEM
                line["error"] = str(e).split(". ")[0][:300]
            print(json.dumps(line), flush=True)
    return lines


def measure_decode(dtype, seed: int = 0) -> dict:
    """The decode list walk at the cell's call: 16 rows at 33-35 k of
    context, 64 blocks a (row, KV head), pages of 256 tokens."""
    import jax
    import jax.numpy as jnp

    from marlin_tpu.ops.paged_attention import paged_decode_attention_blocks

    rng = np.random.default_rng(seed)
    B, kvh, g, dh, W, P, S = 16, 2, 16, 128, 136, 3264, 128
    kq, kk, kv = jax.random.split(jax.random.key(seed), 3)
    pk = jax.random.normal(kk, (P, 256, kvh * dh), dtype)
    pv = jax.random.normal(kv, (P, 256, kvh * dh), dtype)
    q = jax.random.normal(kq, (B, kvh, g, dh), dtype)
    tables = jnp.asarray(rng.integers(1, P, (B, W)), jnp.int32)
    lengths = jnp.asarray(rng.integers(33000, 34800, B), jnp.int32)
    blocks = np.zeros((B, kvh, S), np.int32)
    for b in range(B):
        own = (int(lengths[b]) - 1) // 64
        for h in range(kvh):
            blocks[b, h, :64] = np.concatenate([
                [0], rng.choice(np.arange(1, own - 31), 31, replace=False),
                np.arange(own - 31, own + 1)])
    counts = jnp.full((B, kvh), 64, jnp.int32)

    def walk(lens, q, pk, pv, tables, blocks, counts):
        return paged_decode_attention_blocks(q, pk, pv, tables, blocks,
                                             counts, lens, 64)

    t = _slope(walk, (lengths, q, pk, pv, tables, jnp.asarray(blocks),
                      counts))
    return {"shape": "decode.walk", "dtype": str(jnp.dtype(dtype)),
            "rows": B, "kv_heads": kvh, "blocks_a_list": 64, "us_a_call": t,
            "us_a_block": t / (B * kvh * 64),
            "bytes_us": B * kvh * 64 * 2 * 64 * dh
            * jnp.dtype(dtype).itemsize / 819e9 * 1e6}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tq", nargs="*", type=int, default=[8, 16, 32])
    ap.add_argument("--f32", action="store_true")
    ap.add_argument("--tiny", action="store_true",
                    help="a toy size: the control flow, on any device")
    args = ap.parse_args(argv)
    import jax
    import jax.numpy as jnp

    dev = jax.devices()[0]
    on_chip = dev.platform == "tpu"
    if not on_chip and not args.tiny:
        print(json.dumps({"ok": False, "error": "not a TPU",
                          "device": dev.platform}))
        return 1
    dtype = jnp.float32 if args.f32 else jnp.bfloat16
    measure(TINY if args.tiny else CELL, args.tq, dtype)
    if on_chip:
        print(json.dumps(measure_decode(dtype)), flush=True)
    print(json.dumps({"ok": on_chip, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": jax.device_count()}}))
    return 0 if on_chip else 1


if __name__ == "__main__":
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))
    sys.exit(main())
