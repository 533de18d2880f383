"""What one live page costs a decode-attention kernel, alone on the chip.

    chiprun --chips 1 -- python3 tools/attn_page_step.py [--f32]

**The (K, V) kernel.** At the three calls the spec cells make (Falcon-H1's
global call, Laguna's global and window calls) it times
``paged_decode_attention`` over the SAME random pages held both ways:
``(num_pages, page_len, kv_heads, head_dim)`` (the dense model's layout:
every head in one einsum batched over the block's middle axis) and
``(num_pages, page_len, kv_heads * head_dim)`` (a spec model's: a head's keys
a lane slice, contracted head by head). Two calls a layout, one at the cell's
lengths and one with every row at length 1, give the two unknowns:
microseconds a LIVE grid step and a DEAD one.

**The latent kernel** (shape ``mistral4.latent``: the Mistral cell's decode
call, 32 rows of 65-70 pages, 32 heads against an entry of 320 values stored
384 wide). Four lines, the same two calls each. ``mistral4.latent`` is the
package's kernel, which walks a row's live pages itself (the copies of the
pages ahead in flight, the next page's scores computed beside this page's
softmax and values): microseconds a live page and a ROW (it has no dead
step). The other three are the grid-a-page form that PR 40 took
out of the package (grid (rows, table width), the page a block whose index
map reads the table), kept HERE as the yardstick, and what its live step is
made of: ``.grid`` the whole body, as it was; ``.copy_only``
the same copies with the matmuls taken out (a page's bytes through the
pipeline, and the step's own cost); ``.compute_only`` the same matmuls with
the index map frozen on one page, so that no copy is issued after the first.
A step that reads ``copy_only + compute_only`` is not overlapping them; one
that reads the larger of the two is (the grid form read in between: 0.62 us
for 0.35 and 0.52, PERF.md PR 40). ``max_abs_diff_from_grid`` is the
package's output against the grid form's (0.0: bit-equal). The latent lines
time eight calls chained in one program: a call of 50 us dispatched from
the host reads the host.

Prints one JSON line a shape (four for the latent shape) and ends with
``{"ok": true, "device": ...}``; needs a TPU (a time from the CPU's
interpreter says nothing). No engine, no model."""

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
# the latent call: (rows, table width, heads, entry as stored, value columns,
# pages of the slab, (least, most) tokens a row): a 16384-token document, a
# question and the answer so far (2111 live pages a call in the cell's trace)
LATENT_SHAPES = {"mistral4.latent": (32, 70, 32, 384, 256, 2561,
                                     (16400, 17664))}


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


_CHAIN = 8  # calls of a latent body in one timed program


def _chained(fn, inner: int = _CHAIN):
    """``inner`` calls of ``fn(q, slab, tables, lengths)`` in ONE program, each
    call's lengths read off the previous call's output (they never change:
    no output is that large), so that the calls run back to back on the
    chip. A call of 50-200 us timed from the host reads the host's dispatch
    (~200 us a call here), not the chip."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def many(q, slab, tables, lengths):
        def one(_, carry):
            lens, total = carry
            first = fn(q, slab, tables, lens)[0, 0, 0].astype(jnp.float32)
            return lens + (first > 1e30).astype(lens.dtype), total + first
        return jax.lax.fori_loop(0, inner, one, (lengths, jnp.float32(0)))[1]
    return many


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


def _grid_latent_call(mode: str, value_dim: int):
    """The latent kernel as the package held it until PR 40: grid (rows,
    table width), one page a grid step through a ``BlockSpec`` whose index
    map reads the scalar-prefetched table. ``mode`` ``both`` is that kernel
    to the letter; ``copy`` takes the matmuls out (a live step adds one
    sublane tile of the page into the accumulator, so that the block is
    read); ``compute`` freezes the index map on page 1."""
    import functools

    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from marlin_tpu.ops.paged_attention import _MASKED

    def kernel(tables_ref, lengths_ref, q_ref, e_ref, o_ref, acc_ref, m_ref,
               l_ref, *, page_len):
        b, w = pl.program_id(0), pl.program_id(1)

        @pl.when(w == 0)
        def _init():
            acc_ref[:] = jnp.zeros_like(acc_ref)
            m_ref[:] = jnp.full_like(m_ref, _MASKED)
            l_ref[:] = jnp.zeros_like(l_ref)

        @pl.when(w * page_len < lengths_ref[b])
        def _live_page():
            if mode == "copy":
                acc_ref[:] += e_ref[0, :acc_ref.shape[0], :value_dim].astype(
                    jnp.float32)
                l_ref[:] = jnp.ones_like(l_ref)
                return
            q = q_ref[0]
            e = e_ref[0]
            c = e[:, :value_dim]
            nt = (((1,), (1,)), ((), ()))
            s = (jax.lax.dot_general(q[:, :value_dim], c, nt,
                                     preferred_element_type=jnp.float32)
                 + jax.lax.dot_general(q[:, value_dim:], e[:, value_dim:], nt,
                                       preferred_element_type=jnp.float32))
            at = w * page_len + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(at < lengths_ref[b], s, _MASKED)
            m_prev = m_ref[:]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            m_ref[:] = m_new
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            l_ref[:] = alpha * l_ref[:] + jnp.sum(p, axis=1, keepdims=True)
            acc_ref[:] = acc_ref[:] * alpha + jnp.dot(
                p.astype(q.dtype), c, preferred_element_type=jnp.float32)

        @pl.when(w == pl.num_programs(1) - 1)
        def _flush():
            o_ref[0] = (acc_ref[:] / l_ref[:]).astype(o_ref.dtype)

    @jax.jit
    def call(q, slab, tables, lengths):
        B, H, E = q.shape
        W, page_len = tables.shape[1], slab.shape[1]
        if mode == "compute":
            def page(b, w, tbl, lens):
                return (1, 0, 0)
        else:
            def page(b, w, tbl, lens):
                return (tbl[b, jnp.minimum(w, (lens[b] - 1) // page_len)],
                        0, 0)
        return pl.pallas_call(
            functools.partial(kernel, page_len=page_len),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2, grid=(B, W),
                in_specs=[pl.BlockSpec((1, H, E),
                                       lambda b, w, tbl, lens: (b, 0, 0)),
                          pl.BlockSpec((1, page_len, E), page)],
                out_specs=pl.BlockSpec((1, H, value_dim),
                                       lambda b, w, tbl, lens: (b, 0, 0)),
                scratch_shapes=[pltpu.VMEM((H, value_dim), jnp.float32),
                                pltpu.VMEM((H, 1), jnp.float32),
                                pltpu.VMEM((H, 1), jnp.float32)]),
            out_shape=jax.ShapeDtypeStruct((B, H, value_dim), q.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary")),
        )(tables, lengths, q, slab)

    return call


def measure_latent(name: str, dtype, seed: int = 0) -> list[dict]:
    """The four lines of a latent shape (module docstring)."""
    import jax
    import jax.numpy as jnp

    from marlin_tpu.ops.paged_attention import paged_decode_attention_latent

    rows, width, heads, entry, value_dim, pages, (lo, hi) = LATENT_SHAPES[name]
    rng = np.random.default_rng(seed)
    kq, ke = jax.random.split(jax.random.key(seed))
    slab = jax.random.normal(ke, (pages, PAGE_LEN, entry),
                             jnp.float32).astype(dtype)
    # the absorbed query carries its softmax scale: scores of order 1
    q = (jax.random.normal(kq, (rows, heads, entry), jnp.float32)
         / np.sqrt(entry)).astype(dtype)
    tables = jnp.asarray(rng.integers(1, pages, (rows, width)), jnp.int32)
    cell = jnp.asarray(rng.integers(lo, hi, rows), jnp.int32)
    ones = jnp.ones((rows,), jnp.int32)
    live = int(jnp.sum((cell - 1) // PAGE_LEN + 1))
    base = {"dtype": str(jnp.dtype(dtype)), "rows": rows,
            "table_width": width, "heads": heads, "entry": entry,
            "value_dim": value_dim, "live_pages": live,
            "page_bytes": PAGE_LEN * entry * jnp.dtype(dtype).itemsize}
    base["bytes_us_a_page"] = base["page_bytes"] / 819e9 * 1e6
    bodies = {
        name: jax.jit(lambda q, e, t, n: paged_decode_attention_latent(
            q, e, t, n, value_dim)),
        name + ".grid": _grid_latent_call("both", value_dim),
        name + ".copy_only": _grid_latent_call("copy", value_dim),
        name + ".compute_only": _grid_latent_call("compute", value_dim)}
    lines, outs = [], {}
    for label, fn in bodies.items():
        many = _chained(fn)
        t1 = _time_call(many, (q, slab, tables, cell), calls=4) / _CHAIN
        t0 = _time_call(many, (q, slab, tables, ones), calls=4) / _CHAIN
        outs[label] = np.asarray(fn(q, slab, tables, cell).astype(jnp.float32))
        line = {"shape": label, **base, "call_us": t1 * 1e6,
                "call_us_all_rows_length_1": t0 * 1e6}
        if label == name:
            # t = live * a + rows * r at both points: no dead step exists
            a, r = np.linalg.solve([[live, rows], [rows, rows]], [t1, t0])
            line.update(live_step_us=a * 1e6, row_us=r * 1e6)
        else:
            steps = rows * width
            a, d = np.linalg.solve([[live, steps - live],
                                    [rows, steps - rows]], [t1, t0])
            line.update(live_step_us=a * 1e6, dead_step_us=d * 1e6)
        lines.append(line)
    lines[0]["max_abs_diff_from_grid"] = float(
        np.abs(outs[name] - outs[name + ".grid"]).max())
    return lines


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--f32", action="store_true",
                    help="float32 pages and queries (the f32 checks' blocks)")
    ap.add_argument("--shapes", nargs="*",
                    default=[*SHAPES, *LATENT_SHAPES])
    args = ap.parse_args(argv)
    import jax
    import jax.numpy as jnp

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(json.dumps({"ok": False, "error": "not a TPU",
                          "device": dev.platform}))
        return 1
    dtype = jnp.float32 if args.f32 else jnp.bfloat16
    for name in args.shapes:
        lines = (measure_latent(name, dtype) if name in LATENT_SHAPES
                 else [measure(name, dtype)])
        for line in lines:
            print(json.dumps(line), flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": jax.device_count()}}))
    return 0


if __name__ == "__main__":
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))
    sys.exit(main())
