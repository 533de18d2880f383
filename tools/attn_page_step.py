"""What one live page costs a decode-attention kernel, alone on the chip.

    chiprun --chips 1 -- python3 tools/attn_page_step.py [--f32]

Every line times eight calls chained in ONE program (each call's lengths read
off the previous call's output): a call of 50-600 us dispatched from the host
reads the host. Two chains a line, one at the cell's lengths and one with
every row at length 1, give two unknowns.

**The (K, V) kernel** at the five calls the spec cells make
(``olmohybrid.global``, ``falconh1.global``, ``laguna.global``,
``laguna.window``, and ``lfm2.global``: 8 heads of 64, two to a lane tile),
over a flat slab ``(num_pages, page_len, kv_heads * head_dim)``.
``<shape>.walk.all_heads`` is the package's kernel (``<shape>`` until PR 46):
a grid step a ROW, the call's live pages walked as one stream of copies
through a ring of slots (PR 43), every head of a page in ONE matmul from a
block-diagonal query built in VMEM once a row (at any group since PR 46):
microseconds a live page and a row (it has no dead step), the call at other
ring sizes (``call_us_by_slots``), and the same walk with the arithmetic
taken out (``walk_copy_only_us_a_page``: the copies' own rate). The others
are the grid-a-page form it replaced (grid (rows, table width), the page a
block whose index map reads the table; PR 38's body), kept HERE as the
yardstick with the arithmetic the package held until PR 46 inside, a head
at a time (:func:`_page_head_by_head`: the tool's own copy now), and what its
live step is made of: ``.grid`` the whole body as it was, microseconds a
LIVE grid step and a DEAD one; ``.copy_only`` the same copies with the
matmuls taken out; ``.compute_only`` the same matmuls with the index map
frozen on one page, so that no copy is issued after the first
(``.scores_only`` / ``.values_only``: that arithmetic without its value
matmul, and the value matmul alone; ``.all_heads.compute_only``: under the
same frozen grid the package's page body, the query built by XLA out here,
``max_abs_diff_from_grid`` beside it). A step that reads ``copy_only +
compute_only`` (less one dead step, which both hold) is not overlapping
them; one that reads the larger of the two is. ``max_abs_diff_from_grid`` is
the package's output against the grid form's (0.0 on the chip: the other
heads' lanes add exact zeros; the CPU's interpreter sums a contraction in
another order). Two "copy" figures of one page: ``.copy_only`` is the
grid's pipeline, a copy behind the step before it, a dead step's cost in it;
``walk_copy_only_us_a_page`` the walk's ring with nothing to compute, the
rate the package's kernel is held to.

**The latent kernel** (shape ``mistral4.latent``: the Mistral cell's decode
call, 32 rows of 65-70 pages, 32 heads against an entry of 320 values stored
384 wide): the same four lines (PR 40; the grid form read 0.62 us for 0.35
and 0.52).

Prints one JSON line a body and ends with ``{"ok": true, "device": ...}``;
needs a TPU (a time from the CPU's interpreter says nothing). No engine, no
model."""

from __future__ import annotations

import argparse
import functools
import json
import pathlib
import sys
import time

import numpy as np

# the cells' (K, V) decode calls as PERF.md section 5 reads them: rows, table
# width, kv heads, group, pages of the slab, live pages a call (``tokens``:
# the least and most a row holds, where the cell's rows are all long), window
SHAPES = {
    "olmohybrid.global": dict(rows=24, width=15, kvh=30, group=1, pages=320,
                              tokens=(2100, 3700)),
    "falconh1.global": dict(rows=64, width=20, kvh=4, group=5, pages=641,
                            live_pages=250),
    "laguna.global": dict(rows=32, width=32, kvh=8, group=6, pages=769,
                          live_pages=190),
    "laguna.window": dict(rows=32, width=4, kvh=8, group=9, pages=145,
                          window=512),
    "lfm2.global": dict(rows=96, width=24, kvh=8, group=4, pages=1537,
                        tokens=(2200, 5100), dh=64),
}
# ring sizes the walk is also timed at (the package's own: _kv_slots)
SLOTS = {"olmohybrid.global": (2, 4), "falconh1.global": (2, 3, 4, 8),
         "laguna.global": (2, 3, 4, 8), "laguna.window": (2, 4),
         "lfm2.global": (2, 4)}
PAGE_LEN, HEAD_DIM = 256, 128
# the latent call: (rows, table width, heads, entry as stored, value columns,
# pages of the slab, (least, most) tokens a row): a 16384-token document, a
# question and the answer so far (2111 live pages a call in the cell's trace)
LATENT_SHAPES = {"mistral4.latent": (32, 70, 32, 384, 256, 2561,
                                     (16400, 17664))}


def _lengths(rng, shape: dict) -> np.ndarray:
    """Row lengths (in tokens) as the cell's: between ``tokens``' bounds; or
    a long tail whose pages sum to about ``live_pages`` (most rows a page or
    two, a few nearly the table), each row at least one token; under a
    window a row's position in a long context (the ring holds the last 512
    of it)."""
    rows, width = shape["rows"], shape["width"]
    if "window" in shape:
        return np.clip(rng.lognormal(np.log(1500.0), 1.0, rows), 1,
                       8000).astype(np.int32)
    if "tokens" in shape:
        return rng.integers(*shape["tokens"], rows).astype(np.int32)
    raw = rng.lognormal(0.0, 1.0, rows)
    pages = np.clip(np.round(raw * shape["live_pages"] / raw.sum()), 1, width)
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


_CHAIN = 8  # calls of a body in one timed program


def _chained(fn, inner: int = _CHAIN):
    """``inner`` calls of ``fn(*arrays, lengths)`` in ONE program, each
    call's lengths read off the previous call's output (they never change:
    no output is that large), so that the calls run back to back on the
    chip. A call of 50-600 us timed from the host reads the host's dispatch
    (~200 us a call here), not the chip."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def many(*args):
        *arrays, lengths = args

        def one(_, carry):
            lens, total = carry
            first = fn(*arrays, lens).ravel()[0].astype(jnp.float32)
            return lens + (first > 1e30).astype(lens.dtype), total + first
        return jax.lax.fori_loop(0, inner, one, (lengths, jnp.float32(0)))[1]
    return many


def _time_pair(fn, arrays, cell, ones) -> tuple[float, float]:
    """Seconds a call of ``fn`` at the cell's lengths and with every row at
    length 1, each the chained program's time over its calls."""
    many = _chained(fn)
    return (_time_call(many, (*arrays, cell), calls=4) / _CHAIN,
            _time_call(many, (*arrays, ones), calls=4) / _CHAIN)


def _a_step(walks: bool, live: int, rows: int, width: int, t1: float,
            t0: float) -> dict:
    """The two unknowns of a line from its two timed chains (seconds a call
    at the cell's lengths and with every row at length 1). A walk has no
    dead step: t = live * a + rows * r. A grid runs rows x width steps, of
    which ``live`` (``rows``) hold attended positions."""
    if walks:
        a, r = np.linalg.solve([[live, rows], [rows, rows]], [t1, t0])
        return {"live_step_us": a * 1e6, "row_us": r * 1e6}
    steps = rows * width
    a, d = np.linalg.solve([[live, steps - live], [rows, steps - rows]],
                           [t1, t0])
    return {"live_step_us": a * 1e6, "dead_step_us": d * 1e6}


def _window_of(lens, window: int):
    """The ring's first page and lowest visible position, as the decode
    program derives them from a row's position."""
    import jax.numpy as jnp

    lower = jnp.maximum(lens - window, 0)
    return lower // PAGE_LEN, lower


def _page_head_by_head(q_ref, k_page, v_page, acc_ref, m_ref, l_ref, live):
    """The yardstick's arithmetic, the package's own until PR 46: one live
    page ``(page_len, kvh * dh)``, head ``h``'s keys the lane slice ``[:, h *
    dh:(h + 1) * dh]``, each head's ``group`` query rows against it in a
    plain matmul, softmax, matmul, a head at a time (``live``: (group,
    page_len)); scratch ``acc`` (kvh, group, dh), ``m`` / ``l`` (kvh, group,
    1). 1.15 / 2.20 / 2.28 us a page alone on the chip at Falcon-H1's,
    Laguna's global and window shapes against copies of 0.70 / 1.41 / 1.40
    (PERF.md PR 43): ``kvh`` chains that each wait for their own results."""
    import math

    import jax
    import jax.numpy as jnp

    kvh, group, dh = q_ref.shape[1:]
    nt = (((1,), (1,)), ((), ()))      # contract both minor dimensions
    for h in range(kvh):
        q = q_ref[0, h]                            # (group, dh)
        cols = slice(h * dh, (h + 1) * dh)
        s = jax.lax.dot_general(
            q, k_page[:, cols], nt,
            preferred_element_type=jnp.float32) / math.sqrt(dh)
        s = jnp.where(live, s, -1e30)
        m_prev = m_ref[h]                          # (group, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        m_ref[h] = m_new
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[h] = alpha * l_ref[h] + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[h] = acc_ref[h] * alpha + jnp.dot(
            p.astype(q.dtype), v_page[:, cols],
            preferred_element_type=jnp.float32)


def _block_diagonal(q):
    """``q`` (B, kvh, group, dh) as the block-diagonal query of the
    package's page body, built by XLA: (B, R, kvh * dh), row ``h * group +
    g`` holds ``q[:, h, g]`` at lanes ``[h * dh, (h + 1) * dh)`` and zeros
    elsewhere; R the ``kvh * group`` rows padded to whole sublane tiles (the
    package builds it in VMEM, a row at a time)."""
    import jax.numpy as jnp

    B, kvh, group, dh = q.shape
    eye = jnp.eye(kvh, dtype=q.dtype)[None, :, None, :, None]
    qbd = (q[:, :, :, None, :] * eye).reshape(B, kvh * group, kvh * dh)
    return jnp.pad(qbd, ((0, 0), (0, -(kvh * group) % 16), (0, 0)))


def _scores_only(q_ref, k_page, v_page, acc_ref, m_ref, l_ref, live):
    """:func:`_page_head_by_head` without its value matmul (a lane tile of
    the probabilities goes into the accumulator in its place)."""
    import math

    import jax
    import jax.numpy as jnp

    kvh, group, dh = q_ref.shape[1:]
    nt = (((1,), (1,)), ((), ()))
    for h in range(kvh):
        s = jax.lax.dot_general(
            q_ref[0, h], k_page[:, h * dh:(h + 1) * dh], nt,
            preferred_element_type=jnp.float32) / math.sqrt(dh)
        s = jnp.where(live, s, -1e30)
        m_prev = m_ref[h]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        m_ref[h] = m_new
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[h] = alpha * l_ref[h] + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[h] = acc_ref[h] * alpha + p[:, :dh]


def _values_only(q_ref, k_page, v_page, acc_ref, m_ref, l_ref, live):
    """Only the value matmul of :func:`_page_head_by_head`, under
    probabilities of one."""
    import jax.numpy as jnp

    kvh, group, dh = q_ref.shape[1:]
    p = jnp.where(live, 1.0, 0.0).astype(v_page.dtype)
    l_ref[:] = jnp.ones_like(l_ref)
    for h in range(kvh):
        acc_ref[h] = acc_ref[h] + jnp.dot(
            p, v_page[:, h * dh:(h + 1) * dh],
            preferred_element_type=jnp.float32)


def _grid_kv_call(mode: str = "both", body: str = "head_by_head"):
    """The (K, V) kernel over a flat slab as the package held it until PR
    43: grid (rows, table width), one page of K and of V a grid step through
    ``BlockSpec`` s whose index map reads the scalar-prefetched table,
    :func:`_page_head_by_head` on each live page; called as the
    package's jitted calls are (lengths clamped by the caller), a window's
    ``first_page`` and ``lower`` last. ``mode`` ``both`` is that kernel;
    ``copy`` takes the arithmetic out (a live step adds a sublane tile of
    each block into the accumulator, so that both are read); ``compute``
    freezes the index map on page 1. ``body``: that arithmetic's parts
    (``scores`` / ``values``: :func:`_scores_only`, :func:`_values_only`),
    or the package's page body, ``_page_every_head`` (``all_heads``: the
    block-diagonal query built out here, :func:`_block_diagonal`; the
    accumulator (rows, kvh * dh), of which the flush reads each head's rows
    at its lanes), each under the same grid."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from marlin_tpu.ops import paged_attention as pa

    page_body = {"head_by_head": _page_head_by_head,
                 "scores": _scores_only, "values": _values_only}.get(body)

    def kernel(*refs, page_len, windowed):
        scalars, refs = refs[:2 + 2 * windowed], refs[2 + 2 * windowed:]
        tables_ref, lengths_ref = scalars[:2]
        q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref = refs
        b, w = pl.program_id(0), pl.program_id(1)
        kvh, group, dh = o_ref.shape[1:]

        @pl.when(w == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)
            m_ref[...] = jnp.full_like(m_ref, pa._MASKED)
            l_ref[...] = jnp.zeros_like(l_ref)

        page = scalars[2][b] + w if windowed else w

        @pl.when(page * page_len < lengths_ref[b])
        def _live_page():
            if mode == "copy":
                acc_ref[0] += (k_ref[0, :group, :dh].astype(jnp.float32)
                               + v_ref[0, :group, :dh].astype(jnp.float32))
                l_ref[...] = jnp.ones_like(l_ref)
                return
            at = page * page_len + jax.lax.broadcasted_iota(
                jnp.int32, (1 if page_body is None else group, page_len), 1)
            live = at < lengths_ref[b]
            if windowed:
                live &= at >= scalars[3][b]
            if page_body is None:
                pa._page_every_head(q_ref.at[0], k_ref.at[0], v_ref.at[0],
                                    acc_ref, m_ref, l_ref, live, dh)
            else:
                page_body(q_ref, k_ref.at[0], v_ref.at[0], acc_ref, m_ref,
                          l_ref, live)

        @pl.when(w == pl.num_programs(1) - 1)
        def _flush():
            if page_body is None:
                for h in range(kvh):
                    mine = slice(h * group, (h + 1) * group)
                    o_ref[0, h] = (acc_ref[mine, h * dh:(h + 1) * dh]
                                   / l_ref[mine, :]).astype(o_ref.dtype)
            else:
                o_ref[0] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)

    @jax.jit
    def call(q, k_pages, v_pages, tables, lengths, *window):
        B, kvh, group, dh = q.shape
        W, (page_len, width) = tables.shape[1], k_pages.shape[1:]

        def page(b, w, tbl, lens, *win):
            if mode == "compute":
                return (1, 0, 0)
            last = (lens[b] - 1) // page_len
            if win:
                return (tbl[b, jax.lax.rem(jnp.minimum(win[0][b] + w, last),
                                           W)], 0, 0)
            return (tbl[b, jnp.minimum(w, last)], 0, 0)

        row_spec = pl.BlockSpec((1, kvh, group, dh),
                                lambda b, w, *_: (b, 0, 0, 0))
        page_spec = pl.BlockSpec((1, page_len, width), page)
        q_spec, shapes = row_spec, [(kvh, group, dh), (kvh, group, 1),
                                    (kvh, group, 1)]
        if body == "all_heads":
            q = _block_diagonal(q)   # rows: whole tiles in either dtype
            rows = q.shape[1]
            q_spec = pl.BlockSpec((1, rows, width), lambda b, w, *_: (b, 0, 0))
            shapes = [(rows, width), (rows, 1), (rows, 1)]
        return pl.pallas_call(
            functools.partial(kernel, page_len=page_len,
                              windowed=bool(window)),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2 + len(window), grid=(B, W),
                in_specs=[q_spec, page_spec, page_spec],
                out_specs=row_spec,
                scratch_shapes=[pltpu.VMEM(shape, jnp.float32)
                                for shape in shapes]),
            out_shape=jax.ShapeDtypeStruct((B, kvh, group, dh), q.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary")),
            interpret=jax.default_backend() != "tpu",
        )(tables, lengths, *window, q, k_pages, v_pages)

    return call


def _windowed(call, window: int | None):
    """``call`` at a row's position: under a window the ring's first page
    and lowest visible position follow from the lengths."""
    def at_lengths(q, k_pages, v_pages, tables, lengths):
        win = _window_of(lengths, window) if window else ()
        return call(q, k_pages, v_pages, tables, lengths, *win)
    return at_lengths


def _touch_a_page(q_ref, k_page, v_page, acc_ref, m_ref, l_ref, live, *_):
    """In the place of a page's arithmetic: a sublane tile of the keys and
    of the values goes into the accumulator, so that the slot is read."""
    import jax.numpy as jnp

    tile = min(acc_ref.shape[-2], k_page.shape[0]), min(acc_ref.shape[-1], 128)
    at = (0,) * (acc_ref.ndim - 2) + (slice(tile[0]), slice(tile[1]))
    acc_ref[at] += (k_page[:tile[0], :tile[1]].astype(jnp.float32)
                    + v_page[:tile[0], :tile[1]].astype(jnp.float32))
    l_ref[...] = jnp.ones_like(l_ref)


def _walk_kv_call(copy_only: bool = False, **kw):
    """The package's walk over a flat slab (``slots``: a ring of the tool's
    choosing), called as :func:`_grid_kv_call` is. ``copy_only``: the same
    walk, ring and copies with its page body replaced by
    :func:`_touch_a_page` while the kernel is traced."""
    import contextlib
    from unittest import mock

    import jax

    from marlin_tpu.ops import paged_attention as pa

    @jax.jit
    def call(q, k_pages, v_pages, tables, lengths, *window):
        body = mock.patch.object(pa, "_page_every_head", _touch_a_page) \
            if copy_only else contextlib.nullcontext()
        with body:
            return pa._kv_walk_call(
                q, k_pages, v_pages, tables, lengths, window,
                interpret=jax.default_backend() != "tpu", **kw)
    return call


def measure(name: str, dtype, seed: int = 0) -> list[dict]:
    """The lines of a (K, V) shape (module docstring)."""
    import jax
    import jax.numpy as jnp

    shape = SHAPES[name]
    rows, width, kvh, group, pages = (shape[k] for k in (
        "rows", "width", "kvh", "group", "pages"))
    window, head_dim = shape.get("window"), shape.get("dh", HEAD_DIM)
    rng = np.random.default_rng(seed)
    kq, kk, kv = jax.random.split(jax.random.key(seed), 3)
    slab = (pages, PAGE_LEN, kvh * head_dim)
    k = jax.random.normal(kk, slab, jnp.float32).astype(dtype)
    v = jax.random.normal(kv, slab, jnp.float32).astype(dtype)
    q = jax.random.normal(kq, (rows, kvh, group, head_dim),
                          jnp.float32).astype(dtype)
    tables = jnp.asarray(rng.integers(1, pages, (rows, width)), jnp.int32)
    cell = jnp.asarray(_lengths(rng, shape))
    ones = jnp.ones((rows,), jnp.int32)
    if window:
        first, _ = _window_of(cell, window)
        live = int(jnp.sum((cell - 1) // PAGE_LEN - first + 1))
    else:
        live = int(jnp.sum((cell - 1) // PAGE_LEN + 1))
    arrays = (q, k, v, tables)
    base = {"dtype": str(jnp.dtype(dtype)), "rows": rows,
            "table_width": width, "kv_heads": kvh, "group": group,
            "live_pages": live,
            "head_dim": head_dim,
            "page_bytes": 2 * PAGE_LEN * kvh * head_dim
            * jnp.dtype(dtype).itemsize}
    base["bytes_us_a_page"] = base["page_bytes"] / 819e9 * 1e6
    package = name + ".walk.all_heads"
    calls = {package: _walk_kv_call(),
             name + ".grid": _grid_kv_call("both"),
             name + ".copy_only": _grid_kv_call("copy"),
             name + ".compute_only": _grid_kv_call("compute"),
             name + ".scores_only": _grid_kv_call("compute", "scores"),
             name + ".values_only": _grid_kv_call("compute", "values"),
             name + ".all_heads.compute_only": _grid_kv_call("compute",
                                                             "all_heads")}
    lines, outs = [], {}
    for label, call in calls.items():
        fn = _windowed(call, window)
        line = {"shape": label, **base}
        lines.append(line)
        try:
            t1, t0 = _time_pair(fn, arrays, cell, ones)
            outs[label] = np.asarray(fn(*arrays, cell).astype(jnp.float32))
        except Exception as e:  # e.g. scoped VMEM
            line["error"] = str(e).split(". ")[0][:300]
            continue
        line.update(call_us=t1 * 1e6, call_us_all_rows_length_1=t0 * 1e6,
                    **_a_step(label == package, live, rows, width, t1, t0))
    walk = lines[0]
    try:  # the all-heads body's output, the copies back in
        outs["all_heads"] = np.asarray(_windowed(_grid_kv_call(
            "both", "all_heads"), window)(*arrays, cell).astype(jnp.float32))
    except Exception as e:  # float32 Olmo blocks: scoped VMEM
        lines[-1]["both_error"] = str(e).split(". ")[0][:200]
    for line, label in ((walk, package), (lines[-1], "all_heads")):
        if label in outs and name + ".grid" in outs:
            line["max_abs_diff_from_grid"] = float(
                np.abs(outs[label] - outs[name + ".grid"]).max())
    if package in outs:
        from marlin_tpu.ops.paged_attention import _kv_slots

        walk["slots"] = _kv_slots(k)
        walk["call_us_by_slots"] = {}
        for slots in SLOTS[name]:
            try:
                fn = _windowed(_walk_kv_call(slots=slots), window)
                t = _time_call(_chained(fn), (*arrays, cell),
                               calls=4) / _CHAIN
                walk["call_us_by_slots"][slots] = t * 1e6
            except Exception as e:
                walk["call_us_by_slots"][slots] = str(e).split(". ")[0][:200]
        t1, t0 = _time_pair(_windowed(_walk_kv_call(copy_only=True), window),
                            arrays, cell, ones)
        walk["walk_copy_only_us_a_page"] = (t1 - t0) / (live - rows) * 1e6
    return lines


def _grid_latent_call(mode: str, value_dim: int):
    """The latent kernel as the package held it until PR 40: grid (rows,
    table width), one page a grid step through a ``BlockSpec`` whose index
    map reads the scalar-prefetched table. ``mode`` ``both`` is that kernel
    to the letter; ``copy`` takes the matmuls out (a live step adds one
    sublane tile of the page into the accumulator, so that the block is
    read); ``compute`` freezes the index map on page 1."""
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
        t1, t0 = _time_pair(fn, (q, slab, tables), cell, ones)
        outs[label] = np.asarray(fn(q, slab, tables, cell).astype(jnp.float32))
        lines.append({"shape": label, **base, "call_us": t1 * 1e6,
                      "call_us_all_rows_length_1": t0 * 1e6,
                      **_a_step(label == name, live, rows, width, t1, t0)})
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
                 else measure(name, dtype))
        for line in lines:
            print(json.dumps(line), flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": jax.device_count()}}))
    return 0


if __name__ == "__main__":
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))
    sys.exit(main())
