"""Fused paged decode-attention kernel suite (interpret mode on CPU).

Three layers: kernel-vs-numpy numerics (GQA head grouping, ragged per-row
lengths, page-boundary lengths, dummy/mid-prefill rows; each over BOTH page
layouts, the dense model's ``(page_len, kvh, dh)`` block and a spec model's
``(page_len, kvh * dh)``, whose rank picks the kernel's body), the greedy
bit-identity grid across decode backends (``kernel='pallas'`` vs the
``'gather'`` reference vs unpaged :func:`lm_generate` — the serving
contract: swapping the attention kernel must not change a single emitted
token), and the engine end to end with ``decode_kernel='pallas'``
(including the ``serve_page_len`` alignment the backend forces). The
interpret path runs the REAL kernel body — same index_map, same
online-softmax accumulation — so these tests gate the Mosaic kernel's
logic, not a shadow implementation.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from marlin_tpu.models import TransformerLM
from marlin_tpu.models.transformer import (init_kv_pages, lm_decode_paged,
                                           lm_generate, lm_prefill_paged,
                                           resolve_decode_kernel)
from marlin_tpu.ops.paged_attention import (PAGE_SUBLANE, align_page_len,
                                            paged_decode_attention)
from marlin_tpu.serving import STATUS_OK, Request, ServeEngine

PAGE_LEN = 8  # kernel-legal (multiple of PAGE_SUBLANE); tests/test_paging.py
#               keeps exercising the 4-entry geometry on the gather path


# ------------------------------------------------------- numpy reference


def _ref_attention(q, k_pages, v_pages, tables, lengths):
    """Straight-line numpy decode attention: gather each row's context by
    block table, mask past its length, softmax, weigh V. The obvious
    formulation the kernel must reproduce."""
    q = np.asarray(q, np.float32)
    B, kvh, group, dh = q.shape
    kp, vp = _by_head(k_pages, kvh, dh), _by_head(v_pages, kvh, dh)
    W = tables.shape[1]
    page_len = kp.shape[1]
    out = np.zeros_like(q)
    for b in range(B):
        k = kp[tables[b]].reshape(W * page_len, kvh, dh)
        v = vp[tables[b]].reshape(W * page_len, kvh, dh)
        n = int(np.clip(lengths[b], 1, W * page_len))
        s = np.einsum("kgd,tkd->kgt", q[b], k[:n]) / np.sqrt(dh)
        s = s - s.max(axis=2, keepdims=True)
        p = np.exp(s)
        p = p / p.sum(axis=2, keepdims=True)
        out[b] = np.einsum("kgt,tkd->kgd", p, v[:n])
    return out


def _by_head(pages, kvh, dh):
    """A slab of either layout as float32 ``(num_pages, page_len, kvh,
    dh)``: the references below are written once."""
    pages = np.asarray(pages, np.float32)
    return pages.reshape(*pages.shape[:2], kvh, dh)


# (kv heads, group, head dim, pages held (page_len, kvh * dh)): the dense
# model's slab, and a spec model's at the three calls the cells make
# (Falcon-H1's global layers, Laguna's full and sliding layers,
# Olmo-Hybrid's full layers)
_HEADS = {"dense-2x2": (2, 2, 8, False), "falconh1-4x5": (4, 5, 128, True),
          "laguna-8x6": (8, 6, 128, True), "laguna-8x9": (8, 9, 128, True),
          "olmohybrid-30x1": (30, 1, 128, True)}
both_layouts = pytest.mark.parametrize("heads",
                                       ["dense-2x2", "falconh1-4x5"])


def _random_case(rng, B=4, heads="dense-2x2", W=3, num_pages=16,
                 dtype=np.float32):
    kvh, group, dh, flat = _HEADS[heads]
    slab = ((num_pages, PAGE_LEN, kvh * dh) if flat
            else (num_pages, PAGE_LEN, kvh, dh))
    q = rng.standard_normal((B, kvh, group, dh)).astype(dtype)
    kp = rng.standard_normal(slab).astype(dtype)
    vp = rng.standard_normal(slab).astype(dtype)
    # distinct live pages per row (page 0 is the pool's dummy)
    tables = (1 + rng.permutation(num_pages - 1)[:B * W]).reshape(B, W)
    tables = tables.astype(np.int32)
    return q, kp, vp, tables


@both_layouts
def test_kernel_matches_reference_gqa_ragged(heads):
    """GQA (kv_heads < heads) with ragged lengths straddling page
    boundaries: the in-place kernel matches the gathered reference."""
    rng = np.random.default_rng(0)
    q, kp, vp, tables = _random_case(rng, heads=heads)
    lengths = np.array([1, 9, 17, 24], np.int32)  # mid-page, full-table
    got = paged_decode_attention(q, kp, vp, tables, lengths, interpret=True)
    want = _ref_attention(q, kp, vp, tables, lengths)
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-6, rtol=2e-6)


@both_layouts
def test_kernel_page_boundary_lengths(heads):
    """Lengths landing exactly on page edges — the off-by-one hotspot for
    the absolute-position mask ``w*page_len + t < length``."""
    rng = np.random.default_rng(1)
    q, kp, vp, tables = _random_case(rng, heads=heads)
    for n in (PAGE_LEN - 1, PAGE_LEN, PAGE_LEN + 1, 2 * PAGE_LEN,
              3 * PAGE_LEN):
        lengths = np.full(4, n, np.int32)
        got = paged_decode_attention(q, kp, vp, tables, lengths,
                                     interpret=True)
        want = _ref_attention(q, kp, vp, tables, lengths)
        np.testing.assert_allclose(np.asarray(got), want, atol=2e-6,
                                   rtol=2e-6)


@both_layouts
def test_kernel_dummy_rows_are_harmless(heads):
    """Rows still prefilling ride the batch with an all-dummy (zero) table
    and length 1 — the dummy-row contract. Their outputs must be
    finite (the scheduler discards them) and must not perturb live rows."""
    rng = np.random.default_rng(2)
    q, kp, vp, tables = _random_case(rng, heads=heads)
    lengths = np.array([12, 1, 20, 1], np.int32)
    tables = tables.copy()
    tables[1] = 0  # mid-prefill rows point at the dummy page
    tables[3] = 0
    got = np.asarray(paged_decode_attention(q, kp, vp, tables, lengths,
                                            interpret=True))
    assert np.isfinite(got).all()
    want = _ref_attention(q, kp, vp, tables, lengths)
    np.testing.assert_allclose(got[[0, 2]], want[[0, 2]], atol=2e-6,
                               rtol=2e-6)


@both_layouts
def test_kernel_bf16_matches_f32_reference(heads):
    """bf16 q/slab run the same masked online softmax; scores and the
    accumulator stay f32, so the error is operand rounding, not drift."""
    rng = np.random.default_rng(3)
    q, kp, vp, tables = _random_case(rng, heads=heads)
    lengths = np.array([5, 11, 24, 16], np.int32)
    got = paged_decode_attention(
        jnp.asarray(q, jnp.bfloat16), jnp.asarray(kp, jnp.bfloat16),
        jnp.asarray(vp, jnp.bfloat16), tables, lengths, interpret=True)
    assert got.dtype == jnp.bfloat16
    want = _ref_attention(q, kp, vp, tables, lengths)
    np.testing.assert_allclose(np.asarray(got, np.float32), want, atol=0.05,
                               rtol=0.05)


@both_layouts
def test_kernel_length_clamping(heads):
    """Out-of-range lengths clamp to [1, W*page_len] — a row can never
    attend past its table extent nor to zero positions."""
    rng = np.random.default_rng(4)
    q, kp, vp, tables = _random_case(rng, heads=heads)
    wild = np.array([0, -3, 999, 24], np.int32)
    clamped = np.array([1, 1, 24, 24], np.int32)
    got = paged_decode_attention(q, kp, vp, tables, wild, interpret=True)
    want = _ref_attention(q, kp, vp, tables, clamped)
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-6, rtol=2e-6)


def test_page_len_validation_and_alignment():
    rng = np.random.default_rng(5)
    q = rng.standard_normal((1, 2, 1, 8)).astype(np.float32)
    bad = rng.standard_normal((4, 4, 2, 8)).astype(np.float32)  # page_len 4
    with pytest.raises(ValueError, match="multiple of"):
        paged_decode_attention(q, bad, bad, np.zeros((1, 2), np.int32),
                               np.ones(1, np.int32), interpret=True)
    # a slab whose rows are not the query's heads, in either layout
    for slab in ((4, 8, 3, 8), (4, 8, 3 * 8), (4, 8)):
        with pytest.raises(ValueError, match="k/v pages|does not match"):
            paged_decode_attention(q, np.zeros(slab, np.float32),
                                   np.zeros(slab, np.float32),
                                   np.zeros((1, 2), np.int32),
                                   np.ones(1, np.int32), interpret=True)
    assert align_page_len(1) == PAGE_SUBLANE
    assert align_page_len(8) == 8
    assert align_page_len(9) == 16
    with pytest.raises(ValueError):
        align_page_len(0)


# ------------------------------------------------ pages no row holds


def _ref_window_attention(q, k_pages, v_pages, tables, lengths, lower):
    """The window's obvious formulation: row ``b`` attends positions
    ``[lower[b], lengths[b])``, position ``p`` read from ring slot
    ``(p // page_len) % W``, one position at a time."""
    q = np.asarray(q, np.float32)
    kp = _by_head(k_pages, q.shape[1], q.shape[3])
    vp = _by_head(v_pages, q.shape[1], q.shape[3])
    W = tables.shape[1]
    out = np.zeros_like(q)
    for b in range(q.shape[0]):
        at = np.arange(lower[b], lengths[b])
        pids = tables[b, (at // PAGE_LEN) % W]
        k, v = kp[pids, at % PAGE_LEN], vp[pids, at % PAGE_LEN]
        s = np.einsum("kgd,tkd->kgt", q[b], k) / np.sqrt(q.shape[-1])
        p = np.exp(s - s.max(axis=2, keepdims=True))
        out[b] = np.einsum("kgt,tkd->kgd", p / p.sum(axis=2, keepdims=True), v)
    return out


_WINDOW = 2 * PAGE_LEN  # over a ring of W = 3 pages, as the engine sizes it
_FULL = 3 * PAGE_LEN    # _random_case's table extent

# last attended position of each of the four rows
_DEAD_PAGE_CASES = {
    "global": {
        "one": [0, 0, 0, 0],
        "page": [PAGE_LEN - 1] * 4,
        "page+1": [PAGE_LEN] * 4,
        "ragged": [0, 8, 16, 23],
        "full-table": [_FULL - 1] * 4,
    },
    "window": {
        "one": [0, 0, 0, 0],
        "page": [PAGE_LEN - 1] * 4,
        "page+1": [PAGE_LEN] * 4,
        "ragged": [3, 12, 20, 29],
        "full-ring": [_WINDOW + 6, _WINDOW + 14, 2 * _WINDOW + 6, 30],
        # lower = pos - window + 1 lands on a page's first position
        "lower-on-boundary": [_WINDOW - 1, _WINDOW + 7, _WINDOW + 15, 39],
        # the live pages straddle the ring's end: slots 2, 0 / 1, 2, 0 / ...
        "wrapped-ring": [26, 33, 40, 47],
    },
}


# a spec model's slab, at the call of the cell that runs it: (heads, variant,
# the cases above it takes)
_SPEC_CASES = [
    ("falconh1-4x5", "global", ["one", "page", "ragged"]),
    ("laguna-8x6", "global", ["page+1", "full-table"]),
    ("olmohybrid-30x1", "global", ["one", "ragged", "full-table"]),
    ("laguna-8x9", "window", ["one", "page+1", "full-ring",
                              "lower-on-boundary", "wrapped-ring"]),
]


@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize(
    "variant,case,heads",
    [(v, c, "dense-2x2") for v, cases in _DEAD_PAGE_CASES.items()
     for c in cases]
    + [(v, c, h) for h, v, cases in _SPEC_CASES for c in cases])
def test_pages_past_a_rows_length_are_never_read(variant, case, heads, dtype):
    """Both variants and both page layouts, on lengths around every page
    edge: (a) the kernel matches the obvious formulation, and (b) its
    output does not depend on what a page wholly past a row's length (or the
    pool's dummy page 0) holds — bit-equal with those pages full of NaN and
    of Inf. A kernel that only masks such a page computes ``0 x Inf`` on it
    and fails (b)."""
    rng = np.random.default_rng(7)
    q, kp, vp, tables = _random_case(rng, heads=heads)
    B, W = tables.shape
    pos = np.array(_DEAD_PAGE_CASES[variant][case], np.int32)
    lengths = pos + 1
    window_size = _WINDOW if variant == "window" else _FULL
    lower = np.maximum(pos - window_size + 1, 0).astype(np.int32)
    first = lower // PAGE_LEN  # all zero for the global variant
    window = (dict(first_page=first, lower=lower) if variant == "window"
              else {})
    want = _ref_window_attention(q, kp, vp, tables, lengths, lower)
    # the slots of each row's table that hold no attended position: a row
    # visits pages first .. first + W - 1, in slot page % W
    visited = first[:, None] + np.arange(W)[None, :]
    dead = visited * PAGE_LEN >= lengths[:, None]
    dead_slots = np.zeros_like(dead)
    np.put_along_axis(dead_slots, visited % W, dead, axis=1)
    dead_pages = tables[dead_slots]
    # even rows name the dummy page there, as the engine's tables do; odd
    # rows keep a page of their own (a ring's stale slot)
    tables = np.where(dead_slots & (np.arange(B) % 2 == 0)[:, None], 0,
                      tables).astype(np.int32)
    cast = lambda a: jnp.asarray(a, dtype)
    got = paged_decode_attention(cast(q), cast(kp), cast(vp), tables, lengths,
                                 interpret=True, **window)
    tol = 2e-6 if dtype is np.float32 else 0.05
    np.testing.assert_allclose(np.asarray(got, np.float32), want, atol=tol,
                               rtol=tol)
    for poison in (np.nan, np.inf):
        kx, vx = kp.copy(), vp.copy()
        kx[0] = vx[0] = poison
        kx[dead_pages] = vx[dead_pages] = poison
        again = paged_decode_attention(cast(q), cast(kx), cast(vx), tables,
                                       lengths, interpret=True, **window)
        np.testing.assert_array_equal(np.asarray(again, np.float32),
                                      np.asarray(got, np.float32))


def test_the_two_page_layouts_hold_the_same_numbers():
    """The same pages held ``(page_len, kvh, dh)`` and ``(page_len, kvh *
    dh)`` (a reshape: a token's heads are side by side either way) go to
    the two bodies of the kernel; the results differ by float association
    inside one contraction at most. Global and windowed, a dummy row."""
    rng = np.random.default_rng(11)
    q, kp, vp, tables = _random_case(rng, heads="falconh1-4x5")
    tables[2] = 0
    k4, v4 = (_by_head(t, 4, 128) for t in (kp, vp))
    lengths = np.array([24, 9, 1, 16], np.int32)
    lower = np.array([9, 0, 0, 8], np.int32)
    for window in ({}, dict(first_page=lower // PAGE_LEN, lower=lower)):
        flat = paged_decode_attention(q, kp, vp, tables, lengths,
                                      interpret=True, **window)
        by_head = paged_decode_attention(q, k4, v4, tables, lengths,
                                         interpret=True, **window)
        np.testing.assert_allclose(np.asarray(flat), np.asarray(by_head),
                                   atol=2e-6, rtol=2e-6)


# ------------------------------------- the walk against the grid-a-page body

_RING = 8  # table width of the walk's cases: more pages than the ring's slots

# last attended position of each of the four rows (a table of _RING pages;
# the walk holds six slots at these page sizes), and for a window its size
_WALK_CASES = {
    "global": {
        "one": [0, 0, 0, 0],
        # a length on a page edge, one past it, the whole table
        "page-edge": [PAGE_LEN - 1, 2 * PAGE_LEN - 1, PAGE_LEN,
                      _RING * PAGE_LEN - 1],
        # fewer live pages than slots, as many, one more, the table
        "few-and-many": [3 * PAGE_LEN - 3, 6 * PAGE_LEN - 1, 6 * PAGE_LEN,
                         _RING * PAGE_LEN - 2],
        # rows 1 and 3 are dummy rows: an all-zero table, length 1
        "dummy-rows": [5 * PAGE_LEN + 2, 0, 7 * PAGE_LEN, 0],
    },
    "window": {
        "one": [0, 0, 0, 0],
        # the live pages straddle the ring's end (slots 6, 7, 0, 1 ...)
        "ring-wraps": [9 * PAGE_LEN + 3, 12 * PAGE_LEN, 15 * PAGE_LEN + 7,
                       22 * PAGE_LEN + 1],
        # lower falls inside the first page visited; row 3 has no lower yet
        "lower-inside-first-page": [7 * PAGE_LEN + 3, 8 * PAGE_LEN + 5,
                                    20 * PAGE_LEN - 2, 2 * PAGE_LEN + 1],
    },
}
_WALK_WINDOW = 7 * PAGE_LEN - 2  # positions a windowed row attends


def _walk_case(variant, case, heads, dtype, seed=23):
    """Arrays of one of ``_WALK_CASES``, and the pages no row attends."""
    rng = np.random.default_rng(seed)
    q, kp, vp, tables = _random_case(rng, heads=heads, W=_RING,
                                     num_pages=4 * _RING + 1)
    pos = np.array(_WALK_CASES[variant][case], np.int32)
    lengths = pos + 1
    window = {}
    first = np.zeros_like(pos)
    if variant == "window":
        lower = np.maximum(pos - _WALK_WINDOW + 1, 0).astype(np.int32)
        first = lower // PAGE_LEN
        window = dict(first_page=first, lower=lower)
    if case == "dummy-rows":
        tables[[1, 3]] = 0
    visited = first[:, None] + np.arange(_RING)[None, :]
    dead = visited * PAGE_LEN >= lengths[:, None]
    dead_slots = np.zeros_like(dead)
    np.put_along_axis(dead_slots, visited % _RING, dead, axis=1)
    dead_pages = np.setdiff1d(tables[dead_slots], tables[~dead_slots])
    cast = lambda a: jnp.asarray(a, dtype)
    return cast(q), kp, vp, tables, lengths, window, dead_pages, cast


@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize(
    "variant,case,heads",
    [("global", c, h) for h in ("olmohybrid-30x1", "falconh1-4x5",
                                "laguna-8x6")
     for c in _WALK_CASES["global"]]
    + [("window", c, "laguna-8x9") for c in _WALK_CASES["window"]])
def test_the_walk_is_the_grid_a_page_body_bit_for_bit(variant, case, heads,
                                                      dtype):
    """A spec model's flat slab goes to the kernel that takes a grid step a
    row and walks the row's live pages through a ring of slots; the kernel
    it replaced (grid (rows, table width), a page a grid step, a head at a
    time: kept in ``tools/attn_page_step.py`` as the yardstick) saw the same
    pages in the same order: rows of length 1, lengths on a page edge, fewer
    live pages than slots and more, dummy rows, a ring that wraps, ``lower``
    inside the first page. The walk contracts every head of a page in one
    matmul from a block-diagonal query, at one query row a head (30 heads)
    and at groups of 5, 6 and 9 alike: the yardstick's products, the other
    heads' lanes adding exact zeros. On the chip that is the same bits
    (``max_abs_diff_from_grid`` 0.0, PERF.md PR 46); the CPU's interpreter
    sums a wider contraction in another order, so HERE the outputs agree to
    a few float32 ulps (one bfloat16 ulp of the output at most). And a NaN
    in a page past a row's length does not reach the output."""
    from tools.attn_page_step import _grid_kv_call

    q, kp, vp, tables, lengths, window, dead_pages, cast = _walk_case(
        variant, case, heads, dtype)
    got = np.asarray(paged_decode_attention(
        q, cast(kp), cast(vp), tables, lengths, interpret=True,
        **window).astype(jnp.float32))
    want = np.asarray(_grid_kv_call()(
        q, cast(kp), cast(vp), jnp.asarray(tables), jnp.asarray(lengths),
        *window.values()).astype(jnp.float32))
    tol = 2e-6 if dtype is np.float32 else 2 ** -7
    np.testing.assert_allclose(got, want, atol=tol, rtol=tol)
    assert np.isfinite(got).all()
    kx, vx = kp.copy(), vp.copy()
    kx[dead_pages] = vx[dead_pages] = np.nan
    if case != "dummy-rows":  # no row's live page is the pool's page 0
        kx[0] = vx[0] = np.nan
    again = paged_decode_attention(q, cast(kx), cast(vx), tables, lengths,
                                   interpret=True, **window)
    np.testing.assert_array_equal(np.asarray(again.astype(jnp.float32)), got)


_GQA = ("falconh1-4x5", "laguna-8x6", "laguna-8x9")


@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("variant,case", [
    ("global", "few-and-many"), ("global", "dummy-rows"),
    ("window", "lower-inside-first-page")])
@pytest.mark.parametrize("heads", _GQA)
def test_the_walk_at_gqa_groups_matches_the_reference(heads, variant, case,
                                                      dtype):
    """Groups of 5, 6 and 9 query rows a head (R = 32, 48 and 80 rows of
    the block-diagonal query, 12, 0 and 8 of them padding) against the
    obvious formulation in float32: ragged lengths over more pages than the
    ring has slots, dummy rows, and a window whose ``lower`` falls inside
    the first page visited."""
    q, kp, vp, tables, lengths, window, _, cast = _walk_case(
        variant, case, heads, dtype)
    got = paged_decode_attention(q, cast(kp), cast(vp), tables, lengths,
                                 interpret=True, **window)
    assert got.dtype == q.dtype
    lower = window.get("lower", np.zeros_like(lengths))
    want = _ref_window_attention(q, cast(kp), cast(vp), tables, lengths,
                                 lower)
    tol = 2e-6 if dtype is np.float32 else 0.05
    np.testing.assert_allclose(np.asarray(got, np.float32), want, atol=tol,
                               rtol=tol)


@pytest.mark.parametrize("heads", _GQA + ("olmohybrid-30x1",))
def test_other_heads_lanes_and_padding_rows_never_reach_a_head(heads):
    """Every head of a page in one matmul: a query row of head ``h`` also
    meets the other heads' VALUES (under its own probabilities, at their
    lanes of the accumulator), and the query's rows are padded to whole
    sublane tiles. Neither reaches the output. (a) With head 1's values NaN
    in every LIVE page, every other head's output keeps its bits and head
    1's is NaN. (b) With NaN in the query's padding rows (as a block that
    overhangs its array would hold) the output keeps its bits."""
    from unittest import mock

    from marlin_tpu.ops import paged_attention as pa

    kvh, group, dh, _ = _HEADS[heads]
    q, kp, vp, tables, lengths, window, _, cast = _walk_case(
        "global", "few-and-many", heads, jnp.bfloat16)
    args = (jnp.asarray(tables), jnp.asarray(lengths))
    clean = np.asarray(pa._kv_walk_call(
        q, cast(kp), cast(vp), *args, interpret=True).astype(jnp.float32))
    vx = vp.copy()
    vx[1:, :, dh:2 * dh] = np.nan   # page 0 is no row's: every other page
    got = np.asarray(pa._kv_walk_call(
        q, cast(kp), cast(vx), *args, interpret=True).astype(jnp.float32))
    assert np.isnan(got[:, 1]).all()
    others = [h for h in range(kvh) if h != 1]
    np.testing.assert_array_equal(got[:, others], clean[:, others])

    zero_rows = pa._query_rows

    def garbage_rows(q):
        return zero_rows(q).at[:, kvh * group:].set(jnp.nan)

    with mock.patch.object(pa, "_query_rows", garbage_rows):
        got = pa._kv_walk_call(q, cast(kp), cast(vp), *args, interpret=True)
    if -(kvh * group) % 16:     # Laguna's 48 rows are whole tiles
        assert np.isnan(np.asarray(garbage_rows(q), np.float32)).any()
    np.testing.assert_array_equal(np.asarray(got.astype(jnp.float32)), clean)


def _count_primitive(jaxpr, name):
    """Equations named ``name`` in ``jaxpr`` and every jaxpr under it."""
    n = 0
    for eqn in jaxpr.eqns:
        n += eqn.primitive.name == name
        for v in eqn.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    n += _count_primitive(sub, name)
    return n


def test_every_flat_slab_of_the_configurations_meets_one_page_body():
    """The package has ONE arithmetic for a rank-3 slab: at the heads of
    every attention layer of every benchmark configuration that reads as a
    spec (Falcon-H1 4 x 5, Laguna 8 x 6 and 8 x 9, Olmo-Hybrid 30 x 1, LFM2
    8 x 4 of 64, Solar-Open2 8 x 8; the latent configuration holds no (K, V)
    slab; a configuration that holds a share of its experts reads as a spec
    here without the share's arguments, which no head count depends on) the
    traced
    kernel holds two matmuls, a page's scores and its values, whatever the
    count of heads: no loop over heads around them."""
    import glob
    import json
    import os

    from marlin_tpu.models import hybrid
    from marlin_tpu.ops import paged_attention as pa

    assert not hasattr(pa, "_page_head_by_head")
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    shapes = set()
    for path in glob.glob(os.path.join(here, "benchmarks", "configs",
                                       "*.json")):
        with open(path) as f:
            cfg = json.load(f)
        try:
            spec = hybrid.ModelSpec.from_config(cfg)
        except ValueError:      # the matrix and the dense configurations
            continue
        shapes |= {(spec.kv_heads, ly.q_heads // spec.kv_heads,
                    spec.head_dim, ly.attn == "sliding")
                   for ly in spec.layers if ly.attn in ("full", "sliding")}
    assert shapes == {(4, 5, 128, False), (8, 6, 128, False),
                      (8, 9, 128, True), (30, 1, 128, False),
                      (8, 4, 64, False), (8, 8, 128, False)}
    for kvh, group, dh, windowed in shapes:
        q = jnp.zeros((2, kvh, group, dh), jnp.bfloat16)
        slab = jnp.zeros((5, PAGE_LEN, kvh * dh), jnp.bfloat16)
        row = jnp.ones((2,), jnp.int32)
        jaxpr = jax.make_jaxpr(lambda *a: pa._kv_walk_call(
            *a[:5], a[5:], interpret=False))(
            q, slab, slab, jnp.zeros((2, 3), jnp.int32), row,
            *((row, row) if windowed else ()))
        (call,) = [e for e in jaxpr.eqns if e.primitive.name == "pallas_call"]
        assert _count_primitive(call.params["jaxpr"], "dot_general") == 2


@pytest.mark.parametrize("slots", [2, 3])
@pytest.mark.parametrize("variant,case", [("global", "few-and-many"),
                                          ("window", "ring-wraps")])
def test_a_ring_smaller_than_a_rows_pages_is_reused_in_order(variant, case,
                                                             slots):
    """The ring's size is the kernel's own business (``_kv_slots``: what
    ``_KV_RING_BYTES`` hold of a page's keys and values, two at least): with
    two or three slots under rows of up to eight live pages every slot is
    written again behind its last reader, and the output is the six-slot
    ring's to the bit, and the grid body's."""
    from marlin_tpu.ops.paged_attention import _kv_walk_call
    from tools.attn_page_step import _grid_kv_call

    heads = "laguna-8x9" if variant == "window" else "falconh1-4x5"
    q, kp, vp, tables, lengths, window, _, cast = _walk_case(
        variant, case, heads, jnp.bfloat16)
    args = (q, cast(kp), cast(vp), jnp.asarray(tables), jnp.asarray(lengths))
    win = tuple(jnp.asarray(w) for w in window.values())
    got = np.asarray(_kv_walk_call(*args, win, interpret=True,
                                   slots=slots).astype(jnp.float32))
    np.testing.assert_array_equal(got, np.asarray(_kv_walk_call(
        *args, win, interpret=True).astype(jnp.float32)))
    np.testing.assert_allclose(
        got, np.asarray(_grid_kv_call()(*args, *win).astype(jnp.float32)),
        atol=2 ** -7, rtol=2 ** -7)


def test_the_ring_takes_its_slots_from_a_pages_bytes():
    """Six slots where a page is small (Falcon-H1's 2 x 262 KB, Laguna's 2 x
    524 KB: several copies in flight cover a copy's latency), three at
    Olmo-Hybrid's 2 x 1.97 MB (six would not fit the VMEM a kernel may
    ask for by default), two at its float32 pages."""
    from marlin_tpu.ops.paged_attention import _kv_slots

    def slab(kvh, dtype):
        return jax.ShapeDtypeStruct((2, 256, kvh * 128), dtype)

    assert _kv_slots(slab(4, jnp.bfloat16)) == 6
    assert _kv_slots(slab(8, jnp.bfloat16)) == 6
    assert _kv_slots(slab(8, jnp.float32)) == 6
    assert _kv_slots(slab(30, jnp.bfloat16)) == 3
    assert _kv_slots(slab(30, jnp.float32)) == 2


# ------------------------------------------- backend bit-identity grid


HEADS = 4
KV_HEADS = 2  # GQA: 2 query heads share each K/V head


@pytest.fixture(scope="module")
def params():
    return TransformerLM(vocab=32, d_model=16, heads=HEADS, layers=2,
                         kv_heads=KV_HEADS, seed=11).init_params()


def _ref(params, prompt, steps):
    prompt = np.asarray(prompt, np.int32)
    return np.asarray(lm_generate(
        params, prompt, jax.random.key(0), heads=HEADS,
        max_len=len(prompt) + steps, steps=steps)).tolist()


def _prefill_row(params, pages, table, prompt):
    """One-shot page-aligned prefill of ``prompt`` into ``table``'s pages
    (chunk padded with zeros past the prompt, as the prefill contract
    requires); returns ``(pages, first_token)``."""
    n = len(prompt)
    C = -(-n // PAGE_LEN) * PAGE_LEN
    chunk = np.zeros(C, np.int32)
    chunk[:n] = prompt
    tbl = np.concatenate([np.asarray(table, np.int32), np.zeros(2, np.int32)])
    pages, f = lm_prefill_paged(params, pages, tbl, chunk, 0, n, heads=HEADS,
                                page_len=PAGE_LEN)
    return pages, int(f)


def _decode_stream(params, kernel, prompts, steps, num_pages=32):
    """Prefill each prompt into its own pages, then run ``steps`` greedy
    decode steps through ``lm_decode_paged`` with the chosen backend; rows
    whose prompt is shorter keep decoding (ragged positions in one batch).
    Returns the per-row token streams (first token + decodes)."""
    B = len(prompts)
    W = max((len(p) + steps + PAGE_LEN - 1) // PAGE_LEN + 1 for p in prompts)
    pages = init_kv_pages(params, num_pages, PAGE_LEN, HEADS)
    tables = np.zeros((B, W), np.int32)
    first = np.zeros(B, np.int32)
    nxt_page = 1
    for b, prompt in enumerate(prompts):
        need = (len(prompt) + steps + PAGE_LEN - 1) // PAGE_LEN
        tables[b, :need] = range(nxt_page, nxt_page + need)
        nxt_page += need
        pages, first[b] = _prefill_row(params, pages, tables[b], prompt)
    streams = [[int(first[b])] for b in range(B)]
    positions = np.array([len(p) for p in prompts], np.int32)
    cur = first.copy()
    done = np.ones(B, np.int32)
    z = np.zeros(B, np.int32)
    for _ in range(steps - 1):
        pages, nxt = lm_decode_paged(
            params, pages, tables, positions, cur, done,
            z.astype(np.uint32), z.astype(np.float32),
            np.ones(B, np.float32), z, heads=HEADS, page_len=PAGE_LEN,
            kernel=kernel)
        nxt = np.asarray(nxt)
        for b in range(B):
            streams[b].append(int(nxt[b]))
        positions += 1
        done += 1
        cur = nxt.astype(np.int32)
    return streams


def test_greedy_bit_identity_pallas_vs_gather_vs_unpaged(params):
    """The contract: identical greedy token streams from the pallas kernel,
    the gather reference, and unpaged lm_generate — GQA model, ragged
    prompts (rows cross page boundaries on different steps)."""
    prompts = [np.arange(5) % 32, np.arange(9) % 32, np.arange(12) % 32,
               np.arange(7)[::-1] % 32]
    steps = 8
    gather = _decode_stream(params, "gather", prompts, steps)
    pallas = _decode_stream(params, "pallas", prompts, steps)
    assert pallas == gather
    for b, prompt in enumerate(prompts):
        assert gather[b] == _ref(params, prompt, steps)[len(prompt):]


def test_bit_identity_with_dummy_table_rows(params):
    """A mid-prefill row (all-zero table, the scheduler's dummy contract)
    riding the batch must not perturb live rows' streams, under either
    backend."""
    prompts = [np.arange(6) % 32, np.arange(10) % 32]
    steps = 6
    for kernel in ("gather", "pallas"):
        solo = _decode_stream(params, kernel, prompts, steps)
        B = 3  # same rows + one dummy slot
        W = (10 + steps + PAGE_LEN - 1) // PAGE_LEN + 1
        pages = init_kv_pages(params, 32, PAGE_LEN, HEADS)
        tables = np.zeros((B, W), np.int32)
        first = np.zeros(B, np.int32)
        nxt_page = 1
        for b, prompt in enumerate(prompts):
            need = (len(prompt) + steps + PAGE_LEN - 1) // PAGE_LEN
            tables[b, :need] = range(nxt_page, nxt_page + need)
            nxt_page += need
            pages, first[b] = _prefill_row(params, pages, tables[b], prompt)
        streams = [[int(first[b])] for b in range(2)]
        positions = np.array([6, 10, 0], np.int32)  # row 2: dummy
        cur = first.copy()
        done = np.ones(B, np.int32)
        z = np.zeros(B, np.int32)
        for _ in range(steps - 1):
            pages, nxt = lm_decode_paged(
                params, pages, tables, positions, cur, done,
                z.astype(np.uint32), z.astype(np.float32),
                np.ones(B, np.float32), z, heads=HEADS, page_len=PAGE_LEN,
                kernel=kernel)
            nxt = np.asarray(nxt)
            for b in range(2):
                streams[b].append(int(nxt[b]))
            positions += 1
            done += 1
            cur = nxt.astype(np.int32)
        assert streams == solo


def test_resolve_decode_kernel():
    assert resolve_decode_kernel("gather") == "gather"
    assert resolve_decode_kernel("pallas") == "pallas"
    expected = "pallas" if jax.default_backend() == "tpu" else "gather"
    assert resolve_decode_kernel("auto") == expected
    assert resolve_decode_kernel(None) == expected  # config default: auto
    with pytest.raises(ValueError):
        resolve_decode_kernel("fused")


# ------------------------------------------------------- engine end to end


def test_engine_pallas_backend_end_to_end(params):
    """The engine with ``decode_kernel='pallas'``: serves correct greedy
    outputs and aligns its page geometry to the kernel's block shape."""
    eng = ServeEngine(params, HEADS, buckets=((16, 8),), max_batch=4,
                      queue_depth=16, page_len=6,  # NOT kernel-legal: aligns
                      num_pages=64, decode_kernel="pallas")
    try:
        assert eng._page_len == 8  # align_page_len(6)
        assert eng._decode_kernel == "pallas"
        prompt = (np.arange(9) % 32).astype(np.int32)
        res = eng.submit(Request(prompt=prompt, steps=5)).result(timeout=300)
        assert res.status == STATUS_OK
        # Result.tokens carries prompt + generated, as lm_generate returns
        assert res.tokens.tolist() == _ref(params, prompt, 5)
    finally:
        eng.close()


def test_engine_gather_backend_unchanged_geometry(params):
    """decode_kernel='gather' keeps the configured page_len verbatim — no
    silent geometry change for the reference path."""
    eng = ServeEngine(params, HEADS, buckets=((16, 4),), max_batch=2,
                      queue_depth=8, page_len=4, num_pages=64,
                      decode_kernel="gather")
    try:
        assert eng._page_len == 4
        assert eng._decode_kernel == "gather"
    finally:
        eng.close()
