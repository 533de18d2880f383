"""Attention over a SELECTION of a row's TOKENS, chosen by a learned index
score over a second, narrow key cache (DeepSeek Sparse Attention: the
lightning indexer of the ``deepseek_v32`` configuration family, beside
latent attention; ``index_n_heads`` J, ``index_head_dim`` D, ``index_topk``
k; ``models/hybrid.py`` keeps the index keys in a second page-indexed array
beside a latent layer's slab).

For a token ``t`` with block input ``h_t`` (RMS-normed) and query latent
``c^Q_t = rmsnorm(W_qa h_t)`` (the main attention's own)::

    q^I_{t,j} = (W^I_qb c^Q_t)_j            j = 1..J, D wide
    k^I_t     = LayerNorm(W^I_k h_t)        D wide, gain AND bias, ONE head
    w_{t,j}   = (W^I_w h_t)_j J^-1/2 D^-1/2 float32

``q^I`` and ``k^I`` have their first ``rope_dim`` columns rotated with the
layer's own frequencies in the ROTATE-HALF layout (the main attention of the
family rotates adjacent pairs). ``k^I_t`` is what the second array keeps.

**Index score** ``I_{t,s} = sum_j w_{t,j} ReLU(q^I_{t,j} . k^I_s)`` for ``s
<= t`` (:func:`index_scores`; the kernels :func:`index_scores_chunk`, a
chunk's queries on the MXU, and :func:`index_scores_paged`, a decode row's
walk of its own pages, 256 B a token). The published implementation rotates
``q^I`` and ``k^I`` by a Hadamard matrix and quantises both to FP8 first; the
rotation is orthogonal and leaves ``q . k`` as it is, so it is left out with
the quantisation.

**Selection** ``S_t`` = the ``min(k, t + 1)`` positions ``s <= t`` of largest
``I_{t,s}``, ties to the LOWER position (``lax.top_k``'s order), a function
of the token's position and context alone. :func:`select_tokens` finds it
without a sort, as a LIST of ``k`` positions in ascending order, exactly
``lax.top_k``'s set: the k-th largest score by a radix search on the scores'
bit patterns, the ties at that value ranked by position, the set positions
compacted to the list. Two places hold that work, chosen by the operands'
shapes alone (:func:`select_kernel_supported`), the same list to the bit:

- ONE Pallas kernel a tile of rows (:func:`_select_kernel`, since PR 57;
  what the ``serve.deepseekv32-longctx32`` programs run): a group of 8 rows'
  scores stay in VMEM from the first counting pass to the last list entry
  (2.2 MB at 67,584 keys). 32 counting passes of one bit; the mask and every
  selected position's rank in one pass (prefix counts inside a lane tile by
  a product with a triangle of ones on the MXU); then each selected position
  MOVES to its place in the list, its distance's bits from the lowest up,
  17 passes of compares and selects: no one-hot, no scatter, nothing in HBM
  but the scores in and the list out. 0.15 ms a tile of 32 rows at
  67,584 keys on a v5e, where XLA's form takes 0.30-0.34 inside a program
  (it keeps the tile's scores in VMEM there) and 0.75-0.86 timed alone out
  of HBM (PERF.md section 6, PR 57).
- XLA's form (:func:`selection_mask`, :func:`compact`; whatever the kernel
  does not take, and the tests' second witness): sixteen counting passes of
  two bits over the (R, L) array in HBM, and the list by two levels of
  prefix counts (a lane tile of 128 positions, then the tiles), each a small
  matmul or a compare-and-count, the tile of an output place by a one-hot
  product.

**Attention** over ``S_t`` only, every head, one softmax over the set, in the
absorbed form (the query meets the cache entry itself; :func:`attend_list`):
a query's entries are gathered by its list, ``2048 x 1280 B`` where the
context holds ``t x 1280 B``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_kernels import _interpret

__all__ = ["index_scores", "index_scores_chunk", "index_scores_paged",
           "index_scores_gather", "selection_mask", "compact", "select_tokens",
           "select_kernel_supported", "attend_list", "gather_entries"]

_MASKED = -1e30  # as ops/paged_attention.py: exp() underflows to exactly 0
_LANES = 128     # positions a first-level prefix count spans: one lane tile


def index_scores(qi, w, keys, q_pos):
    """The reference formulation: ``qi`` (T, J, D), ``w`` (T, J) float32,
    ``keys`` (L, D), key ``s`` at position ``s``; returns ``I`` (T, L)
    float32, ``-inf`` where ``s > q_pos[t]``."""
    s = jnp.einsum("tjd,ld->tjl", qi, keys,
                   preferred_element_type=jnp.float32)
    out = jnp.sum(jnp.maximum(s, 0.0) * w[:, :, None], axis=1)
    seen = jnp.arange(keys.shape[0])[None, :] <= q_pos[:, None]
    return jnp.where(seen, out, -jnp.inf)


# ----------------------------------------------------- a chunk's index scores


def _chunk_scores_kernel(start_ref, q_ref, w_ref, k_ref, o_ref, *, heads: int):
    """Grid (query tiles, key tiles). ``q_ref`` (tq * J, D): a tile of
    queries, a query's heads in consecutive rows; ``w_ref`` (tq * J, 1)
    float32; ``k_ref`` (tk, D). One matmul meets every head of the tile with
    the key tile; ReLU, the head weights and the sum over a query's heads
    follow on the tile in VMEM: the (T, J, L) scores never exist. A key tile
    wholly after the tile's last query is not computed (its block index is
    clamped, so it is not fetched either), nor is a tile of queries wholly
    past the chunk's last token (``start_ref[1]``: the padding of a short
    chunk)."""
    qt, kt = pl.program_id(0), pl.program_id(1)
    tq, tk = o_ref.shape
    first_q = start_ref[0] + qt * tq
    met = (kt * tk <= first_q + tq - 1) & (first_q < start_ref[1])

    @pl.when(met)
    def _seen():
        s = jax.lax.dot_general(q_ref[...], k_ref[...],
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = jnp.maximum(s, 0.0) * w_ref[...]
        out = jnp.sum(s.reshape(tq, heads, tk), axis=1)
        q_at = first_q + jax.lax.broadcasted_iota(jnp.int32, out.shape, 0)
        k_at = kt * tk + jax.lax.broadcasted_iota(jnp.int32, out.shape, 1)
        o_ref[...] = jnp.where(k_at <= q_at, out, -jnp.inf)

    @pl.when(jnp.logical_not(met))
    def _ahead():
        o_ref[...] = jnp.full(o_ref.shape, -jnp.inf, jnp.float32)


@functools.partial(jax.jit, static_argnames=("tq", "tk", "interpret"))
def _dsa_index_chunk_call(qi, w, keys, start, tq: int, tk: int,
                          interpret: bool):
    """Its own jitted name: the kernel's operation in a trace takes it.
    ``start`` (2,): the first query's position and the position after the
    chunk's last token."""
    T, J, D = qi.shape
    L = keys.shape[0]
    kernel = functools.partial(_chunk_scores_kernel, heads=J)

    def key_tile(qt, kt, start_ref):
        return (jnp.minimum(kt, (start_ref[0] + (qt + 1) * tq - 1) // tk), 0)

    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(T // tq, L // tk),
            in_specs=[pl.BlockSpec((tq * J, D), lambda qt, kt, s: (qt, 0)),
                      pl.BlockSpec((tq * J, 1), lambda qt, kt, s: (qt, 0)),
                      pl.BlockSpec((tk, D), key_tile)],
            out_specs=pl.BlockSpec((tq, tk), lambda qt, kt, s: (qt, kt))),
        out_shape=jax.ShapeDtypeStruct((T, L), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(start, qi.reshape(T * J, D),
      w.astype(jnp.float32).reshape(T * J, 1), keys)


def index_scores_chunk(qi, w, keys, chunk_start, tokens=None, tq: int = 16,
                       tk: int = 512, interpret: bool | None = None):
    """:func:`index_scores` for a prefill chunk on the chip: ``qi`` (T, J,
    D), query ``i`` at position ``chunk_start + i``, against the row's index
    keys ``keys`` (L, D) in position order. ``T`` is whole query tiles of
    ``tq`` (a multiple of 8) and ``L`` whole key tiles of ``tk``. Only the
    first ``tokens`` queries hold a token (default: all): a tile of queries
    wholly past them reads ``-inf`` throughout."""
    T, L = qi.shape[0], keys.shape[0]
    tq, tk = min(tq, T), min(tk, L)
    if T % tq or L % tk:
        raise ValueError(f"a chunk of {T} queries and {L} keys is not whole "
                         f"tiles of {tq} x {tk}")
    if interpret is None:
        interpret = _interpret()
    start = jnp.asarray(chunk_start, jnp.int32)
    stop = start + (T if tokens is None else jnp.asarray(tokens, jnp.int32))
    return _dsa_index_chunk_call(qi, w, keys.astype(qi.dtype),
                                 jnp.stack([start, stop]), tq=tq, tk=tk,
                                 interpret=bool(interpret))


# ------------------------------------------------ a decode row's index scores

# as the latent decode kernel's: pages in flight beside the one being scored
_PAGE_SLOTS = 6


def _paged_scores_kernel(tables_ref, lengths_ref, q_ref, w_ref, slab_ref,
                         o_ref, page_buf, sem, *, page_len: int):
    """Grid (B,): one step a row; the kernel walks the row's LIVE pages of
    index keys itself, as the latent decode kernel walks its slab (page ``i``
    lands in slot ``i % slots``, the copies of the pages after it in flight
    beside its scores). ``q_ref`` (1, J, D); ``w_ref`` (1, J, 1) float32;
    ``o_ref`` (1, W, page_len): page ``i``'s scores in row ``i``, ``-inf``
    for a table entry past the row's length, which takes no step of any
    kind."""
    b = pl.program_id(0)
    length = lengths_ref[b]
    n = (length - 1) // page_len + 1
    slots = page_buf.shape[0]
    q, w = q_ref[0], w_ref[0]
    o_ref[...] = jnp.full(o_ref.shape, -jnp.inf, jnp.float32)

    def page_copy(i):
        slot = jax.lax.rem(i, slots)
        return pltpu.make_async_copy(slab_ref.at[tables_ref[b, i]],
                                     page_buf.at[slot], sem.at[slot])

    for k in range(slots - 1):
        @pl.when(k < n)
        def _first_pages():
            page_copy(k).start()

    def live_page(i, _):
        @pl.when(i + slots - 1 < n)
        def _page_ahead():   # into the slot of page i - 1, read and done
            page_copy(i + slots - 1).start()

        page_copy(i).wait()
        s = jax.lax.dot_general(q, page_buf[jax.lax.rem(i, slots)],
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        row = jnp.sum(jnp.maximum(s, 0.0) * w, axis=0, keepdims=True)
        at = i * page_len + jax.lax.broadcasted_iota(jnp.int32, row.shape, 1)
        o_ref[0, pl.ds(i, 1), :] = jnp.where(at < length, row, -jnp.inf)
        return None

    jax.lax.fori_loop(0, n, live_page, None)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _dsa_index_paged_call(qi, w, slab, tables, lengths, interpret: bool):
    """Its own jitted name: the kernel's operation in a trace takes it."""
    B, J, D = qi.shape
    W, page_len = tables.shape[1], slab.shape[1]
    kernel = functools.partial(_paged_scores_kernel, page_len=page_len)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B,),
            in_specs=[pl.BlockSpec((1, J, D), lambda b, t, n: (b, 0, 0)),
                      pl.BlockSpec((1, J, 1), lambda b, t, n: (b, 0, 0)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, W, page_len),
                                   lambda b, t, n: (b, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((_PAGE_SLOTS, page_len, D), slab.dtype),
                pltpu.SemaphoreType.DMA((_PAGE_SLOTS,))]),
        out_shape=jax.ShapeDtypeStruct((B, W, page_len), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(tables, lengths, qi, w.astype(jnp.float32)[:, :, None], slab)
    return out.reshape(B, W * page_len)


def index_scores_paged(qi, w, slab, tables, lengths,
                       interpret: bool | None = None):
    """A decode call's index scores, read out of the index keys' page slab in
    place: ``qi`` (B, J, D), ``w`` (B, J), ``slab`` (num_pages, page_len, D),
    ``tables`` (B, W), ``lengths`` (B,): row ``b`` scores the keys at
    positions below ``lengths[b]``. Returns (B, W * page_len) float32,
    ``-inf`` from ``lengths[b]`` on."""
    tables = jnp.asarray(tables, jnp.int32)
    lengths = jnp.clip(jnp.asarray(lengths, jnp.int32), 1,
                       tables.shape[1] * slab.shape[1])
    if interpret is None:
        interpret = _interpret()
    return _dsa_index_paged_call(qi.astype(slab.dtype), w, slab, tables,
                                 lengths, interpret=bool(interpret))


def index_scores_gather(qi, w, slab, tables, lengths):
    """The reference formulation of :func:`index_scores_paged`: each row's
    pages gathered, :func:`index_scores` over them."""
    B, W = tables.shape
    keys = slab[tables].reshape(B, W * slab.shape[1], slab.shape[2])
    return jax.vmap(lambda q, ww, k, n: index_scores(
        q[None], ww[None], k, n[None] - 1)[0])(qi, w, keys, lengths)


# ------------------------------------------------------------------ selection


def _ordered_bits(scores):
    """float32 -> uint32 whose unsigned order is the floats' order (``-0.0``
    as ``+0.0``; ``-inf`` lowest of the non-NaN values)."""
    bits = jax.lax.bitcast_convert_type(scores.astype(jnp.float32),
                                        jnp.uint32)
    bits = jnp.where(bits == jnp.uint32(0x80000000), jnp.uint32(0), bits)
    return jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(0x80000000))


def _prefix_counts(mask):
    """``mask`` (R, L) bool, L whole lane tiles -> ``(P, total)``: ``P`` (R,
    L / 128, 128) float32, the count of set positions of a tile up to and
    with each lane (a product with a triangle of ones: the MXU's, exact),
    and each tile's ``total`` (R, L / 128) int32."""
    R, L = mask.shape
    tri = (jnp.arange(_LANES)[:, None] <= jnp.arange(_LANES)[None, :])
    P = jnp.einsum("rbl,lm->rbm",
                   mask.reshape(R, L // _LANES, _LANES).astype(jnp.bfloat16),
                   tri.astype(jnp.bfloat16),
                   preferred_element_type=jnp.float32)
    return P, P[..., -1].astype(jnp.int32)


def selection_mask(scores, n_valid, k: int):
    """``scores`` (R, L) float32, of which row ``r``'s first ``n_valid[r]``
    positions count. Returns ``(mask (R, L) bool, count (R,))``: the
    ``count = min(k, n_valid)`` positions of largest score, ties to the
    lower position. L is whole lane tiles of 128."""
    R, L = scores.shape
    valid = jnp.arange(L)[None, :] < n_valid[:, None]
    u = jnp.where(valid, _ordered_bits(scores), jnp.uint32(0))
    count = jnp.minimum(n_valid, k).astype(jnp.int32)

    def digit(i, prefix):
        # two bits a pass: the largest of the three candidates that at least
        # `count` values still reach
        shift = (30 - 2 * i).astype(jnp.uint32)
        cands = prefix[:, None] | (jnp.arange(1, 4, dtype=jnp.uint32)[None, :]
                                   << shift)                      # (R, 3)
        reach = jnp.sum(u[:, None, :] >= cands[:, :, None], axis=-1,
                        dtype=jnp.int32)                          # (R, 3)
        step = jnp.sum(reach >= count[:, None], axis=-1).astype(jnp.uint32)
        return prefix | (step << shift)

    kth = jax.lax.fori_loop(0, 16, digit, jnp.zeros((R,), jnp.uint32))
    above = u > kth[:, None]
    tied = (u == kth[:, None]) & valid
    room = count - jnp.sum(above, axis=-1, dtype=jnp.int32)

    def ranked():   # more values tie at the k-th than it has room for
        P, total = _prefix_counts(tied)
        before = (jnp.cumsum(total, axis=-1) - total)[:, :, None]
        rank = (P.astype(jnp.int32) + before).reshape(R, L)   # from 1 on
        return above | (tied & (rank <= room[:, None]))

    # real scores tie at the k-th value with nothing but itself: every tied
    # position is then taken and the ranks (four passes over R x L) are not
    # computed
    fits = jnp.all(jnp.sum(tied, axis=-1, dtype=jnp.int32) == room)
    return jax.lax.cond(fits, lambda: above | tied, ranked), count


def compact(mask, count, k: int):
    """The positions ``mask`` (R, L) sets, ascending, as a list (R, k) int32;
    the places from ``count[r]`` on name position 0. No scatter and no sort:
    place ``o`` lies in the tile whose running total first exceeds ``o``
    (a count of the tiles whose total does not), that tile's prefix counts
    come to it by a one-hot product, and its lane is the count of lanes
    whose prefix does not exceed its rank inside the tile."""
    R, L = mask.shape
    P, total = _prefix_counts(mask)
    run = jnp.cumsum(total, axis=-1)                      # (R, nb) inclusive
    o = jnp.arange(k, dtype=jnp.int32)[None, :, None]     # (1, k, 1)
    behind = run[:, None, :] <= o                         # (R, k, nb)
    tile = jnp.minimum(jnp.sum(behind, axis=-1, dtype=jnp.int32),
                       L // _LANES - 1)
    rank = o[..., 0] - jnp.max(jnp.where(behind, run[:, None, :], 0), axis=-1)
    onehot = (tile[..., None] == jnp.arange(L // _LANES)[None, None, :])
    Psel = jnp.einsum("rkb,rbl->rkl", onehot.astype(jnp.bfloat16),
                      P.astype(jnp.bfloat16),
                      preferred_element_type=jnp.float32)   # (R, k, 128)
    lane = jnp.sum(Psel <= rank[..., None].astype(jnp.float32), axis=-1,
                   dtype=jnp.int32)
    idx = tile * _LANES + jnp.minimum(lane, _LANES - 1)
    return jnp.where(o[..., 0] < count[:, None], idx, 0)


# the selection as one kernel -------------------------------------------------

_ROWS = 8            # rows a grid step holds: one sublane tile
_PIECE = 16 * _LANES  # positions a step of the kernel's loops holds
_DEAD = 1 << 30      # a place no selected position stands on: no low bit set
_INT_MIN = -2 ** 31
_SELECT_VMEM = 24 << 20   # the most the call asks for (a row of ~190 k keys:
#                           the pieces are written out, a longer row is XLA's)
_SCOPED_VMEM = 16 << 20   # what a call has without asking


def _select_pieces(L: int) -> tuple:
    """``(width, pieces)``: the kernel goes over a row in ``pieces`` pieces
    of ``width`` positions, the last of them past ``L`` in part where ``L``
    is not whole pieces."""
    width = min(L, _PIECE)
    return width, -(-L // width)


def _select_vmem_bytes(L: int, k: int) -> int:
    """What :func:`_dsa_select_call` keeps in VMEM: two blocks of scores in
    flight, the keys / places array, two blocks of lists, and room for the
    loops' own values."""
    width, pieces = _select_pieces(L)
    return 4 * _ROWS * (2 * L + (pieces + 1) * width + 2 * k) + (6 << 20)


def select_kernel_supported(rows: int, L: int, k: int) -> bool:
    """Whether :func:`select_tokens` takes these sizes as ONE kernel: whole
    groups of 8 rows, whole lane tiles of positions, a list of whole lane
    tiles no longer than the row, and a group's blocks inside the VMEM the
    call asks for. Anything else keeps XLA's form."""
    return (rows > 0 and rows % _ROWS == 0 and L % _LANES == 0
            and k % _LANES == 0 and 0 < k <= L < _DEAD
            and _select_vmem_bytes(L, k) <= _SELECT_VMEM)


def _select_kernel(n_ref, s_ref, o_ref, a_ref, *, k: int):
    """Grid (R / 8,): a group of 8 rows, a row a sublane, its scores
    ``s_ref`` (8, L) resident in VMEM from the first counting pass to the
    last list entry (the next group's are copied meanwhile). ``n_ref`` (8, 1)
    the rows' ``n_valid``; ``o_ref`` (8, k) the lists; ``a_ref`` (8, (pieces
    + 1) x width) int32 is first the scores' ordered KEYS, then the selected
    positions on their way to the front. Every loop goes over the row in
    pieces of ``width`` positions (:func:`_select_pieces`).

    1. keys: the scores' bit patterns as int32 of the same order (``-0.0``
       as ``+0.0``), ``INT_MIN`` past ``n_valid``: :func:`_ordered_bits`
       with the top bit flipped, for the signed compare the chip has.
    2. the k-th largest key ``kth`` by the radix search, ONE bit a pass (two
       bits a pass are three candidates, nine operations a vreg where two
       passes of one are six): a pass counts the keys that reach a
       candidate, a compare, a select and an add a vreg and no HBM read. The
       count of keys ABOVE ``kth`` is the count of the last candidate
       refused (``kth`` + 1 is that candidate).
    3. one pass makes the mask and each selected position's RANK: the
       prefix counts inside a lane tile by a product with the triangle of
       ones on the MXU (exact, as :func:`_prefix_counts`; a piece's tiles a
       product, a tile's rows stacked), the tiles' running totals by the
       product's other half (ones). Ties at ``kth`` are taken in position
       order while there is room: a tied position's own prefix count says
       whether it is, so the ranks need no second pass and no branch. The
       array now holds ``position - rank`` (how far the position is from its
       place in the list) where a position is selected, ``_DEAD`` elsewhere.
    4. the list: every selected position moves to its place, the distances'
       bits from the lowest up, a bit a pass: whoever has the bit set moves
       ``2^j`` to the left (a lane rotation inside a tile below 128, whole
       tiles from there on). Two selected positions never meet: their
       distances differ by no more than what lies between them. No one-hot,
       no product, no scatter. The first ``k`` places then hold the list in
       ascending order: place ``o`` holds position ``o`` + its distance."""
    L = s_ref.shape[1]
    W, pieces = _select_pieces(L)
    g = W // _LANES
    n_valid = n_ref[...]                                  # (8, 1)
    count = jnp.minimum(n_valid, k)
    lane = jax.lax.broadcasted_iota(jnp.int32, (_ROWS, _LANES), 1)
    lanes = jax.lax.broadcasted_iota(jnp.int32, (_ROWS, W), 1)
    low = jnp.int32(_INT_MIN)

    def piece(c, ahead=0):
        at = c * W + ahead
        if isinstance(at, int):
            return pl.ds(min(at, pieces * W), W)
        return pl.ds(pl.multiple_of(jnp.minimum(at, pieces * W), _LANES), W)

    def keys(scores, at):
        b = pltpu.bitcast(scores, jnp.int32)
        b = jnp.where(b == low, 0, b)
        key = jnp.where(b < 0, b ^ jnp.int32(0x7FFFFFFF), b)
        return jnp.where(at < n_valid, key, low)

    def whole_piece(c, _):
        a_ref[:, piece(c)] = keys(s_ref[:, piece(c)], lanes + c * W)

    jax.lax.fori_loop(0, L // W, whole_piece, None, unroll=True)
    past = pieces * W - L      # of the last piece, what lies past the row
    if past:
        a_ref[:, pl.ds(L // W * W, W - past)] = keys(
            s_ref[:, pl.ds(L // W * W, W - past)],
            lanes[:, :W - past] + L // W * W)
        a_ref[:, pl.ds(L, past)] = jnp.full((_ROWS, past), low, jnp.int32)
    a_ref[:, piece(pieces)] = jnp.full((_ROWS, W), _DEAD, jnp.int32)

    def one_bit(i, carry):
        prefix, above = carry       # (8, 1): the bits found; keys over them
        bit = jnp.left_shift(jnp.int32(1), 31 - i)
        cand = (prefix | bit) ^ low

        def counted(c, acc):
            return acc + jnp.where(a_ref[:, piece(c)] >= cand, 1, 0)

        acc = jax.lax.fori_loop(0, pieces, counted,
                                jnp.zeros((_ROWS, W), jnp.int32), unroll=True)
        reach = jnp.sum(acc, axis=1, keepdims=True)
        take = reach >= count
        return (jnp.where(take, prefix | bit, prefix),
                jnp.where(take, above, reach))

    zero = jnp.zeros((_ROWS, 1), jnp.int32)
    prefix, above = jax.lax.fori_loop(0, 32, one_bit, (zero, zero))
    kth = prefix ^ low
    room = (count - above).astype(jnp.float32)

    # [l, m]: l <= m for m < 128 (prefix counts), 1 from there on (totals)
    tri = (jax.lax.broadcasted_iota(jnp.int32, (_LANES, 2 * _LANES), 0)
           <= jax.lax.broadcasted_iota(jnp.int32, (_LANES, 2 * _LANES), 1)
           ).astype(jnp.bfloat16)
    tiles = [slice(_LANES * t, _LANES * (t + 1)) for t in range(g)]

    def counts(marks):
        """A piece's marks (8, W) bool -> each tile's (prefix counts, total
        on every lane), (8, 128) float32."""
        rows = jnp.concatenate([jnp.where(marks[:, t], 1.0, 0.0)
                                for t in tiles], axis=0)
        # (the precision by name: under a default of "highest" Mosaic is
        # asked for float32 passes over bfloat16 operands and refuses)
        got = jnp.dot(rows.astype(jnp.bfloat16), tri,
                      precision=jax.lax.Precision.DEFAULT,
                      preferred_element_type=jnp.float32)
        return [(got[_ROWS * t:_ROWS * (t + 1), :_LANES],
                 got[_ROWS * t:_ROWS * (t + 1), _LANES:]) for t in range(g)]

    def ranks(c, carry):
        over_before, tied_before = carry    # (8, 128) float32, every lane
        key, at = a_ref[:, piece(c)], lanes + c * W
        is_over = key > kth
        is_tied = (key == kth) & (at < n_valid)
        out = []
        for t, (o, o_all), (e, e_all) in zip(tiles, counts(is_over),
                                             counts(is_tied)):
            tied_rank = e + tied_before
            taken = is_over[:, t] | (is_tied[:, t] & (tied_rank <= room))
            rank = o + over_before + jnp.minimum(tied_rank, room)  # from 1
            out.append(jnp.where(
                taken, at[:, t] + 1 - rank.astype(jnp.int32), _DEAD))
            over_before, tied_before = over_before + o_all, tied_before + e_all
        a_ref[:, piece(c)] = jnp.concatenate(out, axis=1)
        return over_before, tied_before

    # (a loop, not written out: 34 pieces x 16 tiles of it were a third of
    # what the kernel added to every start-up's trace and lowering, +22 s of
    # `setup_s` on the chip's host, for 13 us a tile)
    none = jnp.zeros((_ROWS, _LANES), jnp.float32)
    jax.lax.fori_loop(0, pieces, ranks, (none, none))

    def moved(c, s, ahead):
        # in place: the pieces ahead of this one are not yet moved
        here = a_ref[:, piece(c)]
        a_ref[:, piece(c)] = jnp.where(
            ahead & s != 0, ahead, jnp.where(here & s != 0, _DEAD, here))

    def by_lanes(j, _):
        """A move of ``2^j`` < 128 positions: every tile turned by as many
        lanes and joined to the turned tile after it."""
        s = jnp.left_shift(jnp.int32(1), j)

        def one(c, _):
            z = a_ref[:, pl.ds(pl.multiple_of(c * W, _LANES), W + _LANES)]
            turned = [pltpu.roll(z[:, _LANES * t:_LANES * (t + 1)],
                                 _LANES - s, 1) for t in range(g + 1)]
            moved(c, s, jnp.concatenate(
                [jnp.where(lane < _LANES - s, turned[t], turned[t + 1])
                 for t in range(g)], axis=1))

        jax.lax.fori_loop(0, pieces, one, None, unroll=True)

    def by_tiles(s, held=None):
        """A move of ``s`` >= 128 positions: whole tiles."""
        if held is None:
            jax.lax.fori_loop(
                0, pieces, lambda c, _: moved(c, s, a_ref[:, piece(c, s)]),
                None, unroll=True)
        for c in held or ():
            moved(c, s, a_ref[:, piece(c, s)])

    # the pieces are written out and the STEPS are the loops (a loop's turn
    # costs ~60 cycles that no schedule shows: 17 x 33 of them were as much
    # again as the moves themselves)
    bits = (L - 1).bit_length()
    jax.lax.fori_loop(0, min(bits, 7), by_lanes, None)
    # whoever is still on its way stands a multiple of 2 s from its place,
    # which is one of the first k: from s = k on (where pieces start on
    # multiples of s) the pieces that hold no such place hold nothing
    whole = [j for j in range(7, bits) if (1 << j) < k or (1 << j) % W]
    if whole:
        jax.lax.fori_loop(
            whole[0], whole[-1] + 1,
            lambda j, _: by_tiles(jnp.left_shift(jnp.int32(1), j)), None)
    for j in range(whole[-1] + 1 if whole else 7, bits):
        by_tiles(1 << j, [c for c in range(pieces) if c * W % (1 << j) < k])

    place = jax.lax.broadcasted_iota(jnp.int32, (_ROWS, k), 1)
    o_ref[...] = jnp.where(place < count, place + a_ref[:, :k], 0)


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def _dsa_select_call(scores, n_valid, k: int, interpret: bool):
    """Its own jitted name: the kernel's operation in a trace takes it."""
    R, L = scores.shape
    width, pieces = _select_pieces(L)
    need = _select_vmem_bytes(L, k)
    return pl.pallas_call(
        functools.partial(_select_kernel, k=k),
        grid=(R // _ROWS,),
        in_specs=[pl.BlockSpec((_ROWS, 1), lambda i: (i, 0)),
                  pl.BlockSpec((_ROWS, L), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((_ROWS, k), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((R, k), jnp.int32),
        scratch_shapes=[pltpu.VMEM((_ROWS, (pieces + 1) * width), jnp.int32)],
        # (a limit is named only where the default does not do: beside a
        # call that names one, XLA keeps less of the program's own arrays in
        # VMEM: the decode program's gathered entries, 84 MB a layer, left
        # it and the gather ran 0.82 -> 1.00 ms, PERF.md section 6 PR 57)
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=need if need > _SCOPED_VMEM else None),
        name="dsa_select",
        interpret=interpret,
    )(n_valid.reshape(R, 1), scores)


def select_tokens(scores, n_valid, k: int, interpret: bool | None = None):
    """``(idx (R, k) int32, count (R,) int32)``: each row's selection as a
    list of positions in ascending order, the places from ``count[r]`` on
    naming position 0. One kernel where the sizes allow
    (:func:`select_kernel_supported`: the choice goes by shapes alone), else
    XLA's form of the same algorithm (:func:`selection_mask`,
    :func:`compact`): the same list to the bit."""
    n_valid = jnp.asarray(n_valid, jnp.int32)
    if not select_kernel_supported(*scores.shape, k):
        mask, count = selection_mask(scores, n_valid, k)
        return compact(mask, count, k), count
    if interpret is None:
        interpret = _interpret()
    idx = _dsa_select_call(scores.astype(jnp.float32), n_valid, k=k,
                           interpret=bool(interpret))
    return idx, jnp.minimum(n_valid, k)


# ------------------------------------------------------------------ attention


def gather_entries(ctx, idx):
    """``ctx`` (L, E) and a list ``idx`` (R, k) -> the entries (R, k, E)."""
    return jnp.take(ctx, idx, axis=0, mode="clip")


def attend_list(q, entries, count, value_dim: int):
    """Absorbed attention of ``q`` (R, H, E) (scaled) over each row's own
    gathered ``entries`` (R, k, E) of which the first ``count[r]`` count: one
    softmax over the list, the entry's first ``value_dim`` columns the value.
    Returns the attended latents (R, H, value_dim) in ``q``'s dtype."""
    s = jnp.einsum("rhe,rke->rhk", q, entries,
                   preferred_element_type=jnp.float32)
    live = jnp.arange(entries.shape[1])[None, :] < count[:, None]
    p = jax.nn.softmax(jnp.where(live[:, None, :], s, _MASKED), axis=-1)
    return jnp.einsum("rhk,rkc->rhc", p.astype(q.dtype),
                      entries[..., :value_dim],
                      preferred_element_type=jnp.float32).astype(q.dtype)
