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
without a sort: the k-th largest score by a radix search on the scores' bit
patterns (sixteen counting passes, two bits a pass), the ties at that value
ranked by position, and the set bits of the resulting mask compacted to a
LIST of ``k`` positions with two levels of prefix counts (a lane tile of 128
positions, then the tiles), each a small matmul or a compare-and-count, no
scatter and no sort (:func:`selection_mask`, :func:`compact`). Exact: the
list is ``lax.top_k``'s set, in ascending position.

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
           "index_scores_gather", "selection_mask", "compact", "select_tokens", "attend_list",
           "gather_entries"]

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


def select_tokens(scores, n_valid, k: int):
    """``(idx (R, k) int32, count (R,) int32)``: each row's selection as a
    list of positions in ascending order (:func:`selection_mask`,
    :func:`compact`)."""
    mask, count = selection_mask(scores, n_valid, k)
    return compact(mask, count, k), count


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
