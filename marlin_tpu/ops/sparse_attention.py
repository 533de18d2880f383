"""Attention over a SELECTION of a row's blocks, chosen by a score over
mean-pooled keys (InfLLM-V2, the attention of the MiniCPM4 family: dense
below ``dense_len``, sparse from it on, no parameters of its own).

A KV head's keys are pooled into **compressed keys**: entry ``m`` is the mean
of the ``2 * stride`` keys at tokens ``stride * (m - 1) .. stride * (m + 1)
- 1`` (windows ``kernel = 2 * stride`` wide, ``stride`` apart, so neighbours
overlap by half; entry ``m`` is the published window ``j = m - 1``, and entry
0, whose window would begin before token 0, is never complete). Numbered so,
entry ``m`` is COMPLETE at a query at position ``t`` iff ``stride * (m + 1) -
1 <= t`` and belongs to the page that holds its LAST token: a page's entries
are a function of the tokens up to the page's end, so they can be shared,
copied and evicted with the page (``models/hybrid.py`` keeps them in a
third array beside a sparse layer's K and V slabs, ``page_len / stride``
entries a page).

For a query at position ``t >= dense_len``, per KV head (:func:`select_blocks`):

1. every query head of the group scores the complete entries, ``softmax_m(q_h
   . c_m / sqrt(dh))``, and the group's scores are summed AFTER the softmax;
2. a block ``b`` (tokens ``block * b .. block * (b + 1) - 1``) scores the
   largest of the windows that touch it: entries ``r b .. r b + r`` with ``r =
   block / stride`` (a max-pool of ``r + 1``, stride ``r``, padding 1 in the
   published window numbering);
3. block 0 (``init_blocks``) and the ``window / block`` blocks that end with
   the query's own are taken whatever they score; the rest of the ``topk``
   places go to the highest-scoring blocks between them, ties to the lower
   index (``lax.top_k``); where fewer than ``topk`` blocks exist all are taken;
4. the group's heads attend the tokens ``<= t`` of the chosen blocks, one
   softmax over them all.

A query below ``dense_len`` takes every block it can see. The selection of a
token depends on its POSITION alone, never on how a prompt was cut into
chunks.

Both programs hand the attention LISTS of blocks and read those blocks and
no other. Decode: each (row, KV head) its own list, to
:func:`~marlin_tpu.ops.paged_attention.paged_decode_attention_blocks`, which
reads the blocks out of the page slab (:func:`attend_blocks_gather` is the
same arithmetic on gathered blocks). Prefill: a chunk's queries are cut into
TILES of :func:`tile_tokens` tokens; a tile of a KV head lists the UNION of
the blocks its tokens took, and which of its tokens took each
(:func:`tile_lists`), to
:func:`~marlin_tpu.ops.paged_attention.sparse_prefill_attention`, which
copies a tile's blocks out of the row's context and shows each token its
own: a union never leaks a block to a token that did not choose it, and a
tile of padding is not computed. In the dense regime a tile lists every block
it can see: the same kernel. :func:`attend_selected` is the reference
formulation the tests hold that kernel to: the selection as a (query, block)
mask inside a loop over EVERY key (selecting IS masking; the dense FLOPs, its
float32 scores in HBM), which the prefill program ran until PR 49.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from .paged_attention import _BLOCKS_A_STEP as _ROUND, _LIST_LANES

__all__ = ["SparseSpec", "compress_keys", "select_blocks", "block_mask",
           "tile_tokens", "tile_lists", "attend_selected",
           "attend_blocks_gather"]

_MASKED = -1e30  # as ops/paged_attention.py: exp() underflows to exactly 0


@dataclasses.dataclass(frozen=True)
class SparseSpec:
    """The sizes of the block selection (the MiniCPM4 family's
    ``sparse_config``): pooling windows of ``2 * stride`` tokens ``stride``
    apart, blocks of ``block`` tokens, ``topk`` blocks a query (the forced
    ones among them: the first ``init_blocks`` and the ``window`` tokens'
    worth that end with the query's own), dense attention for a query below
    position ``dense_len``."""

    stride: int
    block: int
    topk: int
    init_blocks: int
    window: int
    dense_len: int

    def __post_init__(self):
        if self.block % self.stride or self.window % self.block:
            raise ValueError(
                f"sparse_config: kernel_stride {self.stride} must divide "
                f"block_size {self.block}, which must divide window_size "
                f"{self.window}")

    @property
    def window_blocks(self) -> int:
        return self.window // self.block

    @property
    def per_block(self) -> int:
        """Compressed entries that END in one block."""
        return self.block // self.stride

    def slots(self, n_blocks: int) -> int:
        """How long a query's list of blocks is among ``n_blocks``: ``topk``,
        or every block a query below ``dense_len`` can see where that is
        more."""
        return min(n_blocks, max(self.topk, -(-self.dense_len // self.block)))


def compress_keys(k_ext, stride: int):
    """``k_ext`` (``(n + 1) * stride``, width): the keys from ``stride``
    tokens BEFORE the first entry's last half on. Returns the ``n`` entries
    ``mean(k_ext[i * stride:(i + 2) * stride])`` in ``k_ext``'s dtype, summed
    in float32."""
    halves = k_ext.astype(jnp.float32).reshape(
        -1, stride, k_ext.shape[-1]).sum(axis=1)
    return ((halves[:-1] + halves[1:]) / (2.0 * stride)).astype(k_ext.dtype)


def _entry_scores(q, ck, complete):
    """Step 1: ``q`` (T, g, dh), ``ck`` (M, dh), ``complete`` (T, M). The
    group's softmax scores over the complete entries, summed over its heads:
    (T, M) float32, 0 where an entry is not complete."""
    s = jnp.einsum("tgd,md->gtm", q, ck,
                   preferred_element_type=jnp.float32) / math.sqrt(q.shape[-1])
    p = jax.nn.softmax(jnp.where(complete[None], s, _MASKED), axis=-1)
    return jnp.where(complete, p.sum(axis=0), 0.0)


def _pool_blocks(sm, per_block: int):
    """Step 2: entry scores ``sm`` (T, M) to block scores (T, M / per_block):
    block ``b`` takes the largest of entries ``r b .. r b + r`` (scores are
    >= 0 and an entry that is not complete holds 0)."""
    T, M = sm.shape
    own = sm.reshape(T, M // per_block, per_block)
    nxt = jnp.concatenate([own[:, 1:, 0], jnp.zeros((T, 1), sm.dtype)],
                          axis=1)
    return jnp.maximum(own.max(axis=-1), nxt)


def _forced(sp: SparseSpec, b_idx, own_block):
    """Step 3's blocks that are taken whatever they score: (T, NB) bool."""
    return (b_idx[None, :] < sp.init_blocks) | (
        b_idx[None, :] > own_block[:, None] - sp.window_blocks)


def select_blocks(q, ck, q_pos, sp: SparseSpec):
    """The blocks one KV head's group attends: ``q`` (T, g, dh) at positions
    ``q_pos`` (T,), ``ck`` (M, dh) the row's compressed keys (entry ``m`` as
    the module docstring numbers them; ``M`` a multiple of ``block /
    stride``). Returns ``(idx (T, S) int32, taken (T, S) bool)``: ``S =
    sp.slots(M / per_block)`` block indices a query, those ``taken`` first
    (a prefix of each row), the others to be ignored."""
    T, M = q.shape[0], ck.shape[0]
    nb = M // sp.per_block
    m_idx = jnp.arange(M)
    complete = (m_idx[None, :] >= 1) & (
        sp.stride * (m_idx[None, :] + 1) - 1 <= q_pos[:, None])
    score = _pool_blocks(_entry_scores(q, ck, complete), sp.per_block)
    b_idx = jnp.arange(nb)
    own = q_pos // sp.block
    dense = (q_pos < sp.dense_len)[:, None]
    key = jnp.where(_forced(sp, b_idx, own) | dense, jnp.inf, score)
    key = jnp.where(b_idx[None, :] <= own[:, None], key, -jnp.inf)
    S = sp.slots(nb)
    vals, idx = jax.lax.top_k(key, S)
    taken = (vals > -jnp.inf) & (dense | (jnp.arange(S)[None, :] < sp.topk))
    return idx.astype(jnp.int32), taken


def block_mask(idx, taken, n_blocks: int):
    """``(T, n_blocks)`` bool: the blocks each query's list names."""
    hit = (idx[:, :, None] == jnp.arange(n_blocks)[None, None, :]) \
        & taken[:, :, None]
    return hit.any(axis=1)


#: rows of a prefill tile's matmuls, its tokens x the group's heads: two
#: passes of the MXU's 128 rows. At the MiniCPM-SALA cell's chunk (a group of
#: 16) tiles of 8 / 16 / 32 tokens read 1.41 / 1.40 / 1.50 ms a layer with
#: their lists where neighbours choose apart and 0.50 / 0.33 / 0.27 where
#: they choose alike (tools/sparse_attend_step.py; PERF.md PR 49): fewer
#: rows copy a block once for fewer tokens, more rows meet a wider union
_TILE_ROWS = 256


def tile_tokens(T: int, group: int) -> int:
    """Tokens a prefill tile of a chunk of ``T`` queries holds: as many as
    fill ``_TILE_ROWS`` rows with the group's heads, at most the 32 bits of a
    word, a divisor of ``T``."""
    return math.gcd(T, max(1, min(32, _TILE_ROWS // group)))


def tile_lists(mask, q_pos, valid, block: int, tq: int):
    """What :func:`~marlin_tpu.ops.paged_attention.sparse_prefill_attention`
    walks: ``mask`` (kvh, T, NB) the blocks each query took
    (:func:`block_mask`; all ones for a query below ``dense_len``), queries
    at ``q_pos`` (T,), ``valid`` (T,) false for padding. A query counts only
    the blocks it can see (``<=`` its own), padding counts none. Tile ``i``
    (queries ``i * tq ..``) of a KV head lists the blocks ANY of its tokens
    took, ascending. Returns ``(lists, rounds, words, met, taken)``:
    ``lists`` (kvh, T / tq, S) int32 with ``S`` whole rows of ``_LIST_LANES``
    entries, block 0 past a list's end; ``rounds`` (kvh, T / tq) the
    ``_ROUND`` blocks' rounds that hold a list (0: a tile of padding);
    ``words`` (kvh, T / tq, S) int32, bit ``t`` of entry ``s`` set iff the
    tile's ``t``-th token took block ``lists[.., s]`` (0 past the end);
    ``met`` the blocks the tiles list and ``taken`` the blocks their tokens
    took, summed over tiles and heads: ``taken / met`` is how many tokens of
    a tile share a block it copies."""
    kvh, T, nb = mask.shape
    b_idx = jnp.arange(nb, dtype=jnp.int32)
    mine = mask & (valid[:, None]
                   & (b_idx[None, :] <= (q_pos // block)[:, None]))[None]
    bits = jnp.left_shift(jnp.uint32(1), jnp.arange(tq, dtype=jnp.uint32))
    words = jnp.sum(jnp.where(mine.reshape(kvh, T // tq, tq, nb),
                              bits[None, None, :, None], jnp.uint32(0)),
                    axis=2, dtype=jnp.uint32)
    listed = words != 0
    # a listed block's place in its tile's list: the listed blocks before it
    # (a triangular matmul; counts to NB are exact), then entry s of a list
    # is the one block whose place is s: no sort, no gather
    place = jnp.einsum("htb,bc->htc", listed.astype(jnp.bfloat16),
                       jnp.triu(jnp.ones((nb, nb), jnp.bfloat16), 1),
                       preferred_element_type=jnp.float32).astype(jnp.int32)
    S = -(-nb // _LIST_LANES) * _LIST_LANES
    here = listed[:, :, None, :] & (
        place[:, :, None, :] == jnp.arange(S, dtype=jnp.int32)[:, None])
    lists = jnp.sum(jnp.where(here, b_idx, 0), axis=-1, dtype=jnp.int32)
    words = jnp.sum(jnp.where(here, jax.lax.bitcast_convert_type(
        words, jnp.int32)[:, :, None, :], 0), axis=-1, dtype=jnp.int32)
    count = listed.sum(axis=-1, dtype=jnp.int32)
    return (lists, -(-count // _ROUND), words, count.sum(),
            mine.sum(dtype=jnp.int32))


def attend_selected(q, k, v, q_pos, mask, block: int, kv_block: int):
    """The reference formulation of prefill's tile walk (no program calls
    it): the attention of one KV head's group over the blocks each query
    selected, as a mask over every key. ``q`` (T, g, dh) at ``q_pos``;
    ``k``, ``v`` (L, dh), key ``j`` at position ``j``; ``mask`` (T, L /
    block) from :func:`block_mask`. Key
    ``j`` is visible to query ``i`` iff ``j <= q_pos[i]`` and the query took
    block ``j // block``. The keys are met ``kv_block`` at a time with a
    running softmax, and a key block that begins after the last query is
    skipped. Returns (T, g, dh) in ``q``'s dtype."""
    T, g, dh = q.shape
    L = k.shape[0]
    kv_block = -(-min(kv_block, L) // block) * block
    nkb, per = -(-L // kv_block), kv_block // block
    pad = nkb * kv_block - L       # keys past the table: no query took them
    k, v = (jnp.pad(x, ((0, pad), (0, 0))) for x in (k, v))
    mask = jnp.pad(mask, ((0, 0), (0, nkb * per - mask.shape[1])))
    scale = 1.0 / math.sqrt(dh)
    masks = mask.reshape(T, nkb, per).transpose(1, 0, 2)      # (nkb, T, per)

    def step(carry, blk):
        kb, vb, mb, b0 = blk

        def meet(carry):
            m, l, acc = carry
            s = jnp.einsum("tgd,sd->gts", q, kb,
                           preferred_element_type=jnp.float32) * scale
            seen = jnp.repeat(mb, block, axis=1) & (
                (b0 + jnp.arange(kv_block))[None, :] <= q_pos[:, None])
            s = jnp.where(seen[None], s, _MASKED)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            alpha = jnp.exp(m - m_new)
            # a query that has met no key of its own yet (m_new still the
            # mask's value) must not count the masked ones: exp(0) = 1
            p = jnp.where(seen[None], jnp.exp(s - m_new[..., None]), 0.0)
            pv = jnp.einsum("gts,sd->gtd", p.astype(q.dtype), vb,
                            preferred_element_type=jnp.float32)
            return (m_new, alpha * l + jnp.sum(p, axis=-1),
                    acc * alpha[..., None] + pv)

        return jax.lax.cond(b0 <= q_pos[-1], meet, lambda c: c, carry), None

    init = (jnp.full((g, T), _MASKED, jnp.float32),
            jnp.zeros((g, T), jnp.float32),
            jnp.zeros((g, T, dh), jnp.float32))
    (_, l, acc), _ = jax.lax.scan(
        step, init, (k.reshape(nkb, kv_block, dh), v.reshape(nkb, kv_block, dh),
                     masks, jnp.arange(nkb) * kv_block))
    return (acc / l[..., None]).transpose(1, 0, 2).astype(q.dtype)


def attend_blocks_gather(q, pk, pv, tables, idx, taken, lengths, block: int):
    """The reference formulation of the block-walk decode kernel: ``q`` (B,
    kvh, g, dh); the slabs ``(num_pages, page_len, kvh * dh)``; ``tables``
    (B, W); ``idx``, ``taken`` (B, kvh, S) each (row, KV head)'s list of
    blocks; ``lengths`` (B,): the tokens of the taken blocks below a row's
    length are gathered and attended, one softmax over them. Returns (B,
    kvh, g, dh) in ``q``'s dtype."""
    B, kvh, g, dh = q.shape
    page_len = pk.shape[1]
    pos = (idx[..., None] * block + jnp.arange(block)).reshape(B, kvh, -1)
    live = jnp.repeat(taken, block, axis=-1) & (pos < lengths[:, None, None])
    pos = jnp.where(live, pos, 0)
    pids = jnp.take_along_axis(tables[:, None, :], pos // page_len, axis=2)
    head = jnp.arange(kvh)[None, :, None]

    def heads(slab):
        return slab.reshape(*slab.shape[:2], kvh, dh)[pids, pos % page_len,
                                                      head]

    s = jnp.einsum("bkgd,bkld->bkgl", q, heads(pk),
                   preferred_element_type=jnp.float32) / math.sqrt(dh)
    p = jax.nn.softmax(jnp.where(live[:, :, None, :], s, _MASKED), axis=-1)
    return jnp.einsum("bkgl,bkld->bkgd", p.astype(q.dtype), heads(pv))
