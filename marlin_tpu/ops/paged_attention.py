"""Fused paged decode-attention: read the KV page slab in place.

The paged decode path (models/transformer.lm_decode_paged) historically
GATHERED each row's context out of the page slab by block table
(``t[tables].reshape(B, L, ...)``) and then ran dense attention over the
materialized copy — a per-step copy of every live row's whole context whose
cost a CPU serve bench older than PR 27 read at −5±3% tok/s vs the dense
slab on no-prefix workloads (no chip reading; `PERF.md` has the chip's).
This module is the kernels that erase the copy: the block table itself
names the pages that stream HBM→VMEM directly from the slab, and the context
is never materialized as a separate array.

Shapes follow the slab exactly, and the slab's RANK picks the kernel
(nothing else does: no argument, no flag, no model's name). The dense model
(:func:`~marlin_tpu.models.transformer.init_kv_pages`) holds K/V pages
``(num_pages, page_len, kv_heads, dh)``; a
:class:`~marlin_tpu.models.hybrid.ModelSpec` model
(:func:`~marlin_tpu.models.hybrid.init_kv_pages`) ``(num_pages, page_len,
kv_heads * dh)``, a token's heads side by side in one row. Queries arrive
in the GQA-grouped form ``(B, kv_heads, group, dh)`` the decode step
already uses (``group = heads // kv_heads``; plain MHA is the group=1
case), and the score/value contractions are the SAME as
:func:`~marlin_tpu.models.transformer._decode_step`'s (``kgd,tkd->kgt`` /
``kgt,tkd->kgd``, f32 scores, masked positions at −1e30) so the kernel's
math is the reference path's math, re-scheduled: on a rank-4 page as
those two einsums, batched over the block's MIDDLE axis
(:func:`_page_all_heads`); on a rank-3 page as ONE matmul for every head
(:func:`_page_every_head`): the row's ``kv_heads * group`` queries stand one
under the other in a block-diagonal query (row ``h * group + g`` holds head
``h``'s ``g``-th query at the head's lanes ``[h * dh, (h + 1) * dh)`` and
zeros elsewhere: :func:`_block_diagonal_query`, built in VMEM once a row),
the page is the other operand whole, never sliced, and the other heads'
lanes add exact zeros to a score. That holds for any count of heads, any
group and any head width (a head of 64 shares a lane tile with its
neighbour and is no special case), so a flat slab has one body.

**Why two layouts, and which model holds which.** In VMEM a ``(page_len,
kv_heads, dh)`` block is ``page_len`` tiles of ``(kv_heads, dh)``, one a
token, 4 or 8 of a tile's sublanes filled, and one head's keys are one
sublane out of each: the batched einsum pays a relayout that goes with
``page_len`` and not with bytes. At a spec model's 256-token pages that was
8.4-9.1 us a live grid step for 0.64-1.28 us of bytes. Over the flat page a
head's keys are a lane slice ``[:, h * dh:(h + 1) * dh]`` of whole tiles,
and contracted a head at a time that is 1.3-2.8 us (PR 38: Falcon-H1's 4
heads x group 5, Laguna's 8 x 6 and 8 x 9), the float32 blocks of a
256-token page fit scoped VMEM, which the relayout's did not; but a head at
a time is ``kv_heads`` chains of matmul, softmax, matmul that each wait for
their own results, 1.15-2.28 us a page for copies of 0.70-1.41 (7.95 for
5.2 at Olmo-Hybrid's thirty heads of one query row). Every head in one
matmul does ``kv_heads`` times the useful flops and costs 0.55-1.5 us, under
the page's copy at every shape a configuration has (R = 32 to 80 query rows;
``tools/attn_page_step.py``, which keeps the head-at-a-time body as its
yardstick; PERF.md PR 43, PR 44, PR 46). The dense model keeps rank 4 and
the einsum body: with ONE query row a head and 16 keys a page its remedy is
more tokens a grid step, another mechanism (ROADMAP Queue 3 has the
condition that ends the split).

**Two schedules.** The dense model's kernel (:func:`_paged_attn_kernel`)
lays a grid over (B, W), pages innermost: the block table drives the Pallas
``index_map``, a grid step a table entry. A spec model's
(:func:`_kv_walk_kernel`, PR 43) takes a grid step a ROW and walks the
row's live pages itself, as the latent kernel below does since PR 40: both
slabs stay in HBM, the kernel reads each row's page count from the
scalar-prefetched lengths and copies the K and V pages into a ring of VMEM
slots (as many as fit ``_KV_RING_BYTES``: six of a Falcon-H1 page, three of
an Olmo-Hybrid one), waiting only for the page it is about to use. The
call's live pages are ONE stream of copies across its rows: while a row's
last pages are computed the next row's first are already in flight, so a
row costs its flush and not a copy's latency. A table entry past the row's
length costs nothing at all, where the grid pays a step that does nothing
(~0.2 us: four fifths of the steps at the chat cells' fill), and a live
page's copy runs beside the pages before it instead of in front of its own
arithmetic. The pages meet the online softmax in the same order and with
the same products, so on the chip the walk's output is the grid form's bit
for bit (``tools/attn_page_step.py`` keeps the grid form of the flat slab,
a head at a time, as the yardstick; tests/test_paged_attention.py compares
them on the CPU, where a wider contraction sums in another order).

Softmax is the online (flash) form: running max ``m``, normalizer ``l`` and
the f32 accumulator live in VMEM scratch across a row's pages; each page
rescales the accumulator by ``exp(m_old − m_new)``. Reduction order
therefore differs from the dense softmax by float associativity (logits
agree to ~ulp); greedy argmax is unaffected, which is the serving
bit-identity contract (tests/test_paged_attention.py drives it).

Per-row ``lengths`` masks the tail: inside a row's last live page,
positions ``>= lengths[b]`` score −1e30 exactly as the gather path masks
them. A page wholly past a row's length is never read: the walk does not
visit it; on the grid its step runs neither einsum nor the softmax update
(``pl.when``), and the index map names the row's last live page again, so
the pipeline starts no copy for it and never reads the table entry. The
numbers are those of masking it (it would contribute ``exp(−1e30 − m) = 0``
and rescale by ``exp(0) = 1``), except that the output no longer depends on
what such a page holds: a NaN or Inf there used to turn ``0 × Inf`` into
NaN. The first page a row visits always has a live position (lengths are
clamped ≥ 1, mirroring the decode path's position clamp), so the running
max is finite at the flush. An engine that pads to ``max_batch`` rows over a
bucket's whole table pays the dense grid one scalar compare for each step
it skips. Dummy rows (all-zero block tables, the free/prefilling-slot
contract) attend one masked-harmless position of the sacrificial page 0: one
live page.

**The latent variant** (:func:`paged_decode_attention_latent`): a latent
layer's page is ONE array ``(num_pages, page_len, entry)`` shared by every
head; the absorbed decode query (``entry`` wide, scale folded in) meets it
directly: the page is the key, its first ``value_dim`` columns the value.
Its kernel takes a grid step a ROW and walks the row's live pages itself:
the slab stays in HBM, the pages ahead are copied into a ring of slots
while this page's softmax and values and the next page's scores are
computed; the same table, mask and online softmax, no dead step (PR 40).

``interpret=`` defaults through :func:`~.pallas_kernels._interpret` —
interpreter everywhere but real TPU — so the tier-1 CPU suites exercise
the real kernel body, not a stand-in.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_kernels import _interpret

__all__ = ["paged_decode_attention", "paged_decode_attention_latent",
           "align_page_len", "PAGE_SUBLANE"]

# TPU sublane multiple: the kernel's K/V block second-to-minor dimension is
# page_len, so pages must stay a multiple of this for an unpadded block
# (init_kv_pages documents the same constraint for the gather fast path).
PAGE_SUBLANE = 8

_MASKED = -1e30  # the decode path's mask value — shared so exp() underflows
#                  to an exact 0.0 for dead positions in both formulations


def align_page_len(page_len: int) -> int:
    """Smallest kernel-legal page length >= ``page_len`` (a multiple of
    :data:`PAGE_SUBLANE`) — the engine aligns ``serve_page_len`` through
    here when the pallas decode backend is selected."""
    if page_len < 1:
        raise ValueError(f"page_len must be >= 1, got {page_len}")
    return -(-page_len // PAGE_SUBLANE) * PAGE_SUBLANE


def _paged_attn_kernel(*refs, page_len: int, windowed: bool = False):
    """Grid (B, W), W innermost ("arbitrary": pages run sequentially per
    row). Scalar-prefetched ``tables`` select the K/V block — the in-place
    read; q/out blocks index by row only, so they stay resident across a
    row's pages while the online-softmax state accumulates in scratch.
    ``windowed`` adds two scalar-prefetched vectors: grid step ``w`` of row
    ``b`` is the page of positions ``(first_page[b] + w) * page_len ...``
    (its block picked by :func:`_paged_decode_attention_window_call`'s index
    map) and positions below ``lower[b]`` are masked like those at or past
    the length. A step whose page starts at or past ``lengths[b]`` skips the
    body; the first page visited holds a live position (position 0, or
    ``lower[b]``), so the running max is finite and ``l`` positive at the
    flush. The dense model's kernel: a page block ``(1, page_len, kvh, dh)``
    meets all heads in one batched einsum (:func:`_page_all_heads`); a spec
    model's flat slab goes to :func:`_kv_walk_kernel`."""
    if windowed:
        (tables_ref, lengths_ref, first_ref, lower_ref, q_ref, k_ref, v_ref,
         o_ref, acc_ref, m_ref, l_ref) = refs
    else:
        (tables_ref, lengths_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref,
         l_ref) = refs
    b = pl.program_id(0)
    w = pl.program_id(1)

    @pl.when(w == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _MASKED)
        l_ref[:] = jnp.zeros_like(l_ref)

    # a page wholly past the row's length holds nothing the row attends:
    # the step does no compute (and, its block index clamped to the row's
    # last live page by the index map, starts no copy). Skipping it leaves
    # m, l and acc exactly as the masked formulation did (alpha = 1, p = 0).
    page = first_ref[b] + w if windowed else w

    @pl.when(page * page_len < lengths_ref[b])
    def _live_page():
        def keep(shape, axis):
            # absolute position of column t is page*page_len + t; live iff
            # < length (and, for a window, >= lower)
            at = page * page_len + jax.lax.broadcasted_iota(jnp.int32, shape,
                                                            axis)
            live = at < lengths_ref[b]
            return live & (at >= lower_ref[b]) if windowed else live

        _page_all_heads(q_ref, k_ref, v_ref, acc_ref, m_ref, l_ref, keep)

    @pl.when(w == pl.num_programs(1) - 1)
    def _flush():
        o_ref[0] = (acc_ref[:] / l_ref[:][:, :, None]).astype(o_ref.dtype)


def _page_all_heads(q_ref, k_ref, v_ref, acc_ref, m_ref, l_ref, keep):
    """One live page of a ``(page_len, kvh, dh)`` block: every KV head in one
    einsum batched over the block's middle axis. The dense model's body (one
    query row a head, 16 keys a page); scratch ``m``/``l`` are (kvh, group)."""
    q = q_ref[0]  # (kvh, group, dh) — compute dtype
    k = k_ref[0]  # (page_len, kvh, dh)
    v = v_ref[0]
    dh = q.shape[-1]
    # the _decode_step score einsum, f32 scores, same 1/sqrt(dh) scaling
    s = jnp.einsum("kgd,tkd->kgt", q, k,
                   preferred_element_type=jnp.float32) / math.sqrt(dh)
    s = jnp.where(keep(s.shape, 2), s, _MASKED)
    # online-softmax update: new running max, rescale the old accumulator
    m_prev = m_ref[:]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=2))
    m_ref[:] = m_new
    alpha = jnp.exp(m_prev - m_new)  # 0.0 on the w==0 init (m_prev=-1e30)
    p = jnp.exp(s - m_new[:, :, None])  # masked cols underflow to exact 0
    l_ref[:] = alpha * l_ref[:] + jnp.sum(p, axis=2)
    # probabilities meet V in the compute dtype — q's dtype, the same cast
    # _decode_step applies (p.astype(cd)); the accumulator stays f32
    pv = jnp.einsum("kgt,tkd->kgd", p.astype(q.dtype), v,
                    preferred_element_type=jnp.float32)
    acc_ref[:] = acc_ref[:] * alpha[:, :, None] + pv


def _page_every_head(qbd_ref, k_page, v_page, acc_ref, m_ref, l_ref, live,
                     dh: int):
    """One live page ``(page_len, kvh * dh)`` of a spec model (``k_page`` /
    ``v_page``: refs of that shape), every head in ONE matmul. ``qbd_ref``
    (R, kvh * dh) is the row's block-diagonal query
    (:func:`_block_diagonal_query`): query row ``h * group + g`` holds head
    ``h``'s ``g``-th query at lanes ``[h * dh, (h + 1) * dh)`` and zeros
    elsewhere, so ``qbd @ k_page^T`` is (R, page_len) with that row the
    scores of head ``h`` (the other heads' lanes add exact zeros); one online
    softmax over the R rows (``live``: (1, page_len), true where the row
    attends the position); ``p @ v_page`` is (R, kvh * dh), of which a row
    of head ``h`` holds its values at ITS lanes (elsewhere another head's
    values under this head's probabilities, never read): the accumulator
    keeps all of it and the flush reads the diagonal blocks. The page is
    never sliced, so a head narrower than a lane tile (``dh`` 64) is no
    special case. (A key that is not finite in ANY head's lanes of a live
    page reaches every head's scores, ``0 x Inf``, where a head at a time
    it reached its own head's: pages hold what the programs wrote.) The
    same scores, mask, online softmax and cast as
    :func:`_page_all_heads`; scratch ``m``/``l`` are (R, 1), ``acc`` (R, kvh
    * dh). ``kvh`` times the useful flops, and still the cheaper form than a
    head at a time (module docstring; PERF.md PR 43, PR 46)."""
    nt = (((1,), (1,)), ((), ()))      # contract both minor dimensions
    s = jax.lax.dot_general(qbd_ref[...], k_page[...], nt,
                            preferred_element_type=jnp.float32) / math.sqrt(dh)
    s = jnp.where(live, s, _MASKED)
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    m_ref[...] = m_new
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
        p.astype(qbd_ref.dtype), v_page[...],
        preferred_element_type=jnp.float32)


def _block_diagonal_query(q_ref, qbd_ref, group: int, dh: int):
    """``qbd_ref`` (R, kvh * dh) from ``q_ref`` (1, R, dh), a row's queries
    one under the other (row ``h * group + g`` is head ``h``'s ``g``-th; the
    rows past ``kvh * group`` pad R to whole sublane tiles): every row
    repeated across the heads' lanes, kept where the lanes are its own
    head's, zero elsewhere (:func:`_page_every_head`). Built in VMEM once a
    row: built by the caller it is an array ``kvh`` times the query's size
    written and read back a call."""
    kvh = qbd_ref.shape[1] // dh
    row = jax.lax.broadcasted_iota(jnp.int32, qbd_ref.shape, 0)
    head = jax.lax.broadcasted_iota(jnp.int32, qbd_ref.shape, 1) // dh
    mine = (row >= head * group) & (row < (head + 1) * group)
    q = jnp.tile(q_ref[0].astype(jnp.float32), (1, kvh))
    qbd_ref[...] = jnp.where(mine, q, 0.0).astype(qbd_ref.dtype)


# page slots of the (K, V) walk: as many as _KV_RING_BYTES hold of one page's
# keys AND values, at least the two that let one copy run beside the
# arithmetic, at most _KV_MAX_SLOTS (a Falcon-H1 page is 2 x 262 KB and
# wants several copies in flight: one takes ~0.5 us from start to done; an
# Olmo-Hybrid page is 2 x 1.97 MB, its copy 5 us, and three slots cover it)
_KV_RING_BYTES = 12 << 20
_KV_MAX_SLOTS = 6


def _kv_slots(slab) -> int:
    pair = 2 * math.prod(slab.shape[1:]) * slab.dtype.itemsize
    return int(min(_KV_MAX_SLOTS, max(2, _KV_RING_BYTES // pair)))


def _kv_walk_kernel(*refs, page_len: int, windowed: bool):
    """Grid (B,): one step a row of a spec model's flat slab, and the kernel
    walks the row's LIVE pages itself, as :func:`_latent_attn_kernel` does.
    ``k_hbm`` / ``v_hbm`` are the whole slabs, left in HBM. The call's live
    pages are ONE stream (row 0's, then row 1's ...): stream entry ``g``
    lands in slot ``g % slots`` of ``k_buf`` / ``v_buf`` (slots, page_len,
    kvh * dh), a DMA semaphore a slot and array, and consuming entry ``g``
    starts the copy of entry ``g + slots - 1``, whichever row holds it: a
    row's first pages are in flight while the row before it is computed (a
    row that starts its own costs 1.5-6.7 us where this costs 0.5-1.0;
    PERF.md PR 43). ``cur`` (SMEM, kept across grid steps) is the cursor of
    the copies: the row and page of the next entry to start and the count
    started; ``used`` the count consumed. Plain: a row's pages are ``0 .. n
    - 1``, ``n`` from its length. ``windowed``: pages ``first_page[b] ..``
    the page of position ``lengths[b] - 1``, page ``p`` in table slot ``p %
    W``, positions below ``lower[b]`` masked. No step of any kind for a
    table entry past a row's length; a row's first page holds a live
    position (position 0, or ``lower[b]``), so ``l`` is positive at the
    flush.

    ONE arithmetic for every page, whatever the heads' count, width and
    query rows: :func:`_page_every_head`, all heads in one matmul, from the
    block-diagonal query ``qbd_ref`` built here once a row out of ``q_ref``
    (1, R, dh), the row's ``kvh * group`` queries one under the other; the
    flush reads each head's ``group`` rows at its lanes. Pages meet the
    online softmax in the order of the grid-a-page kernel this replaced
    (``tools/attn_page_step.py`` keeps that one, a head at a time, as the
    yardstick): on the chip the output is that kernel's bit for bit (the
    other heads' lanes add exact zeros)."""
    n = 4 if windowed else 2               # scalar-prefetched arrays
    (tables_ref, lengths_ref, *window), refs = refs[:n], refs[n:]
    first_ref, lower_ref = window or (None, None)
    (q_ref, k_hbm, v_hbm, o_ref, k_buf, v_buf, sem, cur, used, acc_ref, m_ref,
     l_ref, qbd_ref) = refs
    b = pl.program_id(0)
    rows = pl.num_programs(0)
    W = tables_ref.shape[1]
    slots = k_buf.shape[0]
    kvh, group, dh = o_ref.shape[1:]

    def first_of(r):
        return first_ref[r] if windowed else 0

    def pages_of(r):  # live pages of row r; >= 1 by the clamp
        return (lengths_ref[r] - 1) // page_len - first_of(r) + 1

    def copies(pid, g):
        slot = jax.lax.rem(g, slots)
        return [pltpu.make_async_copy(hbm.at[pid], buf.at[slot],
                                      sem.at[a, slot])
                for a, (hbm, buf) in enumerate(((k_hbm, k_buf),
                                                (v_hbm, v_buf)))]

    def start_next():
        r, p, g = cur[0], cur[1], cur[2]

        @pl.when(r < rows)
        def _start():
            page = first_of(r) + p
            pid = tables_ref[r, jax.lax.rem(page, W) if windowed else page]
            for copy in copies(pid, g):
                copy.start()
            last = p + 1 >= pages_of(r)
            cur[0] = jnp.where(last, r + 1, r)
            cur[1] = jnp.where(last, 0, p + 1)
            cur[2] = g + 1

    @pl.when(b == 0)
    def _first_pages():
        cur[0] = cur[1] = cur[2] = 0
        used[0] = 0
        for _ in range(slots - 1):
            start_next()

    _block_diagonal_query(q_ref, qbd_ref, group, dh)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, _MASKED)
    l_ref[...] = jnp.zeros_like(l_ref)
    length = lengths_ref[b]
    base = used[0]

    @pl.loop(0, pages_of(b))
    def _live_page(i):
        # into the slot of the entry before this one, whose reader has run
        start_next()
        for copy in copies(0, base + i):
            copy.wait()
        # absolute position of column t is page * page_len + t; live iff
        # < length (and, for a window, >= lower)
        at = (first_of(b) + i) * page_len + jax.lax.broadcasted_iota(
            jnp.int32, (1, page_len), 1)
        live = at < length
        if windowed:
            live &= at >= lower_ref[b]
        slot = jax.lax.rem(base + i, slots)
        _page_every_head(qbd_ref, k_buf.at[slot], v_buf.at[slot], acc_ref,
                         m_ref, l_ref, live, dh)

    used[0] = base + pages_of(b)
    for h in range(kvh):    # the diagonal blocks: group rows a head
        mine = slice(h * group, (h + 1) * group)
        o_ref[0, h] = (acc_ref[mine, h * dh:(h + 1) * dh]
                       / l_ref[mine, :]).astype(o_ref.dtype)


def _query_rows(q):
    """``q`` (B, kvh, group, dh) as the walk is handed it, (B, R, dh): a
    row's ``kvh * group`` queries one under the other (row ``h * group + g``
    head ``h``'s ``g``-th) and rows of zeros below them up to R, whole
    sublane tiles in either dtype. No padding row reaches the output,
    whatever it holds (:func:`_page_every_head`)."""
    B, kvh, group, dh = q.shape
    return jnp.pad(q.reshape(B, kvh * group, dh),
                   ((0, 0), (0, -(kvh * group) % 16), (0, 0)))


def _kv_walk_call(q, k_pages, v_pages, tables, lengths, window=(), *,
                  interpret: bool, slots: int | None = None):
    """The walk over a flat slab ``(num_pages, page_len, kvh * dh)``;
    ``window`` is ``(first_page, lower)`` or empty. The kernel is handed a
    row's queries one under the other (:func:`_query_rows`: R is 32 at
    Falcon-H1's, Olmo-Hybrid's and LFM2's heads, 48 / 80 on Laguna's full /
    sliding layers). The ring is :func:`_kv_slots`'s (``slots``: the tool's
    and the tests' to vary) and the compiler is asked for the VMEM it
    needs."""
    B, kvh, group, dh = q.shape
    page_len, width = k_pages.shape[1:]
    slots = slots or _kv_slots(k_pages)
    ring = 2 * slots * page_len * width * k_pages.dtype.itemsize
    rows = _query_rows(q)
    heads = rows.shape[1]
    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    return pl.pallas_call(
        functools.partial(_kv_walk_kernel, page_len=page_len,
                          windowed=bool(window)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2 + len(window),
            grid=(B,),
            in_specs=[pl.BlockSpec((1, heads, dh), lambda b, *_: (b, 0, 0)),
                      in_hbm, in_hbm],
            out_specs=pl.BlockSpec((1, kvh, group, dh),
                                   lambda b, *_: (b, 0, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((slots, page_len, width), k_pages.dtype),
                pltpu.VMEM((slots, page_len, width), v_pages.dtype),
                pltpu.SemaphoreType.DMA((2, slots)),
                pltpu.SMEM((3,), jnp.int32),    # cur: row, page, started
                pltpu.SMEM((1,), jnp.int32),    # used
                pltpu.VMEM((heads, width), jnp.float32),  # accumulator
                pltpu.VMEM((heads, 1), jnp.float32),      # running max m
                pltpu.VMEM((heads, 1), jnp.float32),      # normalizer l
                pltpu.VMEM((heads, width), q.dtype),      # block-diagonal q
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, kvh, group, dh), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=max(16 << 20, ring + (4 << 20))),
        interpret=interpret,
    )(tables, lengths, *window, rows, k_pages, v_pages)


def _page_block(slab, q):
    """The block of one page of the dense model's ``slab`` and the scratch
    of a row's online softmax (see :func:`_paged_attn_kernel`): ``(block
    shape, zeros that follow the page id in its block index, scratch)``."""
    _, kvh, group, dh = q.shape
    return (1,) + slab.shape[1:], (0,) * (slab.ndim - 1), [
        pltpu.VMEM((kvh, group, dh), jnp.float32),  # accumulator
        pltpu.VMEM((kvh, group), jnp.float32),      # running max m
        pltpu.VMEM((kvh, group), jnp.float32),      # normalizer l
    ]


@functools.partial(jax.jit, static_argnames=("page_len", "interpret"))
def _paged_decode_attention_call(q, k_pages, v_pages, tables, lengths,
                                 page_len: int, interpret: bool):
    if k_pages.ndim == 3:
        return _kv_walk_call(q, k_pages, v_pages, tables, lengths,
                             interpret=interpret)
    B, kvh, group, dh = q.shape
    W = tables.shape[1]
    kernel = functools.partial(_paged_attn_kernel, page_len=page_len)
    row_spec = pl.BlockSpec((1, kvh, group, dh),
                            lambda b, w, tbl, lens: (b, 0, 0, 0))
    # THE in-place read: the block table entry is the K/V block index. A
    # step past the row's last live page names that page again: the block
    # index does not change, so the pipeline copies nothing for it
    block, tail, scratch = _page_block(k_pages, q)
    page_spec = pl.BlockSpec(
        block,
        lambda b, w, tbl, lens: (
            tbl[b, jnp.minimum(w, (lens[b] - 1) // page_len)], *tail))
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, W),
            in_specs=[row_spec, page_spec, page_spec],
            out_specs=row_spec,
            scratch_shapes=scratch,
        ),
        out_shape=jax.ShapeDtypeStruct((B, kvh, group, dh), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(tables, lengths, q, k_pages, v_pages)


@functools.partial(jax.jit, static_argnames=("page_len", "interpret"))
def _paged_decode_attention_window_call(q, k_pages, v_pages, tables, lengths,
                                        first_page, lower, page_len: int,
                                        interpret: bool):
    """The kernel over a row's window: ``tables`` is the ring of W pages in
    which position ``p`` lives in slot ``(p // page_len) % W``; the grid
    visits the W pages from ``first_page[b]`` on, in position order. Its own
    jitted name, so a trace tells the two attention kinds apart."""
    if k_pages.ndim == 3:
        return _kv_walk_call(q, k_pages, v_pages, tables, lengths,
                             (first_page, lower), interpret=interpret)
    B, kvh, group, dh = q.shape
    W = tables.shape[1]
    kernel = functools.partial(_paged_attn_kernel, page_len=page_len,
                               windowed=True)
    row_spec = pl.BlockSpec((1, kvh, group, dh),
                            lambda b, w, tbl, lens, first, low: (b, 0, 0, 0))
    block, tail, scratch = _page_block(k_pages, q)
    page_spec = pl.BlockSpec(
        block,
        lambda b, w, tbl, lens, first, low: (
            tbl[b, jax.lax.rem(jnp.minimum(first[b] + w,
                                           (lens[b] - 1) // page_len), W)],
            *tail))
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(B, W),
            in_specs=[row_spec, page_spec, page_spec],
            out_specs=row_spec,
            scratch_shapes=scratch,
        ),
        out_shape=jax.ShapeDtypeStruct((B, kvh, group, dh), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(tables, lengths, first_page, lower, q, k_pages, v_pages)


def paged_decode_attention(q, k_pages, v_pages, tables, lengths,
                           interpret: bool | None = None,
                           first_page=None, lower=None) -> jax.Array:
    """Decode attention for a batch of rows directly over the page slab.

    ``q`` is ``(B, kv_heads, group, dh)`` (the grouped decode-query form;
    ``group = heads // kv_heads``), ``k_pages``/``v_pages`` the slab, as the
    model holds it: ``(num_pages, page_len, kv_heads, dh)`` (the dense
    model; every head in one batched einsum) or ``(num_pages, page_len,
    kv_heads * dh)`` (a spec model; every head in one matmul from a
    block-diagonal query: module docstring). The array's rank says which;
    ``tables`` ``(B, W)`` int32
    block tables (dummy page 0 beyond a row's extent), ``lengths`` ``(B,)``
    the number of live positions per row — for a decode step at position
    ``pos`` whose K/V entry is already written, ``pos + 1``. Returns the
    attention output ``(B, kv_heads, group, dh)`` in ``q``'s dtype.

    The row's pages are read IN PLACE through the block table (no gathered
    context array); masking, GQA mapping, and softmax numerics follow
    :func:`~marlin_tpu.models.transformer._decode_step` (module docstring).
    Only the pages that hold positions below ``lengths[b]`` are read and
    computed on; the grid steps of the others do nothing, and what those
    pages (or table entries) hold cannot reach the output.

    With ``first_page`` and ``lower`` (both (B,)) the row attends a window:
    only positions in ``[lower[b], lengths[b])``, and ``tables`` is the
    row's ring of W pages (position ``p`` in slot ``(p // page_len) % W``),
    visited from page ``first_page[b]`` (= ``lower[b] // page_len``) on, up
    to the page of position ``lengths[b] - 1``: a ring slot past it (stale,
    or not yet written) is skipped like a page past the length.
    """
    q = jnp.asarray(q)
    if q.ndim != 4:
        raise ValueError(f"q must be (B, kv_heads, group, dh), got {q.shape}")
    kvh, dh = q.shape[1], q.shape[3]
    if k_pages.shape != v_pages.shape or k_pages.ndim not in (3, 4):
        raise ValueError(f"k/v pages must share one (num_pages, page_len, "
                         f"kv_heads, dh) or (num_pages, page_len, kv_heads * "
                         f"dh) shape, got {k_pages.shape} vs {v_pages.shape}")
    page_len = int(k_pages.shape[1])
    if tuple(k_pages.shape[2:]) not in ((kvh, dh), (kvh * dh,)):
        raise ValueError(f"page slab {k_pages.shape} does not match query "
                         f"heads {q.shape}")
    if page_len % PAGE_SUBLANE:
        raise ValueError(
            f"page_len {page_len} is not a multiple of {PAGE_SUBLANE} — the "
            f"kernel's K/V block would be sublane-misaligned; size pages "
            f"through align_page_len()")
    tables = jnp.asarray(tables, jnp.int32)
    if tables.ndim != 2 or tables.shape[0] != q.shape[0]:
        raise ValueError(f"tables must be (B, W) with B={q.shape[0]}, got "
                         f"{tables.shape}")
    W = tables.shape[1]
    if interpret is None:
        interpret = _interpret()
    if (first_page is None) != (lower is None):
        raise ValueError("a window needs both first_page and lower")
    lengths = jnp.asarray(lengths, jnp.int32)
    if lower is not None:
        # the same clamp on the ring's own extent: at least one position,
        # never past the W pages that start at first_page
        first_page = jnp.asarray(first_page, jnp.int32)
        lower = jnp.asarray(lower, jnp.int32)
        lengths = jnp.clip(lengths, lower + 1, (first_page + W) * page_len)
        return _paged_decode_attention_window_call(
            q, k_pages, v_pages, tables, lengths, first_page, lower,
            page_len=page_len, interpret=bool(interpret))
    # clamp as the decode path clamps positions: every row attends at least
    # position 0 (length 1), never past its table extent
    lengths = jnp.clip(lengths, 1, W * page_len)
    return _paged_decode_attention_call(q, k_pages, v_pages, tables, lengths,
                                        page_len=page_len,
                                        interpret=bool(interpret))


# page slots of the latent kernel: a page in use, the next being scored, the
# others' copies in flight. A copy takes ~0.47 us from start to done and a
# page's arithmetic 0.25, so two slots leave the step at 0.54 us; alone on
# a v5e at the Mistral cell's call 4 / 5 / 6 / 8 slots read 0.313 / 0.273 /
# 0.269 / 0.267 us a page, the copies alone 0.263 (PERF.md, PR 40)
_LATENT_SLOTS = 6


def _latent_attn_kernel(tables_ref, lengths_ref, q_ref, slab_ref, o_ref,
                        page_buf, sem, *, page_len: int, value_dim: int):
    """Grid (B,): one step a row, and the kernel walks the row's LIVE pages
    itself. ``q_ref`` (1, H, entry) is the row's absorbed, scaled query;
    ``slab_ref`` the whole slab, left in HBM; ``page_buf`` (slots, page_len,
    entry) where pages land, page ``i`` in slot ``i % slots``, ``sem`` a DMA
    semaphore a slot. Two things run beside a page's softmax and value
    product: the copies of the pages after the next (started here: a block
    index read from a table is one the pipeline does not run ahead of far
    enough), and the NEXT page's scores, which the loop carries (one
    iteration's matmuls have nothing to wait for in the other's reductions
    and exponentials, and within one iteration the compiler interleaves
    them). A page is read once: all ``entry`` columns are the key, the first
    ``value_dim`` the value. The score is split at ``value_dim`` (a
    lane-tile boundary at the published sizes) so that neither contraction
    crosses a partial tile. Pages meet the online softmax in order, each
    with the arithmetic of the grid-a-page kernel this replaced: the output
    is that kernel's bit for bit. No step of any kind for a table entry past
    the row's length."""
    b = pl.program_id(0)
    length = lengths_ref[b]
    n = (length - 1) // page_len + 1       # live pages; >= 1 by the clamp
    slots = page_buf.shape[0]
    q = q_ref[0]                           # (H, entry)
    heads = q.shape[0]
    nt = (((1,), (1,)), ((), ()))          # contract both minor dimensions

    def slot_of(i):
        return jax.lax.rem(i, slots)

    def page_copy(i):
        return pltpu.make_async_copy(slab_ref.at[tables_ref[b, i]],
                                     page_buf.at[slot_of(i)],
                                     sem.at[slot_of(i)])

    def scores(i):
        e = page_buf[slot_of(i)]                       # (page_len, entry)
        s = (jax.lax.dot_general(q[:, :value_dim], e[:, :value_dim], nt,
                                 preferred_element_type=jnp.float32)
             + jax.lax.dot_general(q[:, value_dim:], e[:, value_dim:], nt,
                                   preferred_element_type=jnp.float32))
        at = i * page_len + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        return jnp.where(at < length, s, _MASKED)

    def attend(state, s, i):
        m_prev, l_prev, acc = state
        c = page_buf[slot_of(i), :, :value_dim]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)    # 0.0 at the first page
        p = jnp.exp(s - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        return m_new, l_new, acc * alpha + jnp.dot(
            p.astype(q.dtype), c, preferred_element_type=jnp.float32)

    for k in range(slots - 1):
        @pl.when(k < n)
        def _first_pages():
            page_copy(k).start()
    page_copy(0).wait()

    def live_page(i, carry):
        state, s = carry

        @pl.when(i + slots - 1 < n)
        def _page_ahead():
            # into the slot of page i - 1, whose last reader has run
            page_copy(i + slots - 1).start()

        page_copy(i + 1).wait()
        return attend(state, s, i), scores(i + 1)

    state, s = jax.lax.fori_loop(0, n - 1, live_page, ((
        jnp.full((heads, 1), _MASKED, jnp.float32),     # running max m
        jnp.zeros((heads, 1), jnp.float32),             # normalizer l
        jnp.zeros((heads, value_dim), jnp.float32)),    # accumulator
        scores(0)))
    _, l, acc = attend(state, s, n - 1)
    o_ref[0] = (acc / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("value_dim", "interpret"))
def _paged_decode_attention_latent_call(q, slab, tables, lengths,
                                        value_dim: int, interpret: bool):
    """Its own jitted name, so a trace tells the kernel from the (K, V)
    variants."""
    B, H, E = q.shape
    page_len = slab.shape[1]
    kernel = functools.partial(_latent_attn_kernel, page_len=page_len,
                               value_dim=value_dim)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B,),
            in_specs=[pl.BlockSpec((1, H, E), lambda b, tbl, lens: (b, 0, 0)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, H, value_dim),
                                   lambda b, tbl, lens: (b, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((_LATENT_SLOTS, page_len, E), slab.dtype),
                pltpu.SemaphoreType.DMA((_LATENT_SLOTS,)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, value_dim), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(tables, lengths, q, slab)


def paged_decode_attention_latent(q, slab, tables, lengths, value_dim: int,
                                  interpret: bool | None = None) -> jax.Array:
    """Absorbed decode attention of a latent layer, directly over its page
    slab. ``q`` (B, H, entry): each row's per-head query against a cache
    ENTRY (the softmax scale already folded in); ``slab`` ``(num_pages,
    page_len, entry)``; ``tables`` (B, W) and ``lengths`` (B,) as
    :func:`paged_decode_attention`. Row ``b`` attends the entries at
    positions below ``lengths[b]``: score ``q . entry``, value the entry's
    first ``value_dim`` columns. Returns the attended values (B, H,
    value_dim) in ``q``'s dtype; the caller applies the value half of the
    up-projection. Each page a row holds is read once, in place, and a page
    past the row's length not at all."""
    q = jnp.asarray(q)
    if q.ndim != 3 or slab.ndim != 3 or q.shape[2] != slab.shape[2]:
        raise ValueError(f"q must be (B, H, entry) and the slab (num_pages, "
                         f"page_len, entry), got {q.shape} and {slab.shape}")
    if not 0 < value_dim <= slab.shape[2]:
        raise ValueError(f"value_dim {value_dim} is not within the entry's "
                         f"{slab.shape[2]} columns")
    page_len = int(slab.shape[1])
    if page_len % PAGE_SUBLANE:
        raise ValueError(
            f"page_len {page_len} is not a multiple of {PAGE_SUBLANE}; size "
            f"pages through align_page_len()")
    tables = jnp.asarray(tables, jnp.int32)
    if tables.ndim != 2 or tables.shape[0] != q.shape[0]:
        raise ValueError(f"tables must be (B, W) with B={q.shape[0]}, got "
                         f"{tables.shape}")
    if interpret is None:
        interpret = _interpret()
    if not interpret and slab.shape[2] % 128:
        raise ValueError(
            f"an entry of {slab.shape[2]} columns is not whole lane tiles of "
            f"128: the chip copies a page out of the slab tile by tile; store "
            f"it padded with zeros (LatentSpec.entry_width)")
    lengths = jnp.clip(jnp.asarray(lengths, jnp.int32), 1,
                       tables.shape[1] * page_len)
    return _paged_decode_attention_latent_call(
        q, slab, tables, lengths, value_dim=int(value_dim),
        interpret=bool(interpret))


# prefill's side of the slab, below the decode kernels: their programs'
# cache keys hold their line numbers (PERF.md section 7)
__all__ += ["fetch_pages"]


def _fetch_pages_kernel(table_ref, slab_ref, out_ref, sem):
    """One grid step: page ``table[j]`` of the slab to page ``j`` of the
    output, HBM to HBM, every copy started before the first is awaited (all
    are one size, so they share the semaphore)."""
    def copy(j):
        return pltpu.make_async_copy(slab_ref.at[table_ref[j]],
                                     out_ref.at[j], sem)

    n = out_ref.shape[0]
    jax.lax.fori_loop(0, n, lambda j, _: copy(j).start(), None)
    jax.lax.fori_loop(0, n, lambda j, _: copy(j).wait(), None)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _fetch_pages_call(slab, table, interpret: bool):
    return pl.pallas_call(
        _fetch_pages_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(1,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[pltpu.SemaphoreType.DMA(())]),
        out_shape=jax.ShapeDtypeStruct((table.shape[0], *slab.shape[1:]),
                                       slab.dtype),
        interpret=interpret)(table, slab)


def fetch_pages(slab, table, interpret: bool | None = None) -> jax.Array:
    """``slab[table]`` for a slab ``(num_pages, page_len, ...)`` and a table
    ``(W,)`` of page ids: ``(W, page_len, ...)``, the same elements, each
    page one copy that reads its page and nothing else. What a prefill chunk
    of a spec model fetches a row's context with (``hybrid
    ._lm_prefill_paged_spec_jit``, scope ``ctx_gather``): XLA's gather of a
    row wider than 1024 lanes passes over the whole slab, and the kernel is
    opaque to the compiler, which fused page-sized slices of a slab with the
    chunk's page WRITES and rematerialized around them (PERF.md section 6,
    PR 45)."""
    if interpret is None:
        interpret = _interpret()
    return _fetch_pages_call(slab, jnp.asarray(table, jnp.int32),
                             interpret=bool(interpret))


# a sparse layer's side of the slab, below everything older (as above: the
# older kernels' cache keys hold their line numbers)
__all__ += ["paged_decode_attention_blocks"]

# blocks the list walk meets in one matmul: 16 blocks of 64 tokens are a
# (group, 1024) score tile
_BLOCKS_A_STEP = 16


def _meet_keys(q, keys, values, seen, carry):
    """The meeting step of the two list walks (decode's below, prefill's at
    the end of the file): ``q`` (R, dh) against ``keys``, ``values`` (width,
    dh) with the online softmax of the other kernels, ``seen`` (R or 1,
    width) true where a query row attends a column. A row that has met no
    key of its own yet (its running max still the mask's value) must not
    count the masked ones (``exp(0) = 1``): ``p`` is zeroed where not seen.
    ``carry`` and the result are ``(m, l, acc)``: (R, 1), (R, 1), (R, dh)
    float32."""
    m_prev, l_prev, acc = carry
    nt = (((1,), (1,)), ((), ()))      # contract both minor dimensions
    s = jax.lax.dot_general(q, keys, nt, preferred_element_type=jnp.float32) \
        / math.sqrt(q.shape[-1])
    s = jnp.where(seen, s, _MASKED)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.where(seen, jnp.exp(s - m_new), 0.0)
    return (m_new, alpha * l_prev + jnp.sum(p, axis=1, keepdims=True),
            acc * alpha + jnp.dot(p.astype(q.dtype), values,
                                  preferred_element_type=jnp.float32))


def _kv_blocks_kernel(tables_ref, blocks_ref, counts_ref, lengths_ref, q_ref,
                      k_hbm, v_hbm, o_ref, k_buf, v_buf, sem, *, block: int,
                      page_len: int, step: int):
    """Grid (B, kvh): one step a (row, KV head), which walks ITS LIST of
    blocks and not the row's table. ``blocks_ref`` (B * kvh, S) names the
    blocks (of ``block`` tokens), the first ``counts_ref[row]`` of them real;
    block ``n`` is tokens ``n * block ..`` of the row, a ``(block, dh)``
    window of page ``tables[b, n * block // page_len]`` at this head's
    lanes, copied HBM to VMEM as it is (the slabs stay in HBM). Every copy of
    a list is started before the first is awaited (all are one size: one
    semaphore an array), then the list is met ``step`` blocks at a time with
    the online softmax of the other kernels; a token at or past
    ``lengths[b]`` (in the query's own block) and the slots past the count
    are masked. The buffers are zeroed at the call's first step: a slot past
    the count is multiplied by a probability of exactly 0 and must hold
    something finite."""
    b, h = pl.program_id(0), pl.program_id(1)
    kvh = pl.num_programs(1)
    g, dh = q_ref.shape[2:]
    S = blocks_ref.shape[1]
    row = b * kvh + h
    n = counts_ref[row]
    length = lengths_ref[b]
    lane0 = h * dh if dh % 128 else pl.multiple_of(h * dh, 128)

    @pl.when((b == 0) & (h == 0))
    def _zero():
        k_buf[...] = jnp.zeros_like(k_buf)
        v_buf[...] = jnp.zeros_like(v_buf)

    def copies(i):
        tok = blocks_ref[row, i] * block
        pid = tables_ref[b, tok // page_len]
        off = pl.multiple_of(jax.lax.rem(tok, page_len), block)
        dst = pl.ds(pl.multiple_of(i * block, block), block)
        return [pltpu.make_async_copy(
            hbm.at[pid, pl.ds(off, block), pl.ds(lane0, dh)],
            buf.at[dst, :], sem.at[a])
            for a, (hbm, buf) in enumerate(((k_hbm, k_buf), (v_hbm, v_buf)))]

    @pl.loop(0, n)
    def _start(i):
        for copy in copies(i):
            copy.start()

    @pl.loop(0, n)
    def _wait(i):
        for copy in copies(i):
            copy.wait()

    q = q_ref[0, 0]                                   # (g, dh)
    width = step * block
    col = jax.lax.broadcasted_iota(jnp.int32, (1, width), 1)

    def meet(j, carry):
        rows = pl.ds(pl.multiple_of(j * width, width), width)
        # absolute position of column t: its block's first token + t % block
        at = jnp.full((1, width), length, jnp.int32)
        for i in range(step):
            slot = j * step + i
            first = blocks_ref[row, jnp.minimum(slot, S - 1)] * block
            at = jnp.where((col // block == i) & (slot < n),
                           first + col % block, at)
        return _meet_keys(q, k_buf[rows, :], v_buf[rows, :], at < length,
                          carry)

    _, l, acc = jax.lax.fori_loop(
        0, (n + step - 1) // step, meet,
        (jnp.full((g, 1), _MASKED, jnp.float32),
         jnp.zeros((g, 1), jnp.float32), jnp.zeros((g, dh), jnp.float32)))
    o_ref[0, 0] = (acc / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def _paged_decode_attention_blocks_call(q, k_pages, v_pages, tables, blocks,
                                        counts, lengths, block: int,
                                        interpret: bool):
    """Its own jitted name, so a trace tells the list walk from the table
    walks."""
    B, kvh, g, dh = q.shape
    page_len = k_pages.shape[1]
    S = blocks.shape[-1]
    step = min(_BLOCKS_A_STEP, S)
    slots = -(-S // step) * step     # the last matmul's tile is whole
    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    row_spec = pl.BlockSpec((1, 1, g, dh), lambda b, h, *_: (b, h, 0, 0))
    buf = slots * block * dh * k_pages.dtype.itemsize
    return pl.pallas_call(
        functools.partial(_kv_blocks_kernel, block=block, page_len=page_len,
                          step=step),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(B, kvh),
            in_specs=[row_spec, in_hbm, in_hbm],
            out_specs=row_spec,
            scratch_shapes=[
                pltpu.VMEM((slots * block, dh), k_pages.dtype),
                pltpu.VMEM((slots * block, dh), v_pages.dtype),
                pltpu.SemaphoreType.DMA((2,)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=max(16 << 20, 2 * buf + (8 << 20))),
        interpret=interpret,
    )(tables, blocks.reshape(B * kvh, S), counts.reshape(B * kvh), lengths, q,
      k_pages, v_pages)


def paged_decode_attention_blocks(q, k_pages, v_pages, tables, blocks, counts,
                                  lengths, block: int,
                                  interpret: bool | None = None) -> jax.Array:
    """Decode attention of each (row, KV head) over a LIST of blocks of the
    row's pages, read in place. ``q`` (B, kv_heads, group, dh); the slabs
    ``(num_pages, page_len, kv_heads * dh)`` (a spec model's), ``page_len`` a
    multiple of ``block``; ``tables`` (B, W); ``blocks`` (B, kv_heads, S)
    int32: block ``n`` is the row's tokens ``n * block .. (n + 1) * block -
    1``, and the first ``counts[b, h]`` (>= 1) entries of a list are
    attended, each once (a block named twice is counted twice); ``lengths``
    (B,): a token at or past a row's length is masked (at least one listed
    token must lie below it). The group's heads share the list, two KV heads
    have their own. Returns ``(B, kv_heads, group, dh)`` in ``q``'s dtype;
    :func:`~marlin_tpu.ops.sparse_attention.attend_blocks_gather` is the
    same arithmetic on gathered blocks. Only the listed blocks are read:
    ``counts x block x dh`` keys and as many values a (row, KV head)."""
    q = jnp.asarray(q)
    B, kvh, _, dh = q.shape
    if k_pages.shape != v_pages.shape or k_pages.ndim != 3 \
            or k_pages.shape[2] != kvh * dh:
        raise ValueError(f"the slabs must be (num_pages, page_len, kv_heads "
                         f"* dh) alike, got {k_pages.shape} and "
                         f"{v_pages.shape} for queries {q.shape}")
    page_len = int(k_pages.shape[1])
    if page_len % block or block % PAGE_SUBLANE:
        raise ValueError(f"a page of {page_len} tokens is not whole blocks "
                         f"of {block}, or those not whole sublane tiles")
    if interpret is None:
        interpret = _interpret()
    as_i32 = lambda x: jnp.asarray(x, jnp.int32)  # noqa: E731
    return _paged_decode_attention_blocks_call(
        q, k_pages, v_pages, as_i32(tables), as_i32(blocks),
        jnp.maximum(as_i32(counts), 1),
        jnp.clip(as_i32(lengths), 1, tables.shape[1] * page_len),
        block=int(block), interpret=bool(interpret))


# prefill's attention of a sparse layer, below everything older
__all__ += ["sparse_prefill_attention"]

# a tile's list of blocks is met _BLOCKS_A_STEP blocks a matmul; which of a
# round's blocks each TOKEN of the tile took rides beside the list as one
# word a listed block (bit t: the tile's t-th token), _LIST_LANES words a row
_LIST_LANES = 128
# rounds of K and V blocks in VMEM: one being met, the others' copies in
# flight
_TILE_SLOTS = 4


def _tile_blocks_kernel(lists_ref, rounds_ref, next_ref, qpos_ref, q_ref,
                        words_ref, k_hbm, v_hbm, o_ref, k_buf, v_buf, sem,
                        cur, used, spread_ref, *, block: int, step: int,
                        group: int):
    """Grid (tiles, kvh): one step a (tile of ``tq`` query tokens, KV head),
    which walks the UNION of the blocks its tokens took and no other.
    ``q_ref`` (1, R, dh): the tile's ``R = tq * group`` query rows, row ``t *
    group + g`` token ``t``'s ``g``-th head of this KV head's group.
    ``lists_ref`` (tiles * kvh, S) names the blocks of grid step ``tile * kvh
    + h`` in ``rounds_ref[step]`` ROUNDS of ``step`` blocks (the last round
    filled up with block 0, which no token is marked for); ``words_ref`` (1,
    1, S / lanes, lanes) holds for each listed block the tokens that took it
    (bit ``t``). ``k_hbm`` / ``v_hbm`` (L, kvh * dh) stay in HBM: block ``n``
    is rows ``n * block ..`` at this head's lanes, a ``(block, dh)`` window
    copied as it is into its place in a round's ``(step * block, dh)``
    buffer. The call's rounds are ONE stream across its grid steps, as the
    table walk's pages are (:func:`_kv_walk_kernel`): stream entry ``e``
    lands in slot ``e % slots``, consuming an entry starts the copies of
    entry ``e + slots - 1``, whichever grid step holds it (``next_ref[g +
    1]``: the next grid step after ``g`` that has a round; ``next_ref[0]`` the
    first), and a round is awaited ONCE an array, by the bytes of its whole
    buffer (every round copies ``step`` blocks, so a buffer is whole before
    it is met and is never zeroed). A round meets the tile in one matmul; key
    column ``c`` of block ``n`` is seen by query row ``(t, g)`` iff bit ``t``
    of the block's word is set AND its position ``n * block + c % block <=
    q_pos[t]``: a tile's union
    never shows a block to a token that did not take it. The words of a round
    reach the score tile's shape through the MXU: the tokens' bits ``(tq,
    lanes)`` 0 / 1 times the constant ``spread_ref[round % (lanes / step)]``
    (lanes, step * block), which repeats list entry ``i``'s column ``block``
    times, then each token's row under its ``group`` query rows (spread at
    all ``R`` rows it is a third matmul the size of the scores': 1.59 ms a
    call of the cell's chunk against 1.31, PERF.md PR 49). A grid
    step without a round (a tile wholly past the prompt) copies nothing,
    multiplies nothing and writes zeros, as does a row that saw no key."""
    tile, h = pl.program_id(0), pl.program_id(1)
    kvh = pl.num_programs(1)
    total = pl.num_programs(0) * kvh
    gs = tile * kvh + h
    R, dh = q_ref.shape[1:]
    tq = R // group
    slots, width = k_buf.shape[:2]
    lanes = words_ref.shape[-1]
    per = lanes // step                  # rounds a row of words

    def arrays():
        return enumerate(((k_hbm, k_buf), (v_hbm, v_buf)))

    def start_next():
        g, r, e = cur[0], cur[1], cur[2]

        @pl.when(g < total)
        def _start():
            slot = jax.lax.rem(e, slots)
            lane0 = jax.lax.rem(g, kvh) * dh
            if dh % 128 == 0:
                lane0 = pl.multiple_of(lane0, 128)
            for i in range(step):
                first = pl.multiple_of(lists_ref[g, r * step + i] * block,
                                       block)
                for a, (hbm, buf) in arrays():
                    pltpu.make_async_copy(
                        hbm.at[pl.ds(first, block), pl.ds(lane0, dh)],
                        buf.at[slot, pl.ds(i * block, block), :],
                        sem.at[a, slot]).start()
            last = r + 1 >= rounds_ref[g]
            cur[0] = jnp.where(last, next_ref[g + 1], g)
            cur[1] = jnp.where(last, 0, r + 1)
            cur[2] = e + 1

    @pl.when(gs == 0)
    def _first_rounds():
        entry = jax.lax.broadcasted_iota(jnp.int32, spread_ref.shape[1:], 0)
        col = jax.lax.broadcasted_iota(jnp.int32, spread_ref.shape[1:], 1)
        for k in range(per):
            spread_ref[k] = (entry == k * step + col // block).astype(
                spread_ref.dtype)
        cur[0], cur[1], cur[2] = next_ref[0], 0, 0
        used[0] = 0
        for _ in range(slots - 1):
            start_next()

    q = q_ref[0]
    row_token = jax.lax.broadcasted_iota(jnp.int32, (R, 1), 0) // group
    token = jax.lax.broadcasted_iota(jnp.int32, (tq, 1), 0)
    q_pos = jnp.zeros((R, 1), jnp.int32)
    bit = jnp.zeros((tq, 1), jnp.int32)       # token t's bit of a word
    for t in range(tq):
        q_pos = jnp.where(row_token == t, qpos_ref[tile * tq + t], q_pos)
        bit = jnp.where(token == t,   # 1 << 31 as the int32 it is
                        jnp.int32((1 << t) - (t == 31) * 2 ** 32), bit)
    col = jax.lax.broadcasted_iota(jnp.int32, (1, width), 1)
    base = used[0]
    n = rounds_ref[gs]

    def meet(r, carry):
        # into the slot of the entry before this one, whose reader has run
        start_next()
        slot = jax.lax.rem(base + r, slots)
        for a, (_, buf) in arrays():     # the round's copies, by their bytes
            pltpu.make_async_copy(buf.at[slot], buf.at[slot],
                                  sem.at[a, slot]).wait()
        words = words_ref[0, 0, pl.ds(r // per, 1), :]       # (1, lanes)
        # 0 / 1 operands, exact in one bfloat16 pass whatever precision the
        # caller's context asks of its own matmuls
        took = jnp.dot(((words & bit) != 0).astype(spread_ref.dtype),
                       spread_ref[jax.lax.rem(r, per)],
                       precision=jax.lax.Precision.DEFAULT,
                       preferred_element_type=jnp.float32)     # (tq, width)
        took = jnp.concatenate(
            [jnp.broadcast_to(took[t:t + 1], (group, width))
             for t in range(tq)], axis=0)                      # (R, width)
        # absolute position of column c: its block's first token + c % block
        at = jnp.zeros((1, width), jnp.int32)
        for i in range(step):
            at = jnp.where(col // block == i,
                           lists_ref[gs, r * step + i] * block + col % block,
                           at)
        return _meet_keys(q, k_buf[slot], v_buf[slot],
                          (took > 0.5) & (at <= q_pos), carry)

    _, l, acc = jax.lax.fori_loop(
        0, n, meet,
        (jnp.full((R, 1), _MASKED, jnp.float32),
         jnp.zeros((R, 1), jnp.float32), jnp.zeros((R, dh), jnp.float32)))
    used[0] = base + n
    o_ref[0] = jnp.where(l > 0, acc / jnp.where(l > 0, l, 1.0),
                         0.0).astype(o_ref.dtype)


def _next_with_a_round(rounds):
    """The stream's way past the grid steps that copy nothing: entry ``g + 1``
    the first grid step after ``g`` that has a round, entry 0 the first of
    all, the count of grid steps where there is none."""
    n = rounds.shape[0]
    at = jnp.where(rounds > 0, jnp.arange(n), n)
    return jnp.concatenate([jax.lax.cummin(at, reverse=True),
                            jnp.full((1,), n)]).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def _sparse_prefill_attention_call(q, k, v, q_pos, lists, rounds, words,
                                   block: int, interpret: bool):
    """Its own jitted name, so a trace tells prefill's walk from decode's
    (``_paged_decode_attention_blocks_call``, which the benchmark finds by
    name)."""
    T, kvh, group, dh = q.shape
    tiles, S = lists.shape[1:]
    R = T // tiles * group
    lanes, step, slots = _LIST_LANES, _BLOCKS_A_STEP, _TILE_SLOTS
    width = step * block
    # grid step tile * kvh + h: its list and its rounds
    flat = tiles * kvh
    rounds = rounds.T.reshape(flat)
    nxt = _next_with_a_round(rounds)
    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    rows = pl.BlockSpec((1, R, dh), lambda t, h, *_: (h, t, 0))
    ring = 2 * slots * width * dh * k.dtype.itemsize
    tile_bytes = R * width * 4
    out = pl.pallas_call(
        functools.partial(_tile_blocks_kernel, block=block, step=step,
                          group=group),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(tiles, kvh),
            in_specs=[rows,
                      pl.BlockSpec((1, 1, S // lanes, lanes),
                                   lambda t, h, *_: (h, t, 0, 0)),
                      in_hbm, in_hbm],
            out_specs=rows,
            scratch_shapes=[
                pltpu.VMEM((slots, width, dh), k.dtype),
                pltpu.VMEM((slots, width, dh), v.dtype),
                pltpu.SemaphoreType.DMA((2, slots)),
                pltpu.SMEM((3,), jnp.int32),  # cur: grid step, round, started
                pltpu.SMEM((1,), jnp.int32),    # used
                pltpu.VMEM((lanes // step, lanes, width), jnp.bfloat16),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((kvh, T * group, dh), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=max(32 << 20,
                                 ring + 12 * tile_bytes + (8 << 20))),
        interpret=interpret,
    )(lists.transpose(1, 0, 2).reshape(flat, S), rounds, nxt, q_pos,
      q.transpose(1, 0, 2, 3).reshape(kvh, T * group, dh),
      words.reshape(kvh, tiles, S // lanes, lanes), k, v)
    return out.reshape(kvh, T, group, dh).transpose(1, 0, 2, 3)


def sparse_prefill_attention(q, k, v, q_pos, lists, rounds, words, block: int,
                             interpret: bool | None = None) -> jax.Array:
    """Prefill's attention of a sparse layer over the blocks its queries
    took, a TILE of queries at a time. ``q`` (T, kv_heads, group, dh) at
    positions ``q_pos`` (T,); ``k``, ``v`` (L, kv_heads * dh) the row's
    context, key ``j`` at position ``j``, a token's heads side by side (what
    ``fetch_pages`` leaves: contiguous, so block ``n`` is rows ``n * block
    ..``); ``lists`` (kv_heads, tiles, S) int32, ``rounds`` (kv_heads, tiles)
    and ``words`` (kv_heads, tiles, S) int32 from
    :func:`~marlin_tpu.ops.sparse_attention.tile_lists`: tile ``i`` is
    queries ``i * T / tiles ..``, its list the blocks ANY of its tokens took,
    met ``rounds * step`` entries of it, bit ``t`` of a block's word set iff
    the tile's ``t``-th token took it. Query ``t`` attends the keys ``<=
    q_pos[t]`` of ITS blocks, one softmax over them (bfloat16 or float32
    operands as given, float32 scores and accumulation); a token marked for
    no block (padding) gets zeros. Returns (T, kv_heads, group, dh) in
    ``q``'s dtype; :func:`~marlin_tpu.ops.sparse_attention.attend_selected`
    is the same arithmetic as a mask over every key. Only the listed blocks
    are read, and no score leaves VMEM."""
    q = jnp.asarray(q)
    T, kvh, group, dh = q.shape
    tiles, S = lists.shape[1:]
    if k.shape != v.shape or k.ndim != 2 or k.shape[1] != kvh * dh \
            or k.shape[0] % block:
        raise ValueError(f"the context must be (L, kv_heads * dh) alike and "
                         f"whole blocks of {block}, got {k.shape} and "
                         f"{v.shape} for queries {q.shape}")
    if T % tiles or T // tiles > 32 or S % _LIST_LANES:
        raise ValueError(f"{tiles} tiles of {T} queries with lists of {S}: "
                         f"a tile holds at most 32 tokens (a word's bits) and "
                         f"a list whole rows of {_LIST_LANES} entries")
    if interpret is None:
        interpret = _interpret()
    as_i32 = lambda x: jnp.asarray(x, jnp.int32)  # noqa: E731
    return _sparse_prefill_attention_call(
        q, k, v, as_i32(q_pos), as_i32(lists), as_i32(rounds), as_i32(words),
        block=int(block), interpret=bool(interpret))
