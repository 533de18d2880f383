"""The gated delta rule of a linear-attention layer, two ways.

Per head (keys of ``K`` values, values of ``V``; the state ``S`` is ``K x
V``, float32), with the log-decay ``g_t <= 0`` (``a_t = exp(g_t)``) and the
step ``b_t`` in ``(0, 2)``::

    S' = a_t S_{t-1}                          (decay first)
    S_t = S' + b_t k_t (v_t - S'^T k_t)^T     (the delta step, against S')
    o_t = S_t^T q_t

The decay is one scalar a head (``g`` of rank 2: the ``olmo_hybrid`` family's
gated delta rule) or a VECTOR a head, one value a channel of the key (``g`` of
rank 3, ``S' = diag(a_t) S_{t-1}``: Kimi Delta Attention, the ``solar_open2``
family's ``kda_*`` keys); ``g``'s rank picks the form in both functions.

Mamba-2's state (``ops/ssm.py``) moves by a scalar decay and an outer
product; this one is also multiplied by ``I - b_t k_t k_t^T``, a rank-one
matrix that depends on the key, whose eigenvalue ``1 - b_t`` reaches ``-1``.

- :func:`delta_chunk_scan` runs it over ONE row's chunk of tokens, entered
  with the row's state and leaving the state after the chunk's last valid
  token: the chunked (WY / UT) form. Inside a block of ``C`` tokens the
  steps ``d_t = b_t (v_t - S'^T k_t)`` solve a unit lower-triangular ``C x
  C`` system a head (``(I + A) D = diag(b) V - diag(b gamma) K S_0``, ``A[t,
  j] = b_t exp(cs_t - cs_j) k_t . k_j`` for ``j < t``, ``cs`` the running
  sum of ``g`` inside the block, ``gamma = exp(cs)``); the system is solved
  in float32, against ``V`` and ``K`` at once for every block of the chunk,
  and only the ``chunk / C`` block states are chained one after another. A
  decay ratio ``exp(cs_t - cs_j)`` exists for ``t >= j`` only and is masked
  BEFORE the exponential. With a decay a channel the ratio sits INSIDE the
  contraction over the key's channels and cannot be factored out of ``k_t .
  k_j``: :func:`_channel_decay_terms` builds ``A`` from exact sums inside
  sub-blocks of 16 tokens and from keys rescaled against a sub-block's
  entering decay between them; everything after ``A`` is shared. A
  position with ``g == 0`` and ``b == 0`` neither
  decays the state nor adds to it: that is how the positions past a row's
  length are kept out (the caller zeroes both).

  **Which sizes take a kernel** (:func:`chunk_scan_supported`; the choice
  is made from the operands, as the prefill program picks its flash kernel):
  a decay a CHANNEL over heads of 128 x 128 in blocks of 64 (the
  ``solar_open2`` family's) runs as ONE ``pallas_call``
  (:func:`_kda_chunk_kernel`): a grid step holds two pairs of heads and one
  block, the heads' states stay in VMEM over the chunk's blocks, the pairs
  inside a sub-block never leave the chip (XLA's form writes their masked
  exponentials, 268 MB a layer, to HBM and reads them back), and a block
  that begins at or past the chunk's ``valid`` count is skipped: the state
  passes through and ``o`` is zeros there. The same arithmetic at the same
  precisions: float32 decays, exponentials, sub-block pairs, inverse, sums
  and state; matmul operands in the compute dtype. Everything else takes
  XLA's form (:func:`_delta_chunk_scan_xla`), the tests' second witness.
  The scalar decay (``olmo_hybrid``: 30 heads, K 96, V 192) does NOT take
  the kernel yet: neither size is whole lane tiles, a pair of its heads'
  tokens do not tile the kernel's layouts, and its cost is not the channel
  pairs this kernel was written for but the inverse and its product (657 of
  1075 ns a token and layer, PERF.md PR 42): a second kernel.
- :func:`delta_decode_update` advances one token for each row of a batch,
  each row's state living in slot ``slots[b]`` of a slab. ``kernel="pallas"``
  reads and writes each row's slot ONCE, in place
  (:func:`_delta_decode_update_call`: the slot id is scalar-prefetched and
  drives the block index, the slab is aliased to the output); the rows that
  share the dummy slot 0 scribble on it and on nothing else.
  ``kernel="gather"`` is the same arithmetic on a gathered copy.

**The slab's layout**: ``(slots, K, heads * V)``, a row's state of head ``h``
in the lane range ``[h V, (h + 1) V)`` of each of ``K`` rows. At the
published sizes (30 heads, K 96, V 192) neither ``(96, 192)`` nor ``(192,
96)`` is whole 128-lane tiles (held a head at a time the chip would pad 192
lanes to 256: a third more bytes to hold and to move), but ``30 x 192 =
5760`` is 45 tiles and 96 is 12 sublane tiles, so the slab is dense and a
pair of heads (384 lanes) is three whole tiles: the kernel walks a block's
heads in pairs. A key enters the update as a COLUMN broadcast along the
lanes of its head (``K[k, h V + v] = k_h[k]``), made in the kernel from a
row by transposing one 128 x 128 tile (as ``ops/ssm.py`` makes ``B``);
decay, step and value enter as rows laid over the lanes; a decay a channel
enters as the key does, a column along its head's lanes. At 64 heads of 128
x 128 the slab's ``(128, 8192)`` is whole tiles head by head and a block is
16 heads.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_kernels import _interpret

__all__ = ["delta_chunk_scan", "delta_decode_update", "state_to_slab",
           "slab_to_state", "decode_update_supported", "decode_heads_block",
           "chunk_scan_supported"]

_LANES = 128
#: the block and the sub-block the kernel is written for: a pair of heads'
#: 2 x 64 tokens are one lane tile, a sub-block's 16 offsets one roll each
_KDA_BLOCK, _KDA_SUB = 64, 16
#: pairs of heads a grid step may hold (the most that divide the heads)
_KDA_PAIRS = (1, 2)


def state_to_slab(state):
    """``(..., heads, K, V)`` -> the slab's ``(..., K, heads * V)``."""
    *lead, H, K, V = state.shape
    return jnp.moveaxis(state, -3, -2).reshape(*lead, K, H * V)


def slab_to_state(slab, heads: int):
    """The slab's ``(..., K, heads * V)`` -> ``(..., heads, K, V)``."""
    *lead, K, HV = slab.shape
    return jnp.moveaxis(slab.reshape(*lead, K, heads, HV // heads), -2, -3)


# ------------------------------------------------------- a chunk of one row

def _small_mm(a, b):
    """``a @ b`` for batches of SMALL float32 matrices (32 x 32 at most
    here) as a multiply and a sum on the vector units, exact float32: on the
    MXU each of these thousands of tiny products takes a whole pass of the
    array, six at float32 precision."""
    return jnp.sum(a[..., :, :, None] * b[..., None, :, :], axis=-2)


def _unit_lower_inverse(A):
    """``(I + A)^-1`` for strictly lower-triangular ``A`` (..., C, C), in
    float32 whatever the caller's matmul precision: blocks of at most 16
    rows by forward substitution a row at a time (row ``i`` of the inverse
    is ``e_i - A[i] X``, the rows above it final), every batch entry at
    once; larger ones from their two halves, ``[[Ta, 0], [-Tb A21 Ta,
    Tb]]``, the halves inverted as one batch. (XLA's own triangular solve
    takes 2.6 us a token and layer at the published sizes on the chip, more
    than the whole rest of a prefill chunk's mixer: PERF.md, PR 42.)"""
    C = A.shape[-1]
    if C <= 16 or C % 2:
        eye = jnp.eye(C, dtype=A.dtype)
        X = jnp.broadcast_to(eye, A.shape)
        for i in range(1, C):
            X = X.at[..., i, :].set(
                eye[i] - jnp.sum(A[..., i, :, None] * X, axis=-2))
        return X
    h = C // 2
    T = _unit_lower_inverse(jnp.stack([A[..., :h, :h], A[..., h:, h:]],
                                      axis=-3))
    Ta, Tb = T[..., 0, :, :], T[..., 1, :, :]
    low = -_small_mm(_small_mm(Tb, A[..., h:, :h]), Ta)
    return jnp.concatenate(
        [jnp.concatenate([Ta, jnp.zeros_like(Ta)], axis=-1),
         jnp.concatenate([low, Tb], axis=-1)], axis=-2)


def _scalar_decay_terms(qb, kb, gb, bb):
    """One decay a head and token (``gb`` (nc, C, H)): the block's matrices
    ``A`` (strictly lower) and ``P`` (nc, t, j, H) float32, the keys of the
    system's right-hand side ``b gamma k`` (float32), the queries that meet
    the entering state, the keys that leave the block (both in the compute
    dtype) and the block's whole decay, laid over a state ``(K, H, V)``."""
    f32, cd = jnp.float32, qb.dtype
    C = qb.shape[1]
    cs = jnp.cumsum(gb, axis=1)                                   # (nc, C, H)
    t = jnp.arange(C)
    seen = (t[:, None] >= t[None, :])[None, :, :, None]       # j <= t
    before = (t[:, None] > t[None, :])[None, :, :, None]      # j < t
    ratio = jnp.exp(jnp.where(seen, cs[:, :, None] - cs[:, None, :],
                              -jnp.inf))                      # (nc, t, j, H)
    kk = jnp.einsum("cthk,cjhk->ctjh", kb, kb, preferred_element_type=f32)
    A = jnp.where(before, bb[:, :, None, :] * ratio * kk, 0.0)
    gamma = jnp.exp(cs)
    P = ratio * jnp.einsum("cthk,cjhk->ctjh", qb, kb,
                           preferred_element_type=f32)
    q_in = (qb.astype(f32) * gamma[..., None]).astype(cd)
    k_out = (kb.astype(f32) * jnp.exp(cs[:, -1:] - cs)[..., None]).astype(cd)
    return (A, P, (bb * gamma)[..., None] * kb.astype(f32), q_in, k_out,
            gamma[:, -1][:, None, :, None])                    # (nc, 1, H, 1)


def _channel_decay_terms(qb, kb, gb, bb, sub: int):
    """As :func:`_scalar_decay_terms` for a decay a CHANNEL (``gb`` (nc, C,
    H, K)): ``A[t, j] = b_t sum_c k_t[c] k_j[c] exp(cs_t[c] - cs_j[c])``, the
    ratio INSIDE the contraction. A pair of tokens in one sub-block of
    ``sub`` tokens is met exactly, a multiply and a sum in float32 (the
    difference masked before the exponential). A pair in two sub-blocks is a
    matmul of keys rescaled against the decay that ENTERS the later
    sub-block, ``k_t exp(cs_t - ref)`` and ``k_j exp(ref - cs_j)``: ``cs``
    only falls, so both exponents are at most 0 and neither factor leaves
    float32's range whatever the decay (``exp(-cs_j)`` alone would)."""
    f32, cd = jnp.float32, qb.dtype
    nc, C, H, K = kb.shape
    n = C // sub
    cs = jnp.cumsum(gb, axis=1)                                # (nc, C, H, K)
    kf = kb.astype(f32)
    qk = jnp.stack([kf, qb.astype(f32)])                    # (2, nc, C, H, K)
    t = jnp.arange(sub)
    seen = (t[:, None] >= t[None, :])[None, None, :, :, None, None]
    css, kss = cs.reshape(nc, n, sub, H, K), kf.reshape(nc, n, sub, H, K)
    ratio = jnp.exp(jnp.where(seen, css[:, :, :, None] - css[:, :, None, :],
                              -jnp.inf))                # (nc, n, t, j, H, K)
    inside = jnp.sum(qk.reshape(2, nc, n, sub, 1, H, K) * kss[:, :, None]
                     * ratio, axis=-1)                  # (2, nc, n, t, j, H)
    rows = []
    for i in range(n):
        lo, hi = i * sub, (i + 1) * sub
        parts = [inside[:, :, i]]
        if i:
            ref = cs[:, lo - 1][:, None]                       # (nc, 1, H, K)
            late = (qk[:, :, lo:hi] * jnp.exp(cs[:, lo:hi] - ref)).astype(cd)
            early = (kf[:, :lo] * jnp.exp(ref - cs[:, :lo])).astype(cd)
            parts.insert(0, jnp.einsum("xcthk,cjhk->xctjh", late, early,
                                       preferred_element_type=f32))
        if hi < C:
            parts.append(jnp.zeros((2, nc, sub, C - hi, H), f32))
        rows.append(jnp.concatenate(parts, axis=3))
    kk, P = jnp.concatenate(rows, axis=2)                      # (nc, t, j, H)
    tt = jnp.arange(C)
    before = (tt[:, None] > tt[None, :])[None, :, :, None]
    A = jnp.where(before, bb[:, :, None, :] * kk, 0.0)
    gamma = jnp.exp(cs)
    q_in = (qk[1] * gamma).astype(cd)
    k_out = (kf * jnp.exp(cs[:, -1:] - cs)).astype(cd)
    return (A, P, bb[..., None] * gamma * kf, q_in, k_out,
            gamma[:, -1].transpose(0, 2, 1)[..., None])        # (nc, K, H, 1)


def _kda_chunk_kernel(valid_ref, q_ref, k_ref, v_ref, g_ref, b_ref, w_ref,
                      s0_ref, o_ref, s_ref):
    """Grid (groups of ``n`` PAIRS of heads, blocks of the chunk), the blocks
    in turn. Blocks: ``q``, ``k``, ``v``, ``g`` (64, n x 2 x 128) and ``o``
    the same, a head's 128 values in its lane tile; ``b`` (1, 1, n, 128) each
    pair's steps as a ROW, lane ``64 h + t``; ``w`` (15, 128, 128) the 0 / 1
    matrices of :func:`_kda_shift_matrices`, whole; ``s0`` / ``s`` (128, n x
    2 x 128), the heads' states, ``s`` resident over the blocks (its block
    index does not move): the carried state. The pairs of a step share
    nothing; ``one_pair`` is a generator and the step runs its pairs a stage
    at a time, in turn.

    Two layouts of a pair's 128 (head, token) rows. NATURAL: ``(h, t)`` down
    the sublanes, a key's channels along the lanes: what the MXU products
    take. TRANSPOSED: channels down, ``(h, t)`` along the lanes: there the
    pairs inside a sub-block are a shift along the lanes by ``d = t - j``, a
    product and a sum DOWN the sublanes (vector adds, no lane reduction),
    one row of 128 pairs an offset. A sub-block's 16 x 16 tile is kept as
    ``(16 j, 128 (h, t))``: lane ``(h, t)`` holds row ``t``'s 16 entries, so
    forward substitution runs on all eight tiles of the pair at once with
    the same rolls.

    What the stages wait for (the compiler's own schedule for a v5e, which
    runs a step's stages one after another: ~2800 bundles a pair): the lane
    rolls, three units that each take a vreg every ~8 cycles, in the pairs
    and the substitution; the MXU's result pops in the float32 products of
    the joins (six passes each). Hence the keys' shift by the MXU where they
    are bfloat16, the substitution by halves of 8 (78 rolled vregs for 240),
    and g's running sum as three bfloat16 passes."""
    f32, bf16, cd = jnp.float32, jnp.bfloat16, q_ref.dtype
    # every product pins its precision: left to the caller's context, a
    # float32 check's ``default_matmul_precision("highest")`` reaches the
    # bfloat16 ones, which Mosaic refuses ("Bad lhs type")
    high, one_pass = jax.lax.Precision.HIGHEST, jax.lax.Precision.DEFAULT
    prec = high if cd == f32 else one_pass
    c = pl.program_id(1)
    B, S, L = _KDA_BLOCK, _KDA_SUB, _LANES

    def mm(a, b, precision=prec):
        return jnp.dot(a, b, preferred_element_type=f32, precision=precision)

    @pl.when(c == 0)
    def _enter():
        s_ref[...] = s0_ref[...]

    def one_pair(pair):
        lanes = [slice((2 * pair + h) * L, (2 * pair + h + 1) * L)
                 for h in (0, 1)]

        def stack(ref):       # (64, 2 x 128) -> (128 (h, t), 128)
            return jnp.concatenate([ref[:, at] for at in lanes], axis=0)

        # (row >> 6: its head, B = 64; >> 4: its sub-block, S = 16)
        row = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
        same_head = (row >> 6) == (col >> 6)
        top = row < B                                  # head 0's rows
        k2, q2 = stack(k_ref).astype(f32), stack(q_ref).astype(f32)
        # the running sum of g inside the block, a head at a time: a
        # triangle of ones times g as the three bfloat16 that add up to it
        # exactly, so three passes of the MXU are float32's own sums
        g_hi = stack(g_ref)
        tri = jnp.where(same_head & (col <= row), 1.0, 0.0).astype(bf16)
        parts = []
        for _ in range(3):
            parts.append(g_hi.astype(bf16))
            g_hi = g_hi - parts[-1].astype(f32)
        cs2 = sum(mm(tri, part, one_pass) for part in reversed(parts))
        csT, kT, qT = cs2.T, k2.T, q2.T

        def earlier(d):
            # the keys d tokens earlier. bfloat16 keys go through the MXU, a
            # product with a 0 / 1 matrix that moves every lane d to the
            # right (exact: each entry is one key times one): the vector
            # units' lane rolls, three at a time and busy with the decays',
            # are what this stage waits for
            if cd == bf16:
                return mm(kT.astype(bf16), w_ref[d - 1], one_pass)
            return pltpu.roll(kT, d, 1)

        yield
        brow = b_ref[0, 0, pair:pair + 1]                          # (1, 128)

        # ---- pairs inside a sub-block, an offset d at a time
        tl = jax.lax.broadcasted_iota(jnp.int32, (1, L), 1) & (S - 1)
        kk, qk = [None], [jnp.sum(qT * kT, axis=0, keepdims=True)]
        for d in range(1, S):
            # (masked BEFORE the exponential: -inf where t - d leaves the
            # sub-block, whatever the difference there)
            w = jnp.exp(csT - pltpu.roll(csT, d, 1)
                        + jnp.where(tl >= d, 0.0, -jnp.inf)) * earlier(d)
            kk.append(jnp.sum(kT * w, axis=0, keepdims=True))
            qk.append(jnp.sum(qT * w, axis=0, keepdims=True))
            yield
        # a tile as (16 j, 128 (h, t)): entry [j, (h, t)] is offset t - j
        jl = jax.lax.broadcasted_iota(jnp.int32, (S, L), 0)
        tc = jax.lax.broadcasted_iota(jnp.int32, (S, L), 1) & (S - 1)
        qd = jnp.zeros((S, L), f32)
        for d in range(S):
            qd = jnp.where(tc - jl == d, qk[d], qd)
        # (I + A)^-1 of the eight diagonal tiles. Each tile's two halves of
        # 8 a row at a time: row i is e_i - sum_m A[i, m] X[m], the rows
        # above it final; X[m] of lane (h, t) lies d = i - m lanes to its
        # left
        a = [None] + [brow * kk[d] for d in range(1, S)]
        half = S // 2
        j8 = jax.lax.broadcasted_iota(jnp.int32, (half, L), 0)
        t16 = jax.lax.broadcasted_iota(jnp.int32, (half, L), 1) & (S - 1)
        t8 = t16 & (half - 1)
        # xt's rows j < 8 and j >= 8 apart: a vreg each
        eyes = [jnp.where(t16 == j8 + at, 1.0, 0.0) for at in (0, half)]
        xs = eyes
        for i in range(1, half):
            a8 = [jnp.where(t8 >= d, a[d], 0.0) for d in range(1, i + 1)]
            xs = [jnp.where(t8 == i, eye - sum(
                a8[d - 1] * pltpu.roll(x, d, 1) for d in range(1, i + 1)), x)
                for eye, x in zip(eyes, xs)]
            yield
        # then the tile from its halves, [[Ta, 0], [-Tb A21 Ta, Tb]]: both
        # products again sums over offsets of a rolled factor, the left one
        # entering by its diagonals (a row of A21 reaches 1..15 to the left,
        # a row of Tb 0..7)
        z = sum(jnp.where((t16 >= half) & (t16 - e < half), a[e], 0.0)
                * pltpu.roll(xs[0], e, 1) for e in range(1, S))
        yield
        low = sum(jnp.sum(jnp.where(j8 + half == t16 - e, xs[1], 0.0),
                          axis=0, keepdims=True)
                  * (pltpu.roll(z, e, 1) if e else z) for e in range(half))
        xt = jnp.concatenate([xs[0] - low, xs[1]], axis=0)
        yield

        def tiles(x):   # (16 j, 128 (h, t)) -> block-diagonal (128, 128)
            same_tile = (row >> 4) == (col >> 4)
            return jnp.where(same_tile, jnp.concatenate([x] * (L // S), 0),
                             0.0).T

        # ---- pairs in two sub-blocks: keys rescaled against the decay that
        # enters the later one, on the MXU
        tpos = row & (B - 1)
        outs = []
        for i in range(1, B // S):
            refs = [cs2[h * B + i * S - 1:h * B + i * S] for h in (0, 1)]
            early = (k2 * jnp.exp(jnp.where(
                tpos < i * S, jnp.where(top, refs[0], refs[1]) - cs2,
                -jnp.inf))).astype(cd)
            late = []
            for x2 in (k2, q2):
                for h in (0, 1):
                    at = slice(h * B + i * S, h * B + (i + 1) * S)
                    late.append(x2[at] * jnp.exp(cs2[at] - refs[h]))
            outs.append(jax.lax.dot_general(
                jnp.concatenate(late, axis=0).astype(cd), early,
                (((1,), (1,)), ((), ())), preferred_element_type=f32,
                precision=prec))                         # (64, 128 (h, j))
            yield
        zero = jnp.zeros((S, L), f32)

        def between(x):   # x: 0 the keys' rows, 2 the queries'
            return jnp.concatenate(
                [piece for h in (0, 1) for piece in
                 [zero] + [o[(x + h) * S:(x + h + 1) * S] for o in outs]],
                axis=0)

        bcol = jnp.broadcast_to(brow, (L, L)).T   # b of row (h, t), every lane
        a_off = jnp.where(same_head, bcol * between(0), 0.0)
        P = jnp.where(same_head & (col <= row), tiles(qd) + between(2),
                      0.0).astype(cd)
        # the tiles' inverses joined by halves: [[Ta, 0], [-Tb A21 Ta, Tb]]
        # (only the rows of the lower halves change: the 16-row groups 1 and
        # 3 of a head, then 2 and 3; the products take those rows alone)
        nr, ncol = (row >> 4) & 3, (col >> 4) & 3
        inv = tiles(xt)
        for lower, groups in ((((nr & 1) == 1) & (ncol == nr - 1),
                               (1, 3, 5, 7)),
                              ((nr >= 2) & (ncol < 2), (2, 3, 6, 7))):
            pieces = [inv[n * S:(n + 1) * S] for n in range(L // S)]
            new = mm(mm(jnp.concatenate([pieces[n] for n in groups], axis=0),
                        jnp.where(lower, a_off, 0.0), high), inv, high)
            for at, n in enumerate(groups):
                pieces[n] = pieces[n] - new[at * S:(at + 1) * S]
            inv = jnp.concatenate(pieces, axis=0)
            yield

        # ---- (I + A) [W | U] = [diag(b) V | diag(b gamma) K]
        gamma = jnp.exp(cs2)
        rhs = jnp.concatenate([bcol * stack(v_ref).astype(f32),
                               bcol * gamma * k2], axis=1).astype(cd)
        solved = mm(inv.astype(cd), rhs)
        W, U = solved[:, :L], solved[:, L:].astype(cd)
        q_in = (q2 * gamma).astype(cd)
        yield
        # ---- the chain: d = W - U S, o = q_in S + P d, S = S tot + k_out^T d
        heads = [slice(h * B, (h + 1) * B) for h in (0, 1)]
        sc = [s_ref[:, at].astype(cd) for at in lanes]
        d2 = jnp.concatenate([(W[at] - mm(U[at], s)).astype(cd)
                              for at, s in zip(heads, sc)], axis=0)
        o2 = jnp.concatenate([mm(q_in[at], s) for at, s in zip(heads, sc)],
                             axis=0) + mm(P, d2)
        lastT = jnp.where(top, cs2[B - 1:B], cs2[2 * B - 1:2 * B]).T
        k_outT, totT = kT * jnp.exp(lastT - csT), jnp.exp(lastT)
        turned = pltpu.roll(totT, B, 1)
        for h in (0, 1):
            mine = (col >> 6) == h
            o_ref[:, lanes[h]] = o2[heads[h]]
            s_ref[:, lanes[h]] = (
                s_ref[:, lanes[h]] * jnp.where(mine, totT, turned)
                + mm(jnp.where(mine, k_outT, 0.0).astype(cd), d2))

    # a block past the row's length does NOTHING: its index maps name the
    # last live block again, so no copy moves in or out either
    @pl.when(c * B < valid_ref[0])
    def _block():
        # the step's pairs a stage at a time, in turn (each ``yield`` of
        # ``one_pair`` ends a stage): written one whole pair after another
        # the compiler's schedule runs them one after another too
        pairs = [one_pair(pair) for pair in range(b_ref.shape[2])]
        while pairs:
            pairs = [run for run in pairs if next(run, run) is None]


def chunk_scan_supported(heads: int, key_dim: int, value_dim: int, block: int,
                         sub: int = _KDA_SUB, channel_decay: bool = True,
                         state_dtype="float32") -> bool:
    """Whether :func:`_kda_chunk_call` takes these sizes: a decay a
    channel, heads of 128 x 128 (a head's keys and values each ONE lane
    tile) in pairs, blocks of 64 in sub-blocks of 16, a float32 state."""
    return (channel_decay and key_dim == _LANES and value_dim == _LANES
            and heads % 2 == 0 and block == _KDA_BLOCK and sub == _KDA_SUB
            and jnp.dtype(state_dtype) == jnp.float32)


@functools.lru_cache(maxsize=1)
def _kda_shift_matrices():
    """The kernel's ``w_ref``: ``[d - 1][m, l] = (m == l - d)`` over a
    pair's 128 (head, token) lanes, d in 1..15: a product with it moves
    every lane ``d`` to the right."""
    lane = np.arange(2 * _KDA_BLOCK)
    return np.stack([lane[:, None] == lane[None, :] - d
                     for d in range(1, _KDA_SUB)]).astype(jnp.bfloat16)


@functools.partial(jax.jit, static_argnames=("interpret", "pairs"))
def _kda_chunk_call(q, k, v, g, beta, state, valid, interpret: bool,
                    pairs: int):
    """The chunk scan with a decay a channel as ONE kernel (its own jitted
    name: the kernel's operation in a trace takes it). Operands as
    :func:`delta_chunk_scan`'s; ``valid`` an int32 scalar."""
    T, H, K = q.shape
    C, nc, n = _KDA_BLOCK, T // _KDA_BLOCK, pairs
    # a pair's steps as a row, lane 64 h + t
    brow = beta.astype(jnp.float32).reshape(nc, C, H // 2, 2).transpose(
        0, 2, 3, 1).reshape(nc, H // (2 * n), n, 2 * C)

    def at(c, valid):     # a block past the row's length moves nothing
        return jnp.minimum(c, jnp.maximum((valid[0] + C - 1) // C - 1, 0))

    tok = pl.BlockSpec((C, 2 * n * K), lambda p, c, valid: (at(c, valid), p))
    st = pl.BlockSpec((K, 2 * n * K), lambda p, c, valid: (0, p))
    o, s = pl.pallas_call(
        _kda_chunk_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(H // (2 * n), nc),
            in_specs=[tok, tok, tok, tok,
                      pl.BlockSpec((1, 1, n, 2 * C),
                                   lambda p, c, valid: (at(c, valid), p, 0,
                                                        0)),
                      pl.BlockSpec((_KDA_SUB - 1, 2 * C, 2 * C),
                                   lambda p, c, valid: (0, 0, 0)),
                      st],
            out_specs=[tok, st]),
        out_shape=[jax.ShapeDtypeStruct((T, H * K), jnp.float32),
                   jax.ShapeDtypeStruct((K, H * K), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=64 << 20),
        interpret=interpret,
    )(jnp.reshape(valid, (1,)).astype(jnp.int32), q.reshape(T, H * K),
      k.reshape(T, H * K), v.reshape(T, H * K),
      g.astype(jnp.float32).reshape(T, H * K), brow, _kda_shift_matrices(),
      state.reshape(K, H * K))
    # the blocks the kernel skipped were never written
    ran = jnp.arange(T) < (valid + C - 1) // C * C
    return (jnp.where(ran[:, None, None], o.reshape(T, H, K), 0.0),
            s.reshape(K, H, K))


def delta_chunk_scan(q, k, v, g, beta, state, block: int, sub: int = 16,
                     valid=None, interpret: bool | None = None):
    """The recurrence over one row's chunk. ``q``, ``k`` (T, H, K) and ``v``
    (T, H, V) in the compute dtype (``q`` scaled, ``k`` of unit length);
    ``g`` float32 log-decay, (T, H) one a head or (T, H, K) one a CHANNEL of
    the key (its rank picks the form; 0 where the position is not a token),
    ``beta`` (T, H) float32 (0 there too); ``state`` (K, H, V), the state
    before the chunk as the slab holds it (:func:`state_to_slab` with the
    heads split). Matmul operands in ``q``'s dtype; the triangular system,
    every decay, accumulation and the carried state in float32. ``sub``: the
    channel form's sub-block (:func:`_channel_decay_terms`). ``valid``: how
    many of the chunk's positions are tokens (a traced scalar; all of them
    when None): the caller has zeroed ``g`` and ``beta`` past it either way,
    and the kernel (:func:`chunk_scan_supported`) skips the blocks that
    begin at or past it, whose ``o`` it leaves zero. Returns ``(o (T, H, V)
    float32, state after the chunk)``."""
    T, H, K = q.shape
    V = v.shape[-1]
    if T % block:
        raise ValueError(f"a chunk of {T} tokens is not whole blocks of "
                         f"{block}")
    if chunk_scan_supported(H, K, V, block, sub, g.ndim == 3, state.dtype):
        return _kda_chunk_call(
            q, k, v, g, beta, state, T if valid is None else valid,
            interpret=_interpret() if interpret is None else interpret,
            pairs=max(n for n in _KDA_PAIRS if H % (2 * n) == 0))
    return _delta_chunk_scan_xla(q, k, v, g, beta, state, block, sub)


def _delta_chunk_scan_xla(q, k, v, g, beta, state, block: int, sub: int = 16):
    """:func:`delta_chunk_scan` as XLA's program: every size, the scalar
    decay, and the kernel's second witness in the tests."""
    T, H, K = q.shape
    V = v.shape[-1]
    nc, C, cd, f32 = T // block, block, q.dtype, jnp.float32
    qb, kb = q.reshape(nc, C, H, K), k.reshape(nc, C, H, K)
    vb = v.reshape(nc, C, H, V)
    bb = beta.astype(f32).reshape(nc, C, H)
    if g.ndim == 2:
        A, P, rhs_k, q_in, k_out, total = _scalar_decay_terms(
            qb, kb, g.astype(f32).reshape(nc, C, H), bb)
    else:
        sub = math.gcd(sub, C)
        A, P, rhs_k, q_in, k_out, total = _channel_decay_terms(
            qb, kb, g.astype(f32).reshape(nc, C, H, K), bb, sub)
    # (I + A) [W | U] = [diag(b) V | diag(b gamma) K], a head and block
    rhs = jnp.concatenate([bb[..., None] * vb.astype(f32), rhs_k], axis=-1)
    # the inverse in float32; applied as every other product here is, its
    # operands in the compute dtype and the sums in float32
    solved = jnp.matmul(_unit_lower_inverse(A.transpose(0, 3, 1, 2))
                        .astype(cd), rhs.transpose(0, 2, 1, 3).astype(cd),
                        preferred_element_type=f32)
    W, U = solved[..., :V], solved[..., V:].astype(cd)   # (nc, H, C, V | K)
    P = P.transpose(0, 3, 1, 2).astype(cd)                    # (nc, H, t, j)
    s = state.astype(f32)                                         # (K, H, V)
    outs = []
    for c in range(nc):       # the block states, chained
        sc = s.astype(cd)
        d = (W[c] - jnp.einsum("htk,khv->htv", U[c], sc,
                               preferred_element_type=f32)).astype(cd)
        outs.append(
            jnp.einsum("thk,khv->thv", q_in[c], sc,
                       preferred_element_type=f32)
            + jnp.einsum("htj,hjv->thv", P[c], d,
                         preferred_element_type=f32))
        s = s * total[c] + jnp.einsum(
            "jhk,hjv->khv", k_out[c], d, preferred_element_type=f32)
    return jnp.concatenate(outs), s.astype(state.dtype)


# ------------------------------------------------------- one token, many rows


def decode_heads_block(heads: int, key_dim: int, value_dim: int) -> int:
    """Heads a grid step of :func:`_delta_decode_update_call` holds: the
    largest even divisor of ``heads`` whose float32 block stays under a
    megabyte (0: the kernel does not take these sizes). Even, because the
    kernel walks heads in pairs of whole lane tiles."""
    if key_dim % 8 or key_dim > _LANES or (2 * value_dim) % _LANES \
            or value_dim > _LANES * 2 or value_dim < _LANES // 2:
        return 0
    fits = [hb for hb in range(2, heads + 1, 2)
            if heads % hb == 0 and hb * key_dim * value_dim * 4 <= 1 << 20]
    return max(fits, default=0)


def decode_update_supported(heads: int, key_dim: int, value_dim: int) -> bool:
    """Whether :func:`_delta_decode_update_call` takes these sizes."""
    return decode_heads_block(heads, key_dim, value_dim) > 0


def _delta_update_kernel(slots_ref, state_ref, k_ref, q_ref, a_ref, b_ref,
                         bv_ref, o_ref, out_ref, *, value_dim: int):
    """Grid (B, head blocks). Blocks: ``state`` / ``out`` (1, K, hb V) of
    the slab at slot ``slots[b]``; ``k``, ``q`` (1, 1, hb, 128) rows (a
    head's ``K`` values, zeros after them); ``b`` (the step) and ``bv`` (``b
    v``) (1, 1, hb V) rows, a head's scalars laid along its lanes; ``a``
    (the decay) such a row where a head has ONE decay, or (1, 1, hb, 128)
    rows as ``k``'s where it has one a channel of the key (it then enters as
    a column along the head's lanes, as the key does): its block's rank says
    which; ``o`` (1, 1, hb V)."""
    del slots_ref  # only the index maps read it
    K, V = state_ref.shape[1], value_dim
    hb = k_ref.shape[2]
    lane = jax.lax.broadcasted_iota(jnp.int32, (K, _LANES), 1)

    def column(ref, h):
        # (1, 128) values k -> (K, 128): value k along row k
        row = ref[0, 0, h:h + 1, :]
        return jnp.broadcast_to(row, (_LANES, _LANES)).T[:K, :]

    def pair(ref, h):
        # the columns of heads h, h + 1 over the pair's 2 V lanes
        c0, c1 = column(ref, h), column(ref, h + 1)
        cut = V % _LANES                  # where head h + 1 begins in a tile
        parts = [c0] * (V // _LANES)
        if cut:
            parts.append(jnp.where(lane < cut, c0, c1))
        parts += [c1] * ((2 * V) // _LANES - len(parts))
        return jnp.concatenate(parts, axis=1)

    for h in range(0, hb, 2):
        at = slice(h * V, (h + 2) * V)
        kc, qc = pair(k_ref, h), pair(q_ref, h)
        ac = pair(a_ref, h) if len(a_ref.shape) == 4 else a_ref[0, :, at]
        sd = state_ref[0, :, at].astype(jnp.float32) * ac
        u = jnp.sum(sd * kc, axis=0, keepdims=True)              # S'^T k
        sn = sd + kc * (bv_ref[0, :, at] - b_ref[0, :, at] * u)
        out_ref[0, :, at] = sn.astype(out_ref.dtype)
        o_ref[0, :, at] = jnp.sum(sn * qc, axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("heads", "heads_block",
                                             "interpret"))
def _delta_decode_update_call(slab, slots, k, q, a, b, bv, heads: int,
                              heads_block: int, interpret: bool):
    """The in-place update (its own jitted name: the kernel's operation in a
    trace takes it). ``slab`` (S, K, H V); ``k``, ``q`` (B, H, K) float32;
    ``b``, ``bv`` (B, H V) float32 rows; ``a`` such a row (a decay a head) or
    (B, H, K) (a decay a channel). Returns ``(slab, o (B, H V))``."""
    _, K, HV = slab.shape
    B, H, hb = k.shape[0], heads, heads_block
    V, nb = HV // H, H // hb

    def rows(x):        # (B, H, K) -> (B, nb, hb, 128)
        return jnp.pad(x, [(0, 0), (0, 0), (0, _LANES - K)]).reshape(
            B, nb, hb, _LANES)

    slot_spec = pl.BlockSpec((1, K, hb * V),
                             lambda i, j, slots: (slots[i], 0, j))
    head_spec = pl.BlockSpec((1, 1, hb, _LANES),
                             lambda i, j, slots: (i, j, 0, 0))
    lane_spec = pl.BlockSpec((1, 1, hb * V), lambda i, j, slots: (i, 0, j))
    o, slab = pl.pallas_call(
        functools.partial(_delta_update_kernel, value_dim=V),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, nb),
            in_specs=[slot_spec, head_spec, head_spec,
                      head_spec if a.ndim == 3 else lane_spec, lane_spec,
                      lane_spec],
            out_specs=[lane_spec, slot_spec]),
        out_shape=[jax.ShapeDtypeStruct((B, 1, HV), jnp.float32),
                   jax.ShapeDtypeStruct(slab.shape, slab.dtype)],
        # operand 0 is the scalar-prefetched slots; the slab is operand 1
        input_output_aliases={1: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=32 << 20),
        interpret=interpret,
    )(slots, slab, rows(k), rows(q),
      rows(a) if a.ndim == 3 else a[:, None, :], b[:, None, :],
      bv[:, None, :])
    return slab, o[:, 0, :]


def delta_decode_update(slab, slots, q, k, v, g, beta, kernel: str = "gather",
                        interpret: bool | None = None):
    """One token for each of B rows: ``slab`` (S, K, H V) the states,
    ``slots`` (B,) each row's slot in it (the rows without one name the
    dummy slot 0), ``q``, ``k`` (B, H, K), ``v`` (B, H, V), ``g`` (B, H)
    log-decay, ``beta`` (B, H). Everything in float32 but the stored state
    (the slab's dtype). Returns ``(slab, o (B, H, V) float32)``; ``slab`` is
    updated in the live rows' slots and the dummy's and nowhere else."""
    f32 = jnp.float32
    B, H, K = q.shape
    V = v.shape[-1]
    qf, kf, vf = q.astype(f32), k.astype(f32), v.astype(f32)
    a, bt = jnp.exp(g.astype(f32)), beta.astype(f32)    # (B, H[, K]), (B, H)
    hb = decode_heads_block(H, K, V)
    if kernel == "pallas" and hb:
        def lanes(x):   # (B, H) -> (B, H V): a head's scalar over its lanes
            return jnp.repeat(x, V, axis=1)

        slab, o = _delta_decode_update_call(
            slab, slots.astype(jnp.int32), kf, qf,
            a if a.ndim == 3 else lanes(a), lanes(bt),
            (bt[..., None] * vf).reshape(B, H * V), heads=H, heads_block=hb,
            interpret=_interpret() if interpret is None else interpret)
        return slab, o.reshape(B, H, V)
    s = slab_to_state(slab[slots].astype(f32), H) * (
        a[..., None] if a.ndim == 3 else a[..., None, None])
    d = bt[..., None] * (vf - jnp.einsum("bhkv,bhk->bhv", s, kf))
    s = s + kf[..., None] * d[..., None, :]
    o = jnp.einsum("bhkv,bhk->bhv", s, qf)
    return slab.at[slots].set(state_to_slab(s).astype(slab.dtype)), o
