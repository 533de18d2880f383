"""Flash attention as a Pallas TPU kernel.

The XLA formulation in :mod:`marlin_tpu.parallel.ring_attention` materializes
each (sq × kv_tile) score tile in HBM between the two matmuls and the softmax
update — at 32k tokens that is hundreds of MB of HBM traffic per tile, and the
measured ceiling is a few TFLOP/s. This kernel is the classic flash-attention
schedule on the MXU: score tiles live only in VMEM, the running max/denominator
(m, l) and the f32 output accumulator update in VMEM scratch across KV blocks,
and fully-masked causal blocks are predicated off with ``pl.when`` so the
causal pass does half the matmul work.

The kernel is shaped as a *panel* update so ring attention can drive it: it
takes the carried (m, l, acc) state in and returns the updated state, with
global query/key offsets and a valid-length bound supplied as scalar-prefetch
arguments (the ring rotates K/V panels, so the key offset changes per step).
Single-device attention is the one-panel special case.

No reference analog (the reference predates attention, SURVEY.md §2.7);
this is the long-context mandate's hot kernel.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_kernels import _interpret

__all__ = ["flash_attention_panel", "flash_attention_panel_bwd",
           "flash_attention_single_panel", "block_divisor"]

_NEG = -1e30

# Every kernel dot pins an EXPLICIT precision: left to the backend default,
# the f32 matmuls silently ran single-pass bf16 after a runtime update
# changed Mosaic's default — rel err 3.03e-3 (2^-8 mantissa) against the
# pinned-precision oracle, caught by an on-chip smoke run (rounds 2-4 rode the
# OLD default, which extended f32 operands to true-f32 MXU passes — the
# class every prior measurement of this kernel had). The pin is HIGHEST:
# Mosaic lowers exactly DEFAULT and HIGHEST (HIGH/bf16_3x is rejected:
# "Unsupported dot precision"), and HIGHEST reproduces the historical
# numerics. The measured 2x single-pass speedup (13 ms vs 26 ms at 32k)
# remains available through the EXISTING accuracy knob — precision="default"
# casts Q/K/V to bf16, and bf16 operands are unaffected by the pin
# (precision controls only the f32 decomposition). The backward casts its
# f32 probability/ds tiles DOWN to the input dtype before each dot, so
# bf16-mode backward matmuls stay single-pass like the forward's.
_DOT_PREC = jax.lax.Precision.HIGHEST


def _prec(ref_or_val):
    """HIGHEST for f32 operands only: Mosaic rejects an explicit precision
    on bf16 dots ("Bad lhs type" — there is no f32 decomposition to pick),
    and bf16's native single-pass matmul is the wanted behavior anyway."""
    return _DOT_PREC if ref_or_val.dtype == jnp.float32 else None


def block_divisor(n: int, cap: int | None = None) -> int:
    """The flash block-size policy shared by every caller of
    :func:`flash_attention_panel` (ring + ulysses + prefill).

    m/l (and the backward's lse/Δ) cross the kernel boundary in the
    exact-packed ``(n//128, 128)`` form (see ``_panel_kernel`` — the
    ``(n, 1)`` form tile-pads 128x in HBM), and Pallas requires their
    ``(bq//128, 128)`` blocks to have sublanes divisible by 8 or equal to
    the whole array. Hence the contract: panels longer than 1024 are padded
    by the callers to 1024 multiples and run ``bq=1024`` (blocks (8, 128) —
    legal, and with the packed m/l the old 1024-block scoped-VMEM overflow
    at ≥64k panels is gone: the overage WAS the six (1024, 1)→(1024, 128)
    padded m/l blocks); shorter panels run as one whole-panel block
    (``bq == n``, the "equal to the array" clause). 1024 is also the VMEM
    ceiling for the (bq, bkv) f32 score tile itself — 4 MB; a 2048
    whole-panel tile would be 16 MB, the entire scoped budget. With an
    explicit ``cap`` (tests), the largest power-of-two divisor ≤ cap is
    returned unchanged."""
    if cap is None:
        if n % 1024 == 0:
            return 1024
        if n % 128 == 0 and n <= 1024:
            return n  # single whole-panel block
        cap = 1024  # unpadded legacy caller: interpret-mode only
    b = 1
    while b < cap and n % (b * 2) == 0:
        b *= 2
    return b


def _panel_kernel(s_ref, q_ref, k_ref, v_ref, m_in, l_in, acc_in,
                  m_out, l_out, acc_out, m_s, l_s, acc_s,
                  *, causal: bool, scale: float, bq: int, bkv: int):
    # m/l cross the kernel boundary as (bq//128, 128) blocks — the
    # exact-packed form of the per-row vectors: value for q-row p lives at
    # (p // 128, p % 128), which under the TPU's (8, 128) tiling is the SAME
    # byte layout as the 1-D (bq,) row vector, so every reshape between
    # (bq, X) and (bq//128, 128, X) below is layout-free. The (bq, 1) form
    # this replaces tile-pads 128x — ~0.5 GiB of dead HBM per m/l tensor per
    # head at 1M-token panels, the dominant non-data term in the measured
    # flash footprint — and plain 1-D (bq,) blocks Mosaic rejects whenever
    # bq differs from XLA's 1024-element 1-D tile.
    g = bq // 128
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _load_carry():
        m_s[:] = m_in[:]
        l_s[:] = l_in[:]
        acc_s[:] = acc_in[:]

    q_start = s_ref[0] + pl.program_id(0) * bq
    k_start = s_ref[1] + j * bkv
    valid = s_ref[2]
    live = k_start < valid
    if causal:
        # block is fully masked when even the last query row precedes the
        # first key of the block — skip the matmuls entirely
        live = jnp.logical_and(live, q_start + bq - 1 >= k_start)

    @pl.when(live)
    def _accumulate():
        s = jax.lax.dot_general(
            q_ref[:], k_ref[:], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32, precision=_prec(q_ref),
        ) * scale
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)
        keep = kpos < valid
        if causal:
            qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 0)
            keep = jnp.logical_and(keep, qpos >= kpos)
        s3 = jnp.where(keep, s, _NEG).reshape(g, 128, bkv)
        m_prev = m_s[:]
        m_new = jnp.maximum(m_prev, jnp.max(s3, axis=2))
        alpha = jnp.exp(m_prev - m_new)
        # exp(s - m_new) alone mis-handles a fully-masked row whose running
        # max is still _NEG (exp(0) = 1 per masked key); zero them exactly
        p3 = jnp.where(keep.reshape(g, 128, bkv),
                       jnp.exp(s3 - m_new[:, :, None]), 0.0)
        l_s[:] = l_s[:] * alpha + jnp.sum(p3, axis=2)
        pv = jnp.dot(p3.reshape(bq, bkv).astype(v_ref.dtype), v_ref[:],
                     preferred_element_type=jnp.float32,
                     precision=_prec(v_ref))
        d = acc_s.shape[-1]
        acc3 = acc_s[:].reshape(g, 128, d)
        acc_s[:] = (acc3 * alpha[:, :, None]
                    + pv.reshape(g, 128, d)).reshape(bq, d)
        m_s[:] = m_new

    @pl.when(j == pl.num_programs(1) - 1)
    def _flush():
        m_out[:] = m_s[:]
        l_out[:] = l_s[:]
        acc_out[:] = acc_s[:]


def _bwd_block_live(q_start, k_start, valid, bq, causal: bool):
    live = k_start < valid
    if causal:
        live = jnp.logical_and(live, q_start + bq - 1 >= k_start)
    return live


def _bwd_p_ds(q_blk, k_blk, v_blk, do_blk, lse_blk, delta_blk,
              q_start, k_start, valid, *, causal: bool, scale: float,
              bq: int, bkv: int):
    """Recompute the (bq, bkv) probability tile from the forward's logsumexp
    and form ds = p ⊙ (dOᐧVᵀ − Δ) — the shared core of both backward kernels.
    Saved state is O(seq): lse and Δ rows in the exact-packed (bq//128, 128)
    block form (see _panel_kernel on why), never score tiles."""
    g = bq // 128
    s = jax.lax.dot_general(
        q_blk, k_blk, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32, precision=_prec(q_blk),
    ) * scale
    kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)
    keep = kpos < valid
    if causal:
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 0)
        keep = jnp.logical_and(keep, qpos >= kpos)
    s3 = s.reshape(g, 128, bkv)
    p = jnp.where(keep, jnp.exp(s3 - lse_blk[:, :, None]).reshape(bq, bkv),
                  0.0)
    dp = jax.lax.dot_general(
        do_blk, v_blk, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32, precision=_prec(do_blk),
    )
    ds = p * (dp.reshape(g, 128, bkv)
              - delta_blk[:, :, None]).reshape(bq, bkv)
    return p, ds


def _bwd_dkv_kernel(s_ref, q_ref, do_ref, lse_ref, delta_ref, k_ref, v_ref,
                    dk_out, dv_out, dk_s, dv_s,
                    *, causal: bool, scale: float, bq: int, bkv: int):
    """dK/dV for one K/V panel: grid (kv blocks, q blocks) — the kv block is
    outer so its (dk, dv) accumulators stay resident in VMEM while every q
    block streams past."""
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        dk_s[:] = jnp.zeros_like(dk_s)
        dv_s[:] = jnp.zeros_like(dv_s)

    q_start = s_ref[0] + i * bq
    k_start = s_ref[1] + pl.program_id(0) * bkv
    valid = s_ref[2]

    @pl.when(_bwd_block_live(q_start, k_start, valid, bq, causal))
    def _accumulate():
        p, ds = _bwd_p_ds(q_ref[:], k_ref[:], v_ref[:], do_ref[:], lse_ref[:],
                          delta_ref[:], q_start, k_start, valid,
                          causal=causal, scale=scale, bq=bq, bkv=bkv)
        # explicit-transpose dot: the canonical Mosaic-supported form for
        # contracting the sublane dim (jax pallas tpu flash kernels)
        dv_s[:] += jax.lax.dot(p.T.astype(do_ref.dtype), do_ref[:],
                               preferred_element_type=jnp.float32,
                               precision=_prec(do_ref))
        dk_s[:] += jax.lax.dot(ds.T.astype(q_ref.dtype), q_ref[:],
                               preferred_element_type=jnp.float32,
                               precision=_prec(q_ref)) * scale

    @pl.when(i == pl.num_programs(1) - 1)
    def _flush():
        dk_out[:] = dk_s[:]
        dv_out[:] = dv_s[:]


def _bwd_dq_kernel(s_ref, q_ref, do_ref, lse_ref, delta_ref, k_ref, v_ref,
                   dq_out, dq_s,
                   *, causal: bool, scale: float, bq: int, bkv: int):
    """dQ for one K/V panel: grid (q blocks, kv blocks) — q outer so the dq
    accumulator stays resident while the panel's kv blocks stream past."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        dq_s[:] = jnp.zeros_like(dq_s)

    q_start = s_ref[0] + pl.program_id(0) * bq
    k_start = s_ref[1] + j * bkv
    valid = s_ref[2]

    @pl.when(_bwd_block_live(q_start, k_start, valid, bq, causal))
    def _accumulate():
        _, ds = _bwd_p_ds(q_ref[:], k_ref[:], v_ref[:], do_ref[:], lse_ref[:],
                          delta_ref[:], q_start, k_start, valid,
                          causal=causal, scale=scale, bq=bq, bkv=bkv)
        dq_s[:] += jnp.dot(
            ds.astype(k_ref.dtype), k_ref[:],
            preferred_element_type=jnp.float32, precision=_prec(k_ref),
        ) * scale

    @pl.when(j == pl.num_programs(1) - 1)
    def _flush():
        dq_out[:] = dq_s[:]


def flash_attention_panel_bwd(q, k, v, do, lse, delta, q_offset, k_offset,
                              valid_len, *, causal: bool, scale: float,
                              bq: int = 1024, bkv: int = 1024,
                              interpret: bool | None = None):
    """Backward of one flash panel — the classic two-pass recompute schedule:
    probabilities are rebuilt per tile from the forward's ``lse`` rows
    (lse = m + log l) and ``delta`` (= rowsum(dO ⊙ O)), both 1-D ``(sq,)``
    (lane-major — see _panel_kernel on the (n, 1) HBM padding), so the
    backward holds O(block²) score memory instead of the O(seq · tile)
    residuals an autodiff of the tiled formulation would save. Returns f32
    ``(dq, dk, dv)`` for this panel; the ring caller sums dq over panels and
    rotates dk/dv home.
    """
    sq, d = q.shape
    skv = k.shape[0]
    bq = min(bq, sq)
    bkv = min(bkv, skv)
    # the backward holds three (bq, bkv) f32 tiles at once (p, ds, dOᐧVᵀ) —
    # at 1024x1024 that is 12 MB of tiles and the kernel total overflows the
    # 16 MB scoped-VMEM budget by ~0.8 MB (the forward's two tiles fit), so
    # the K/V tile halves at the 1024 block size
    if bq >= 1024 and bkv >= 1024 and skv % (bkv // 2) == 0:
        bkv //= 2
    if sq % bq or skv % bkv:
        raise ValueError(f"block sizes ({bq},{bkv}) must divide panel dims "
                         f"({sq},{skv})")
    if sq % 128 or bq % 128:
        raise ValueError(f"panel length ({sq}) and bq ({bq}) must be "
                         "multiples of 128 (lse/Δ rows are carried in the "
                         "exact-packed (n//128, 128) form)")
    if interpret is None:
        interpret = _interpret()
    if not interpret and (bq // 128) % 8 and bq != sq:
        raise ValueError(
            f"bq ({bq}) must be a multiple of 1024 or the whole panel "
            f"({sq}) for the TPU lowering — pad panels > 1024 to 1024 "
            "multiples (block_divisor documents the contract)")
    scalars = jnp.stack([jnp.asarray(q_offset, jnp.int32),
                         jnp.asarray(k_offset, jnp.int32),
                         jnp.asarray(valid_len, jnp.int32)])
    vma = jax.typeof(q).vma
    f32 = jnp.float32
    g = bq // 128
    lse2 = lse.reshape(sq // 128, 128)
    delta2 = delta.reshape(sq // 128, 128)

    kern_kv = functools.partial(_bwd_dkv_kernel, causal=causal, scale=scale,
                                bq=bq, bkv=bkv)
    dk, dv = pl.pallas_call(
        kern_kv,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(skv // bkv, sq // bq),
            in_specs=[
                pl.BlockSpec((bq, d), lambda j, i, *_: (i, 0)),
                pl.BlockSpec((bq, d), lambda j, i, *_: (i, 0)),
                pl.BlockSpec((g, 128), lambda j, i, *_: (i, 0)),
                pl.BlockSpec((g, 128), lambda j, i, *_: (i, 0)),
                pl.BlockSpec((bkv, d), lambda j, i, *_: (j, 0)),
                pl.BlockSpec((bkv, d), lambda j, i, *_: (j, 0)),
            ],
            out_specs=[
                pl.BlockSpec((bkv, d), lambda j, i, *_: (j, 0)),
                pl.BlockSpec((bkv, d), lambda j, i, *_: (j, 0)),
            ],
            scratch_shapes=[
                pltpu.VMEM((bkv, d), f32),
                pltpu.VMEM((bkv, d), f32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((skv, d), f32, vma=vma),
            jax.ShapeDtypeStruct((skv, d), f32, vma=vma),
        ],
        interpret=interpret,
    )(scalars, q, do, lse2, delta2, k, v)

    kern_q = functools.partial(_bwd_dq_kernel, causal=causal, scale=scale,
                               bq=bq, bkv=bkv)
    dq = pl.pallas_call(
        kern_q,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(sq // bq, skv // bkv),
            in_specs=[
                pl.BlockSpec((bq, d), lambda i, j, *_: (i, 0)),
                pl.BlockSpec((bq, d), lambda i, j, *_: (i, 0)),
                pl.BlockSpec((g, 128), lambda i, j, *_: (i, 0)),
                pl.BlockSpec((g, 128), lambda i, j, *_: (i, 0)),
                pl.BlockSpec((bkv, d), lambda i, j, *_: (j, 0)),
                pl.BlockSpec((bkv, d), lambda i, j, *_: (j, 0)),
            ],
            out_specs=pl.BlockSpec((bq, d), lambda i, j, *_: (i, 0)),
            scratch_shapes=[pltpu.VMEM((bq, d), f32)],
        ),
        out_shape=jax.ShapeDtypeStruct((sq, d), f32, vma=vma),
        interpret=interpret,
    )(scalars, q, do, lse2, delta2, k, v)
    return dq, dk, dv


def flash_attention_panel(q, k, v, m, l, acc, q_offset, k_offset, valid_len,
                          *, causal: bool, scale: float, bq: int = 1024,
                          bkv: int = 1024, interpret: bool | None = None):
    """One flash pass of queries ``q`` (sq, d) against a K/V panel (skv, d),
    updating the running state:

    - ``m``/``l``: (sq,) f32 running max / softmax denominator — 1-D because
      (sq, 1) tile-pads 128x in HBM (see _panel_kernel)
    - ``acc``: (sq, d) f32 unnormalized output accumulator
    - ``q_offset``/``k_offset``: global positions of q row 0 / panel key 0
      (the ring caller's device coordinate × block size)
    - ``valid_len``: global sequence length; keys at/after it are masked

    Returns the updated ``(m, l, acc)``. The caller divides ``acc / l`` after
    the last panel. Block sizes are clamped to the panel dims; sq and skv must
    then divide by them (the ring caller pads to guarantee it).
    """
    sq, d = q.shape
    skv = k.shape[0]
    bq = min(bq, sq)
    bkv = min(bkv, skv)
    if sq % bq or skv % bkv:
        raise ValueError(f"block sizes ({bq},{bkv}) must divide panel dims "
                         f"({sq},{skv})")
    if sq % 128 or bq % 128:
        raise ValueError(f"panel length ({sq}) and bq ({bq}) must be "
                         "multiples of 128 (the m/l rows are carried in the "
                         "exact-packed (n//128, 128) form)")
    if interpret is None:
        interpret = _interpret()
    if not interpret and (bq // 128) % 8 and bq != sq:
        # the packed m/l BlockSpec needs 8-divisible sublanes or the whole
        # array (Pallas TPU constraint) — fail here with the contract named
        # instead of deep inside Mosaic; interpret mode has no such limit
        raise ValueError(
            f"bq ({bq}) must be a multiple of 1024 or the whole panel "
            f"({sq}) for the TPU lowering — pad panels > 1024 to 1024 "
            "multiples (block_divisor documents the contract)")
    scalars = jnp.stack([jnp.asarray(q_offset, jnp.int32),
                         jnp.asarray(k_offset, jnp.int32),
                         jnp.asarray(valid_len, jnp.int32)])
    g = bq // 128
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(sq // bq, skv // bkv),
        in_specs=[
            pl.BlockSpec((bq, d), lambda i, j, *_: (i, 0)),
            pl.BlockSpec((bkv, d), lambda i, j, *_: (j, 0)),
            pl.BlockSpec((bkv, d), lambda i, j, *_: (j, 0)),
            pl.BlockSpec((g, 128), lambda i, j, *_: (i, 0)),
            pl.BlockSpec((g, 128), lambda i, j, *_: (i, 0)),
            pl.BlockSpec((bq, d), lambda i, j, *_: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((g, 128), lambda i, j, *_: (i, 0)),
            pl.BlockSpec((g, 128), lambda i, j, *_: (i, 0)),
            pl.BlockSpec((bq, d), lambda i, j, *_: (i, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((g, 128), jnp.float32),
            pltpu.VMEM((g, 128), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
    )
    kern = functools.partial(_panel_kernel, causal=causal, scale=scale,
                             bq=bq, bkv=bkv)
    # under shard_map the inputs carry varying-manual-axes types; the outputs
    # must declare the same so the vma checker can see through pallas_call
    vma = jax.typeof(q).vma
    m2, l2, a2 = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((sq // 128, 128), jnp.float32, vma=vma),
            jax.ShapeDtypeStruct((sq // 128, 128), jnp.float32, vma=vma),
            jax.ShapeDtypeStruct((sq, d), jnp.float32, vma=vma),
        ],
        interpret=interpret,
    )(scalars, q, k, v, m.reshape(sq // 128, 128),
      l.reshape(sq // 128, 128), acc)
    return m2.reshape(sq), l2.reshape(sq), a2


def flash_attention_single_panel(q, k, v, valid_len, *, causal: bool,
                                 scale: float):
    """Full-sequence attention for one head as ONE flash panel: init the
    (m, l, acc) state, a single :func:`flash_attention_panel` pass over all
    keys, then normalize. Returns ``(out, lse)`` with ``out`` in f32 (callers
    cast) and 1-D ``lse = m + log l`` rows of shape ``(seq,)`` for custom-vjp
    backwards — 1-D end to end, because a ``(seq, 1)`` f32 array pads 128x
    under the TPU's (8, 128) tiling, in HBM and in any fusion that stack-
    allocates it in scoped VMEM (at 32k x heads that padding alone blew the
    VMEM budget; at 1M panels it was ~0.5 GiB of dead HBM per tensor).

    The shared single-panel idiom of ulysses local attention
    (parallel/ulysses.py) and the decode flash prefill
    (models/transformer.py) — one home for the state-init/normalize contract
    (the ``_NEG`` sentinel and the 1e-30 denominator floor)."""
    seq, d = q.shape
    b = block_divisor(seq)
    m = jnp.full((seq,), _NEG, jnp.float32)
    l = jnp.zeros((seq,), jnp.float32)
    acc = jnp.zeros((seq, d), jnp.float32)
    m, l, acc = flash_attention_panel(q, k, v, m, l, acc, 0, 0, valid_len,
                                      causal=causal, scale=scale, bq=b, bkv=b)
    lse = m + jnp.log(jnp.maximum(l, 1e-30))
    return acc / jnp.maximum(l, 1e-30)[:, None], lse
