"""The selective state-space recurrence of a Mamba-2 mixer, two ways.

Per head ``h`` (``P`` channels, a state of ``N`` columns a channel; head
``h`` reads the ``B``, ``C`` of group ``h // (heads / groups)``)::

    S_t = exp(dt_t A) S_{t-1} + dt_t B_t x_t^T        (S is N x P)
    y_t = S_t^T C_t + D x_t

- :func:`ssd_chunk_scan` runs it over ONE row's chunk of tokens, entered
  with the row's state and leaving the state after the chunk's last valid
  token: the chunked ("state-space dual") form. The chunk is cut into blocks
  of ``block`` tokens; inside a block the recurrence is a masked matmul
  (``(C B^T * decay) (dt x)``), a block leaves ``sum_s decay_to_end dt_s B_s
  x_s^T`` to the state, and only the ``chunk / block`` block states are
  chained sequentially. A position with ``dt == 0`` neither decays the state
  nor adds to it: that is how the positions past a row's length are kept out
  (the caller zeroes their ``dt``).
- :func:`ssd_decode_update` advances one token for each row of a batch,
  each row's state living in slot ``slots[b]`` of a slab ``(slots, heads, N,
  P)``. ``kernel="pallas"`` reads and writes each row's slot ONCE, in place
  (:func:`_ssm_decode_update_call`: the slot id is scalar-prefetched and
  drives the block index, the slab is aliased to the output); the rows that
  share the dummy slot 0 scribble on it and on nothing else.
  ``kernel="gather"`` is the same arithmetic on a gathered copy.

The state is stored ``(N, P)``, channels on the lanes: the update's outer
product ``B x^T`` then takes ``x`` as a row (a sublane broadcast) and ``B``
as a column, which the kernel makes from a row by transposing one 128 x 128
tile; the contraction with ``C`` is a sum over sublanes.

The causal depthwise convolution that feeds the recurrence is here too
(:func:`causal_conv`, :func:`conv_step`): ``taps`` taps over time a channel,
carried across calls as the last ``taps - 1`` inputs (the "tail"). A decode
call reads and writes each live row's tail IN ITS SLOT, as the state beside
it is (:func:`conv_step_slots`; ``kernel="pallas"``: one
:func:`_conv_slots_call` a layer, the slot id scalar-prefetched, the slab
aliased to the output, the taps and the bias resident; ``kernel="gather"``:
:func:`conv_step`, the reference, on a gathered copy). A slot of the slab is
whole tiles in one piece for that (:func:`tail_slot_shape`). The delta-rule
mixers' tails (:mod:`~marlin_tpu.ops.delta_rule`) take the same step.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_kernels import _interpret

__all__ = ["causal_conv", "conv_step", "conv_step_slots", "tail_slot_shape",
           "slot_tails", "tails_slots", "conv_slots_supported",
           "ssd_chunk_scan", "ssd_decode_update", "decode_update_supported"]

_LANES = 128


# ---------------------------------------------------------------- convolution


def causal_conv(u, tail, w, b, n_valid):
    """``out[t] = b + sum_k w[k] * ext[t + k]`` over ``ext = [tail; u]``:
    ``u`` (T, ch) the chunk's inputs, ``tail`` (taps - 1, ch) the inputs
    before it, ``w`` (taps, ch), ``b`` (ch,). Returns ``(out (T, ch) float32,
    new tail)``: the tail after the chunk's first ``n_valid`` inputs (the
    ``taps - 1`` inputs that end at the last valid one; the old tail's where
    the chunk holds fewer)."""
    taps = w.shape[0]
    ext = jnp.concatenate([tail.astype(u.dtype), u], axis=0)
    wf = w.astype(jnp.float32)
    out = b.astype(jnp.float32)[None, :] + sum(
        ext[k:k + u.shape[0]].astype(jnp.float32) * wf[k][None, :]
        for k in range(taps))
    new_tail = jax.lax.dynamic_slice_in_dim(ext, n_valid, taps - 1, axis=0)
    return out, new_tail.astype(tail.dtype)


def conv_step(u, tails, w, b):
    """One token a row: ``u`` (B, ch), ``tails`` (B, taps - 1, ch). Returns
    ``(out (B, ch) float32, new tails)``."""
    ext = jnp.concatenate([tails.astype(u.dtype), u[:, None, :]], axis=1)
    out = b.astype(jnp.float32)[None, :] + jnp.einsum(
        "bkc,kc->bc", ext.astype(jnp.float32), w.astype(jnp.float32))
    return out, ext[:, 1:].astype(tails.dtype)


def conv_slots_supported(channels: int) -> bool:
    """Whether :func:`_conv_slots_call` takes a tail of ``channels``
    channels: whole 128-lane tiles."""
    return channels % _LANES == 0


def tail_slot_shape(taps: int, channels: int) -> tuple:
    """What ONE slot of a tails slab holds. Where the channels are whole lane
    tiles, ``(rows, 128)``: the ``(taps - 1, channels)`` values in that order
    (tap ``k`` the rows ``k * channels / 128`` onward), then zeros up to a
    whole sublane tile of 8 rows, so that a slot is whole tiles, lies in one
    piece and can be copied alone. ``(taps - 1, channels)`` otherwise. (XLA
    keeps ``(slots, taps - 1, channels)`` with the SLOTS on the sublanes, two
    slots to a 32-bit word in bfloat16: no slot can then be written alone,
    and a program that tries copies the whole array into another layout and
    back.) :func:`slot_tails` and :func:`tails_slots` pass between the two."""
    if conv_slots_supported(channels):
        return (-(-(taps - 1) * channels // _LANES // 8) * 8, _LANES)
    return (taps - 1, channels)


def slot_tails(values, taps: int, channels: int):
    """``(..., *tail_slot_shape)`` as the slab holds it to ``(..., taps - 1,
    channels)``."""
    lead, n = values.shape[:-2], (taps - 1) * channels
    return values.reshape(*lead, -1)[..., :n].reshape(*lead, taps - 1,
                                                      channels)


def tails_slots(tails, taps: int, channels: int):
    """``(..., taps - 1, channels)`` to what the slab holds,
    :func:`slot_tails`' inverse."""
    lead, shape = tails.shape[:-2], tail_slot_shape(taps, channels)
    flat = tails.reshape(*lead, -1)
    pad = shape[0] * shape[1] - flat.shape[-1]
    if pad:
        flat = jnp.pad(flat, [(0, 0)] * len(lead) + [(0, pad)])
    return flat.reshape(*lead, *shape)


#: tails in flight either way in :func:`_conv_slots_kernel`, and the most
#: rows one of its grid steps takes
_TAILS_IN_FLIGHT = 4
_ROWS_A_STEP = 8


def _conv_slots_kernel(slots_ref, slab_ref, u_ref, w_ref, b_ref, out_ref,
                       new_slab_ref, tails, shifted, fetched, stored, *,
                       rows: int):
    """Grid (rows / rb,), a step ``rb`` rows. ``slab`` / ``new_slab`` (S,
    *:func:`tail_slot_shape`), the one array, left where it lies: row
    ``r``'s tail is copied out of slot ``slots[r]`` into ``tails[r % D]`` ``D
    - 1`` rows ahead of its turn, and the tail that follows it from
    ``shifted[r % D]`` back into the slot behind it, awaited ``D`` rows on
    (and at the last step): a row's copy is small, so several must be in
    flight to fill the memory's pipe. Blocks: ``u`` (rb, R, 128); ``w``
    (taps, R, 128) and ``b`` (R, 128), the same at every step; ``out`` (rb,
    R, 128) float32."""
    i = pl.program_id(0)
    rb = u_ref.shape[0]
    taps, R = w_ref.shape[:2]
    D, held, f32 = tails.shape[0], (taps - 1) * R, jnp.float32

    def fetch(r):
        at = jax.lax.rem(r, D)
        return pltpu.make_async_copy(slab_ref.at[slots_ref[r]], tails.at[at],
                                     fetched.at[at])

    def store(r):
        at = jax.lax.rem(r, D)
        return pltpu.make_async_copy(shifted.at[at],
                                     new_slab_ref.at[slots_ref[r]],
                                     stored.at[at])

    @pl.when(i == 0)
    def _():
        for r in range(min(D - 1, rows)):
            fetch(r).start()

    for j in range(rb):
        r = i * rb + j
        at = jax.lax.rem(r, D)
        pl.when(r + D - 1 < rows)(lambda r=r: fetch(r + D - 1).start())
        fetch(r).wait()
        pl.when(r >= D)(lambda r=r: store(r - D).wait())
        u = u_ref[j]
        acc = b_ref[...].astype(f32) + u.astype(f32) * w_ref[
            taps - 1].astype(f32)
        for k in range(taps - 1):
            t = tails[at, k * R:(k + 1) * R, :]
            acc = acc + t.astype(f32) * w_ref[k].astype(f32)
            if k:
                shifted[at, (k - 1) * R:k * R, :] = t
        shifted[at, held - R:held, :] = u.astype(shifted.dtype)
        if shifted.shape[1] > held:     # what pads the slot to whole tiles
            shifted[at, held:, :] = jnp.zeros(
                (shifted.shape[1] - held, _LANES), shifted.dtype)
        out_ref[j] = acc
        store(r).start()

    @pl.when(i == pl.num_programs(0) - 1)
    def _():
        for r in range(max(rows - D, 0), rows):
            store(r).wait()


def _conv_slots_call(slab, slots, u, w, b, interpret: bool):
    """The in-place step (no jitted name of its own: the kernel's operation
    in a trace sits under the caller's scope). ``slab`` (S,
    *:func:`tail_slot_shape`); ``u`` (B, R, 128); ``w`` (taps, R, 128);
    ``b`` (R, 128). Returns ``(out (B, R, 128) float32, slab)``. The slab is
    held to HBM: left to choose, XLA moves a slab of tens of megabytes into
    VMEM ahead of the call and back behind it, all of it. The caller's
    program must DONATE the slab, as the paged programs do: where XLA has to
    copy a slab that is held to HBM, its memory assignment aborts the
    compile (jaxlib 0.9.0: "Conflicting pending required assignment")."""
    B, R = u.shape[:2]
    rb = max(d for d in range(1, _ROWS_A_STEP + 1) if B % d == 0)
    row_spec = pl.BlockSpec((rb, R, _LANES), lambda i, slots: (i, 0, 0))
    in_place = pl.BlockSpec(memory_space=pltpu.HBM)
    buffers = pltpu.VMEM((_TAILS_IN_FLIGHT, *slab.shape[1:]), slab.dtype)
    copies = pltpu.SemaphoreType.DMA((_TAILS_IN_FLIGHT,))
    return pl.pallas_call(
        functools.partial(_conv_slots_kernel, rows=B),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B // rb,),
            in_specs=[in_place, row_spec,
                      pl.BlockSpec(w.shape, lambda i, slots: (0, 0, 0)),
                      pl.BlockSpec(b.shape, lambda i, slots: (0, 0))],
            out_specs=[row_spec, in_place],
            scratch_shapes=[buffers, buffers, copies, copies]),
        out_shape=[jax.ShapeDtypeStruct((B, R, _LANES), jnp.float32),
                   pltpu.HBM(slab.shape, slab.dtype)],
        # operand 0 is the scalar-prefetched slots; the slab is operand 1
        input_output_aliases={1: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(slots, slab, u, w, b)


def conv_step_slots(slab, slots, u, w, b, kernel: str = "gather",
                    interpret: bool | None = None):
    """:func:`conv_step` for B rows whose tails live in a slab: ``slab`` (S,
    *:func:`tail_slot_shape`) the tails, ``slots`` (B,) each row's slot in
    it (the rows without one name the dummy slot 0), ``u`` (B, ch), ``w``
    (taps, ch), ``b`` (ch,). Returns ``(out (B, ch) float32, slab)``;
    ``slab`` is advanced in the live rows' slots and the dummy's and nowhere
    else. ``kernel="pallas"`` reads and writes each row's slot ONCE, in
    place (:func:`_conv_slots_call`, where :func:`conv_slots_supported`;
    the jitted program around it must donate ``slab``);
    ``kernel="gather"`` is :func:`conv_step` on a gathered copy."""
    B, ch = u.shape
    taps = w.shape[0]
    if slab.shape[1:] != tail_slot_shape(taps, ch):
        raise ValueError(f"a slot of the tails slab is {slab.shape[1:]}, not "
                         f"{tail_slot_shape(taps, ch)} (tail_slot_shape)")
    if kernel == "pallas" and conv_slots_supported(ch):
        R = ch // _LANES
        out, slab = _conv_slots_call(
            slab, slots.astype(jnp.int32), u.reshape(B, R, _LANES),
            w.reshape(taps, R, _LANES), b.reshape(R, _LANES),
            interpret=_interpret() if interpret is None else interpret)
        return out.reshape(B, ch), slab
    out, t1 = conv_step(u, slot_tails(slab[slots], taps, ch), w, b)
    return out, slab.at[slots].set(tails_slots(t1, taps, ch))


# ------------------------------------------------------- a chunk of one row


def ssd_chunk_scan(x, dt, A, Bm, Cm, D, state, block: int):
    """The recurrence over one row's chunk. ``x`` (T, H, P) in the compute
    dtype; ``dt`` (T, H) float32, already positive (0 where the position is
    not a token); ``A`` (H,) negative; ``Bm``, ``Cm`` (T, G, N); ``D`` (H,);
    ``state`` (H, N, P), the state before the chunk. Matmul operands in
    ``x``'s dtype, every accumulation, decay and the carried state in
    float32. Returns ``(y (T, H, P) float32, state after the chunk)``."""
    T, H, P = x.shape
    G, N = Bm.shape[1:]
    if T % block:
        raise ValueError(f"a chunk of {T} tokens is not whole blocks of "
                         f"{block} (mamba_chunk_size)")
    nc, Q, hpg, cd = T // block, block, H // G, x.dtype
    f32 = jnp.float32
    xb = x.reshape(nc, Q, G, hpg, P)
    dtb = dt.astype(f32).reshape(nc, Q, G, hpg)
    Bb, Cb = Bm.reshape(nc, Q, G, N), Cm.reshape(nc, Q, G, N)
    # cs[c, t]: the log-decay from the block's start through position t
    cs = jnp.cumsum(dtb * A.astype(f32).reshape(G, hpg), axis=1)
    # inside a block: y_t += sum_{s <= t} exp(cs_t - cs_s) (C_t . B_s) dt_s x_s
    diff = cs[:, :, None] - cs[:, None, :]                 # (nc, t, s, G, k)
    causal = (jnp.arange(Q)[:, None] >= jnp.arange(Q)[None, :])
    decay = jnp.exp(jnp.where(causal[None, :, :, None, None], diff, -jnp.inf))
    cb = jnp.einsum("ctgn,csgn->ctsg", Cb, Bb, preferred_element_type=f32)
    m = (decay * cb[..., None] * dtb[:, None]).astype(cd)
    y = jnp.einsum("ctsgk,csgkp->ctgkp", m, xb, preferred_element_type=f32)
    # what a block leaves to the state: sum_s exp(cs_end - cs_s) dt_s B_s x_s^T
    to_end = jnp.exp(cs[:, -1:] - cs) * dtb                    # (nc, Q, G, k)
    left = jnp.einsum("csgn,csgkp->cgknp", Bb,
                      (xb.astype(f32) * to_end[..., None]).astype(cd),
                      preferred_element_type=f32)
    # the block states, chained: nc sequential steps on (H, N, P)
    total = jnp.exp(cs[:, -1])                                    # (nc, G, k)
    s = state.astype(f32).reshape(G, hpg, N, P)
    entering = []
    for c in range(nc):
        entering.append(s)
        s = s * total[c][..., None, None] + left[c]
    entering = jnp.stack(entering)                          # (nc, G, k, N, P)
    y = y + jnp.exp(cs)[..., None] * jnp.einsum(
        "ctgn,cgknp->ctgkp", Cb, entering.astype(cd),
        preferred_element_type=f32)
    y = y.reshape(T, H, P) + D.astype(f32)[None, :, None] * x.astype(f32)
    return y, s.reshape(H, N, P).astype(state.dtype)


# ------------------------------------------------------- one token, many rows


def decode_update_supported(heads: int, groups: int, state: int,
                            head_dim: int) -> bool:
    """Whether :func:`_ssm_decode_update_call` takes these sizes: the state
    in whole 128 x 128 tiles, the heads of a group in whole sublane tiles."""
    return (head_dim % _LANES == 0 and state % _LANES == 0
            and heads % groups == 0 and (heads // groups) % 8 == 0)


def _ssm_update_kernel(slots_ref, state_ref, da_ref, dtx_ref, b_ref, c_ref,
                       y_ref, out_ref, *, hpg: int):
    """Grid (B, head blocks). Blocks: ``state`` / ``out`` (1, hb, N, P) of
    the slab at slot ``slots[b]``; ``da`` (the decay ``exp(dt A)``) and
    ``dtx`` (``dt x``) (1, hb, P), a head's scalar decay laid along its
    channels; ``b``, ``c`` (1, groups in the block, N / 128, 128) rows;
    ``y`` (1, hb, P)."""
    del slots_ref  # only the index maps read it
    hb, N, P = state_ref.shape[1:]
    nt = N // _LANES

    def column(row):
        # (1, 128) values n -> (128 n, P): value n along row n
        col = jnp.broadcast_to(row, (_LANES, _LANES)).T
        return col if P == _LANES else jnp.tile(col, (1, P // _LANES))

    for g in range(b_ref.shape[1]):
        bcols = [column(b_ref[0, g, j:j + 1, :]) for j in range(nt)]
        ccols = [column(c_ref[0, g, j:j + 1, :]) for j in range(nt)]
        for h in range(g * hpg, min((g + 1) * hpg, hb)):
            da = da_ref[0, h:h + 1, :]
            dtx = dtx_ref[0, h:h + 1, :]
            acc = jnp.zeros((1, P), jnp.float32)
            for j in range(nt):
                rows = slice(j * _LANES, (j + 1) * _LANES)
                s = (state_ref[0, h, rows, :].astype(jnp.float32) * da
                     + bcols[j] * dtx)
                out_ref[0, h, rows, :] = s.astype(out_ref.dtype)
                acc = acc + jnp.sum(s * ccols[j], axis=0, keepdims=True)
            y_ref[0, h:h + 1, :] = acc


@functools.partial(jax.jit, static_argnames=("heads_block", "interpret"))
def _ssm_decode_update_call(slab, slots, da, dtx, Bm, Cm, heads_block: int,
                            interpret: bool):
    """The in-place update (its own jitted name: the kernel's operation in a
    trace takes it). ``slab`` (S, H, N, P); ``da``, ``dtx`` (B, H, P)
    float32; ``Bm``, ``Cm`` (B, G, N) float32. Returns ``(slab, y0 (B, H,
    P))``, ``y0 = S_t^T C_t``."""
    _, H, N, P = slab.shape
    B, G = Bm.shape[:2]
    hpg, hb, nt = H // G, heads_block, N // _LANES
    if hb >= hpg:
        gb = hb // hpg
        group_of = lambda b, g, slots: (b, g, 0, 0)  # noqa: E731
    else:
        gb = 1
        group_of = lambda b, g, slots: (b, g * hb // hpg, 0, 0)  # noqa: E731
    slot_spec = pl.BlockSpec((1, hb, N, P),
                             lambda b, g, slots: (slots[b], g, 0, 0))
    row_spec = pl.BlockSpec((1, hb, P), lambda b, g, slots: (b, g, 0))
    bc_spec = pl.BlockSpec((1, gb, nt, _LANES), group_of)
    block_bytes = hb * N * P * 4
    y, slab = pl.pallas_call(
        functools.partial(_ssm_update_kernel, hpg=min(hpg, hb)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, H // hb),
            in_specs=[slot_spec, row_spec, row_spec, bc_spec, bc_spec],
            out_specs=[row_spec, slot_spec]),
        out_shape=[jax.ShapeDtypeStruct((B, H, P), jnp.float32),
                   jax.ShapeDtypeStruct(slab.shape, slab.dtype)],
        # operand 0 is the scalar-prefetched slots; the slab is operand 1
        input_output_aliases={1: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            # the slot's block in and out, each double-buffered
            vmem_limit_bytes=max(32 << 20, 5 * block_bytes + (8 << 20))),
        interpret=interpret,
    )(slots, slab, da, dtx, Bm.reshape(B, G, nt, _LANES),
      Cm.reshape(B, G, nt, _LANES))
    return slab, y


def ssd_decode_update(slab, slots, x, dt, A, Bm, Cm, D, kernel: str = "gather",
                      interpret: bool | None = None):
    """One token for each of B rows: ``slab`` (S, H, N, P) the states,
    ``slots`` (B,) each row's slot in it (the rows without one name the
    dummy slot 0), ``x`` (B, H, P), ``dt`` (B, H) positive, ``A`` (H,),
    ``Bm``, ``Cm`` (B, G, N), ``D`` (H,). Everything in float32 but the
    stored state (the slab's dtype). Returns ``(slab, y (B, H, P)
    float32)``; ``slab`` is updated in the live rows' slots and the dummy's
    and nowhere else."""
    f32 = jnp.float32
    H, N, P = slab.shape[1:]
    G = Bm.shape[1]
    xf, dtf = x.astype(f32), dt.astype(f32)
    da = jnp.exp(dtf * A.astype(f32)[None, :])                       # (B, H)
    dtx = dtf[..., None] * xf                                     # (B, H, P)
    Bf, Cf = Bm.astype(f32), Cm.astype(f32)
    skip = D.astype(f32)[None, :, None] * xf
    if kernel == "pallas" and decode_update_supported(H, G, N, P):
        slab, y = _ssm_decode_update_call(
            slab, slots.astype(jnp.int32),
            jnp.broadcast_to(da[..., None], dtx.shape), dtx, Bf, Cf,
            heads_block=H // G,
            interpret=_interpret() if interpret is None else interpret)
        return slab, y + skip
    hpg = H // G
    s = slab[slots].astype(f32)                                # (B, H, N, P)
    s = s * da[..., None, None] + jnp.einsum(
        "bgn,bgkp->bgknp", Bf, dtx.reshape(-1, G, hpg, P)).reshape(s.shape)
    y = jnp.einsum("bgknp,bgn->bgkp", s.reshape(-1, G, hpg, N, P),
                   Cf).reshape(-1, H, P)
    return slab.at[slots].set(s.astype(slab.dtype)), y + skip
