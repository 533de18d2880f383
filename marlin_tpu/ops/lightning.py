"""Lightning attention (linear attention with a CONSTANT decay a head), two
ways.

Per head (keys and queries of ``K`` values, values of ``V``; the state ``S``
is ``K x V``, float32), with a decay ``lambda`` in ``(0, 1]`` that is a
constant of the head and not a function of the token::

    S_t = lambda S_{t-1} + k_t v_t^T
    o_t = S_t^T q_t

It is the gated delta rule (``ops/delta_rule.py``) with the delta step and
the input-dependent gate taken out, so its chunked form has no triangular
system to solve, and a Mamba-2 recurrence (``ops/ssm.py``) with ``dt = 1``
and no grouping of heads.

- :func:`lightning_chunk_scan` runs it over ONE row's chunk of tokens,
  entered with the row's state and leaving the state after the chunk's last
  valid token. The chunk is cut into blocks of ``block`` tokens; inside a
  block the recurrence is a decay-masked ``Q K^T`` (``exp((t - s) log
  lambda)`` for ``t >= s``, masked BEFORE the exponential), a block leaves
  ``sum_s lambda^(end - s) k_s v_s^T`` to the state, and only the ``chunk /
  block`` block states are chained one after another. A position that is
  not a token (``valid`` false: the chunk's padding) neither decays the state
  nor adds to it.
- :func:`lightning_decode_update` advances one token for each row of a
  batch, each row's state living in slot ``slots[b]`` of a slab ``(slots,
  heads, K, V)``. ``kernel="pallas"`` reads and writes each row's slot ONCE,
  in place (:func:`_lightning_decode_update_call`: the slot id is
  scalar-prefetched and drives the block index, the slab is aliased to the
  output); the rows that share the dummy slot 0 scribble on it and on
  nothing else. ``kernel="gather"`` is the same arithmetic on a gathered
  copy.

The state is stored ``(K, V)``, values on the lanes: at the published sizes
(128 x 128) a head's state is sixteen whole float32 tiles. The update's outer
product takes ``v`` as a row (a sublane broadcast) and ``k`` as a column,
which the kernel makes from a row by transposing one 128 x 128 tile (as
``ops/ssm.py`` makes ``B``); the read ``S^T q`` is a sum over sublanes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_kernels import _interpret

__all__ = ["lightning_chunk_scan", "lightning_decode_update",
           "decode_update_supported", "lightning_decay"]

_LANES = 128


def lightning_decay(heads: int, layer: int, layers: int):
    """The decay ``lambda_h = exp(-2^(-8 (h + 1) / heads) * (1 - layer /
    (layers - 1) + 1e-5))`` of head ``h`` of published layer ``layer`` of
    ``layers`` (Lightning Attention-2's slope tensor): float32 ``(heads,)``."""
    import numpy as np

    slope = 2.0 ** (-8.0 * np.arange(1, heads + 1, dtype=np.float64) / heads)
    return np.exp(-slope * (1.0 - layer / max(layers - 1, 1) + 1e-5)).astype(
        np.float32)


# ------------------------------------------------------- a chunk of one row


def lightning_chunk_scan(q, k, v, log_decay, valid, state, block: int):
    """The recurrence over one row's chunk. ``q``, ``k`` (T, H, K), ``v`` (T,
    H, V) in the compute dtype; ``log_decay`` (H,) float32, ``log lambda <=
    0``; ``valid`` (T,) bool, true where the position is a token (a prefix
    of the chunk); ``state`` (H, K, V), the state before the chunk. Matmul
    operands in ``q``'s dtype, every accumulation, decay and the carried
    state in float32. Returns ``(o (T, H, V) float32, state after the
    chunk)``."""
    T, H, K = q.shape
    V = v.shape[-1]
    if T % block:
        raise ValueError(f"a chunk of {T} tokens is not whole blocks of "
                         f"{block} (lightning_chunk_size)")
    nc, Q, cd, f32 = T // block, block, q.dtype, jnp.float32

    def blocks(x):      # (T, H, D) -> (nc, H, Q, D): heads lead the matmuls
        return x.reshape(nc, Q, H, x.shape[-1]).transpose(0, 2, 1, 3)

    live = valid.reshape(nc, 1, Q)
    qb, vb = blocks(q), blocks(v)
    kb = jnp.where(live[..., None], blocks(k), 0)       # padding adds nothing
    # cs[c, h, t]: the log-decay from the block's start through position t
    # (a position that is no token does not decay)
    cs = jnp.cumsum(live.astype(f32) * log_decay.astype(f32)[None, :, None],
                    axis=2)
    diff = cs[..., :, None] - cs[..., None, :]                # (nc, H, t, s)
    causal = jnp.arange(Q)[:, None] >= jnp.arange(Q)[None, :]
    decay = jnp.exp(jnp.where(causal, diff, -jnp.inf))
    qk = jnp.einsum("chtk,chsk->chts", qb, kb, preferred_element_type=f32)
    o = jnp.einsum("chts,chsv->chtv", (qk * decay).astype(cd), vb,
                   preferred_element_type=f32)
    # what a block leaves to the state: sum_s exp(cs_end - cs_s) k_s v_s^T
    to_end = jnp.exp(cs[..., -1:] - cs)                          # (nc, H, Q)
    left = jnp.einsum("chsk,chsv->chkv",
                      (kb.astype(f32) * to_end[..., None]).astype(cd), vb,
                      preferred_element_type=f32)
    total = jnp.exp(cs[..., -1])                                    # (nc, H)
    s = state.astype(f32)
    entering = []
    for c in range(nc):   # the block states, chained: nc sequential steps
        entering.append(s)
        s = s * total[c][:, None, None] + left[c]
    entering = jnp.stack(entering)                            # (nc, H, K, V)
    o = o + jnp.exp(cs)[..., None] * jnp.einsum(
        "chtk,chkv->chtv", qb, entering.astype(cd), preferred_element_type=f32)
    o = o.transpose(0, 2, 1, 3)
    return o.reshape(T, H, V), s.astype(state.dtype)


# ------------------------------------------------------- one token, many rows


def decode_update_supported(heads: int, key_dim: int, value_dim: int) -> bool:
    """Whether :func:`_lightning_decode_update_call` takes these sizes: a
    head's state one 128-row tile column of whole lane tiles, the heads in
    whole sublane tiles."""
    return key_dim == _LANES and value_dim % _LANES == 0 and heads % 8 == 0


def _lightning_update_kernel(slots_ref, state_ref, decay_ref, k_ref, v_ref,
                             q_ref, o_ref, out_ref):
    """Grid (B, head blocks). Blocks: ``state`` / ``out`` (1, hb, K, V) of
    the slab at slot ``slots[b]``; ``decay`` (hb, V), a head's decay laid
    along its lanes; ``k``, ``q`` (1, hb, K) and ``v`` (1, hb, V) rows; ``o``
    (1, hb, V)."""
    del slots_ref  # only the index maps read it
    hb, K, V = state_ref.shape[1:]

    def column(row):
        # (1, 128) values n -> (128, V): value n along row n
        col = jnp.broadcast_to(row, (_LANES, _LANES)).T
        return col if V == _LANES else jnp.tile(col, (1, V // _LANES))

    for h in range(hb):
        s = (state_ref[0, h].astype(jnp.float32) * decay_ref[h:h + 1, :]
             + column(k_ref[0, h:h + 1, :]) * v_ref[0, h:h + 1, :])
        out_ref[0, h] = s.astype(out_ref.dtype)
        o_ref[0, h:h + 1, :] = jnp.sum(s * column(q_ref[0, h:h + 1, :]),
                                       axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("heads_block", "interpret"))
def _lightning_decode_update_call(slab, slots, decay, k, v, q,
                                  heads_block: int, interpret: bool):
    """The in-place update (its own jitted name: the kernel's operation in a
    trace takes it). ``slab`` (S, H, K, V); ``decay`` (H, V), ``k``, ``q``
    (B, H, K), ``v`` (B, H, V), all float32. Returns ``(slab, o (B, H,
    V))``."""
    _, H, K, V = slab.shape
    B, hb = k.shape[0], heads_block
    slot_spec = pl.BlockSpec((1, hb, K, V),
                             lambda b, g, slots: (slots[b], g, 0, 0))
    key_spec = pl.BlockSpec((1, hb, K), lambda b, g, slots: (b, g, 0))
    val_spec = pl.BlockSpec((1, hb, V), lambda b, g, slots: (b, g, 0))
    block_bytes = hb * K * V * 4
    o, slab = pl.pallas_call(
        _lightning_update_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, H // hb),
            in_specs=[slot_spec,
                      pl.BlockSpec((hb, V), lambda b, g, slots: (g, 0)),
                      key_spec, val_spec, key_spec],
            out_specs=[val_spec, slot_spec]),
        out_shape=[jax.ShapeDtypeStruct((B, H, V), jnp.float32),
                   jax.ShapeDtypeStruct(slab.shape, slab.dtype)],
        # operand 0 is the scalar-prefetched slots; the slab is operand 1
        input_output_aliases={1: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            # the slot's block in and out, each double-buffered
            vmem_limit_bytes=max(32 << 20, 5 * block_bytes + (8 << 20))),
        interpret=interpret,
    )(slots, slab, decay, k, v, q)
    return slab, o


def lightning_decode_update(slab, slots, q, k, v, log_decay,
                            kernel: str = "gather",
                            interpret: bool | None = None):
    """One token for each of B rows: ``slab`` (S, H, K, V) the states,
    ``slots`` (B,) each row's slot in it (the rows without one name the
    dummy slot 0), ``q``, ``k`` (B, H, K), ``v`` (B, H, V), ``log_decay``
    (H,). Everything in float32 but the stored state (the slab's dtype).
    Returns ``(slab, o (B, H, V) float32)``; ``slab`` is updated in the live
    rows' slots and the dummy's and nowhere else."""
    f32 = jnp.float32
    H, K, V = slab.shape[1:]
    qf, kf, vf = q.astype(f32), k.astype(f32), v.astype(f32)
    lam = jnp.exp(log_decay.astype(f32))                               # (H,)
    if kernel == "pallas" and decode_update_supported(H, K, V):
        return _lightning_decode_update_call(
            slab, slots.astype(jnp.int32),
            jnp.broadcast_to(lam[:, None], (H, V)), kf, vf, qf,
            heads_block=8,
            interpret=_interpret() if interpret is None else interpret)
    s = slab[slots].astype(f32) * lam[None, :, None, None] \
        + kf[..., :, None] * vf[..., None, :]
    o = jnp.einsum("bhkv,bhk->bhv", s, qf)
    return slab.at[slots].set(s.astype(slab.dtype)), o
