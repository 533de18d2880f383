"""Ulysses-style all-to-all sequence parallelism for multi-head attention.

The second canonical long-context strategy next to :mod:`ring_attention`
(no reference analog — the reference predates attention, SURVEY.md §2.7; the
task's long-context mandate makes both strategies first-class here):

- **Ring**: Q stays sequence-sharded; K/V panels rotate via ``ppermute``.
  Communication is O(seq/p · d) per step × p steps, overlapped with compute.
  Works for any head count, including single-head.
- **Ulysses** (this module): inputs arrive sequence-sharded; one
  ``all_to_all`` re-shards them over *heads*, so each device holds the FULL
  sequence for ``heads/p`` heads and runs plain local attention (the Pallas
  flash kernel) with zero communication inside the softmax; a second
  ``all_to_all`` restores sequence sharding. Total communication is two
  all-to-alls of the activation volume — independent of the number of
  softmax steps — which beats the ring when heads ≥ p and the per-step
  ring latency would dominate (short sequences per device, many devices).

The trade: Ulysses needs ``heads % p == 0`` to balance (enforced), and each
device must hold seq × d × heads/p activations — sequence memory is NOT
reduced per device beyond the head split, where the ring bounds it by the
panel size. Pick per workload; both produce the exact softmax.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..mesh import ROWS, default_mesh, pad_to_multiple

__all__ = ["ulysses_attention"]

_NEG = -1e30


def _flash_fwd_impl(q, k, v, valid_len, causal: bool, scale: float):
    from ..ops.flash_attention import flash_attention_single_panel

    out, lse = flash_attention_single_panel(q, k, v, valid_len,
                                            causal=causal, scale=scale)
    return out.astype(q.dtype), lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _local_flash_attention(q, k, v, valid_len, causal: bool, scale: float):
    """Full-sequence exact attention for one head via the flash panel kernel
    (ops/flash_attention.py) — one panel covering all keys, VMEM score tiles.
    Differentiable: the backward is the two-pass Pallas recompute schedule
    (flash_attention_panel_bwd) driven by the forward's logsumexp rows, so
    backward score memory is O(block²), not O(seq · tile)."""
    return _flash_fwd_impl(q, k, v, valid_len, causal, scale)[0]


def _local_flash_fwd(q, k, v, valid_len, causal, scale):
    out, lse = _flash_fwd_impl(q, k, v, valid_len, causal, scale)
    return out, (q, k, v, out, lse, valid_len)


def _local_flash_bwd(causal, scale, res, ct):
    from ..ops.flash_attention import block_divisor, flash_attention_panel_bwd

    q, k, v, out, lse, valid_len = res
    b = block_divisor(q.shape[0])
    delta = jnp.sum(ct.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)  # 1-D rows, like lse (see flash_attention)
    dq, dk, dv = flash_attention_panel_bwd(
        q, k, v, ct.astype(q.dtype), lse, delta, 0, 0, valid_len,
        causal=causal, scale=scale, bq=b, bkv=b)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype), None


_local_flash_attention.defvjp(_local_flash_fwd, _local_flash_bwd)


@functools.lru_cache(maxsize=32)
def _ulysses_fn(mesh: Mesh, axis: str, causal: bool, scale: float):
    def local(q, k, v, valid_len):
        # per device in: (H, S/p, d) sequence-sharded slabs
        # all_to_all -> (H/p, S, d): full sequence for this device's heads
        q, k, v = (
            jax.lax.all_to_all(x, axis, split_axis=0, concat_axis=1, tiled=True)
            for x in (q, k, v)
        )
        out = jax.vmap(
            lambda qh, kh, vh: _local_flash_attention(
                qh, kh, vh, valid_len, causal, scale)
        )(q, k, v)
        # restore sequence sharding: (H/p, S, d) -> (H, S/p, d)
        return jax.lax.all_to_all(out, axis, split_axis=1, concat_axis=0,
                                  tiled=True)

    @jax.jit
    def f(q, k, v, valid_len):
        # check_vma off: the pallas interpreter's block slicing mixes varying
        # and invariant operands (same caveat as the ring flash path)
        return jax.shard_map(
            local,
            mesh=mesh,
            in_specs=(P(None, axis, None),) * 3 + (P(),),
            out_specs=P(None, axis, None),
            check_vma=False,
        )(q, k, v, valid_len)

    return f


def ulysses_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh: Mesh | None = None,
    axis: str = ROWS,
    causal: bool = False,
    scale: float | None = None,
    precision: str = "high",
) -> jax.Array:
    """Exact multi-head attention with all-to-all head/sequence re-sharding.

    ``q``/``k``/``v``: (heads, seq, d) — or any leading batch dims
    (..., heads, seq, d), folded into one head axis — with the folded axis
    divisible by the mesh axis size (the balance requirement of the head
    split). Sequence lengths that don't divide the axis are padded and masked
    exactly, like :func:`ring_attention`. ``precision`` as in
    :func:`ring_attention` ("default" narrows the MXU operands to bf16,
    keeping f32 softmax stats).
    """
    if q.ndim < 3 or k.shape != q.shape or v.shape != q.shape:
        raise ValueError(
            f"ulysses needs (..., heads, seq, d) q/k/v of one shape, got "
            f"{q.shape} {k.shape} {v.shape}"
        )
    if q.ndim > 3:
        lead = q.shape[:-2]
        q2, k2, v2 = (x.reshape(-1, *x.shape[-2:]) for x in (q, k, v))
        out = ulysses_attention(q2, k2, v2, mesh, axis, causal, scale,
                                precision)
        return out.reshape(*lead, *out.shape[-2:])
    if precision not in ("high", "default"):
        raise ValueError(f"unknown ulysses precision: {precision!r}")
    mesh = mesh or default_mesh()
    p_size = mesh.shape[axis]
    heads, seq, d = q.shape
    if heads % p_size:
        raise ValueError(
            f"heads ({heads}) must divide by the '{axis}' axis size "
            f"({p_size}) — pad the head axis or use ring_attention"
        )
    # pad the sequence so both shardings (seq-split slabs and full-seq heads)
    # are well-formed. The full-seq panel follows the flash block contract
    # (ops/flash_attention.block_divisor): a total length past 1024 must be
    # a 1024 multiple, so the slab pads to the minimal multiple that makes
    # p·slab one (1024/gcd(p, 1024)); shorter totals pad the slab to 128
    slab = pad_to_multiple(pad_to_multiple(seq, p_size) // p_size, 128)
    if p_size * slab > 1024:
        slab = pad_to_multiple(slab, 1024 // math.gcd(p_size, 1024))
    sp = p_size * slab
    if sp != seq:
        pad = ((0, 0), (0, sp - seq), (0, 0))
        q, k, v = (jnp.pad(x, pad) for x in (q, k, v))
    out_dtype = q.dtype
    if precision == "default":
        q, k, v = (x.astype(jnp.bfloat16) for x in (q, k, v))
    scale_val = float(scale if scale is not None else 1.0 / math.sqrt(d))
    sh = NamedSharding(mesh, P(None, axis, None))
    q, k, v = (jax.device_put(x, sh) for x in (q, k, v))
    f = _ulysses_fn(mesh, axis, causal, scale_val)
    out = f(q, k, v, jnp.asarray(seq, jnp.int32)).astype(out_dtype)
    return out[:, :seq, :] if sp != seq else out
