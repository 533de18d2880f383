"""Ring attention: exact attention over sequences sharded across the mesh.

The reference has no attention (SURVEY.md §2.7 "not present"), but its answer
to "a dimension too big for one node" — split it, rotate partial operands,
accumulate (the k-split RMM) — extends naturally to attention, and the task's
long-context requirement makes it first-class here. This is the blockwise-
softmax formulation (flash-attention style numerically-stable running max /
denominator), with K/V panels rotating around the device ring via
``lax.ppermute`` exactly like :mod:`marlin_tpu.parallel.ring`'s B-panels:
every device keeps its Q rows stationary, sees each K/V panel once, and the
DMA for panel i+1 overlaps the softmax·V math for panel i. Communication per
step is O(seq/p · d) on ICI; memory per device never exceeds the local panel
— sequences scale linearly with the ring size.

Masking uses global positions (the Q block index is the device's mesh
coordinate; the K block owner is tracked through the rotation), so the sharded
result — causal or not, padded or not — is the single-device result exactly.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..mesh import ROWS, default_mesh, pad_to_multiple

__all__ = ["ring_attention", "attention_reference"]

_NEG = -1e30


def attention_reference(q, k, v, causal: bool = False, scale: float | None = None):
    """Single-device oracle: softmax(q kᵀ · scale) v. Pinned to highest
    precision — an oracle that silently drops to bf16 on TPU would misreport
    kernel error."""
    scale = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    s = jnp.einsum("...qd,...kd->...qk", q, k, precision="highest") * scale
    if causal:
        qlen, klen = s.shape[-2], s.shape[-1]
        mask = jnp.arange(qlen)[:, None] >= jnp.arange(klen)[None, :]
        s = jnp.where(mask, s, _NEG)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("...qk,...kd->...qd", p, v, precision="highest")


_KV_TILE = 2048  # inner tile bounding the (sq × tile) score buffer

# the flash block-size policy lives next to the kernel; re-exported here for
# back-compat with callers/tests that imported it from this module
from ..ops.flash_attention import block_divisor as _block_divisor  # noqa: E402


def softmax_tile_update(q_blk, k_t, v_t, m, l, acc, q_pos, k_pos, valid_len,
                        causal: bool, scale: float):
    """One blockwise-softmax step: fold the (q_blk × k_t) score tile into the
    running (m, l, acc) state. The numerically delicate core shared by the
    ring's XLA path and ulysses' recompute backward — fix masking/precision
    here and both strategies get it."""
    s = jnp.dot(q_blk, k_t.T, precision="highest",
                preferred_element_type=jnp.float32) * scale
    keep = k_pos[None, :] < valid_len
    if causal:
        keep = keep & (q_pos[:, None] >= k_pos[None, :])
    s = jnp.where(keep, s, _NEG)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1))
    alpha = jnp.exp(m - m_new)
    p = jnp.exp(s - m_new[:, None])
    l = l * alpha + jnp.sum(p, axis=-1)
    # p cast to v's dtype: f32 inputs keep the f32 "highest" path; bf16
    # inputs (precision="default") run a native bf16 MXU matmul with f32
    # accumulation — the flash kernel makes the same cast
    acc = acc * alpha[:, None] + jnp.dot(
        p.astype(v_t.dtype), v_t, precision="highest",
        preferred_element_type=jnp.float32,
    )
    return m_new, l, acc


@functools.lru_cache(maxsize=32)
def _ring_attn_fn(mesh: Mesh, axis: str, causal: bool, scale: float,
                  flash: bool):
    """One kernel covers all cases: ``valid_len`` masks padded key positions
    (a no-op when the sequence fills the padded length), and ``causal`` adds
    the triangular mask on top. With ``flash`` the per-panel inner loop is the
    Pallas flash kernel (ops/flash_attention.py — score tiles never leave
    VMEM); otherwise, within each ring step the resident K/V panel is
    processed in fixed KV tiles, so per-device score memory is
    O(seq/p · tile) instead of O((seq/p)²) — long sequences on small rings
    (including ring size 1) stay in HBM."""
    p_size = mesh.shape[axis]
    perm = [(j, (j + 1) % p_size) for j in range(p_size)]

    def _var(t):
        return jax.lax.pcast(t, (axis,), to="varying")

    def _flash_state(q_blk, k_blk, v_blk, valid_len):
        from ..ops.flash_attention import flash_attention_panel

        sq, d = q_blk.shape
        skv = k_blk.shape[0]
        b = _block_divisor(min(sq, skv))
        idx = jax.lax.axis_index(axis)

        # m/l are 1-D (sq,) end to end: the (sq, 1) form tile-pads 128x in
        # HBM (ops/flash_attention._panel_kernel) — at 1M-token panels that
        # padding was ~0.5 GiB of dead HBM per tensor per head
        m = _var(jnp.full((sq,), _NEG, jnp.float32))
        l = _var(jnp.zeros((sq,), jnp.float32))
        acc = _var(jnp.zeros((sq, d), jnp.float32))

        panel = functools.partial(flash_attention_panel, causal=causal,
                                  scale=scale, bq=b, bkv=b)
        # home panel first (i = 0, owner = idx) — outside the loop, so the
        # ring below rotates only p-1 times and never ships a dead panel
        m, l, acc = panel(q_blk, k_blk, v_blk, m, l, acc,
                          idx * sq, idx * skv, valid_len)
        if p_size == 1:  # no ring: one panel, no rotation/loop overhead
            return m, l, acc

        # ring steps as a fori_loop (matching the xla path): the unrolled
        # form kept every rotated K/V panel alive simultaneously — ~2·p
        # full panels of buffer liveness per chip, the dominant term in the
        # per-chip HBM accounting at long context (AOT_MEMORY.json). The
        # loop carry holds exactly one panel in flight.
        def step(i, carry):
            k_cur, v_cur, m, l, acc = carry
            k_cur = jax.lax.ppermute(k_cur, axis, perm)
            v_cur = jax.lax.ppermute(v_cur, axis, perm)
            owner = (idx - i) % p_size
            m, l, acc = panel(q_blk, k_cur, v_cur, m, l, acc,
                              idx * sq, owner * skv, valid_len)
            return k_cur, v_cur, m, l, acc

        _, _, m, l, acc = jax.lax.fori_loop(
            1, p_size, step, (k_blk, v_blk, m, l, acc))
        return m, l, acc

    def local_flash(q_blk, k_blk, v_blk, valid_len):
        m, l, acc = _flash_state(q_blk, k_blk, v_blk, valid_len)
        return (acc / jnp.maximum(l, 1e-30)[:, None]).astype(q_blk.dtype)

    def local_flash_fwd(q_blk, k_blk, v_blk, valid_len):
        m, l, acc = _flash_state(q_blk, k_blk, v_blk, valid_len)
        # the saved lse stays 1-D (sq,): a (sq, 1) residual's 1-wide lane dim
        # pads 128x under TPU (8, 128) tiling — in HBM and the moment a
        # fusion holds it in scoped VMEM (at 32k tokens x heads that padding
        # alone exceeded the VMEM budget and the non-remat train step failed
        # to compile)
        lse = m + jnp.log(jnp.maximum(l, 1e-30))
        return (acc / jnp.maximum(l, 1e-30)[:, None]).astype(q_blk.dtype), lse

    def local_flash_bwd(q_blk, k_blk, v_blk, out_blk, lse_blk, do_blk,
                        valid_len):
        """Ring backward: the SAME rotation as the forward, with per-panel
        dK/dV accumulators riding the ring alongside their panels — after p
        steps every panel is home carrying the sum of all devices'
        contributions. dQ accumulates locally. Per-device memory is
        O(panel · d); probabilities are rebuilt per tile from lse/Δ inside
        the two-pass Pallas backward (ops/flash_attention.py)."""
        from ..ops.flash_attention import flash_attention_panel_bwd

        sq, d = q_blk.shape
        skv = k_blk.shape[0]
        b = _block_divisor(min(sq, skv))
        idx = jax.lax.axis_index(axis)
        do_f = do_blk.astype(jnp.float32)
        delta = jnp.sum(do_f * out_blk.astype(jnp.float32), axis=-1)  # (sq,)
        panel_bwd = functools.partial(flash_attention_panel_bwd, causal=causal,
                                      scale=scale, bq=b, bkv=b)
        # home panel first (i = 0), outside the loop: the K/V panels then
        # rotate only p-1 times. The dK/dV accumulators DO permute after
        # every accumulate, including the last — those p hops are what
        # brings each panel's gradient sum home; only the K/V rotation on
        # the final step was dead weight.
        dq, dk_cur, dv_cur = panel_bwd(
            q_blk, k_blk, v_blk, do_blk, lse_blk, delta,
            idx * sq, idx * skv, valid_len)
        if p_size == 1:  # no ring: single panel backward, nothing rotates
            return dq, dk_cur, dv_cur
        # (no pcast needed: the kernel outputs already carry the inputs' vma)
        dk_cur = jax.lax.ppermute(dk_cur, axis, perm)
        dv_cur = jax.lax.ppermute(dv_cur, axis, perm)

        # fori_loop for the same buffer-liveness reason as the forward: the
        # unrolled form held p copies of the rotating panels AND their f32
        # dK/dV accumulators at once
        def step(i, carry):
            k_cur, v_cur, dk_cur, dv_cur, dq = carry
            k_cur = jax.lax.ppermute(k_cur, axis, perm)
            v_cur = jax.lax.ppermute(v_cur, axis, perm)
            owner = (idx - i) % p_size
            dq_p, dk_p, dv_p = panel_bwd(
                q_blk, k_cur, v_cur, do_blk, lse_blk, delta,
                idx * sq, owner * skv, valid_len)
            # rotate the accumulators WITH their panels: after p rotations
            # every panel's dk/dv sum is home
            return (k_cur, v_cur,
                    jax.lax.ppermute(dk_cur + dk_p, axis, perm),
                    jax.lax.ppermute(dv_cur + dv_p, axis, perm),
                    dq + dq_p)

        _, _, dk_cur, dv_cur, dq = jax.lax.fori_loop(
            1, p_size, step, (k_blk, v_blk, dk_cur, dv_cur, dq))
        return dq, dk_cur, dv_cur

    def local(q_blk, k_blk, v_blk, valid_len):
        # q_blk: (sq, d) stationary; k_blk/v_blk: (skv, d) rotating
        sq, d = q_blk.shape
        skv = k_blk.shape[0]
        # the caller pads so that skv > _KV_TILE implies _KV_TILE | skv
        tile = _KV_TILE if skv % _KV_TILE == 0 else skv
        n_tiles = skv // tile
        idx = jax.lax.axis_index(axis)
        q_pos = idx * sq + jnp.arange(sq)

        def accumulate_tile(t, carry, k_cur, v_cur, owner):
            m, l, acc = carry
            off = t * tile
            k_t = jax.lax.dynamic_slice(k_cur, (off, 0), (tile, d))
            v_t = jax.lax.dynamic_slice(v_cur, (off, 0), (tile, d))
            k_pos = owner * skv + off + jnp.arange(tile)
            return softmax_tile_update(q_blk, k_t, v_t, m, l, acc,
                                       q_pos, k_pos, valid_len, causal, scale)

        def panel_tiles(carry, k_cur, v_cur, owner):
            return jax.lax.fori_loop(
                0, n_tiles,
                lambda t, c: accumulate_tile(t, c, k_cur, v_cur, owner),
                carry,
            )

        m0 = _var(jnp.full((sq,), _NEG, jnp.float32))
        l0 = _var(jnp.zeros((sq,), jnp.float32))
        acc0 = _var(jnp.zeros((sq, d), jnp.float32))
        # home panel outside the loop; the ring rotates p-1 times and never
        # ships a dead final panel (same structure as the flash path)
        m, l, acc = panel_tiles((m0, l0, acc0), k_blk, v_blk, idx)

        def step(i, carry):
            k_cur, v_cur, m, l, acc = carry
            k_cur = jax.lax.ppermute(k_cur, axis, perm)
            v_cur = jax.lax.ppermute(v_cur, axis, perm)
            owner = (idx - i) % p_size
            m, l, acc = panel_tiles((m, l, acc), k_cur, v_cur, owner)
            return k_cur, v_cur, m, l, acc

        if p_size > 1:
            _, _, m, l, acc = jax.lax.fori_loop(
                1, p_size, step, (k_blk, v_blk, m, l, acc)
            )
        return (acc / jnp.maximum(l, 1e-30)[:, None]).astype(q_blk.dtype)

    def shard_mapped(fn, check_vma):
        # check_vma off on the flash path: the pallas interpreter's block
        # slicing mixes varying and invariant operands, which the vma checker
        # rejects (the XLA path keeps full checking)
        return jax.shard_map(
            fn,
            mesh=mesh,
            in_specs=(P(axis, None), P(axis, None), P(axis, None), P()),
            out_specs=P(axis, None),
            check_vma=check_vma,
        )

    xla_call = shard_mapped(local, True)
    if not flash:
        return jax.jit(xla_call)

    flash_call = shard_mapped(local_flash, False)
    flash_fwd_call = jax.shard_map(
        local_flash_fwd, mesh=mesh,
        in_specs=(P(axis, None), P(axis, None), P(axis, None), P()),
        out_specs=(P(axis, None), P(axis)),  # lse rows are 1-D (see fwd)
        check_vma=False,
    )
    flash_bwd_call = jax.shard_map(
        local_flash_bwd, mesh=mesh,
        in_specs=(P(axis, None),) * 4 + (P(axis), P(axis, None), P()),
        out_specs=(P(axis, None),) * 3,
        check_vma=False,
    )

    # The Pallas forward kernel has no VJP; training through flash attention
    # gets a custom one: forward also returns the logsumexp rows, backward
    # runs the two-pass Pallas recompute kernels per ring panel
    # (ops/flash_attention.py:flash_attention_panel_bwd) with dK/dV
    # accumulators riding the ring. Backward memory is O(seq/p · d) per
    # device — no score residuals at any length (the previous autodiff-
    # through-XLA backward saved O(seq · tile) score tiles per layer, a
    # ~256 GB bill at 256k tokens).
    @jax.custom_vjp
    def f(q, k, v, valid_len):
        return flash_call(q, k, v, valid_len)

    def f_fwd(q, k, v, valid_len):
        out, lse = flash_fwd_call(q, k, v, valid_len)
        return out, (q, k, v, out, lse, valid_len)

    def f_bwd(res, ct):
        q, k, v, out, lse, valid_len = res
        dq, dk, dv = flash_bwd_call(q, k, v, out, lse,
                                    ct.astype(q.dtype), valid_len)
        return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype), None

    f.defvjp(f_fwd, f_bwd)
    return jax.jit(f)


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh: Mesh | None = None,
    axis: str = ROWS,
    causal: bool = False,
    scale: float | None = None,
    backend: str = "auto",
    precision: str = "high",
) -> jax.Array:
    """Exact attention with the sequence sharded over ``axis``.

    ``q``/``k``/``v``: (seq, d), (heads, seq, d), or any leading batch dims
    (..., seq, d) — leading axes fold into one vmapped axis. Sequence lengths
    are padded to the ring size; padded key positions are masked out of the
    softmax exactly.

    ``backend``: ``"flash"`` runs each panel through the Pallas flash kernel
    (score tiles stay in VMEM, causal blocks below the diagonal skipped);
    ``"xla"`` keeps the tiled XLA formulation; ``"auto"`` picks flash on TPU
    for MXU-friendly head dims and XLA elsewhere.

    ``precision``: ``"high"`` keeps Q/K/V in their own dtype and both
    backends then pin true-f32 matmuls (the flash kernel via
    ``ops.flash_attention._DOT_PREC`` — pinned because a runtime update
    changed Mosaic's unpinned default to single-pass bf16, 3e-3 error
    against the oracle). ``"default"`` casts Q/K/V to bfloat16 for the
    matmuls — the standard production-attention contract, and the speed
    path: the kernel is matmul-bound on chip (13 ms bf16 vs 26 ms f32 at
    32k/d=128). Softmax statistics and the output accumulator stay f32 in
    every mode. Mirrors ``DenseVecMatrix.multiply``'s ``precision`` knob."""
    if q.ndim < 2 or k.shape != q.shape or v.shape != q.shape:
        raise ValueError(f"q/k/v shape mismatch: {q.shape} {k.shape} {v.shape}")
    if q.ndim > 3:
        # fold (batch..., heads) into ONE vmapped axis and restore after
        lead = q.shape[:-2]
        q2, k2, v2 = (x.reshape(-1, *x.shape[-2:]) for x in (q, k, v))
        out = ring_attention(q2, k2, v2, mesh, axis, causal, scale, backend,
                             precision)
        return out.reshape(*lead, *out.shape[-2:])
    if backend not in ("auto", "flash", "xla"):
        raise ValueError(f"unknown ring attention backend: {backend!r}")
    if precision not in ("high", "default"):
        raise ValueError(f"unknown ring attention precision: {precision!r}")
    seq, d = q.shape[-2], q.shape[-1]
    mesh = mesh or default_mesh()
    p_size = mesh.shape[axis]
    # "auto" resolves from the MESH's device platform, not
    # jax.default_backend(): the mesh is what the program actually runs (or
    # AOT-compiles) on, and default_backend() names the runtime backend —
    # the CPU, under a compile-only TPU topology
    flash = backend == "flash" or (
        backend == "auto" and d % 128 == 0
        and next(iter(mesh.devices.flat)).platform == "tpu"
    )
    sp = pad_to_multiple(seq, p_size)
    if sp // p_size > _KV_TILE:
        # pad so each device's panel is a whole number of KV tiles — the
        # memory bound (sq × _KV_TILE scores) must hold for ANY length, and
        # valid_len masks the padded keys exactly
        sp = p_size * pad_to_multiple(sp // p_size, _KV_TILE)
    if flash:
        # the flash block contract (ops/flash_attention.block_divisor):
        # panels > 1024 pad to 1024 multiples (bq=1024, legal (8, 128)
        # packed-m/l blocks); shorter panels pad to 128 and run whole
        panel = sp // p_size
        sp = p_size * (pad_to_multiple(panel, 1024) if panel > 1024
                       else pad_to_multiple(panel, 128))
    pad = ((0, 0),) * (q.ndim - 2) + ((0, sp - seq), (0, 0))
    if sp != seq:
        q, k, v = (jnp.pad(x, pad) for x in (q, k, v))
    out_dtype = q.dtype
    if precision == "default":
        q, k, v = (x.astype(jnp.bfloat16) for x in (q, k, v))
    scale_val = float(scale if scale is not None else 1.0 / math.sqrt(d))
    # sharding is placed on the SEQUENCE axis here, before any head vmap —
    # sharding inside the vmapped function would partition the heads axis
    spec = P(axis, None) if q.ndim == 2 else P(None, axis, None)
    sh = NamedSharding(mesh, spec)
    q, k, v = (jax.device_put(x, sh) for x in (q, k, v))
    f = _ring_attn_fn(mesh, axis, causal, scale_val, flash)
    vl = jnp.asarray(seq, jnp.int32)
    if q.ndim == 3:
        out = jax.vmap(lambda qh, kh, vh: f(qh, kh, vh, vl))(q, k, v)
    else:
        out = f(q, k, v, vl)
    out = out.astype(out_dtype)
    return out[..., :seq, :] if sp != seq else out
