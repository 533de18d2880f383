"""Ring attention vs the dense single-device oracle."""

import numpy as np
import pytest

import jax.numpy as jnp

from marlin_tpu.parallel.ring_attention import attention_reference, ring_attention


def _qkv(seq, d, seed, heads=None):
    rng = np.random.default_rng(seed)
    shape = (seq, d) if heads is None else (heads, seq, d)
    return tuple(jnp.asarray(rng.standard_normal(shape).astype(np.float32))
                 for _ in range(3))


def test_ring_attention_matches_dense(mesh):
    q, k, v = _qkv(64, 32, 0)
    out = ring_attention(q, k, v, mesh)
    ref = attention_reference(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4)


def test_ring_attention_causal(mesh):
    q, k, v = _qkv(64, 16, 1)
    out = ring_attention(q, k, v, mesh, causal=True)
    ref = attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4)


def test_ring_attention_uneven_seq(mesh):
    # 51 is odd — not divisible by the ring axis (size 2), so the pad/mask
    # paths genuinely run
    q, k, v = _qkv(51, 16, 2)
    out = ring_attention(q, k, v, mesh)
    ref = attention_reference(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4)
    out_c = ring_attention(q, k, v, mesh, causal=True)
    ref_c = attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out_c), np.asarray(ref_c), rtol=2e-4, atol=2e-4)


def test_ring_attention_multihead(mesh):
    q, k, v = _qkv(32, 8, 3, heads=4)
    out = ring_attention(q, k, v, mesh, causal=True)
    ref = attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4)


def test_ring_attention_custom_scale(mesh):
    q, k, v = _qkv(16, 8, 4)
    out = ring_attention(q, k, v, mesh, scale=0.1)
    ref = attention_reference(q, k, v, scale=0.1)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4)


def test_ring_attention_shape_mismatch(mesh):
    q, k, v = _qkv(16, 8, 5)
    with pytest.raises(ValueError):
        ring_attention(q, k[:8], v, mesh)


def test_ring_attention_tile_padding(mesh):
    # seq just over ring*KV_TILE forces the tile-multiple padding path
    import importlib

    ra = importlib.import_module("marlin_tpu.parallel.ring_attention")

    seq = 2 * ra._KV_TILE + 3  # ring axis size 2 -> skv > _KV_TILE
    q, k, v = _qkv(seq, 8, 6)
    out = ra.ring_attention(q, k, v, mesh, causal=True)
    ref = attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=3e-4, atol=3e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_flash_backend(mesh, causal):
    # the Pallas panel kernel (interpret mode here), driven through the ring:
    # 2-device ring on the "rows" axis, uneven length exercises valid_len
    q, k, v = _qkv(100, 32, 6)
    out = ring_attention(q, k, v, mesh, causal=causal, backend="flash")
    ref = attention_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_ring_attention_flash_multihead(mesh):
    q, k, v = _qkv(64, 16, 7, heads=3)
    out = ring_attention(q, k, v, mesh, causal=True, backend="flash")
    ref = attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_ring_attention_flash_odd_length(mesh):
    # 1000/ring=500 per device is not a power-of-two multiple — the flash
    # path must pad the panel to a 128 multiple rather than degenerate to
    # 1-wide blocks
    q, k, v = _qkv(1000, 32, 9)
    out = ring_attention(q, k, v, mesh, causal=True, backend="flash")
    ref = attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_ring_attention_bad_backend(mesh):
    q, k, v = _qkv(16, 8, 8)
    with pytest.raises(ValueError):
        ring_attention(q, k, v, mesh, backend="cuda")


@pytest.mark.parametrize("backend", ["xla", "flash"])
def test_ring_attention_bf16_precision(mesh, backend):
    # precision="default" narrows the MXU operands to bf16 but keeps softmax
    # statistics and the accumulator f32 — ~1e-2 relative class, f32 output
    # dtype preserved
    q, k, v = _qkv(100, 32, 11)
    out = ring_attention(q, k, v, mesh, causal=True, backend=backend,
                         precision="default")
    assert out.dtype == q.dtype
    ref = attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-2, atol=2e-2)


def test_ring_attention_bad_precision(mesh):
    q, k, v = _qkv(16, 8, 8)
    with pytest.raises(ValueError):
        ring_attention(q, k, v, mesh, precision="low")


@pytest.mark.parametrize(
    "backend", ["xla", "flash"])
def test_ring_attention_grad(mesh, backend):
    # long-context TRAINING: gradients must flow through both backends (the
    # flash path's custom VJP runs the two-pass Pallas recompute kernels,
    # dK/dV accumulators riding the ring)
    import jax

    q, k, v = _qkv(64, 16, 12)

    def loss(q_, k_, v_):
        out = ring_attention(q_, k_, v_, mesh, causal=True, backend=backend)
        return (out * np.cos(np.arange(16))).sum()  # non-uniform cotangent

    gq, gk, gv = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    def loss_ref(q_, k_, v_):
        out = attention_reference(q_, k_, v_, causal=True)
        return (out * np.cos(np.arange(16))).sum()

    rq, rk, rv = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for g, r in ((gq, rq), (gk, rk), (gv, rv)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                   rtol=2e-4, atol=2e-4)


def test_ring_attention_grad_uneven_seq(mesh):
    # padded queries/keys must receive exactly zero gradient
    import jax

    q, k, v = _qkv(51, 8, 13)
    g = jax.grad(
        lambda q_: float(np.pi) * ring_attention(q_, k, v, mesh, causal=True,
                                                 backend="flash").sum()
    )(q)
    r = jax.grad(
        lambda q_: float(np.pi) * attention_reference(q_, k, v, causal=True).sum()
    )(q)
    np.testing.assert_allclose(np.asarray(g), np.asarray(r), rtol=3e-4, atol=3e-4)


def test_flash_xla_equivalence_sweep(mesh):
    # property sweep: both backends must agree with the dense oracle across
    # random shapes, head dims, causality, and ragged lengths
    rng = np.random.default_rng(10)
    for _ in range(6):
        seq = int(rng.integers(16, 400))
        d = int(rng.choice([8, 16, 32, 64]))
        causal = bool(rng.integers(0, 2))
        q, k, v = (jnp.asarray(rng.standard_normal((seq, d)).astype(np.float32))
                   for _ in range(3))
        ref = np.asarray(attention_reference(q, k, v, causal=causal))
        for backend in ("xla", "flash"):
            out = ring_attention(q, k, v, mesh, causal=causal, backend=backend)
            np.testing.assert_allclose(
                np.asarray(out), ref, rtol=3e-4, atol=3e-4,
                err_msg=f"seq={seq} d={d} causal={causal} backend={backend}")


def test_flash_backward_memory_subquadratic(mesh):
    """The flash backward saves O(seq) state (lse/Δ rows), never score
    residuals: compiled temp memory must grow far slower than the quadratic
    autodiff-through-XLA backward it replaced (regression for the 256k+
    training regime — quadratic growth is ~4x per doubling)."""
    import jax

    def temp_bytes(seq):
        q = jnp.zeros((seq, 128), jnp.float32)
        g = jax.jit(jax.grad(
            lambda q, k, v: jnp.sum(
                ring_attention(q, k, v, mesh, causal=True,
                               backend="flash")),
            argnums=(0, 1, 2)))
        return g.lower(q, q, q).compile().memory_analysis().temp_size_in_bytes

    t8, t16 = temp_bytes(8192), temp_bytes(16384)
    assert t16 / t8 < 3.0, (t8, t16)  # quadratic would be ~4x
