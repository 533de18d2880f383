"""The lightning layers of the ``minicpm_sala`` configuration family: linear
attention with a CONSTANT decay a head, a ``heads x head_dim x head_dim``
float32 state in the row's slot and no page. The two forms of the recurrence
against the token-by-token one, the paged programs against the plain
reference (``benchmarks/reference/serve_minicpmsala.py``; the model and the
helpers are ``tests/test_sparse_attention.py``'s), a row that enters from
another row's snapshot at the wrong boundary, and each flaw the comparison
must catch.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import serve_minicpmsala as reference
from marlin_tpu.models import hybrid
from marlin_tpu.ops import lightning
from tests.test_sparse_attention import (CAUGHT, PAGE, TIGHT, fresh_prefill,
                                         model, prompt_of, ref_logits,
                                         serve_one, shared, tiny_cfg)

__all__ = ["model"]  # the fixture


# the recurrence, alone -------------------------------------------------------


def _naive(q, k, v, lam, valid, S):
    outs = []
    for t in range(q.shape[0]):
        if valid[t]:
            S = lam[:, None, None] * S + k[t][:, :, None] * v[t][:, None, :]
        outs.append(np.einsum("hkv,hk->hv", S, q[t]))
    return np.stack(outs), S


def _operands(rng, T, H, K, V):
    return (rng.normal(size=(T, H, K)) * K ** -0.5,
            rng.normal(size=(T, H, K)) * K ** -0.5,
            rng.normal(size=(T, H, V)), rng.normal(size=(H, K, V)))


@pytest.mark.parametrize("T, H, K, V, block, n_valid", [
    (24, 3, 12, 20, 8, 24), (24, 3, 12, 20, 8, 21), (32, 4, 16, 16, 32, 5),
    (16, 2, 8, 8, 4, 0)])
def test_the_chunked_form_is_the_token_by_token_recurrence(T, H, K, V, block,
                                                           n_valid):
    """Across block edges, from a state that is not zero, with a tail of
    padding that moves nothing (all of the chunk padding: the state is handed
    through)."""
    rng = np.random.default_rng(T + n_valid)
    q, k, v, S0 = _operands(rng, T, H, K, V)
    lam = np.asarray(lightning.lightning_decay(H, 9, 32), np.float64)
    valid = np.arange(T) < n_valid
    want_o, want_S = _naive(q, k, v, lam, valid, S0)
    with jax.default_matmul_precision("highest"):
        o, S = lightning.lightning_chunk_scan(
            *(jnp.asarray(x, jnp.float32) for x in (q, k, v)),
            jnp.log(jnp.asarray(lam, jnp.float32)), jnp.asarray(valid),
            jnp.asarray(S0, jnp.float32), block=block)
    np.testing.assert_allclose(o[:n_valid], want_o[:n_valid], atol=2e-5)
    np.testing.assert_allclose(S, want_S, atol=2e-5)


def test_the_decay_is_the_published_slope():
    """Head h of published layer l of 32: exp(-2^(-8 (h + 1) / H) (1 - l / 31
    + 1e-5)): the first head forgets in a few tokens, the last remembers
    thousands, and a deeper layer remembers longer."""
    lam = lightning.lightning_decay(32, 9, 32)
    assert lam.dtype == np.float32 and lam.shape == (32,)
    assert (np.diff(lam) > 0).all() and 0.5 < lam[0] < 0.6 and lam[-1] > 0.997
    np.testing.assert_allclose(lam, reference.decay(32, 9, 32), rtol=1e-6)
    assert (lightning.lightning_decay(32, 24, 32) > lam).all()


@pytest.mark.parametrize("kernel, H, K, V", [("gather", 3, 12, 20),
                                             ("gather", 8, 128, 128),
                                             ("pallas", 8, 128, 128),
                                             ("pallas", 16, 128, 256)])
def test_the_decode_update_moves_the_live_slots_and_no_other(kernel, H, K, V):
    rng = np.random.default_rng(H)
    q, k, v, _ = (jnp.asarray(x, jnp.float32)
                  for x in _operands(rng, 3, H, K, V))
    slab = jnp.asarray(rng.normal(size=(5, H, K, V)), jnp.float32)
    slots = jnp.asarray([2, 0, 4])
    log_decay = jnp.log(jnp.asarray(lightning.lightning_decay(H, 12, 32)))
    assert lightning.decode_update_supported(H, K, V) == (K == 128)
    got, o = lightning.lightning_decode_update(slab, slots, q, k, v,
                                               log_decay, kernel=kernel)
    want = slab[slots] * jnp.exp(log_decay)[None, :, None, None] \
        + k[..., :, None] * v[..., None, :]
    live = np.array([0, 2])
    got, o, want = np.asarray(got), np.asarray(o), np.asarray(want)
    np.testing.assert_allclose(got[[2, 4]], want[live], atol=1e-5)
    np.testing.assert_allclose(o[live], np.einsum(
        "bhkv,bhk->bhv", want, np.asarray(q))[live], atol=1e-4)
    np.testing.assert_array_equal(got[[1, 3]], np.asarray(slab)[[1, 3]])


# the programs ------------------------------------------------------------------


@pytest.fixture(scope="module")
def kernel_model():
    """Heads of 128, which the Pallas update takes: 8 lightning heads, 8
    query heads over 2 KV heads; one layer of each kind."""
    cfg = tiny_cfg(hidden_size=64, head_dim=128, num_attention_heads=8,
                   lightning_nh=8, lightning_nkv=8, lightning_head_dim=128,
                   num_hidden_layers=2, dim_model_base=16,
                   mixer_types=["lightning-attn", "minicpm4"])
    spec = hybrid.ModelSpec.from_config(cfg)
    return cfg, spec, hybrid.init_params(spec, jax.random.key(4))


@pytest.mark.parametrize("kernel", ["gather", "pallas"])
def test_the_update_kernel_serves_the_reference(kernel_model, kernel):
    cfg, spec, params = kernel_model
    with jax.default_matmul_precision("highest"):
        toks, served, _ = serve_one(spec, params, prompt_of(53), 9,
                                    kernel=kernel)
        want = ref_logits(params, cfg, toks, 53)
    np.testing.assert_allclose(served, want, atol=TIGHT)


def _zero_states(spec):
    def between(pages):
        return {f"l{i}": tuple(jnp.zeros_like(a) for a in pages[f"l{i}"])
                if ly.attn == "lightning" else pages[f"l{i}"]
                for i, ly in enumerate(spec.layers)}
    return between


def _per_head_output_norm():
    real = hybrid._rmsnorm

    def norm(x, g, eps):   # 64 columns: only a lightning layer's o_norm
        if g.shape[0] != 64:
            return real(x, g, eps)
        return real(x.reshape(x.shape[0], 4, 16), g.reshape(4, 16),
                    eps).reshape(x.shape)
    return norm


FLAWS = ["mixer_dropped", "decay_dropped", "decay_of_the_held_layer",
         "state_not_carried", "slot_not_zeroed",
         "padding_advances_the_state", "rope_left_out", "qk_norm_dropped",
         "output_norm_a_head", "gate_dropped"]


@pytest.mark.parametrize("flaw", FLAWS)
def test_each_flaw_fails_the_comparison(flaw, model, monkeypatch):
    cfg, spec, params = model
    kw, n = {}, 37
    if flaw == "mixer_dropped":
        real = hybrid._lightning_mixer
        kw["prefill"] = fresh_prefill(
            monkeypatch, hybrid, "_lightning_mixer",
            lambda *a: jnp.zeros_like(real(*a)))
    elif flaw == "decay_dropped":
        params = dict(params, l1=dict(params["l1"], decay=jnp.ones((4,))))
    elif flaw == "decay_of_the_held_layer":   # layer 1 of 4, not 10 of 32
        params = dict(params, l1=dict(params["l1"], decay=jnp.asarray(
            lightning.lightning_decay(4, 1, 4))))
    elif flaw == "state_not_carried":
        kw["between"] = _zero_states(spec)
    elif flaw == "slot_not_zeroed":
        with jax.default_matmul_precision("highest"):
            kw["pages"] = serve_one(spec, params, prompt_of(40, seed=5), 6)[2]
        kw["prefill"] = fresh_prefill(monkeypatch, hybrid, "_enter_state",
                                      lambda fresh, *arrays: arrays)
    elif flaw == "padding_advances_the_state":
        real = lightning.lightning_chunk_scan
        kw["prefill"] = fresh_prefill(
            monkeypatch, lightning, "lightning_chunk_scan",
            lambda q, k, v, ld, valid, *a, **k_: real(
                q, k, v, ld, jnp.ones_like(valid), *a, **k_))
    elif flaw == "rope_left_out":
        kw["prefill"] = fresh_prefill(monkeypatch, hybrid, "_rope",
                                      lambda x, positions, rope: x)
    elif flaw == "qk_norm_dropped":
        # (a factor on every head's queries alone, such as `lightning_scale`
        # left out, is NOT a flaw a comparison can see: the output norm over
        # all columns takes it out again)
        kw["prefill"] = fresh_prefill(
            monkeypatch, hybrid, "_head_norm",
            lambda x, g, eps, heads: x.reshape(x.shape[0], heads, -1))
    elif flaw == "output_norm_a_head":
        kw["prefill"] = fresh_prefill(monkeypatch, hybrid, "_rmsnorm",
                                      _per_head_output_norm())
    elif flaw == "gate_dropped":
        params = dict(params, l2=dict(params["l2"],
                                      w_g=jnp.zeros_like(params["l2"]["w_g"])))
    with jax.default_matmul_precision("highest"):
        toks, served, _ = serve_one(spec, params, prompt_of(n), 8, **kw)
        want = ref_logits(params, cfg, toks, n)
    assert np.abs(served - want).max() > CAUGHT, flaw


@pytest.mark.parametrize("flaw, kw", [
    ("entered_from_zeros", {"enter": "zeros"}),
    ("snapshot_a_page_early", {"boundary": 56}),
    ("snapshot_a_page_late", {"boundary": 72})])
def test_a_hit_entered_from_the_wrong_state_fails_the_comparison(flaw, kw,
                                                                 model):
    cfg, spec, params = model
    with jax.default_matmul_precision("highest"):
        toks, served = shared(spec, params, **kw)
        want = ref_logits(params, cfg, toks, 81)
    assert np.abs(served - want).max() > CAUGHT, flaw


def test_a_bfloat16_state_is_a_departure_the_spec_can_name(model):
    """``lightning_state_dtype`` is not a published key: float32 by default
    (the state is multiplied and added to at every token), bfloat16 halves
    the slot."""
    cfg, spec, _ = model
    half = hybrid.ModelSpec.from_config(
        tiny_cfg(lightning_state_dtype="bfloat16"))
    assert half.state_slot_bytes() * 2 == spec.state_slot_bytes()
    assert dataclasses.replace(
        half, lightning=spec.lightning) == spec
