"""Pipeline-parallel LM: stage split correctness and training.

The oracle is the same blocks applied sequentially (the pipeline is a
schedule, not a different model), built from the identical init_transformer
params.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import marlin_tpu as mt
from marlin_tpu.models.pipeline_lm import (pp_lm_loss, pp_lm_train_step,
                                           pp_stage_params, _pp_block)
from marlin_tpu.models.transformer import (_head_logits, _n_layers,
                                           _rmsnorm, init_transformer,
                                           synthetic_stream)


@pytest.fixture
def mesh4():
    return mt.create_mesh((4, 2))


def _sequential_loss(params, tokens, heads):
    tokens = jnp.asarray(tokens)
    n_layers = _n_layers(params)
    x = params["emb"][tokens[:, :-1]]
    for i in range(n_layers):
        x = jax.vmap(lambda row, lp=params[f"l{i}"]: _pp_block(
            lp, row, heads))(x)
    x = _rmsnorm(x, params["ln_f"])
    logp = jax.nn.log_softmax(_head_logits(x, params["emb"]), axis=-1)
    tgt = tokens[:, 1:]
    return -jnp.mean(jnp.take_along_axis(logp, tgt[..., None], axis=-1))


def _token_batch(b, t, vocab=32):
    return np.stack([synthetic_stream(t, vocab=vocab, seed=i)
                     for i in range(b)])


def test_pp_lm_loss_matches_sequential(mesh4):
    p = init_transformer(jax.random.key(0), 32, 32, 2, 4)
    toks = _token_batch(8, 17)
    sp, outer = pp_stage_params(p, mesh4)
    got = float(pp_lm_loss(sp, outer, toks, mesh4, heads=2, microbatch=2))
    want = float(_sequential_loss(p, toks, heads=2))
    np.testing.assert_allclose(got, want, rtol=1e-5)


def test_pp_lm_trains(mesh4):
    p = init_transformer(jax.random.key(1), 32, 32, 2, 4)
    sp, outer = pp_stage_params(p, mesh4)
    import optax

    opt = optax.adam(1e-2)
    opt_state = opt.init((sp, outer))
    toks = _token_batch(8, 33)
    losses = []
    for _ in range(8):
        sp, outer, opt_state, l = pp_lm_train_step(
            sp, outer, opt_state, toks, mesh4, heads=2, microbatch=2,
            lr=1e-2)
        losses.append(float(l))
    assert losses[-1] < losses[0] * 0.9, losses


def test_pp_stage_params_validation(mesh4):
    p = init_transformer(jax.random.key(2), 32, 32, 2, 3)  # 3 layers, 4 stages
    with pytest.raises(ValueError, match="do not split"):
        pp_stage_params(p, mesh4)
    pm = init_transformer(jax.random.key(3), 32, 32, 2, 4, n_experts=4)
    with pytest.raises(ValueError, match="dense-FFN"):
        pp_stage_params(pm, mesh4)


def test_pp_lm_gqa(mesh4):
    # GQA params flow through the stage blocks (kv broadcast inside)
    p = init_transformer(jax.random.key(4), 32, 32, 4, 4, kv_heads=2)
    toks = _token_batch(4, 17)
    sp, outer = pp_stage_params(p, mesh4)
    got = float(pp_lm_loss(sp, outer, toks, mesh4, heads=4, microbatch=1))
    want = float(_sequential_loss(p, toks, heads=4))
    np.testing.assert_allclose(got, want, rtol=1e-5)


def test_pp_lm_matches_flagship_lm_loss(mesh4):
    # cross-MODEL parity: the pipelined stack must compute the same function
    # as TransformerLM's lm_loss on the same params (pins _pp_block to the
    # flagship _block math — a drift in either shows up here, unlike the
    # sequential oracle built from _pp_block itself)
    from marlin_tpu.models.transformer import lm_loss

    p = init_transformer(jax.random.key(5), 32, 32, 2, 4)
    toks = _token_batch(1, 65)
    sp, outer = pp_stage_params(p, mesh4)
    got = float(pp_lm_loss(sp, outer, toks, mesh4, heads=2, microbatch=1))
    want = float(lm_loss(p, toks[0], mesh4, heads=2))
    np.testing.assert_allclose(got, want, rtol=2e-4)


def test_pp_lm_grad_matches_sequential(mesh4):
    # gradient parity through the reversed pipeline (incl. the masked-psum
    # output collection), stage-by-stage against the sequential stack
    p = init_transformer(jax.random.key(6), 32, 32, 2, 4)
    toks = _token_batch(4, 17)
    sp, outer = pp_stage_params(p, mesh4)
    g_sp, g_outer = jax.grad(
        lambda t: pp_lm_loss(t[0], t[1], toks, mesh4, heads=2, microbatch=1)
    )((sp, outer))
    g_seq = jax.grad(lambda pp: _sequential_loss(pp, toks, heads=2))(p)
    for s in range(4):
        np.testing.assert_allclose(np.asarray(g_sp["wq"][s, 0]),
                                   np.asarray(g_seq[f"l{s}"]["wq"]),
                                   rtol=1e-4, atol=1e-6)
        np.testing.assert_allclose(np.asarray(g_sp["w2"][s, 0]),
                                   np.asarray(g_seq[f"l{s}"]["w2"]),
                                   rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(np.asarray(g_outer["emb"]),
                               np.asarray(g_seq["emb"]),
                               rtol=1e-4, atol=1e-6)
