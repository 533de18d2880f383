"""Mixture-of-experts routing, expert parallelism, and LM integration.

The no-drop oracle is the direct per-token mixture (every token computes its
renormalized top-k expert average densely); capacity semantics are checked
against the choice-major priority rule; expert parallelism is checked by
sharded == unsharded on the CPU mesh.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import marlin_tpu as mt
from marlin_tpu.models.moe import (init_moe, moe_capacity, moe_decode_ffn,
                                   moe_ffn, shard_moe_params)
from marlin_tpu.models.transformer import (TransformerLM, init_transformer,
                                           lm_loss)


@pytest.fixture
def mesh():
    return mt.create_mesh((4, 2))


def _dense_mixture(mp, x, top_k):
    """Per-token oracle: renormalized top-k expert mixture, no capacity."""
    gates = jax.nn.softmax(x.astype(jnp.float32) @ mp["wg"].astype(jnp.float32))
    topv, topi = jax.lax.top_k(gates, top_k)
    topv = topv / jnp.sum(topv, axis=-1, keepdims=True)
    outs = []
    for t in range(x.shape[0]):
        acc = 0.0
        for j in range(top_k):
            e = int(topi[t, j])
            h = jax.nn.gelu(x[t] @ mp["w1"][e])
            acc = acc + float(topv[t, j]) * (h @ mp["w2"][e])
        outs.append(acc)
    return jnp.stack(outs)


def test_moe_exact_no_drops():
    rng = np.random.default_rng(0)
    mp = init_moe(jax.random.key(0), 8, 16, 4)
    x = jnp.asarray(rng.standard_normal((24, 8)).astype(np.float32))
    out, aux = moe_ffn(mp, x, mesh=None, top_k=2, capacity_factor=100.0,
                       group_size=None)
    ref = _dense_mixture(mp, x, 2)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)
    assert np.isfinite(float(aux))


def test_moe_grouped_equals_single():
    rng = np.random.default_rng(1)
    mp = init_moe(jax.random.key(1), 8, 16, 4)
    x = jnp.asarray(rng.standard_normal((64, 8)).astype(np.float32))
    # capacity large enough that grouping never changes which tokens fit
    a, _ = moe_ffn(mp, x, mesh=None, top_k=2, capacity_factor=100.0,
                   group_size=None)
    b, _ = moe_ffn(mp, x, mesh=None, top_k=2, capacity_factor=100.0,
                   group_size=16)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-5, atol=1e-6)


def test_moe_ragged_tail_group():
    # 50 tokens over group_size 16: the tail group is padded — padding must
    # not route (it would consume capacity) and the output must match the
    # no-drop oracle exactly
    rng = np.random.default_rng(2)
    mp = init_moe(jax.random.key(2), 8, 16, 4)
    x = jnp.asarray(rng.standard_normal((50, 8)).astype(np.float32))
    out, _ = moe_ffn(mp, x, mesh=None, top_k=2, capacity_factor=100.0,
                     group_size=16)
    ref = _dense_mixture(mp, x, 2)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)


def test_moe_capacity_drops_choice_major():
    # Every token prefers expert 0 (huge logit): with top_k=1 and capacity
    # cap < S, exactly the FIRST cap tokens get expert 0's output; the rest
    # lose their only choice and emit zeros.
    d, ff, e, s = 4, 8, 2, 12
    mp = init_moe(jax.random.key(3), d, ff, e)
    mp = dict(mp, wg=jnp.zeros((d, e)).at[:, 0].set(10.0))
    x = jnp.ones((s, d), jnp.float32)
    cap = moe_capacity(s, e, 1, 0.5)  # 3 slots
    out, _ = moe_ffn(mp, x, mesh=None, top_k=1, capacity_factor=0.5,
                     group_size=None)
    expert0 = jax.nn.gelu(x[0] @ mp["w1"][0]) @ mp["w2"][0]
    for t in range(s):
        if t < cap:
            np.testing.assert_allclose(np.asarray(out[t]),
                                       np.asarray(expert0), rtol=1e-5)
        else:
            np.testing.assert_allclose(np.asarray(out[t]), 0.0, atol=1e-7)


def test_moe_sharded_matches_unsharded(mesh):
    rng = np.random.default_rng(4)
    mp = init_moe(jax.random.key(4), 8, 16, 8)
    x = jnp.asarray(rng.standard_normal((32, 8)).astype(np.float32))
    a, aux_a = moe_ffn(mp, x, mesh=None, top_k=2)
    mps = shard_moe_params(mp, mesh)
    assert "rows" in str(mps["w1"].sharding.spec)
    b, aux_b = jax.jit(
        lambda m, xx: moe_ffn(m, xx, mesh=mesh, top_k=2))(mps, x)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(float(aux_a), float(aux_b), rtol=1e-5)


def test_moe_expert_axis_validation(mesh):
    mp = init_moe(jax.random.key(5), 8, 16, 6)  # 6 % 4 != 0
    x = jnp.zeros((8, 8), jnp.float32)
    with pytest.raises(ValueError, match="multiple of mesh axis"):
        moe_ffn(mp, x, mesh=mesh)
    with pytest.raises(ValueError, match="n_experts must be >= 2"):
        init_moe(jax.random.key(5), 8, 16, 1)


def test_moe_aux_near_one_for_balanced_router():
    # random inputs + random router ≈ balanced: the Switch aux term is ~1
    rng = np.random.default_rng(6)
    mp = init_moe(jax.random.key(6), 16, 8, 4)
    x = jnp.asarray(rng.standard_normal((512, 16)).astype(np.float32))
    _, aux = moe_ffn(mp, x, mesh=None, top_k=2)
    assert 0.7 < float(aux) < 1.6, float(aux)


def test_moe_decode_ffn_matches_mixture():
    rng = np.random.default_rng(7)
    mp = init_moe(jax.random.key(7), 8, 16, 4)
    h = jnp.asarray(rng.standard_normal(8).astype(np.float32))
    out = moe_decode_ffn(mp, h, top_k=2)
    ref = _dense_mixture(mp, h[None], 2)[0]
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)


def test_moe_init_interleaving():
    p = init_transformer(jax.random.key(0), 32, 16, 2, 4, n_experts=4,
                         moe_every=2)
    assert "w1" in p["l0"] and "moe" not in p["l0"]
    assert "moe" in p["l1"] and "w1" not in p["l1"]
    assert "w1" in p["l2"] and "moe" in p["l3"]
    assert p["l1"]["moe"]["w1"].shape == (4, 16, 64)


def test_moe_lm_trains(mesh):
    toks = mt.models.transformer.synthetic_stream(257, vocab=32, seed=0)
    lm = TransformerLM(vocab=32, d_model=16, heads=2, layers=2,
                       learning_rate=1e-2, n_experts=4, moe_group=64,
                       moe_capacity_factor=2.0)
    params, losses = lm.train(toks, steps=12, mesh=mesh)
    assert losses[-1] < losses[0] * 0.9, losses
    assert "moe" in params["l0"]


def test_moe_grads_reach_router(mesh):
    # the load-balance aux and the combine weights both feed wg's gradient
    # (jitted, like lm_train_step — eager grad through the ring's internal
    # placement is unsupported for dense models too)
    toks = mt.models.transformer.synthetic_stream(65, vocab=16, seed=1)
    p = init_transformer(jax.random.key(1), 16, 16, 2, 1, n_experts=4)
    g = jax.jit(jax.grad(lambda pp: lm_loss(pp, toks, mesh, heads=2,
                                            moe=(2, 2.0, 64))))(p)
    gw = np.asarray(g["l0"]["moe"]["wg"])
    assert np.isfinite(gw).all() and np.abs(gw).max() > 0


def test_moe_decode_matches_forward(mesh):
    # greedy decode through the MoE decode path continues the argmax of the
    # training forward (capacity high enough that prefill routing is exact)
    toks = mt.models.transformer.synthetic_stream(129, vocab=32, seed=2)
    lm = TransformerLM(vocab=32, d_model=16, heads=2, layers=2,
                       learning_rate=1e-2, n_experts=4, moe_group=64,
                       moe_capacity_factor=100.0)
    params, _ = lm.train(toks, steps=8, mesh=mesh)
    from marlin_tpu.models.transformer import transformer_forward

    prompt = list(toks[:16])
    out = np.asarray(lm.generate(params, prompt, steps=8))
    cur = list(prompt)
    for _ in range(8):
        logits = transformer_forward(params, np.array(cur, np.int32), mesh,
                                     heads=2, moe=(2, 100.0, 64))
        cur.append(int(jnp.argmax(logits[-1])))
    np.testing.assert_array_equal(out, np.array(cur))


def test_moe_generate_batch_matches_single():
    # the vmapped composition is brand-new: grouped MoE routing under the
    # batched prefill vmap + gather-decode under the per-step vmap; ragged
    # rows must reproduce the single-sequence decode exactly (capacity high
    # enough that the padded batch prefill routes like the unpadded single)
    from marlin_tpu.models.transformer import lm_generate, lm_generate_batch

    p = init_transformer(jax.random.key(5), 16, 16, 2, 1, n_experts=4)
    moe = (2, 100.0, 32)
    pr1 = (np.arange(5) % 16).astype(np.int32)
    pr2 = (np.arange(3) * 2 % 16).astype(np.int32)
    singles = [np.asarray(lm_generate(p, pr, jax.random.key(9), heads=2,
                                      max_len=16, steps=4, moe=moe))
               for pr in (pr1, pr2)]
    prompts = np.zeros((2, 5), np.int32)
    prompts[0, :5] = pr1
    prompts[1, :3] = pr2
    out = np.asarray(lm_generate_batch(
        p, prompts, np.array([5, 3], np.int32), jax.random.key(9), heads=2,
        max_len=16, steps=4, moe=moe))
    np.testing.assert_array_equal(out[0, :9], singles[0])
    np.testing.assert_array_equal(out[1, :7], singles[1])


def test_moe_decode_compute_dtype():
    # bf16 decode: the expert matmuls follow the compute dtype (not the f32
    # params), matching the prefill/training convention
    import jax.numpy as jnp

    mp = init_moe(jax.random.key(8), 8, 16, 4)
    h16 = jnp.ones((8,), jnp.bfloat16)
    out = moe_decode_ffn(mp, h16, top_k=2)
    assert out.dtype == jnp.bfloat16


def test_moe_bf16_training(mesh):
    # mixed precision composes with MoE: bf16 activations route through f32
    # gating and bf16 expert matmuls; the step learns and params stay f32
    toks = mt.models.transformer.synthetic_stream(257, vocab=32, seed=4)
    lm = TransformerLM(vocab=32, d_model=16, heads=2, layers=2,
                       learning_rate=1e-2, n_experts=4, moe_group=64,
                       moe_capacity_factor=2.0, compute_dtype="bfloat16")
    params, losses = lm.train(toks, steps=12, mesh=mesh)
    assert losses[-1] < losses[0] * 0.9, losses
    assert params["l0"]["moe"]["w1"].dtype == jnp.float32


def test_moe_offload_structure_guard(mesh):
    toks = mt.models.transformer.synthetic_stream(33, vocab=16, seed=3)
    p = init_transformer(jax.random.key(2), 16, 16, 2, 2, n_experts=4,
                         moe_every=2)
    with pytest.raises(ValueError, match="uniform layer structure"):
        lm_loss(p, toks, mesh, heads=2, remat=True, offload_residuals=True)


# group-limited picks (the deepseek_v32 family's routing) ----------------------


def _group_limited_by_hand(scores, bias, n_group, topk_group, top_k):
    """A plain loop: a group scores the sum of its TWO largest biased scores;
    the best ``topk_group`` groups stay; the ``top_k`` largest biased scores
    among their experts are the picks; the weights are the picks' own
    UNBIASED scores renormalised."""
    T, E = scores.shape
    per = E // n_group
    picks, weights = [], []
    for t in range(T):
        c = scores[t] + bias
        gs = [np.sort(c[g * per:(g + 1) * per])[-2:].sum()
              for g in range(n_group)]
        stay = sorted(range(n_group), key=lambda g: (-gs[g], g))[:topk_group]
        ok = [e for e in range(E) if e // per in stay]
        top = sorted(ok, key=lambda e: (-c[e], e))[:top_k]
        picks.append(top)
        weights.append(scores[t, top] / scores[t, top].sum())
    return np.asarray(weights), np.asarray(picks)


def test_group_limited_picks_are_the_plain_loop():
    from marlin_tpu.models.moe import _picks

    rng = np.random.default_rng(0)
    logits = jnp.asarray(rng.standard_normal((40, 32)), jnp.float32)
    # a bias large enough to move picks: it selects and never weighs
    mp = {"e_bias": jnp.asarray(0.3 * rng.standard_normal(32), jnp.float32)}
    w, i = _picks(logits, mp, 4, "sigmoid", 0.0, n_group=8, topk_group=3)
    sc = np.asarray(jax.nn.sigmoid(logits))
    want_w, want_i = _group_limited_by_hand(sc, np.asarray(mp["e_bias"]), 8,
                                            3, 4)
    np.testing.assert_array_equal(np.asarray(i), want_i)
    np.testing.assert_allclose(np.asarray(w), want_w, atol=1e-6)
    # the sum of TWO, not the max: some token's kept groups differ
    c = sc + np.asarray(mp["e_bias"])
    by_max = np.argsort(-c.reshape(40, 8, 4).max(-1), axis=-1)[:, :3]
    by_two = np.argsort(-np.sort(c.reshape(40, 8, 4), -1)[..., -2:].sum(-1),
                        axis=-1)[:, :3]
    assert any(set(a) != set(b) for a, b in zip(by_max, by_two))
    # the bias selects: without it other experts are picked
    _, unbiased = _picks(logits, {"e_bias": jnp.zeros(32)}, 4, "sigmoid",
                         0.0, n_group=8, topk_group=3)
    assert (np.asarray(unbiased) != np.asarray(i)).any()


@pytest.mark.parametrize("scoring", ["softmax", "sigmoid"])
def test_one_group_of_which_one_stays_is_todays_picks_bit_for_bit(scoring):
    from marlin_tpu.models.moe import _picks

    rng = np.random.default_rng(1)
    logits = jnp.asarray(rng.standard_normal((24, 16)), jnp.float32)
    mp = {"e_bias": jnp.asarray(0.05 * rng.standard_normal(16), jnp.float32)}
    a = jax.jit(lambda x: _picks(x, mp, 4, scoring, 0.0))(logits)
    b = jax.jit(lambda x: _picks(x, mp, 4, scoring, 0.0, n_group=1,
                                 topk_group=1))(logits)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    low = [str(jax.jit(lambda x, kw=kw: _picks(x, mp, 4, scoring, 0.0, **kw))
               .lower(logits).as_text())
           for kw in ({}, {"n_group": 1, "topk_group": 1})]
    assert low[0] == low[1]


def test_the_sixteen_shares_of_a_group_limited_layer_add_up():
    """The guide's share test at a small size: what each of 16 chips computes
    for its own experts of a group-limited layer (64 experts, 8 groups of
    which 4 stay, 8 picks), the shared expert counted once, is the uncut
    layer."""
    from marlin_tpu.models.moe import moe_experts_ffn

    E, d, f, T, shares = 64, 32, 16, 24, 16
    ks = jax.random.split(jax.random.key(2), 9)
    nrm = lambda k, shape, s: jax.random.normal(k, shape, jnp.float32) * s  # noqa: E731
    mp = {"router": nrm(ks[0], (d, E), d ** -0.5),
          "e_gate": nrm(ks[1], (E, d, f), d ** -0.5),
          "e_up": nrm(ks[2], (E, d, f), d ** -0.5),
          "e_down": nrm(ks[3], (E, f, d), f ** -0.5),
          "s_gate": nrm(ks[4], (d, f), d ** -0.5),
          "s_up": nrm(ks[5], (d, f), d ** -0.5),
          "s_down": nrm(ks[6], (f, d), f ** -0.5),
          "e_bias": nrm(ks[7], (E,), 0.05)}
    h = nrm(ks[8], (T, d), 1.0)
    valid = jnp.ones((T,), bool)
    kw = dict(top_k=8, routed_scale=2.5, scoring="sigmoid", n_group=8,
              topk_group=4)
    held = E // shares

    def share(first):
        cut = dict(mp)
        for k in ("e_gate", "e_up", "e_down"):
            cut[k] = mp[k][first:first + held]
        return cut

    whole, counts = moe_experts_ffn(mp, h, valid, **kw)
    outs = [moe_experts_ffn(share(k * held), h, valid, first_expert=k * held,
                            **kw) for k in range(shares)]
    shared_only, _ = moe_experts_ffn(share(0), h, jnp.zeros((T,), bool), **kw)
    total = sum(np.asarray(o, np.float64) for o, _ in outs) \
        - (shares - 1) * np.asarray(shared_only, np.float64)
    np.testing.assert_allclose(total, np.asarray(whole), atol=2e-5)
    assert int(counts[1]) == T * 8
    assert sum(int(c[1]) for _, c in outs) == T * 8
