"""A model whose layers are mostly delta-rule mixers with a decay a CHANNEL
of the key (Kimi Delta Attention; the ``solar_open2`` configuration family),
one gated NoPE GQA layer to every three of them and a held share of routed
experts in EVERY layer: the two forms of the recurrence against the
token-by-token one (with decays under which an unscaled ``exp(-cs)`` leaves
float32's range), their equality with the scalar forms where a head's
channels decay alike, the paged programs against the plain reference
(``benchmarks/reference/serve_solaropen2.py``) through chunk, page and block
boundaries, a second row that enters from the first row's state SNAPSHOT,
each flaw the comparison must catch, and the engine that serves state slots,
snapshots and a share of the experts in one layer.

Small sizes that are awkward on purpose: 3 heads of 12 (neither a power of
two nor a multiple of the block of 8), a width that is not heads x head
size, 4 of 16 experts held from the fifth on.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import serve_solaropen2 as reference
from marlin_tpu.models import hybrid
from marlin_tpu.models.transformer import init_kv_pages
from marlin_tpu.ops import delta_rule
from marlin_tpu.serving import Request, ServeEngine
from tests.test_delta_rule import (CAUGHT, CHUNK, PAGE, TIGHT, VOCAB,
                                   _capture, _naive, _prompt, _serve_one,
                                   _table)

EXPERTS, FIRST = 16, 4


def tiny_cfg(**over):
    cfg = {
        "model_type": "solar_open2", "hidden_size": 40, "head_dim": 16,
        "num_attention_heads": 4, "num_key_value_heads": 2,
        "vocab_size": VOCAB, "num_hidden_layers": 4,
        "gqa_layers": [0, 4, 8],
        "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 12,
                               "num_heads": 3, "num_kv_heads": None},
        "use_gqa_gate": True, "use_rope": False, "kda_use_full_proj": False,
        "kda_allow_neg_eigval": True, "first_k_dense_replace": 0,
        "intermediate_size": 72, "moe_intermediate_size": 24,
        "n_routed_experts": 4, "n_shared_experts": 1,
        "num_experts_per_tok": 3, "norm_topk_prob": True,
        "routed_scaling_factor": 1, "rms_norm_eps": 1e-5,
        "rope_theta": 10000, "tie_word_embeddings": False,
        "deployment_share": {"experts_total": EXPERTS, "first_expert": FIRST},
        "kda_chunk_size": 8, "kda_gate_rank": 6,
        "param_dtype": "float32", "compute_dtype": "float32"}
    cfg.update(over)
    return cfg


def _spec(cfg):
    share = cfg["deployment_share"]
    return hybrid.ModelSpec.from_config(
        cfg, experts_total=share["experts_total"],
        first_expert=share["first_expert"])


@pytest.fixture(scope="module")
def model():
    cfg = tiny_cfg()
    spec = _spec(cfg)
    return cfg, spec, hybrid.init_params(spec, jax.random.key(3))


@pytest.fixture(scope="module")
def kernel_model():
    """A mixer the Pallas update takes: 2 heads of 128 x 128 (a head is one
    lane tile, as at the published sizes); one GQA layer and one kda layer."""
    cfg = tiny_cfg(num_hidden_layers=2, linear_attn_config={
        "short_conv_kernel_size": 4, "head_dim": 128, "num_heads": 2,
        "num_kv_heads": None})
    spec = _spec(cfg)
    return cfg, spec, hybrid.init_params(spec, jax.random.key(4))


def _ref_logits(params, cfg, toks, n_prompt, flaw=""):
    return np.asarray(reference.logits_at(
        params, cfg, toks[:-1], np.arange(n_prompt - 1, len(toks) - 1), 64,
        flaw=flaw))


# ops/delta_rule.py -----------------------------------------------------------


def _operands(rng, T, H, K, V, strongest: float):
    """``g`` a channel, uniform in [-strongest, -0.001]."""
    unit = lambda x: x / np.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    f32 = lambda x: jnp.asarray(x, jnp.float32)  # noqa: E731
    return (f32(unit(rng.normal(size=(T, H, K))) * K ** -0.5),
            f32(unit(rng.normal(size=(T, H, K)))),
            f32(rng.normal(size=(T, H, V))),
            f32(-rng.uniform(0.001, strongest, size=(T, H, K))),
            f32(rng.uniform(0.05, 2.0, size=(T, H))))


@pytest.mark.parametrize("T, H, K, V, block, strongest", [
    (24, 3, 12, 20, 8, 0.3),      # blocks shorter than a sub-block
    (64, 3, 12, 12, 32, 0.3),     # two sub-blocks a block, two blocks
    (64, 2, 16, 16, 64, 8.0),     # cs reaches -280: exp(-cs) is inf
    (8, 3, 12, 12, 8, 3.0)])      # a row shorter than its chunk
def test_the_channel_form_is_the_token_by_token_recurrence(T, H, K, V, block,
                                                           strongest):
    """A decay a channel, steps up to 2, a state to enter with, padding at
    the end that moves nothing; the strongest decays sum to far under
    float32's smallest exponent inside one block."""
    rng = np.random.default_rng(T + block)
    q, k, v, g, b = _operands(rng, T, H, K, V, strongest)
    g, b = g.at[-3:].set(0.0), b.at[-3:].set(0.0)
    S0 = rng.normal(size=(H, K, V))
    want_o, want_S = _naive(q, k, v, g, b, S0)
    if strongest >= 8:
        assert np.cumsum(np.asarray(g), 0).min() < -200
    with jax.default_matmul_precision("highest"):
        o, S = delta_rule.delta_chunk_scan(
            q, k, v, g, b, jnp.moveaxis(jnp.asarray(S0, jnp.float32), 0, 1),
            block=block)
    assert np.isfinite(o).all()
    np.testing.assert_allclose(o, want_o, atol=1e-5)
    np.testing.assert_allclose(jnp.moveaxis(S, 1, 0), want_S, atol=1e-5)
    _, before_padding = _naive(q[:-3], k[:-3], v[:-3], g[:-3], b[:-3], S0)
    np.testing.assert_allclose(want_S, before_padding, atol=1e-12)


@pytest.mark.parametrize("kernel", ["gather", "pallas"])
def test_the_channel_update_moves_the_live_slots_and_no_other(kernel):
    """Five rows, two of them on the dummy slot, heads of 128 x 128 as
    published: each live row's slot advances by the three lines with its
    decay a channel; the slots no row named are untouched."""
    H, K, V = 4, 128, 128
    assert delta_rule.decode_heads_block(64, K, V) == 16
    rng = np.random.default_rng(1)
    q, k, v, g, b = _operands(rng, 5, H, K, V, 3.0)
    states = rng.normal(size=(6, H, K, V))
    slab = delta_rule.state_to_slab(jnp.asarray(states, jnp.float32))
    slots = jnp.asarray([2, 0, 5, 1, 0])
    new, o = delta_rule.delta_decode_update(slab, slots, q, k, v, g, b,
                                            kernel=kernel, interpret=True)
    new = np.asarray(delta_rule.slab_to_state(new, H))
    for row in (0, 2, 3):
        want_o, want_S = _naive(q[row:row + 1], k[row:row + 1],
                                v[row:row + 1], g[row:row + 1],
                                b[row:row + 1], states[int(slots[row])])
        np.testing.assert_allclose(o[row], want_o[0], atol=1e-5)
        np.testing.assert_allclose(new[int(slots[row])], want_S, atol=1e-5)
    np.testing.assert_array_equal(new[[3, 4]], states[[3, 4]]
                                  .astype(np.float32))


@pytest.mark.parametrize("form", ["chunk", "gather", "pallas"])
def test_a_decay_constant_over_a_heads_channels_is_the_scalar_form(form):
    """With one value for all of a head's channels the channel forms compute
    what ``delta_chunk_scan`` / ``delta_decode_update`` compute from the
    scalar."""
    H, K, V, T = 2, 128, 128, 32
    rng = np.random.default_rng(2)
    q, k, v, g, b = _operands(rng, T, H, K, V, 0.3)
    wide = jnp.broadcast_to(g[:, :, :1], g.shape)
    states = rng.normal(size=(3, H, K, V))
    slab = delta_rule.state_to_slab(jnp.asarray(states, jnp.float32))
    with jax.default_matmul_precision("highest"):
        if form == "chunk":
            run = lambda d: delta_rule.delta_chunk_scan(  # noqa: E731
                q, k, v, d, b, slab[1].reshape(K, H, V), block=16)
        else:
            slots = jnp.asarray([2, 0, 1])
            run = lambda d: delta_rule.delta_decode_update(  # noqa: E731
                slab, slots, q[:3], k[:3], v[:3], d[:3], b[:3], kernel=form,
                interpret=True)
        got, want = run(wide), run(g[:, :, 0])
    for a, w in zip(got, want):
        np.testing.assert_allclose(a, w, atol=2e-6)


# the chunk scan's kernel ---------------------------------------------------


def _kernel_case(T, valid, strongest, cd, H=2, seed=0):
    """Operands the kernel takes (heads of 128 x 128, blocks of 64) with
    ``valid`` tokens of ``T`` positions, the rest masked as the prefill
    program masks them; the recurrence over the tokens alone."""
    K = V = 128
    rng = np.random.default_rng(1000 * T + valid + seed)
    q, k, v, g, b = _operands(rng, T, H, K, V, strongest)
    q, k, v = (x.astype(cd) for x in (q, k, v))
    g, b = g.at[valid:].set(0.0), b.at[valid:].set(0.0)
    S0 = rng.normal(size=(H, K, V))
    want = _naive(*(np.asarray(x.astype(jnp.float32))[:valid]
                    for x in (q, k, v)), g[:valid], b[:valid], S0) \
        if valid else (np.zeros((0, H, V)), S0)
    return (q, k, v, g, b,
            jnp.moveaxis(jnp.asarray(S0, jnp.float32), 0, 1)), want


#: (positions, tokens, heads): one block and several; nothing, inside the
#: first block, on a block boundary, inside the last block, everything; one
#: pair of heads a grid step and two
WIDTHS = [(64, 0, 2), (64, 17, 2), (64, 64, 2), (256, 0, 2), (256, 40, 2),
          (256, 128, 2), (256, 200, 2), (256, 256, 2), (128, 100, 4),
          (128, 128, 4)]


@pytest.mark.parametrize("strongest", [0.3, 8.0])
@pytest.mark.parametrize("T, valid, H", WIDTHS)
def test_the_chunk_kernel_is_the_recurrence_and_xlas_form(T, valid, H,
                                                          strongest):
    """float32 operands: the kernel against the token-by-token recurrence to
    the tolerance XLA's form is held to, and against XLA's form; an entering
    state that is not zero; the strongest decay sums to far under float32's
    smallest exponent inside a block."""
    args, (want_o, want_S) = _kernel_case(T, valid, strongest, jnp.float32,
                                          H=H)
    if strongest >= 8 and valid >= 64:
        assert np.cumsum(np.asarray(args[3]), 0).min() < -200
    with jax.default_matmul_precision("highest"):
        o, S = delta_rule.delta_chunk_scan(*args, block=64,
                                           valid=jnp.int32(valid))
        xo, xS = delta_rule._delta_chunk_scan_xla(*args, block=64)
    assert np.isfinite(o).all() and np.isfinite(S).all()
    # the state where a block's running sum of g reaches -280: a ratio is
    # the exponential of the DIFFERENCE of two such sums, each rounded to
    # 3e-5; XLA's form reads 5e-6 to 2.5e-5 there over seeds, as this does
    loose = 4e-5 if strongest >= 8 else 1e-5
    np.testing.assert_allclose(o[:valid], want_o, atol=1e-5)
    np.testing.assert_allclose(jnp.moveaxis(S, 1, 0), want_S, atol=loose)
    # (two witnesses, each that close to the recurrence)
    np.testing.assert_allclose(o[:valid], xo[:valid], atol=2e-5)
    np.testing.assert_allclose(S, xS, atol=2 * loose)


@pytest.mark.parametrize("T, valid, H", WIDTHS[1:3] + WIDTHS[4:])
def test_the_chunk_kernel_in_bfloat16_is_the_recurrence(T, valid, H):
    """bfloat16 operands (XLA's form has a product in them that the CPU does
    not run): against the recurrence over the same rounded operands, to what
    rounding U, d, P and the state's operand to eight bits leaves. (With
    bfloat16 keys the kernel moves them by the MXU, not by a lane roll.)"""
    args, (want_o, want_S) = _kernel_case(T, valid, 3.0, jnp.bfloat16, H=H)
    o, S = delta_rule.delta_chunk_scan(*args, block=64,
                                       valid=jnp.int32(valid))
    assert 0.05 < np.abs(want_o).max() and 0.5 < np.abs(want_S).max()
    np.testing.assert_allclose(o[:valid], want_o, atol=3e-3)
    np.testing.assert_allclose(jnp.moveaxis(S, 1, 0), want_S, atol=2e-2)


@pytest.mark.parametrize("cd", [jnp.float32, jnp.bfloat16])
def test_a_skipped_block_leaves_the_state_bit_equal_and_writes_zeros(cd):
    """No token: the state leaves as it entered, bit for bit, and every
    output is zero. Tokens in the first block of three: the two blocks past
    them write zeros, and the state is the state a chunk of that one block
    leaves, bit for bit. Without the count the same chunk runs every block
    (the padding masked by ``g = 0, b = 0``) and leaves the same state to
    rounding."""
    args, _ = _kernel_case(192, 50, 3.0, cd, H=4, seed=1)
    o, S = delta_rule.delta_chunk_scan(*args, block=64, valid=jnp.int32(0))
    assert not np.asarray(o).any()
    np.testing.assert_array_equal(S, args[5])
    o, S = delta_rule.delta_chunk_scan(*args, block=64, valid=jnp.int32(50))
    assert np.asarray(o[:50]).any() and not np.asarray(o[64:]).any()
    o1, S1 = delta_rule.delta_chunk_scan(*(x[:64] for x in args[:5]),
                                         args[5], block=64)
    np.testing.assert_array_equal(o[:64], o1)
    np.testing.assert_array_equal(S, S1)
    o3, S3 = delta_rule.delta_chunk_scan(*args, block=64)
    np.testing.assert_array_equal(o[:50], o3[:50])
    np.testing.assert_allclose(S, S3, atol=1e-6)


def _scan_jaxpr(T, H, K, V, block, channel=True, state_dtype=jnp.float32):
    sds = jax.ShapeDtypeStruct
    f32 = jnp.float32
    fn = jax.jit(lambda q, k, v, g, b, s: delta_rule.delta_chunk_scan(
        q, k, v, g, b, s, block=block, valid=jnp.int32(T)))
    traced = fn.trace(sds((T, H, K), f32), sds((T, H, K), f32),
                      sds((T, H, V), f32),
                      sds((T, H, K) if channel else (T, H), f32),
                      sds((T, H), f32), sds((K, H, V), state_dtype))
    return str(traced.jaxpr), traced.lower().as_text()


@pytest.mark.parametrize("what, T, H, K, V, block, channel, state", [
    ("a decay a head", 128, 2, 128, 128, 64, False, jnp.float32),
    ("the Olmo-Hybrid head", 128, 2, 96, 192, 64, False, jnp.float32),
    ("keys of 96", 128, 2, 96, 128, 64, True, jnp.float32),
    ("values of 192", 128, 2, 128, 192, 64, True, jnp.float32),
    ("an odd count of heads", 128, 3, 128, 128, 64, True, jnp.float32),
    ("a block of 32", 128, 2, 128, 128, 32, True, jnp.float32),
    ("a state in bfloat16", 128, 2, 128, 128, 64, True, jnp.bfloat16)])
def test_sizes_the_kernel_does_not_take_run_xlas_form(what, T, H, K, V, block,
                                                      channel, state):
    """The choice is made from the operands: anything but a decay a channel
    over pairs of 128 x 128 heads in blocks of 64 lowers to XLA's program,
    with no kernel and no custom call in it."""
    assert not delta_rule.chunk_scan_supported(H, K, V, block,
                                               channel_decay=channel,
                                               state_dtype=state)
    jaxpr, text = _scan_jaxpr(T, H, K, V, block, channel, state)
    assert "pallas_call" not in jaxpr, what
    assert "custom_call" not in text and "custom-call" not in text, what


def test_the_published_sizes_take_the_kernel():
    assert delta_rule.chunk_scan_supported(64, 128, 128, 64)
    jaxpr, _ = _scan_jaxpr(128, 4, 128, 128, 64)
    assert jaxpr.count("pallas_call") == 1
    assert "cumsum" not in jaxpr        # nothing of XLA's form beside it


def test_the_span_counts_the_blocks_a_layer_runs():
    """``KdaSpec.scan_blocks``: the blocks that hold a token where the
    kernel takes the sizes, every block of the chunk where it does not."""
    big = hybrid.KdaSpec(heads=64, key_dim=128, value_dim=128, conv=4,
                         chunk=64)
    assert [big.scan_blocks(n, 512) for n in (0, 1, 64, 65, 301, 512)] \
        == [0, 1, 1, 2, 5, 8]
    assert big.scan_blocks(20, 32) == 1      # a chunk narrower than a block
    small = hybrid.KdaSpec(heads=3, key_dim=12, value_dim=12, conv=4, chunk=8)
    assert [small.scan_blocks(n, 16) for n in (1, 9, 16)] == [2, 2, 2]


# the spec --------------------------------------------------------------------


def test_from_config_reads_the_solar_open2_keys(model):
    cfg, spec, params = model
    assert [ly.attn for ly in spec.layers] == ["full"] + ["kda"] * 3
    assert all(ly.ffn == "moe" and ly.has_state == (ly.attn == "kda")
               for ly in spec.layers)
    assert spec.kda == hybrid.KdaSpec(heads=3, key_dim=12, value_dim=12,
                                      conv=4, chunk=8, neg_eigval=True,
                                      rank=6)
    assert spec.has_state and spec.delta is None and spec.mixer is spec.kda
    assert (spec.n_experts, spec.experts_held, spec.first_expert,
            spec.top_k) == (EXPERTS, 4, FIRST, 3)
    assert spec.scoring == "sigmoid" and spec.shared_width == 24
    # a kda layer owns no page: the global class covers the GQA layer
    assert spec.page_values("full", PAGE) == PAGE * 2 * 2 * 16
    assert spec.state_slot_bytes() == 3 * 4 * (3 * 12 * 12 + 3 * 3 * 36)
    pages = init_kv_pages(params, 5, PAGE, spec, state_slots=3)
    assert [a.shape for a in pages["l0"]] == [(5, PAGE, 32)] * 2
    assert [a.shape for a in pages["l1"]] == [(3, 12, 36), (3, 3, 108)]
    assert set(params["l1"]) >= {"w_qkv", "w_a1", "w_a2", "w_b", "w_z1",
                                 "w_z2", "b_z", "conv_w", "A_log", "dt_bias",
                                 "o_norm", "wo", "moe"}
    assert set(params["l0"]) >= {"wq", "wk", "wv", "w_g", "wo", "moe"}
    assert "q_norm" not in params["l0"] and "wq" not in params["l1"]
    assert params["l1"]["moe"]["router"].shape == (40, EXPERTS)
    assert params["l1"]["moe"]["e_gate"].shape == (4, 40, 24)


@pytest.mark.parametrize("change, match", [
    ({"gqa_layers": None, "layer_types": ["full_attention"] * 4},
     "sliding_window"),
    ({"kda_allow_neg_eigval": None, "use_gqa_gate": None},
     r"solar_open2.*use_gqa_gate.*kda_allow_neg_eigval"),
    ({"linear_attn_config": {"head_dim": 12, "num_heads": 3}},
     r"linear_attn_config.*short_conv_kernel_size.*num_kv_heads"),
    ({"use_rope": True}, "rotary"),
    ({"use_gqa_gate": False}, "gate"),
    ({"kda_use_full_proj": True}, "full-rank"),
    ({"first_k_dense_replace": 1}, "dense"),
    ({"linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 12,
                             "num_heads": 3, "num_kv_heads": 1}}, "grouped")])
def test_from_config_names_what_is_missing_or_not_built(change, match):
    cfg = {k: v for k, v in tiny_cfg(**change).items() if v is not None}
    with pytest.raises(ValueError, match=match):
        _spec(cfg)


def test_the_decays_differ_across_a_heads_channels_and_the_step_passes_one(
        model):
    """The weights' laws let a check see the mechanism: over a random prompt
    the decays lie in about 0.86-0.9995, a head's channels spread over most
    of that (one scalar a head in their place is another model), the step
    passes 1 for a good share of tokens and heads, the gates stay off 0 and
    1."""
    cfg, spec, params = model
    x = params["emb"][_prompt(64)].astype(jnp.float32)
    lp = params["l1"]
    a = np.exp(-np.exp(lp["A_log"])[None, :, None] * jax.nn.softplus(
        (x @ lp["w_a1"] @ lp["w_a2"] + lp["dt_bias"]).reshape(64, 3, 12)))
    assert 0.8 < a.min() < a.max() < 0.9999
    within = a.max(-1) - a.min(-1)               # across a head's channels
    assert within.mean() > 0.3 * (a.max() - a.min())
    b = 2 * jax.nn.sigmoid(x @ lp["w_b"])
    assert 0.2 < float((b > 1).mean()) < 0.8
    gate = jax.nn.sigmoid(x @ lp["w_z1"] @ lp["w_z2"] + lp["b_z"])
    assert 0.02 < np.quantile(gate, 0.05) and np.quantile(gate, 0.95) < 0.98


# programs against the reference ----------------------------------------------


@pytest.mark.parametrize("kernel, which", [("gather", "model"),
                                           ("pallas", "kernel_model")])
def test_chunked_prefill_then_decode_agree_with_the_reference(
        kernel, which, request):
    """A prompt of 37 tokens in chunks of 16 (it ends 5 tokens into its
    third chunk, inside a block of 8, past a page edge), then 7 decode steps
    through the pages and the state slot, against the reference's one full
    pass with its token-by-token recurrence and every held expert computed
    the plain way: float32, tightly."""
    cfg, spec, params = request.getfixturevalue(which)
    with jax.default_matmul_precision("highest"):
        toks, served, pages = _serve_one(spec, params, _prompt(37), 8, kernel)
        want = _ref_logits(params, cfg, toks, 37)
    assert np.abs(want).max() > 1.0
    np.testing.assert_allclose(served, want, atol=TIGHT)
    # the row's slot holds its state; the slots no row was given hold none
    state = np.asarray(pages["l1"][0])
    assert np.abs(state[2]).max() > 0 and not state[[1, 3]].any()


def test_a_prefill_through_the_chunk_kernel_agrees_with_the_reference():
    """Heads of 128 x 128 in blocks of 64, chunks of 128: the chunk scan is
    the kernel. A prompt of 150 tokens ends 22 tokens into its second chunk,
    whose second block is skipped; then 4 decode steps from the state and
    the tail the chunks left: float32, tightly, as XLA's form is held."""
    cfg = tiny_cfg(num_hidden_layers=2, kda_chunk_size=64,
                   linear_attn_config={"short_conv_kernel_size": 4,
                                       "head_dim": 128, "num_heads": 2,
                                       "num_kv_heads": None})
    spec = _spec(cfg)
    assert spec.kda.scan_blocks(22, 128) == 1
    params = hybrid.init_params(spec, jax.random.key(5))
    with jax.default_matmul_precision("highest"):
        toks, served, _ = _serve_one(spec, params, _prompt(150), 5, "pallas",
                                     chunk=128)
        want = np.asarray(reference.logits_at(
            params, cfg, toks[:-1], np.arange(149, len(toks) - 1), 192))
    assert np.abs(want).max() > 1.0
    np.testing.assert_allclose(served, want, atol=TIGHT)


def _shared(spec, params):
    """Row A (45 tokens, slot 2, pages 1..) prefills in chunks of one page
    and leaves a snapshot of its state behind the chunk that ends at 32
    (slot 6). Row B (its first 32 tokens A's, then its own, 41 in all; slot
    3) takes A's first four pages, copies the snapshot into its slot and
    prefills from 32. Returns B's tokens and logits."""
    a = _prompt(45, seed=7)
    b = np.concatenate([a[:32], _prompt(9, seed=8)])
    _, _, pages = _serve_one(spec, params, a, 2, chunk=PAGE,
                             snapshots={32: 6})
    table = _table(10, 6, PAGE)
    table[:4] = np.arange(1, 5)
    pages = hybrid.state_slot_copy(pages, 6, 3, spec)
    return _serve_one(spec, params, b, 6, pages=pages, state_id=3,
                      chunk=PAGE, start=32, table=table)[:2]


def test_a_row_that_enters_from_a_snapshot_computes_what_it_would_have(model):
    cfg, spec, params = model
    with jax.default_matmul_precision("highest"):
        toks, served = _shared(spec, params)
        want = _ref_logits(params, cfg, toks, 41)
    np.testing.assert_allclose(served, want, atol=TIGHT)


@pytest.mark.parametrize("flaw", reference.FLAWS)
def test_the_comparison_sees_each_flaw(model, flaw):
    """The reference with one piece bent or left out moves the logits of the
    tokens the program served by far more than the tolerance."""
    cfg, spec, params = model
    with jax.default_matmul_precision("highest"):
        toks, served, _ = _serve_one(spec, params, _prompt(37), 8)
        flawed = _ref_logits(params, cfg, toks, 37, flaw=flaw)
    assert np.abs(served - flawed).max() > CAUGHT


def test_the_float8_control_is_another_model(model):
    cfg, spec, params = model
    toks = _prompt(40)
    with jax.default_matmul_precision("highest"):
        got = reference.served_gaps(params, cfg, toks, 30, 64, 16,
                                    control=True)
    assert got["gaps"].shape == got["control_gaps"].shape == (10,)
    assert got["control_gaps"].max() > 0


# the engine ------------------------------------------------------------------

BUCKETS = ((48, 8), (64, 16))
#: (system prompt, own tokens, steps): three system prompts of 32 tokens
SESSIONS = ((0, 5, 4), (1, 9, 6), (2, 13, 8), (0, 11, 5), (1, 3, 3),
            (0, 20, 7), (2, 7, 16), (1, 30, 4), (0, 2, 9), (2, 25, 6))


@pytest.fixture(scope="module")
def served(model, tmp_path_factory):
    """The sessions through a ServeEngine under a profiler capture: the
    requests, their results, the pool's audit afterwards, the spans."""
    cfg, spec, params = model
    eng = ServeEngine(params, spec, buckets=BUCKETS, max_batch=3,
                      page_len=PAGE, prefill_chunk=CHUNK, num_pages=96,
                      prefix_cache=True, start=False)
    eng.warmup()
    systems = [_prompt(32, seed=100 + h) for h in range(3)]
    reqs = [Request(prompt=np.concatenate([systems[h], _prompt(own, seed=i)]),
                    steps=steps, temperature=0.0)
            for i, (h, own, steps) in enumerate(SESSIONS)]
    results, spans = _capture(eng, reqs,
                              str(tmp_path_factory.mktemp("capture")))
    audit = eng.kvpool_audit()
    eng.close()
    return reqs, results, audit, spans


def test_the_engine_serves_the_reference_through_snapshots_and_a_share(
        served, model):
    """Ten requests behind three system prompts over two buckets of three
    rows: every request ok and every served token the reference's first
    choice (float32, greedy), though most rows entered from another row's
    snapshot, in a model whose every layer routes over experts of which it
    holds a quarter."""
    cfg, spec, params = model
    reqs, results, audit, _ = served
    assert [r.status for r in results] == ["ok"] * len(SESSIONS)
    with jax.default_matmul_precision("highest"):
        for req, res in zip(reqs, results):
            n = len(req.prompt)
            assert len(res.tokens) == n + req.steps
            want = np.asarray(reference.logits_at(
                params, cfg, res.tokens[:-1],
                np.arange(n - 1, len(res.tokens) - 1), 96))
            gap = want.max(-1) - want[np.arange(req.steps), res.tokens[n:]]
            assert gap.max() < 1e-4, (n, gap)
    shared = [r.metrics["shared_pages"] for r in results]
    assert sum(s == 4 for s in shared) >= 4 and set(shared) <= {0, 4}, shared
    assert audit["ok"], audit["errors"]
    assert audit["state_used"] == 0 and audit["snapshots_held"] >= 3


def test_the_prefill_spans_carry_the_tokens_and_the_blocks(served, model):
    """``kda_tokens`` is the chunk's valid tokens and ``kda_blocks`` the
    blocks of the scan a layer ran for it: at heads of 12 XLA's form runs,
    which computes every block of the chunk whatever it holds."""
    cfg, spec, params = model
    chunks = [s.fields for s in served[3]
              if s.name == "serve.prefill.dispatch"]
    assert chunks and any(f["tokens"] < f["width"] for f in chunks)
    assert all(f["kda_tokens"] == f["tokens"] for f in chunks)
    assert all(f["kda_blocks"] == f["width"] // spec.kda.chunk == 2
               for f in chunks)
