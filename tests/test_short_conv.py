"""A model whose layers are mostly gated SHORT CONVOLUTIONS with no attention
and no recurrent matrix (the ``lfm2_moe`` configuration family): a state slot
that is a two-token tail and nothing else, heads narrower than a lane tile,
an expert layer with no shared expert, a tied head. The paged programs
against the plain reference (``benchmarks/reference/serve_lfm2.py``) through
chunk and page boundaries, a second row that enters from the first row's
SNAPSHOT, each flaw the comparison must catch, the share of the expert layer
against the whole, and the pool and the engine that keep a snapshot behind
every chunk.

Small sizes that are awkward on purpose: heads of 10 (no tile of anything),
8 experts of width 24 with 3 picks, a vocabulary of 97; the norms' gains and
the selection bias are drawn wide so that a flaw in their use shows.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import engine_spans
from benchmarks.reference import serve_lfm2 as reference
from benchmarks.trace_reduce import find_xplane
from marlin_tpu.models import hybrid, moe
from marlin_tpu.models.transformer import (init_kv_pages, lm_decode_paged,
                                           lm_prefill_paged)
from marlin_tpu.ops import ssm as ssm_ops
from marlin_tpu.ops.paged_attention import paged_decode_attention
from marlin_tpu.serving import Request, ServeEngine
from marlin_tpu.serving.kvpool import PagedKVPool

PAGE, CHUNK = 8, 16
NO_RING = np.zeros(0, np.int32)
#: program against reference, both float32, sums in another order; measured
#: 4e-6 on logits of size 3
TIGHT = 3e-5
#: the least a flaw may move a logit to count as caught: over 30 x TIGHT
CAUGHT = 1e-3
VOCAB = 97
KINDS = ["conv", "conv", "full_attention", "conv", "conv", "conv",
         "full_attention", "conv"]


def tiny_cfg(**over):
    cfg = {
        "model_type": "lfm2_moe", "hidden_size": 40,
        "num_attention_heads": 4, "num_key_value_heads": 2,
        "intermediate_size": 72, "moe_intermediate_size": 24,
        "vocab_size": VOCAB, "num_hidden_layers": 6, "layer_types": KINDS,
        "conv_L_cache": 3, "conv_bias": False, "num_dense_layers": 2,
        "num_experts": 8, "num_experts_per_tok": 3, "use_expert_bias": True,
        "norm_topk_prob": True, "routed_scaling_factor": 1,
        "norm_eps": 1e-5, "rope_theta": 1000000,
        "param_dtype": "float32", "compute_dtype": "float32"}
    cfg.update(over)
    return cfg


def _widen(params, spec, key):
    """Gains that are not all 1 (a norm after the rotation then differs from
    one before it) and a selection bias wide enough to change the picks."""
    out = dict(params)
    for i, ly in enumerate(spec.layers):
        lp = dict(out[f"l{i}"])
        k = jax.random.fold_in(key, i)
        for j, name in enumerate(("q_norm", "k_norm")):
            if name in lp:
                lp[name] = jnp.exp(0.5 * jax.random.normal(
                    jax.random.fold_in(k, j), lp[name].shape))
        if "moe" in lp:
            lp["moe"] = dict(lp["moe"], e_bias=0.2 * jax.random.normal(
                jax.random.fold_in(k, 7), lp["moe"]["e_bias"].shape))
        out[f"l{i}"] = lp
    return out


@pytest.fixture(scope="module")
def model():
    cfg = tiny_cfg()
    spec = hybrid.ModelSpec.from_config(cfg)
    params = hybrid.init_params(spec, jax.random.key(3))
    return cfg, spec, _widen(params, spec, jax.random.key(4))


def _table(first_page: int, n_pages: int, chunk: int = CHUNK):
    t = np.zeros(n_pages + chunk // PAGE, np.int32)
    t[:n_pages] = np.arange(first_page, first_page + n_pages)
    return t


def _serve_one(spec, params, prompt, steps, kernel="gather", pages=None,
               state_id=2, prefill=lm_prefill_paged, between=None,
               chunk=CHUNK, start=0, table=None, snapshots=None):
    """Chunked paged prefill of ``prompt`` from position ``start`` into
    state slot ``state_id``, then decode through the cache in a call of
    three rows (the middle one live, the others the dummy row on the dummy
    slot); the tokens and the float32 logits every served token was picked
    from. ``between(pages)`` may tamper with the slabs between two chunks;
    ``snapshots`` maps a position to the slot the state is copied to behind
    the chunk that ends there."""
    n = len(prompt)
    need = -(-(n + steps) // PAGE)
    if pages is None:
        pages = init_kv_pages(params, 40, PAGE, spec, state_slots=8)
    if table is None:
        table = _table(1, need, chunk)
    padded = np.zeros(-(-n // chunk) * chunk + chunk, np.int32)
    padded[:n] = prompt
    for cs in range(start, n, chunk):
        if cs > start and between is not None:
            pages = between(pages)
        pages, first, _, logits = prefill(
            params, pages, (table, NO_RING, state_id), padded[cs:cs + chunk],
            cs, n, heads=spec, page_len=PAGE)
        if snapshots and cs + chunk in snapshots:
            pages = hybrid.state_slot_copy(pages, state_id,
                                           snapshots[cs + chunk], spec)
    toks, served = list(prompt) + [int(first)], [np.asarray(logits)]
    B = 3
    gt = np.zeros((B, need), np.int32)
    gt[1] = table[:need]
    zeros = np.zeros(B)
    for t in range(steps - 1):
        pages, nxt, _, logits = lm_decode_paged(
            params, pages,
            (gt, np.zeros((B, 0), np.int32), np.array([0, state_id, 0])),
            np.array([0, n + t, 0]), np.array([0, toks[-1], 0]), zeros, zeros,
            zeros, np.ones(B), zeros, heads=spec, page_len=PAGE,
            kernel=kernel)
        toks.append(int(nxt[1]))
        served.append(np.asarray(logits[1]))
    return np.asarray(toks), np.stack(served), pages


def _ref_logits(params, cfg, toks, n_prompt, pad=64):
    return np.asarray(reference.logits_at(
        params, cfg, toks[:-1], np.arange(n_prompt - 1, len(toks) - 1), pad))


def _prompt(n: int, seed: int = 0):
    return np.random.default_rng(seed).integers(0, VOCAB, n).astype(np.int32)


# the spec --------------------------------------------------------------------


def test_from_config_reads_the_lfm2_keys(model):
    cfg, spec, params = model
    assert [ly.attn for ly in spec.layers] == [
        "conv", "conv", "full", "conv", "conv", "conv"]
    assert [ly.ffn for ly in spec.layers] == ["dense"] * 2 + ["moe"] * 4
    assert spec.conv == hybrid.ConvSpec(taps=3, channels=40)
    assert spec.has_state and not spec.has_window
    assert spec.ssm is None and spec.delta is None and spec.mixer is spec.conv
    assert spec.head_dim == 10 and spec.kv_heads == 2     # 40 / 4: no key
    assert (spec.scoring, spec.shared_width, spec.renorm_eps) \
        == ("sigmoid", 0, 1e-6)
    assert spec.tied_head and spec.qk_norm and not spec.head_gate
    assert (spec.n_experts, spec.experts_held, spec.first_expert) == (8, 8, 0)
    # a conv layer owns no page: the global class covers the full layer
    assert spec.page_values("full", PAGE) == PAGE * 2 * 2 * 10
    # a slot with NO state part: five layers' tails of 2 x 40 values
    assert spec.conv.slot_arrays() == (((2, 40), None),)
    assert spec.state_slot_bytes() == 5 * 2 * 40 * 4
    assert spec.state_slot_bytes("bfloat16") == 5 * 2 * 40 * 2
    pages = init_kv_pages(params, 5, PAGE, spec, state_slots=3)
    assert [a.shape for a in pages["l0"]] == [(3, 2, 40)]    # ONE array
    assert [a.shape for a in pages["l2"]] == [(5, PAGE, 20)] * 2
    assert set(params["l0"]) == {"ln1", "ln2", "w_in", "conv_w", "w_out",
                                 "w_gate", "w_up", "w_down"}
    assert params["l0"]["w_in"].shape == (40, 120)
    assert {"q_norm", "k_norm", "wq"} <= set(params["l2"]) \
        and "wgate" not in params["l2"]
    assert params["l2"]["q_norm"].shape == (10,)
    # no shared expert: no such array; a tied head: no second table
    assert set(params["l3"]["moe"]) == {"router", "e_bias", "e_gate", "e_up",
                                        "e_down"}
    assert "head" not in params and params["emb"].shape == (VOCAB, 40)


@pytest.mark.parametrize("change, match", [
    ({"moe_intermediate_size": None, "num_dense_layers": None},
     r"moe_intermediate_size.*num_dense_layers|num_dense_layers.*moe_inter"),
    ({"conv_bias": True}, "conv_bias"),
    ({"num_shared_experts": 1}, "num_shared_experts"),
    ({"norm_topk_prob": False}, "norm_topk_prob"),
    ({"layer_types": ["conv", "sliding_attention"] * 4}, "sliding_attention"),
    ({"rope_theta": None}, "rope_theta")])
def test_from_config_refuses_what_the_family_does_not_build(change, match):
    cfg = {k: v for k, v in tiny_cfg(**change).items() if v is not None}
    with pytest.raises(ValueError, match=match):
        hybrid.ModelSpec.from_config(cfg)


def test_the_other_families_specs_and_slots_are_what_they_were():
    """The slot of a mixer WITH a state part still is (state, tail), and a
    spec of another family ties nothing, gates its heads and norms none."""
    sm = hybrid.SsmSpec(heads=4, head_dim=8, state=16, groups=2, conv=4,
                        chunk=8)
    assert sm.slot_arrays() == (((4, 16, 8), "float32"), ((3, 96), None))
    ds = hybrid.DeltaSpec(heads=3, key_dim=12, value_dim=20, conv=4)
    assert ds.slot_arrays() == (((12, 60), "float32"), ((3, 132), None))
    fields = {f.name: f.default for f in dataclasses.fields(hybrid.ModelSpec)}
    assert (fields["head_gate"], fields["qk_norm"], fields["tied_head"],
            fields["renorm_eps"], fields["conv"]) == (True, False, False,
                                                      0.0, None)


# programs against the reference ----------------------------------------------


@pytest.mark.parametrize("n, chunk, kernel", [
    (13, 16, "gather"),     # one chunk
    (37, 16, "gather"),     # three chunks, the last 5 valid tokens of 16
    (37, 16, "pallas"),     # ... decode through the walk kernel, heads of 10
    (33, 16, "gather"),     # a last chunk of ONE valid token
    (41, 8, "gather")])     # chunks of one page
def test_prefill_then_decode_agree_with_the_reference(n, chunk, kernel,
                                                      model):
    """Prefill in one chunk and in many, then 7 decode steps through the
    pages and the tail's slot, against the reference's one full pass (no
    tail, no chunk): float32, tightly."""
    cfg, spec, params = model
    with jax.default_matmul_precision("highest"):
        toks, served, pages = _serve_one(spec, params, _prompt(n), 8, kernel,
                                         chunk=chunk)
        want = _ref_logits(params, cfg, toks, n)
    assert np.abs(want).max() > 1.0
    np.testing.assert_allclose(served, want, atol=TIGHT)
    # the row's slot holds its tail; the slots no row was given hold none
    tails = np.asarray(pages["l0"][0])
    assert np.abs(tails[2]).max() > 0 and not tails[[1, 3]].any()


def test_a_reused_slot_and_a_dirty_pool_change_nothing(model):
    """A second row through the slot (and the pages) a first row left full:
    its first chunk enters with zeros, so it is served as in a fresh pool."""
    cfg, spec, params = model
    with jax.default_matmul_precision("highest"):
        _, _, dirty = _serve_one(spec, params, _prompt(40, seed=5), 6)
        toks, served, _ = _serve_one(spec, params, _prompt(37), 8,
                                     pages=dirty)
        want = _ref_logits(params, cfg, toks, 37)
    np.testing.assert_allclose(served, want, atol=TIGHT)


def _shared(spec, params, boundary: int, enter: str = "snapshot"):
    """Row A (45 tokens, slot 2, pages 1..) prefills in chunks of one page
    and leaves snapshots of its tails behind the chunks that end at 24, 32
    and 40 (slots 5, 6, 7). Row B (its first 32 tokens A's, then its own, 41
    in all; slot 3) takes A's first four pages, copies the snapshot of
    ``boundary`` into its slot (``enter`` ``zeros``: copies nothing) and
    prefills from 32. Returns B's tokens and logits."""
    a = _prompt(45, seed=7)
    b = np.concatenate([a[:32], _prompt(9, seed=8)])
    _, _, pages = _serve_one(spec, params, a, 2, chunk=PAGE,
                             snapshots={24: 5, 32: 6, 40: 7})
    table = _table(10, 6, PAGE)       # 41 + 6 tokens: six pages
    table[:4] = np.arange(1, 5)       # A's first four, shared
    if enter == "snapshot":
        pages = hybrid.state_slot_copy(pages, {24: 5, 32: 6, 40: 7}[boundary],
                                       3, spec)
    toks, served, _ = _serve_one(spec, params, b, 6, pages=pages, state_id=3,
                                 chunk=PAGE, start=32, table=table)
    return toks, served


def test_a_row_that_enters_from_a_snapshot_computes_what_it_would_have(model):
    """A request that shares a whole-page prefix with another and enters
    from that one's snapshot (two tokens of ``s`` a layer) agrees with the
    reference's full pass, which never saw a cache."""
    cfg, spec, params = model
    with jax.default_matmul_precision("highest"):
        toks, served = _shared(spec, params, 32)
        want = _ref_logits(params, cfg, toks, 41)
    np.testing.assert_allclose(served, want, atol=TIGHT)


@pytest.mark.parametrize("flaw, kw", [
    ("entered_from_zeros", {"boundary": 32, "enter": "zeros"}),
    ("snapshot_a_page_early", {"boundary": 24}),
    ("snapshot_a_page_late", {"boundary": 40})])
def test_a_hit_entered_from_the_wrong_tail_fails_the_comparison(
        flaw, kw, model):
    cfg, spec, params = model
    with jax.default_matmul_precision("highest"):
        toks, served = _shared(spec, params, **kw)
        want = _ref_logits(params, cfg, toks, 41)
    assert np.abs(served - want).max() > CAUGHT, flaw


# each flaw -------------------------------------------------------------------


def _fresh_prefill(monkeypatch, patches):
    """``lm_prefill_paged`` traced anew with each ``(target, name, flawed)``
    in place."""
    for target, name, flawed in patches:
        monkeypatch.setattr(target, name, flawed)
    raw = hybrid._lm_prefill_paged_spec_jit.__wrapped__

    def run(*args, spec, page_len, **kw):  # (jit keeps traces by function)
        return raw(*args, spec=spec, page_len=page_len, **kw)

    fresh = jax.jit(run, static_argnames=("spec", "page_len"))

    def prefill(params, pages, tables, chunk, cs, n, heads, page_len):
        args, static = hybrid._prefill_args(params, pages, tables, chunk, cs,
                                            n, heads, page_len)
        return fresh(*args, **static)

    return prefill


def _zero_tails(spec):
    def between(pages):
        return {f"l{i}": tuple(jnp.zeros_like(a) for a in pages[f"l{i}"])
                if ly.attn == "conv" else pages[f"l{i}"]
                for i, ly in enumerate(spec.layers)}
    return between


def _exchanged_short_conv(spec, lp, u, mix):
    ch = spec.conv.channels
    p = hybrid._mm(u, lp["w_in"], jnp.float32)
    c, b, z = p[:, :ch], p[:, ch:2 * ch], p[:, 2 * ch:]     # b and c exchanged
    return hybrid._mm((c * mix((b * z).astype(u.dtype), lp)).astype(u.dtype),
                      lp["w_out"], jnp.float32)


def _conv_with_its_own_tail_only():
    """A chunk's new tail from the chunk's inputs alone (zeros before them):
    right wherever the chunk holds two valid tokens, a RESET where it holds
    one."""
    real = ssm_ops.causal_conv

    def flawed(u, tail, w, b, n_valid):
        out, _ = real(u, tail, w, b, n_valid)
        _, new_tail = real(u, jnp.zeros_like(tail), w, b, n_valid)
        return out, new_tail
    return flawed


def _norm_after_the_rotation():
    """``_rmsnorm`` over a head's width hands its input through and
    ``_rope`` norms what it has turned: the QK-norm AFTER the rotation."""
    real_norm, real_rope, kept = hybrid._rmsnorm, hybrid._rope, []

    def norm(x, g, eps):
        if g.shape[0] != 10:
            return real_norm(x, g, eps)
        kept.append((g, eps))
        return x

    def rope(x, positions, spec):
        return real_norm(real_rope(x, positions, spec), *kept.pop())
    return [(hybrid, "_rmsnorm", norm), (hybrid, "_rope", rope)]


def _picks_with(flaw: str):
    def flawed(logits, mp, top_k, scoring, renorm_eps, n_group, topk_group):
        scores = jax.nn.sigmoid(logits)
        biased = scores + mp["e_bias"]
        _, topi = jax.lax.top_k(biased, top_k)
        topv = jnp.take_along_axis(
            biased if flaw == "bias_in_the_weights" else scores, topi, axis=-1)
        if flaw == "no_renormalisation":
            return topv, topi
        return topv / (jnp.sum(topv, -1, keepdims=True) + renorm_eps), topi
    return flawed


def _with_experts_in_layer_1(params):
    """``params`` with an expert layer's arrays beside layer 1's dense FFN,
    and the spec that has ONE leading dense layer (which runs them)."""
    one = hybrid.ModelSpec.from_config(tiny_cfg(num_dense_layers=1))
    extra = hybrid.init_layer_params(one, one.layers[1],
                                     jax.random.key(9))["moe"]
    return dict(params, l1=dict(params["l1"], moe=extra)), one


FLAWS = ["mixer_dropped", "b_and_c_exchanged", "taps_reversed",
         "tail_not_carried", "tail_reset_by_a_chunk_of_one_token",
         "padding_advances_the_tail", "slot_not_zeroed", "no_qk_norm",
         "qk_norm_after_the_rotation", "bias_in_the_weights",
         "no_renormalisation", "a_dense_layer_as_an_expert_layer",
         "an_untied_random_head"]


def test_the_router_stand_in_is_sound(model, monkeypatch):
    """The flawed routers below differ from the sound one in ONE line: with
    no flaw asked for, the stand-in agrees with the reference."""
    cfg, spec, params = model
    prefill = _fresh_prefill(monkeypatch, [(moe, "_picks",
                                            _picks_with("none"))])
    with jax.default_matmul_precision("highest"):
        toks, served, _ = _serve_one(spec, params, _prompt(37), 2,
                                     prefill=prefill)
        want = _ref_logits(params, cfg, toks, 37)
    np.testing.assert_allclose(served[:1], want[:1], atol=TIGHT)


@pytest.mark.parametrize("flaw", FLAWS)
def test_each_flaw_fails_the_comparison(flaw, model, monkeypatch):
    """What the comparison must catch: every piece of the mixer's
    arithmetic, of the tail's way through the cache, of the family's
    attention, router and head, left out or bent one at a time, moves a
    logit by far more than the agreement above allows."""
    cfg, spec, params = model
    n, kw, ref_params = 37, {}, params
    if flaw == "mixer_dropped":
        real = hybrid._short_conv
        kw["prefill"] = _fresh_prefill(monkeypatch, [(
            hybrid, "_short_conv", lambda *a: jnp.zeros_like(real(*a)))])
    elif flaw == "b_and_c_exchanged":
        kw["prefill"] = _fresh_prefill(monkeypatch, [(
            hybrid, "_short_conv", _exchanged_short_conv)])
    elif flaw == "taps_reversed":
        params = {k: dict(v, conv_w=v["conv_w"][::-1])
                  if isinstance(v, dict) and "conv_w" in v else v
                  for k, v in params.items()}
    elif flaw == "tail_not_carried":
        kw["between"] = _zero_tails(spec)
    elif flaw == "tail_reset_by_a_chunk_of_one_token":
        n = 33      # chunks of 16: the third holds ONE valid token
        kw["prefill"] = _fresh_prefill(monkeypatch, [(
            ssm_ops, "causal_conv", _conv_with_its_own_tail_only())])
    elif flaw == "padding_advances_the_tail":
        real = ssm_ops.causal_conv
        kw["prefill"] = _fresh_prefill(monkeypatch, [(
            ssm_ops, "causal_conv",
            lambda u, tail, w, b, n_valid: real(u, tail, w, b, u.shape[0]))])
    elif flaw == "slot_not_zeroed":
        with jax.default_matmul_precision("highest"):
            kw["pages"] = _serve_one(spec, params, _prompt(40, seed=5), 6)[2]
        kw["prefill"] = _fresh_prefill(monkeypatch, [(
            hybrid, "_enter_state", lambda fresh, *arrays: arrays)])
    elif flaw == "no_qk_norm":
        real = hybrid._rmsnorm
        kw["prefill"] = _fresh_prefill(monkeypatch, [(   # a head's 10 values
            hybrid, "_rmsnorm",
            lambda x, g, eps: x if g.shape[0] == 10 else real(x, g, eps))])
    elif flaw == "qk_norm_after_the_rotation":
        kw["prefill"] = _fresh_prefill(monkeypatch,
                                       _norm_after_the_rotation())
    elif flaw in ("bias_in_the_weights", "no_renormalisation"):
        kw["prefill"] = _fresh_prefill(monkeypatch, [(moe, "_picks",
                                                      _picks_with(flaw))])
    elif flaw == "a_dense_layer_as_an_expert_layer":
        # layer 1 holds BOTH: the reference runs its dense FFN (two leading
        # dense layers), the program told of one runs its experts
        params, spec = _with_experts_in_layer_1(params)
        ref_params = params
    elif flaw == "an_untied_random_head":
        spec = dataclasses.replace(spec, tied_head=False)
        params = dict(params, head=40 ** -0.5 * jax.random.normal(
            jax.random.key(11), (VOCAB, 40)))
    with jax.default_matmul_precision("highest"):
        toks, served, _ = _serve_one(spec, params, _prompt(n), 8, **kw)
        want = _ref_logits(ref_params, cfg, toks, n)
    assert np.abs(served - want).max() > CAUGHT, flaw


def test_the_merged_layer_is_sound_under_the_right_spec(model):
    """The control of ``a_dense_layer_as_an_expert_layer``: the same merged
    parameters under the spec that has two dense layers agree."""
    cfg, spec, params = model
    params, _ = _with_experts_in_layer_1(params)
    with jax.default_matmul_precision("highest"):
        toks, served, _ = _serve_one(spec, params, _prompt(37), 3)
        want = _ref_logits(params, cfg, toks, 37)
    np.testing.assert_allclose(served, want, atol=TIGHT)


# the expert layer ------------------------------------------------------------


def test_four_shares_of_the_experts_add_up_to_the_whole_layer(model):
    """The share tied to the model (with NO shared expert nothing is counted
    twice): the parts that four shares of 2 experts give, each routed over
    all 8 and computing its own experts' part, add up to what the layer that
    holds all 8 gives, and to the reference's whole layer."""
    cfg, spec, params = model
    mp = params["l3"]["moe"]
    h = jax.random.normal(jax.random.key(5), (19, 40))
    valid = jnp.arange(19) < 17
    kw = dict(top_k=3, scoring="sigmoid", renorm_eps=1e-6)
    with jax.default_matmul_precision("highest"):
        whole, counts = moe.moe_experts_ffn(mp, h, valid, **kw)
        parts, local, touched = [], 0, 0
        for first in range(0, 8, 2):
            share = dict(mp, **{k: mp[k][first:first + 2]
                                for k in ("e_gate", "e_up", "e_down")})
            out, c = moe.moe_experts_ffn(share, h, valid, first_expert=first,
                                         **kw)
            parts.append(out)
            local += int(c[1])
            touched += int(c[2])
            assert int(c[0]) == 17 * 3
        want = reference.experts(h, mp, dict(reference.describe(cfg)),
                                 lambda x: x)
    assert local == int(counts[1]) == int(counts[0]) == 17 * 3
    assert 4 <= touched == int(counts[2]) <= 8      # all 8 are held here
    np.testing.assert_allclose(sum(parts)[:17], whole[:17], atol=1e-5)
    np.testing.assert_allclose(whole[:17], want[:17], atol=1e-5)
    assert not np.asarray(whole[17:]).any()     # padding is routed nowhere


def test_a_layer_without_a_shared_expert_draws_and_runs_none(model):
    """``shared_width`` 0 drew ``s_gate`` at ``0 ** -0.5`` and the layer read
    it unconditionally; a layer WITH one still adds it."""
    cfg, spec, params = model
    assert spec.shared_width == 0
    mp = params["l3"]["moe"]
    h = jax.random.normal(jax.random.key(6), (5, 40))
    valid = jnp.ones((5,), bool)
    kw = dict(top_k=3, scoring="sigmoid", renorm_eps=1e-6)
    ks = jax.random.split(jax.random.key(7), 3)
    shared = dict(s_gate=jax.random.normal(ks[0], (40, 16)) * 40 ** -0.5,
                  s_up=jax.random.normal(ks[1], (40, 16)) * 40 ** -0.5,
                  s_down=jax.random.normal(ks[2], (16, 40)) * 0.25)
    with jax.default_matmul_precision("highest"):
        plain, _ = moe.moe_experts_ffn(mp, h, valid, **kw)
        both, _ = moe.moe_experts_ffn(dict(mp, **shared), h, valid, **kw)
        want = (jax.nn.silu(h @ shared["s_gate"]) * (h @ shared["s_up"])) \
            @ shared["s_down"]
    np.testing.assert_allclose(both - plain, want, atol=1e-5)


@pytest.mark.parametrize("k, n, want", [
    (3072, 1024, (128, 1024, 1024)),    # Laguna, gate / up
    (1024, 3072, (128, 1024, 1024)),    # ... down
    (4096, 2048, (128, 1024, 1024)),    # Mistral, gate / up
    (2048, 4096, (128, 1024, 1024)),    # ... down
    (2048, 1792, (128, 2048, 1024)),    # LFM2, gate / up
    (1792, 2048, (128, 1792, 1024)),    # ... down
    (4096, 1280, (128, 2048, 1280)),    # Solar-Open2, gate / up
    (1280, 4096, (128, 1280, 1024)),    # ... down
    (5120, 1536, (128, 2048, 1024))])   # a wider stream, experts of 1536
def test_the_grouped_matmuls_tile_follows_the_widths(k, n, want):
    """(128, 1024, 1024) wherever an expert's widths are whole tiles of 1024
    (every call the Laguna and Mistral cells make); experts of 1792 or 1280
    take the whole contraction in one tile (up to 2048) and 1024 columns (a
    width of at most 1280 whole), as read on the chip; the rows stay 128. The bfloat16 tiles of the expert
    cells are pinned here: operands of two bytes are the default."""
    assert moe._gmm_tiling(k, n) == moe._gmm_tiling(k, n, 2) == want


@pytest.mark.parametrize("k, n, want", [
    (4096, 2048, (128, 512, 1024)),     # Mistral's float32 check
    (2048, 1792, (128, 1024, 1024)),    # LFM2's: 2048 x 1024 x 4 B twice
    (1792, 2048, (128, 896, 1024)),     # filled the kernel's 16 MB
    (4096, 1280, (128, 1024, 1280)),    # Solar-Open2's
    (1280, 4096, (128, 640, 1024))])
def test_float32_operands_take_half_the_contraction_a_tile(k, n, want):
    """A float32 check's grouped matmul holds as many BYTES of the
    contraction a tile as the bfloat16 one: half its length, whole lane
    tiles, so its two weight buffers fit the kernel's VMEM."""
    assert moe._gmm_tiling(k, n, 4) == want
    assert want[1] * want[2] * 4 * 2 <= 10 << 20


# the decode kernel at heads narrower than a lane tile ------------------------


@pytest.mark.parametrize("dtype, atol", [(jnp.float32, 2e-6),
                                         (jnp.bfloat16, 2 ** -7)])
def test_the_walk_at_eight_heads_of_64_group_4_is_the_gather(dtype, atol):
    """The (K, V) walk over a page ``(page_len, 8 x 64)``, four query rows a
    head, in the interpreter: every head in one matmul from the
    block-diagonal query, against the gathered formulation; rows of one
    token, of whole pages and of a partly filled last page, and a dummy
    row."""
    B, kvh, group, dh, page_len, W = 5, 8, 4, 64, 32, 4
    ks = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(ks[0], (B, kvh, group, dh), jnp.float32)
    k = jax.random.normal(ks[1], (9, page_len, kvh * dh), jnp.float32)
    v = jax.random.normal(ks[2], (9, page_len, kvh * dh), jnp.float32)
    tables = jnp.asarray([[1, 2, 3, 4], [5, 6, 0, 0], [7, 0, 0, 0],
                          [8, 3, 1, 0], [0, 0, 0, 0]], jnp.int32)
    lengths = jnp.asarray([128, 37, 1, 65, 1], jnp.int32)
    q, k, v = (a.astype(dtype) for a in (q, k, v))
    zero = jnp.zeros((B,), jnp.int32)
    with jax.default_matmul_precision("highest"):
        got = paged_decode_attention(q, k, v, tables, lengths, interpret=True)
        want = hybrid._attend_gather(q, k, v, tables, lengths, zero, zero,
                                     page_len)
    assert got.shape == (B, kvh, group, dh) and got.dtype == dtype
    np.testing.assert_allclose(got.astype(jnp.float32),
                               want.astype(jnp.float32), atol=atol)


# the pool --------------------------------------------------------------------


def test_a_pool_whose_slot_is_smaller_than_a_page_snapshots_every_chunk(
        model):
    """A slot of tails is cheaper than a page: every chunk that ends on a
    shareable boundary is due a snapshot, not only the deepest; a model with
    a recurrent matrix keeps the deepest-or-seen rule (its tests)."""
    cfg, spec, params = model
    # 16-token pages: a page id holds 1280 B, a slot 800 B
    pool = PagedKVPool(params, spec, 12, 16, state_slots=3, snapshot_slots=4)
    assert pool._snapshot_cheap
    assert [pool.snapshot_due(e, 16, 70) for e in (16, 32, 48, 64, 80)] \
        == [True, True, True, True, False]
    # the page that holds the prompt's last token is never shared
    assert not pool.snapshot_due(64, 16, 64)
    # 8-token pages hold 640 B: dearer than a page, the old rule
    pool = PagedKVPool(params, spec, 12, PAGE, state_slots=3,
                       snapshot_slots=4)
    assert not pool._snapshot_cheap
    assert [pool.snapshot_due(e, 16, 70) for e in (16, 32, 48, 64)] \
        == [False, False, False, True]
    assert not PagedKVPool(params, spec, 12, 16, state_slots=3,
                           prefix_cache=False).snapshot_due(16, 16, 70)


# the engine ------------------------------------------------------------------

EPAGE = 16      # a page dearer than a slot: a snapshot behind every chunk
BUCKETS = ((96, 16), (128, 32))
#: sessions: three system prompts of 64 tokens (4 pages), each followed by a
#: caller's own part; (system prompt, own tokens, steps)
SESSIONS = ((0, 5, 4), (1, 9, 6), (2, 13, 8), (0, 11, 5), (1, 3, 3),
            (0, 40, 7), (2, 7, 16), (1, 60, 4), (0, 2, 9), (2, 25, 6),
            (1, 14, 5), (0, 9, 3))


def _engine(spec, params, **kw):
    kw.setdefault("buckets", BUCKETS)
    kw.setdefault("max_batch", 3)
    kw.setdefault("page_len", EPAGE)
    kw.setdefault("prefill_chunk", 2 * EPAGE)
    kw.setdefault("num_pages", 96)
    kw.setdefault("prefix_cache", True)
    kw.setdefault("snapshot_slots", 24)
    return ServeEngine(params, spec, **kw)


def _sessions(schedule=SESSIONS, length=64):
    systems = [_prompt(length, seed=100 + h) for h in range(3)]
    return [Request(prompt=np.concatenate([systems[h], _prompt(own, seed=i)]),
                    steps=steps, temperature=0.0)
            for i, (h, own, steps) in enumerate(schedule)]


@pytest.fixture(scope="module")
def served(model, tmp_path_factory):
    """The sessions through a ServeEngine with the prefix cache on, under a
    profiler capture: results, spans, and the pool's audit afterwards."""
    cfg, spec, params = model
    eng = _engine(spec, params, start=False)
    eng.warmup()
    reqs = _sessions()
    where = str(tmp_path_factory.mktemp("capture"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(where, profiler_options=opts)
    try:
        handles = eng.submit_many(reqs)
        eng.start()
        results = [h.result(timeout=300) for h in handles]
    finally:
        jax.profiler.stop_trace()
    audit = eng.kvpool_audit()
    slot_bytes = eng._state_slot_bytes
    eng.close()
    return {"requests": reqs, "results": results, "audit": audit,
            "slot_bytes": slot_bytes,
            "spans": engine_spans.load(find_xplane(where))["spans"]}


def test_the_engine_serves_the_reference_through_snapshots(served, model):
    """Twelve requests over three system prompts, two buckets of three slots
    under the pipelined decode: every request ok and every served token the
    reference's first choice (float32, greedy), though most rows entered
    from another row's snapshot and never prefilled their system prompt."""
    cfg, spec, params = model
    assert [r.status for r in served["results"]] == ["ok"] * len(SESSIONS)
    with jax.default_matmul_precision("highest"):
        for req, res in zip(served["requests"], served["results"]):
            n = len(req.prompt)
            assert len(res.tokens) == n + req.steps
            want = _ref_logits(params, cfg, res.tokens, n, pad=160)
            gap = want.max(-1) - want[np.arange(req.steps), res.tokens[n:]]
            assert gap.max() < 1e-4, (n, gap)
    shared = [r.metrics["shared_pages"] for r in served["results"]]
    assert sum(s >= 4 for s in shared) >= 6, shared
    audit = served["audit"]
    assert audit["ok"], audit["errors"]
    assert audit["state_used"] == 0 and audit["used"] == audit["cached"] > 0
    assert audit["snapshot_total"] == 24
    # a snapshot behind every chunk: more than one a request that prefilled
    # its system prompt, and the cache holds more than the three prefixes'
    assert audit["snapshots_taken"] >= 9 and audit["snapshots_held"] > 3


def test_the_spans_read_right_for_this_spec(served, model):
    """``conv_tokens`` on the prefill dispatches (the name ``_mixer_tokens``
    hands them), the state and snapshot fields, and the expert counts of
    four expert layers with every expert held."""
    cfg, spec, params = model
    by = {}
    for s in served["spans"]:
        by.setdefault(s.name, []).append(s.fields)
    chunks = by["serve.prefill.dispatch"]
    assert all(f["conv_tokens"] == f["tokens"] for f in chunks)
    assert not any("ssm_tokens" in f or "delta_tokens" in f for f in chunks)
    admits = by["serve.admit"]
    assert len(admits) == len(SESSIONS)
    assert all(f["snapshot_tokens"] == f["shared_tokens"] for f in admits)
    # a hit enters at the deepest boundary that has a snapshot: the system
    # prompt's 64, every one of them page- and chunk-aligned
    assert {f["snapshot_tokens"] for f in admits} >= {0, 64}
    assert all(f["snapshot_tokens"] % EPAGE == 0 for f in admits)
    hits = sum(f["shared_tokens"] for f in admits)
    assert sum(f["conv_tokens"] for f in chunks) \
        == sum(f["prompt_tokens"] for f in admits) - hits
    # snapshots behind EVERY chunk that ends on a shareable boundary
    assert {f["start"] + f["width"] for f in chunks if f.get("snapshots")} \
        >= {32, 64}
    iters = [f for f in by["serve.iter"] if "snapshot_slots" in f]
    assert iters and all(f["snapshot_slots"] == 24 for f in iters)
    assert all(f["state_rows"] == f["resident_rows"] for f in iters)
    assert all(f["state_bytes"] == f["state_rows"] * served["slot_bytes"]
               for f in iters)
    assert served["slot_bytes"] == 5 * 2 * 40 * 4      # five conv layers
    calls = [f for f in by["serve.decode.dispatch"] if f.get("rows")]
    assert calls and all(f["state_rows"] == f["rows"] for f in calls)
    landed = [f for f in by["serve.decode.sync"] if "moe_assignments" in f]
    assert landed
    for f in landed:    # 4 expert layers x 3 picks a live row, all local
        assert f["moe_assignments"] == f["moe_local_assignments"]
        assert f["moe_assignments"] % (4 * 3) == 0
        assert 4 <= f["moe_experts_touched"] <= 4 * 8


def test_admission_charges_the_tails_slot(model):
    cfg, spec, params = model
    eng = _engine(spec, params, start=False)
    try:
        prog = eng._programs["lm"]
        req = Request(prompt=_prompt(40), steps=5)
        pages = -(-(40 + 5 - 1) // EPAGE)
        assert prog.admission_cost(req, (96, 16)) \
            == pages * eng._page_bytes + spec.state_slot_bytes()
        assert eng._prefix_cache and eng._snapshot_slots == 24
        assert eng._mixer_tokens == "conv_tokens"
    finally:
        eng.close()
    # any chunk width: the convolution has no block to be whole multiples of
    _engine(spec, params, prefill_chunk=3 * EPAGE, start=False).close()
