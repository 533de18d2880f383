"""A model whose layers are mostly gated delta-rule mixers with NO attention
(the ``olmo_hybrid`` configuration family): the two forms of the recurrence
against the token-by-token one, the paged programs against the plain
reference (``benchmarks/reference/serve_olmohybrid.py``) through chunk, page
and block boundaries, a second row that enters from the first row's state
SNAPSHOT, each flaw the comparison must catch, and the pool and the engine
that keep snapshots beside the prefix cache's pages.

Small sizes that are awkward on purpose: 3 heads (not a power of two), keys
of 12 and values of 20 (unequal, neither a multiple of the block of 8), a
width that is not heads x head size.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import engine_spans
from benchmarks.reference import serve_olmohybrid as reference
from benchmarks.trace_reduce import find_xplane
from marlin_tpu.models import hybrid
from marlin_tpu.models.transformer import (init_kv_pages, lm_decode_paged,
                                           lm_prefill_paged)
from marlin_tpu.ops import delta_rule
from marlin_tpu.serving import Request, ServeEngine
from marlin_tpu.serving.engine import MigrationError
from marlin_tpu.serving.kvpool import PagedKVPool

PAGE, CHUNK = 8, 16
NO_RING = np.zeros(0, np.int32)
#: program against reference, both float32: sums in another order (blocks of
#: 8 tokens and a triangular solve against one token at a time); measured
#: 3e-6 on logits of size 3
TIGHT = 3e-5
#: the least a flaw may move a logit to count as caught: over 30 x TIGHT
CAUGHT = 1e-3
VOCAB = 97


def tiny_cfg(**over):
    cfg = {
        "model_type": "olmo_hybrid", "hidden_size": 40, "head_dim": 16,
        "num_attention_heads": 3, "num_key_value_heads": 3,
        "intermediate_size": 72, "vocab_size": VOCAB, "num_hidden_layers": 4,
        "layer_types": ["linear_attention", "linear_attention",
                        "linear_attention", "full_attention"],
        "linear_num_key_heads": 3, "linear_num_value_heads": 3,
        "linear_key_head_dim": 12, "linear_value_head_dim": 20,
        "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
        "linear_chunk_size": 8, "rope_parameters": {"rope_theta": None},
        "attention_bias": False, "hidden_act": "silu", "rms_norm_eps": 1e-6,
        "param_dtype": "float32", "compute_dtype": "float32"}
    cfg.update(over)
    return cfg


@pytest.fixture(scope="module")
def model():
    cfg = tiny_cfg()
    spec = hybrid.ModelSpec.from_config(cfg)
    return cfg, spec, hybrid.init_params(spec, jax.random.key(3))


@pytest.fixture(scope="module")
def kernel_model():
    """A mixer the Pallas update takes: 4 heads, keys of 16, values of 64 (a
    pair of heads is one lane tile, the second head beginning mid-tile); one
    linear layer and one full one."""
    cfg = tiny_cfg(linear_num_key_heads=4, linear_num_value_heads=4,
                   linear_key_head_dim=16, linear_value_head_dim=64,
                   num_hidden_layers=2,
                   layer_types=["linear_attention", "full_attention"])
    spec = hybrid.ModelSpec.from_config(cfg)
    return cfg, spec, hybrid.init_params(spec, jax.random.key(4))


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


def _ref_logits(params, cfg, toks, n_prompt):
    return np.asarray(reference.logits_at(
        params, cfg, toks[:-1], np.arange(n_prompt - 1, len(toks) - 1), 64))


def _prompt(n: int, seed: int = 0):
    return np.random.default_rng(seed).integers(0, VOCAB, n).astype(np.int32)


# ops/delta_rule.py -----------------------------------------------------------


def _naive(q, k, v, g, b, S, decay_first=True):
    """The three lines, a token at a time, float64. ``S`` (H, K, V); ``g``
    (T, H) one decay a head or (T, H, K) one a channel of the key."""
    q, k, v, g, b = (np.asarray(a, np.float64) for a in (q, k, v, g, b))
    S = np.asarray(S, np.float64)
    out = []
    for t in range(q.shape[0]):
        Sd = S * np.exp(g[t]).reshape(*g[t].shape, *(1,) * (3 - g[t].ndim))
        against = Sd if decay_first else S
        d = b[t][:, None] * (v[t] - np.einsum("hkv,hk->hv", against, k[t]))
        S = Sd + k[t][:, :, None] * d[:, None, :]
        out.append(np.einsum("hkv,hk->hv", S, q[t]))
    return np.stack(out), S


def _operands(rng, T, H, K, V):
    unit = lambda x: x / np.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    f32 = lambda x: jnp.asarray(x, jnp.float32)  # noqa: E731
    return (f32(unit(rng.normal(size=(T, H, K))) * K ** -0.5),
            f32(unit(rng.normal(size=(T, H, K)))),
            f32(rng.normal(size=(T, H, V))),
            f32(-rng.uniform(0.001, 0.3, size=(T, H))),
            f32(rng.uniform(0.05, 2.0, size=(T, H))))


@pytest.mark.parametrize("T, H, K, V, block", [(24, 3, 12, 20, 8),
                                               (64, 4, 16, 64, 16),
                                               (8, 3, 12, 20, 8)])
def test_the_chunked_form_is_the_token_by_token_recurrence(T, H, K, V, block):
    """Steps up to 2 (an eigenvalue down to -1), a state to enter with,
    padding at the end that moves nothing."""
    rng = np.random.default_rng(T)
    q, k, v, g, b = _operands(rng, T, H, K, V)
    g, b = g.at[-3:].set(0.0), b.at[-3:].set(0.0)
    S0 = rng.normal(size=(H, K, V))
    want_o, want_S = _naive(q, k, v, g, b, S0)
    with jax.default_matmul_precision("highest"):
        o, S = delta_rule.delta_chunk_scan(
            q, k, v, g, b, jnp.moveaxis(jnp.asarray(S0, jnp.float32), 0, 1),
            block=block)
    np.testing.assert_allclose(o, want_o, atol=1e-5)
    np.testing.assert_allclose(jnp.moveaxis(S, 1, 0), want_S, atol=1e-5)
    _, before_padding = _naive(q[:-3], k[:-3], v[:-3], g[:-3], b[:-3], S0)
    np.testing.assert_allclose(want_S, before_padding, atol=1e-12)


@pytest.mark.parametrize("kernel", ["gather", "pallas"])
def test_the_decode_update_moves_the_live_slots_and_no_other(kernel):
    """Five rows, two of them on the dummy slot: each live row's slot
    advances by the three lines; the slots no row named are untouched."""
    H, K, V = 4, 16, 64
    assert delta_rule.decode_update_supported(H, K, V)
    assert not delta_rule.decode_update_supported(3, 12, 20)
    rng = np.random.default_rng(1)
    q, k, v, g, b = _operands(rng, 5, H, K, V)
    states = rng.normal(size=(6, H, K, V))
    slab = delta_rule.state_to_slab(jnp.asarray(states, jnp.float32))
    np.testing.assert_array_equal(delta_rule.slab_to_state(slab, H), states
                                  .astype(np.float32))
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


# the spec --------------------------------------------------------------------


def test_from_config_reads_the_olmo_hybrid_keys(model):
    cfg, spec, params = model
    assert [ly.attn for ly in spec.layers] == ["linear"] * 3 + ["full"]
    assert spec.delta == hybrid.DeltaSpec(heads=3, key_dim=12, value_dim=20,
                                          conv=4, chunk=8, neg_eigval=True)
    assert spec.has_state and not spec.has_window and spec.ssm is None
    assert spec.head_dim == 16 and spec.kv_heads == 3
    # a linear layer owns no page: the global class covers the full layer
    assert spec.page_values("full", PAGE) == PAGE * 2 * 3 * 16
    assert spec.state_slot_bytes() == 3 * 4 * (3 * 12 * 20 + 3 * 3 * 44)
    pages = init_kv_pages(params, 5, PAGE, spec, state_slots=3)
    assert [a.shape for a in pages["l0"]] == [(3, 12, 60), (3, 3, 132)]
    assert [a.shape for a in pages["l3"]] == [(5, PAGE, 48)] * 2
    assert set(params["l0"]) >= {"w_qkv", "w_ab", "w_g", "conv_w", "A_log",
                                 "dt_bias", "o_norm", "wo"}
    assert "wq" not in params["l0"] and "q_norm" in params["l3"]


@pytest.mark.parametrize("change, match", [
    ({"linear_value_head_dim": None}, "linear_value_head_dim"),
    ({"rope_parameters": {"rope_theta": 5e5}}, "rotary"),
    ({"linear_num_value_heads": 6}, "grouped"),
    ({"attention_bias": True}, "attention_bias")])
def test_from_config_refuses_what_the_family_does_not_build(change, match):
    cfg = tiny_cfg(**change)
    cfg = {k: v for k, v in cfg.items() if v is not None}
    with pytest.raises(ValueError, match=match):
        hybrid.ModelSpec.from_config(cfg)


def test_the_decay_spreads_and_the_step_passes_one(model):
    """The weights' laws let a check see the mixer: over a random prompt the
    decay lies in about 0.86-0.9995 and spreads, and the step passes 1 for a
    good share of tokens and heads."""
    cfg, spec, params = model
    x = params["emb"][_prompt(64)].astype(jnp.float32)
    lp = params["l0"]
    ab = x @ lp["w_ab"]
    a = np.exp(-np.exp(lp["A_log"]) * jax.nn.softplus(ab[:, :3]
                                                      + lp["dt_bias"]))
    b = 2 * jax.nn.sigmoid(ab[:, 3:])
    assert 0.8 < a.min() < a.max() < 0.9999
    assert 0.2 < float((b > 1).mean()) < 0.8


# programs against the reference ----------------------------------------------


@pytest.mark.parametrize("kernel, which", [("gather", "model"),
                                           ("pallas", "kernel_model")])
def test_chunked_prefill_then_decode_agree_with_the_reference(
        kernel, which, request):
    """A prompt of 37 tokens in chunks of 16 (the prompt ends 5 tokens into
    its third chunk, inside a block of 8), then 7 decode steps through the
    pages and the state slot, against the reference's one full pass with its
    token-by-token recurrence: float32, tightly."""
    cfg, spec, params = request.getfixturevalue(which)
    with jax.default_matmul_precision("highest"):
        toks, served, pages = _serve_one(spec, params, _prompt(37), 8, kernel)
        want = _ref_logits(params, cfg, toks, 37)
    assert np.abs(want).max() > 1.0
    np.testing.assert_allclose(served, want, atol=TIGHT)
    # the row's slot holds its state; the slots no row was given hold none
    state = np.asarray(pages["l0"][0])
    assert np.abs(state[2]).max() > 0 and not state[[1, 3]].any()


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
    and leaves snapshots of its state behind the chunks that end at 24, 32
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
    from that one's snapshot agrees with the reference's full pass, which
    never saw a cache."""
    cfg, spec, params = model
    with jax.default_matmul_precision("highest"):
        toks, served = _shared(spec, params, 32)
        want = _ref_logits(params, cfg, toks, 41)
    np.testing.assert_allclose(served, want, atol=TIGHT)


def _fresh_prefill(monkeypatch, target, name, flawed):
    """``lm_prefill_paged`` traced anew with ``target.name`` replaced."""
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


def _zero_state_arrays(spec, index: int):
    """Zero array ``index`` (0 the states, 1 the tails) of the linear
    layers."""
    def between(pages):
        return {f"l{i}": tuple(
            jnp.zeros_like(a) if ly.attn == "linear" and j == index else a
            for j, a in enumerate(pages[f"l{i}"]))
            for i, ly in enumerate(spec.layers)}
    return between


def _scan_as(decay_first: bool):
    """The chunked form's place taken by the token-by-token recurrence."""
    def scan(q, k, v, g, b, state, block, valid=None):
        def step(S, tok):
            q_t, k_t, v_t, g_t, b_t = tok
            Sd = S * jnp.exp(g_t)[None, :, None]
            against = Sd if decay_first else S
            d = b_t[:, None] * (v_t - jnp.einsum("khv,hk->hv", against, k_t))
            S = Sd + jnp.einsum("hk,hv->khv", k_t, d)
            return S, jnp.einsum("khv,hk->hv", S, q_t)
        S, o = jax.lax.scan(step, state, (q, k, v, g, b))
        return o, S
    return scan


def _operands_without(what: str):
    real = hybrid._delta_operands

    def flawed(ds, conv, cd):
        q, k, v = real(ds, conv, cd)
        if what == "query_scale":
            return q * ds.key_dim ** 0.5, k, v
        act = jax.nn.silu(conv)
        H, K = ds.heads, ds.key_dim
        raw = act[:, :2 * H * K].reshape(-1, 2, H, K)
        return raw[:, 0] * K ** -0.5, raw[:, 1], v
    return flawed


FLAWS = ["mixer_dropped", "state_not_carried", "slot_not_zeroed",
         "tail_lost_at_a_chunk_edge", "padding_advances_the_state",
         "step_not_doubled", "step_against_the_undecayed_state",
         "no_l2_norm", "no_query_scale", "no_qk_norm"]


def test_the_token_by_token_stand_in_is_sound(model, monkeypatch):
    """The flawed recurrence below differs from the sound one in ONE line:
    with the line right it agrees with the reference as the chunked form
    does."""
    cfg, spec, params = model
    prefill = _fresh_prefill(monkeypatch, delta_rule, "delta_chunk_scan",
                             _scan_as(decay_first=True))
    with jax.default_matmul_precision("highest"):
        toks, served, _ = _serve_one(spec, params, _prompt(37), 8,
                                     prefill=prefill)
        want = _ref_logits(params, cfg, toks, 37)
    np.testing.assert_allclose(served, want, atol=TIGHT)


@pytest.mark.parametrize("flaw", FLAWS)
def test_each_flaw_fails_the_comparison(flaw, model, monkeypatch):
    """What the comparison must catch: every piece of the mixer's
    arithmetic and of the state's way through the cache, left out one at a
    time, moves a logit by far more than the agreement above allows."""
    cfg, spec, params = model
    kw = {}
    if flaw == "mixer_dropped":
        real = hybrid._delta_mixer
        kw["prefill"] = _fresh_prefill(
            monkeypatch, hybrid, "_delta_mixer",
            lambda *a: jnp.zeros_like(real(*a)))
    elif flaw == "state_not_carried":
        kw["between"] = _zero_state_arrays(spec, 0)
    elif flaw == "tail_lost_at_a_chunk_edge":
        kw["between"] = _zero_state_arrays(spec, 1)
    elif flaw == "slot_not_zeroed":
        with jax.default_matmul_precision("highest"):
            kw["pages"] = _serve_one(spec, params, _prompt(40, seed=5), 6)[2]
        kw["prefill"] = _fresh_prefill(
            monkeypatch, hybrid, "_enter_state",
            lambda fresh, state, tail: (state, tail))
    elif flaw == "padding_advances_the_state":
        real = delta_rule.delta_chunk_scan
        kw["prefill"] = _fresh_prefill(
            monkeypatch, delta_rule, "delta_chunk_scan",
            lambda q, k, v, g, b, *a, **k_: real(
                q, k, v, jnp.where(g == 0, -0.05, g),
                jnp.where(b == 0, 1.0, b), *a, **k_))
    elif flaw == "step_not_doubled":
        spec = dataclasses.replace(spec, delta=dataclasses.replace(
            spec.delta, neg_eigval=False))
    elif flaw == "step_against_the_undecayed_state":
        kw["prefill"] = _fresh_prefill(
            monkeypatch, delta_rule, "delta_chunk_scan",
            _scan_as(decay_first=False))
    elif flaw in ("no_l2_norm", "no_query_scale"):
        kw["prefill"] = _fresh_prefill(
            monkeypatch, hybrid, "_delta_operands",
            _operands_without(flaw[3:]))
    elif flaw == "no_qk_norm":
        real = hybrid._rmsnorm
        kw["prefill"] = _fresh_prefill(   # 48 columns: only q_norm, k_norm
            monkeypatch, hybrid, "_rmsnorm",
            lambda x, g, eps: x if g.shape[0] == 48 else real(x, g, eps))
    with jax.default_matmul_precision("highest"):
        toks, served, _ = _serve_one(spec, params, _prompt(37), 8, **kw)
        want = _ref_logits(params, cfg, toks, 37)
    assert np.abs(served - want).max() > CAUGHT, flaw


@pytest.mark.parametrize("flaw, kw", [
    ("entered_from_zeros", {"boundary": 32, "enter": "zeros"}),
    ("snapshot_a_page_early", {"boundary": 24}),
    ("snapshot_a_page_late", {"boundary": 40})])
def test_a_hit_entered_from_the_wrong_state_fails_the_comparison(
        flaw, kw, model):
    cfg, spec, params = model
    with jax.default_matmul_precision("highest"):
        toks, served = _shared(spec, params, **kw)
        want = _ref_logits(params, cfg, toks, 41)
    assert np.abs(served - want).max() > CAUGHT, flaw


# the pool --------------------------------------------------------------------


def _pool(model, **kw):
    cfg, spec, params = model
    kw.setdefault("state_slots", 4)
    kw.setdefault("snapshot_slots", 2)
    return PagedKVPool(params, spec, 24, PAGE, **kw)


class _Row:
    """What :meth:`PagedKVPool.audit` reads of a group, for one row."""

    def __init__(self, pages, state_id, snapshots):
        self.row_pages, self.window_row_pages = [pages], [None]
        self.state_ids, self.snapshots = np.asarray([state_id]), [snapshots]

    def occupied_slots(self):
        return [0]


def test_the_pool_takes_publishes_hits_and_evicts_snapshots(model):
    """A snapshot's life, host side: due at the deepest shareable boundary
    only; owned by the row, then by the cache entry of its boundary; a hit
    stops AT the boundary that has one, however deep the pages match; the
    coldest goes first, one that was hit after one that never was."""
    pool = _pool(model)
    assert pool.prefix_cache_enabled and pool.snapshot_slots == 2
    assert pool.pages["l0"][0].shape[0] == 4 + 2    # rows' slots, snapshots'
    # a prompt of 45 tokens shares up to 40: chunks of 16 end at 16, 32, 48
    assert [pool.snapshot_due(e, 16, 45) for e in (16, 32, 48)] \
        == [False, True, False]
    # ... and the boundary up to which its pages were found cached with no
    # snapshot to enter from: others share that prefix
    assert [pool.snapshot_due(e, 16, 45, 16) for e in (16, 32, 48)] \
        == [True, True, False]
    # the page that holds the prompt's last token is never shared
    assert pool.snapshot_due(16, 16, 17) and not pool.snapshot_due(16, 16, 16)
    a = _prompt(45, seed=1)
    pages = pool.alloc(6)
    state = pool.alloc_state()
    sid = pool.alloc_snapshot()
    assert sid == 4 and pool.snapshots_held() == 0
    row = _Row(pages, state, {32: sid})
    assert pool.audit([row])["ok"]                  # held by the row
    taken = {32: sid}
    assert pool.insert_prefix(a, pages, taken) == 5 and taken == {}
    row.snapshots = [{}]
    audit = pool.audit([row])
    assert audit["ok"] and audit["snapshots_held"] == 1, audit
    # a request that shares 40 tokens' pages is handed the 32 that have the
    # state at their end, and that snapshot
    b = np.concatenate([a[:40], _prompt(9, seed=2)])
    assert pool.match_prefix_state(b) == (32, pages[:4], sid, 40)
    pool.release(pages[:4])
    # one that shares only 24 tokens finds pages and no snapshot: a miss
    c = np.concatenate([a[:24], _prompt(30, seed=3)])
    assert pool.match_prefix_state(c) == (0, [], 0, 24)
    assert (pool.hits, pool.misses) == (1, 1)
    # a second snapshot (never hit) is colder than the one that was hit
    other = _prompt(20, seed=4)
    opages = pool.alloc(3)
    s2 = pool.alloc_snapshot()
    assert s2 == 5
    pool.insert_prefix(other, opages, {16: s2})
    assert pool.alloc_snapshot() == s2 and pool.snapshot_evictions == 1
    assert pool.match_prefix_state(other) == (0, [], 0, 16)  # a shorter hit
    assert pool.match_prefix_state(b)[2] == sid
    pool.release(pages[:4])
    # a duplicate of a boundary that has one goes back to the free list
    pool.insert_prefix(a, pages, {32: s2})
    assert pool._snapfree == [s2] and pool.snapshots_held() == 1
    # the entry's eviction takes its snapshot along
    pool.release(pages)
    pool.release(opages)
    pool.release_state(state)
    while pool._evict_one():
        pass
    audit = pool.audit([])
    assert audit["ok"] and audit["snapshots_held"] == 0 \
        and audit["used"] == 0, audit
    assert sorted(pool._snapfree) == [4, 5]


def test_snapshots_never_hit_go_oldest_first_then_the_least_recently_hit(
        model):
    """Three slots: two prompts' own boundaries (never hit), then a prefix
    that is hit. Room is made from the OLDEST never-hit one, then the
    other, and only then from the one rows enter from: a new prefix's
    snapshot is not the first to go."""
    pool = _pool(model, snapshot_slots=3)
    prompts = [_prompt(20, seed=s) for s in (1, 2, 3)]
    sids = []
    for p in prompts:
        pages = pool.alloc(3)
        sids.append(pool.alloc_snapshot())
        pool.insert_prefix(p, pages, {16: sids[-1]})
        pool.release(pages)
    third = np.concatenate([prompts[2][:16], _prompt(9, seed=9)])
    shared_len, pages, snap, _ = pool.match_prefix_state(third)
    assert (shared_len, snap) == (16, sids[2])
    pool.release(pages)
    assert [pool.alloc_snapshot() for _ in range(3)] == sids
    assert pool.snapshot_evictions == 3 and pool.snapshots_held() == 0
    assert not pool._snap_hit


def test_the_audit_sees_a_leaked_and_a_doubly_owned_snapshot(model):
    pool = _pool(model)
    pages, state = pool.alloc(3), pool.alloc_state()
    sid = pool.alloc_snapshot()
    leaked = pool.audit([_Row(pages, state, {})])
    assert not leaked["ok"] and "leaked" in " ".join(leaked["errors"])
    pool.insert_prefix(_prompt(20), pages, {16: sid})
    twice = pool.audit([_Row(pages, state, {16: sid})])
    assert not twice["ok"] and "2 owners" in " ".join(twice["errors"])


def test_a_pool_without_snapshot_slots_shares_nothing(model):
    """The prefix cache rests on snapshots for a model with state: without a
    slot for one it is off, as it is for a window."""
    pool = _pool(model, snapshot_slots=0)
    assert not pool.prefix_cache_enabled
    assert _pool(model, prefix_cache=False).snapshot_slots == 0
    for entry, args in ((pool.export_rows, ([],)), (pool.import_rows, (b"",)),
                        (pool.export_prefixes, (1,)),
                        (pool.import_prefixes, (b"",))):
        with pytest.raises(NotImplementedError, match="recurrent"):
            entry(*args)


# the engine ------------------------------------------------------------------

BUCKETS = ((48, 8), (64, 16))
#: sessions: three histories of 32 tokens (4 pages), each resent with a new
#: turn; (history, turn tokens, steps)
SESSIONS = ((0, 5, 4), (1, 9, 6), (2, 13, 8), (0, 11, 5), (1, 3, 3),
            (0, 20, 7), (2, 7, 16), (1, 30, 4), (0, 2, 9), (2, 25, 6),
            (1, 14, 5), (0, 9, 3))


def _engine(spec, params, **kw):
    kw.setdefault("buckets", BUCKETS)
    kw.setdefault("max_batch", 3)
    kw.setdefault("page_len", PAGE)
    kw.setdefault("prefill_chunk", CHUNK)
    kw.setdefault("num_pages", 96)
    kw.setdefault("prefix_cache", True)
    return ServeEngine(params, spec, **kw)


def _sessions(schedule=SESSIONS, length=32):
    histories = [_prompt(length, seed=100 + h) for h in range(3)]
    return [Request(prompt=np.concatenate([histories[h],
                                           _prompt(turn, seed=i)]),
                    steps=steps, temperature=0.0)
            for i, (h, turn, steps) in enumerate(schedule)]


def _capture(eng, reqs, where):
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
    return results, engine_spans.load(find_xplane(where))["spans"]


@pytest.fixture(scope="module")
def served(model, tmp_path_factory):
    """The sessions through a ServeEngine with the prefix cache on, under a
    profiler capture: results, spans, and the pool's audit afterwards."""
    cfg, spec, params = model
    eng = _engine(spec, params, start=False)
    eng.warmup()
    reqs = _sessions()
    results, spans = _capture(eng, reqs,
                              str(tmp_path_factory.mktemp("capture")))
    audit = eng.kvpool_audit()
    eng.close()
    return {"requests": reqs, "results": results, "audit": audit,
            "spans": spans}


def test_the_engine_serves_the_reference_through_snapshots(served, model):
    """Twelve turns of three sessions over two buckets of three slots under
    the pipelined decode: every request ok and every served token the
    reference's first choice (float32, greedy), though most rows entered
    from another row's snapshot and never prefilled their history."""
    cfg, spec, params = model
    assert [r.status for r in served["results"]] == ["ok"] * len(SESSIONS)
    with jax.default_matmul_precision("highest"):
        for req, res in zip(served["requests"], served["results"]):
            n = len(req.prompt)
            assert len(res.tokens) == n + req.steps
            want = _ref_logits_long(params, cfg, res.tokens, n)
            gap = want.max(-1) - want[np.arange(req.steps), res.tokens[n:]]
            assert gap.max() < 1e-4, (n, gap)
    shared = [r.metrics["shared_pages"] for r in served["results"]]
    assert sum(s == 4 for s in shared) >= 6 and set(shared) <= {0, 4}, shared


def _ref_logits_long(params, cfg, toks, n_prompt):
    return np.asarray(reference.logits_at(
        params, cfg, toks[:-1], np.arange(n_prompt - 1, len(toks) - 1), 96))


def test_snapshots_and_slots_are_freed_or_cached_and_audited(served):
    audit = served["audit"]
    assert audit["ok"], audit["errors"]
    assert audit["state_used"] == 0
    # what is still used is the cache's: pages, a snapshot a history, and
    # those of the longer turns' own deepest boundaries (48)
    assert audit["used"] == audit["cached"] > 0
    assert 3 <= audit["snapshots_held"] <= audit["snapshots_taken"]
    assert audit["snapshot_total"] == len(BUCKETS) * 3


def test_the_spans_carry_the_snapshot_fields(served, model):
    cfg, spec, params = model
    by = {}
    for s in served["spans"]:
        by.setdefault(s.name, []).append(s.fields)
    iters = [f for f in by["serve.iter"] if "snapshot_slots" in f]
    assert iters and all(f["snapshot_slots"] == 6 for f in iters)
    assert all(0 <= f["snapshots_held"] <= 6 for f in iters)
    assert max(f["snapshots_held"] for f in iters) >= 3
    assert all(f["state_rows"] == f["resident_rows"] for f in iters)
    admits = by["serve.admit"]
    assert len(admits) == len(SESSIONS)
    # a hit never passes the boundary its state was copied from
    assert all(f["snapshot_tokens"] == f["shared_tokens"] for f in admits)
    assert {f["snapshot_tokens"] for f in admits} == {0, 32}
    chunks = by["serve.prefill.dispatch"]
    assert all(f["delta_tokens"] == f["tokens"] and "ssm_tokens" not in f
               for f in chunks)
    # a hit starts at its snapshot's boundary; a miss at 0
    hits = sum(f["snapshot_tokens"] == 32 for f in admits)
    assert sum(f["start"] == 32 and f["tokens"] > 0 for f in chunks) >= hits
    prefilled = sum(f["delta_tokens"] for f in chunks)
    prompts = sum(f["prompt_tokens"] for f in admits)
    assert prefilled == prompts - 32 * hits
    assert sum(f.get("snapshots", 0) for f in chunks) >= 3
    # snapshots are taken behind a chunk that ends on a shareable boundary
    assert {f["start"] + f["width"] for f in chunks
            if f.get("snapshots")} <= {32, 48}
    calls = [f for f in by["serve.decode.dispatch"] if f.get("rows")]
    assert calls and all(f["state_rows"] == f["rows"] for f in calls)


def test_snapshots_are_evicted_under_pressure_and_a_full_pool_skips(model):
    """One snapshot slot for three histories: each new history's snapshot
    evicts the last one's, every request is served all the same, and the
    audit stays clean; with rows in flight holding the only slot a snapshot
    is skipped, not waited for."""
    cfg, spec, params = model
    eng = _engine(spec, params, snapshot_slots=1, start=False)
    try:
        handles = eng.submit_many(_sessions())
        eng.start()
        results = [h.result(timeout=300) for h in handles]
        assert [r.status for r in results] == ["ok"] * len(SESSIONS)
        audit = eng.kvpool_audit()
        assert audit["ok"], audit["errors"]
        assert audit["snapshots_held"] <= 1
        assert audit["snapshot_evictions"] >= 2
        with jax.default_matmul_precision("highest"):
            req, res = _sessions()[-1], results[-1]
            n = len(req.prompt)
            want = _ref_logits_long(params, cfg, res.tokens, n)
            gap = want.max(-1) - want[np.arange(req.steps), res.tokens[n:]]
            assert gap.max() < 1e-4
    finally:
        eng.close()


def test_pages_evicted_under_pressure_take_their_snapshots_along(model):
    """A pool too small to cache three histories beside the live rows:
    allocation evicts cached chains, their snapshots go back with them, and
    nothing leaks."""
    cfg, spec, params = model
    eng = _engine(spec, params, buckets=(BUCKETS[1],), max_batch=2,
                  num_pages=2 * 10 + 6, start=False)
    try:
        handles = eng.submit_many(_sessions())
        eng.start()
        results = [h.result(timeout=300) for h in handles]
        assert [r.status for r in results] == ["ok"] * len(SESSIONS)
        audit = eng.kvpool_audit()
        assert audit["ok"], audit["errors"]
        assert audit["evictions"] > 0
        assert audit["snapshots_held"] <= audit["cached"]
    finally:
        eng.close()


def test_admission_charges_the_state_slot_and_migration_is_refused(model):
    cfg, spec, params = model
    eng = _engine(spec, params, start=False)
    try:
        prog = eng._programs["lm"]
        req = Request(prompt=_prompt(20), steps=5)
        pages = -(-(20 + 5 - 1) // PAGE)
        assert prog.admission_cost(req, (48, 8)) \
            == pages * eng._page_bytes + spec.state_slot_bytes()
        assert eng._prefix_cache and eng._snapshot_slots == 6
        for entry, args in ((eng.freeze_rows, ()),
                            (eng.adopt_rows, ({"entries": {1: None},
                                               "blob": b"x"},)),
                            (eng.export_prefixes, (4,)),
                            (eng.import_prefixes, (b"x",))):
            with pytest.raises(MigrationError, match="recurrent"):
                entry(*args)
    finally:
        eng.close()
    off = _engine(spec, params, prefix_cache=None, start=False)
    assert not off._prefix_cache and off._snapshot_slots == 0
    off.close()
    with pytest.raises(ValueError, match="linear_chunk_size"):
        _engine(hybrid.ModelSpec.from_config(tiny_cfg(linear_chunk_size=6)),
                params, start=False)
