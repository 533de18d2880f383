"""Latent attention (models/hybrid.py, the ``latent`` layer kind) on the CPU
at a small size: hidden 64, 4 heads of 16 + 8 query columns and 12 value
columns, latents of 32 (queries) and 16 (cache), 3 layers (one dense, two
expert layers of 16 experts top-4 under sigmoid scoring), YaRN from an
``original_max`` of 16 so that the position-dependent query scale bites
inside a 60-token sequence, vocabulary 96, seeded float32 weights.
Everything is compared with ``benchmarks/reference/serve_mistral4.py`` (the
equations, unabsorbed, in plain float32) in LOGITS, not tokens."""

import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import serve_mistral4 as reference
from marlin_tpu.models import hybrid
from marlin_tpu.models.moe import moe_experts_ffn
from marlin_tpu.models.planner import kv_page_bytes, request_pages
from marlin_tpu.models.transformer import (init_kv_pages, lm_decode_paged,
                                           lm_prefill_paged)
from marlin_tpu.ops.paged_attention import paged_decode_attention_latent
from marlin_tpu.serving import Request, ServeEngine
from marlin_tpu.serving.kvpool import (MigrationCorruptError, PagedGroup,
                                       PagedKVPool)
from marlin_tpu.utils import faults
from marlin_tpu.utils.faults import DelayFault, Schedule

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PAGE, CHUNK = 8, 16
NO_RING = np.zeros(0, np.int32)


def tiny_cfg(**over):
    cfg = {
        "hidden_size": 64, "num_attention_heads": 4,
        "num_key_value_heads": 4, "head_dim": 24, "num_hidden_layers": 3,
        "q_lora_rank": 32, "kv_lora_rank": 16, "qk_nope_head_dim": 16,
        "qk_rope_head_dim": 8, "qk_head_dim": 24, "v_head_dim": 12,
        "first_k_dense_replace": 1, "intermediate_size": 128,
        "moe_intermediate_size": 32, "n_routed_experts": 16,
        "n_shared_experts": 1, "num_experts_per_tok": 4, "n_group": 1,
        "topk_group": 1, "norm_topk_prob": True,
        "routed_scaling_factor": 1.0, "vocab_size": 96,
        "rms_norm_eps": 1e-6, "rope_interleave": True,
        "sliding_window": None,
        "rope_parameters": {
            "rope_theta": 10000, "rope_type": "yarn", "type": "yarn",
            "factor": 128, "original_max_position_embeddings": 16,
            "beta_fast": 32, "beta_slow": 1, "mscale": 1,
            "mscale_all_dim": 1, "llama_4_scaling_beta": 0.1},
        "param_dtype": "float32", "compute_dtype": "float32"}
    cfg.update(over)
    return cfg


@pytest.fixture(scope="module")
def model():
    cfg = tiny_cfg()
    spec = hybrid.ModelSpec.from_config(cfg)
    return cfg, spec, hybrid.init_params(spec, jax.random.key(0))


def _table(first_page: int, n_pages: int):
    t = np.zeros(n_pages + CHUNK // PAGE, np.int32)
    t[:n_pages] = np.arange(first_page, first_page + n_pages)
    return t


def _prefill(spec, params, pages, table, prompt, start=0):
    """Chunked paged prefill of ``prompt`` from position ``start`` on (what
    lies before it is in the pages of ``table`` already)."""
    n = len(prompt)
    padded = np.zeros(-(-n // CHUNK) * CHUNK + CHUNK, np.int32)
    padded[:n] = prompt
    for cs in range(start, n, CHUNK):
        pages, first, _, logits = lm_prefill_paged(
            params, pages, (table, NO_RING), padded[cs:cs + CHUNK], cs, n,
            heads=spec, page_len=PAGE)
    return pages, int(first), np.asarray(logits)


def _serve_one(spec, params, prompt, steps, kernel, pages=None, table=None,
               start=0):
    """Prefill, then decode through the latent page pool in a bucket of three
    rows (the middle one live); the tokens and the float32 logits every
    served token was picked from."""
    n = len(prompt)
    need = -(-(n + steps) // PAGE)
    if pages is None:
        pages = init_kv_pages(params, 40, PAGE, spec)
        table = _table(1, need)
    pages, first, logits = _prefill(spec, params, pages, table, prompt, start)
    toks, served = list(prompt) + [first], [logits]
    B = 3
    gt = np.zeros((B, need), np.int32)
    gt[1] = table[:need]
    zeros = np.zeros(B)
    for t in range(steps - 1):
        pages, nxt, _, logits = lm_decode_paged(
            params, pages, (gt, np.zeros((B, 0), np.int32)),
            np.array([0, n + t, 0]), np.array([0, toks[-1], 0]), zeros, zeros,
            zeros, np.ones(B), zeros, heads=spec, page_len=PAGE,
            kernel=kernel)
        toks.append(int(nxt[1]))
        served.append(np.asarray(logits[1]))
    return np.asarray(toks), np.stack(served), pages


def _ref_logits(params, cfg, toks, n_prompt, **kw):
    return np.asarray(reference.logits_at(
        params, cfg, toks[:-1], np.arange(n_prompt - 1, len(toks) - 1), 96,
        **kw))


# the layer and its two forms -------------------------------------------------


def test_the_latent_layer_matches_the_references_unabsorbed_layer(model):
    """``layer_forward`` over a whole sequence, its attention the prefill
    form over the sequence's own entries, against the reference's layer: an
    expert layer (l1) and the dense one (l0). 2e-5 on values of size ~3:
    both float32, the sums in another order (blocks of 16 keys with a
    running softmax against one softmax; grouped against per-expert
    matmuls)."""
    cfg, spec, params = model
    T = 40
    x = jax.random.normal(jax.random.key(2), (T, 64), jnp.float32)
    pos = jnp.arange(T)

    def attend(q_nope, q_pe, entry, scale, wkv_b):
        return hybrid._attend_latent_blocks(q_nope, q_pe, entry, wkv_b,
                                            scale, pos, spec.latent, block=16)

    for i in (0, 1):
        got, _ = hybrid.layer_forward(spec, i, params[f"l{i}"], x, pos,
                                      jnp.ones((T,), bool), attend)
        want = reference.layer(x, params[f"l{i}"], dense=i < 1,
                               dims=reference._dims(cfg))
        assert np.abs(np.asarray(want) - np.asarray(x)).max() > 1.0
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5, rtol=0)


@pytest.mark.parametrize("kernel", ["gather", "pallas"])
def test_the_absorbed_and_the_unabsorbed_form_agree(model, kernel):
    """Decode's form (the query through the key half of ``W_kvb``, the page
    itself as key and value, the value half afterwards; kernel or gather)
    and prefill's (every latent up-projected, attention per head) give the
    same outputs for the same queries and entries: 1e-5, float32 sums in
    another order."""
    _, spec, _ = model
    la, H = spec.latent, 4
    ks = jax.random.split(jax.random.key(7), 5)
    lengths = np.array([29, 8, 1], np.int32)
    tables = np.array([[1, 2, 3, 4], [5, 0, 0, 0], [0, 0, 0, 0]], np.int32)
    slab = jax.random.normal(ks[0], (6, PAGE, la.entry_width))
    slab = slab.at[..., la.entry_dim:].set(0.0)
    q_nope = jax.random.normal(ks[1], (3, H, la.nope_dim))
    q_pe = jax.random.normal(ks[2], (3, H, la.rope_dim))
    wkv_b = jax.random.normal(ks[3], (la.kv_rank, H,
                                      la.nope_dim + la.v_dim)) / 4
    scale = la.query_scale(jnp.asarray(lengths - 1))
    q = hybrid._absorbed_query(q_nope, q_pe, scale, wkv_b, la)
    if kernel == "pallas":
        ot = paged_decode_attention_latent(q, slab, tables, lengths,
                                           value_dim=la.kv_rank)
    else:
        ot = hybrid._attend_latent_gather(q, slab, tables, lengths,
                                          la.kv_rank, PAGE)
    absorbed = jnp.einsum("bhc,chv->bhv", ot, wkv_b[..., la.nope_dim:])
    for b in range(3):
        ctx = slab[tables[b]].reshape(-1, la.entry_width)
        want = hybrid._attend_latent_blocks(
            q_nope[b:b + 1], q_pe[b:b + 1], ctx, wkv_b, scale[b:b + 1],
            jnp.asarray(lengths[b:b + 1] - 1), la, block=16)
        np.testing.assert_allclose(np.asarray(absorbed[b]),
                                   np.asarray(want[0]), atol=1e-5, rtol=0)


# more pages than the kernel holds slots for, so that a slot is used again
_WALK_WIDTH = 9
_WALK_CASES = {
    "one_token": [1],
    "a_page_less_one": [PAGE - 1],
    "a_page": [PAGE],
    "a_page_and_one": [PAGE + 1],
    "the_whole_table": [_WALK_WIDTH * PAGE],
    "a_dummy_row": [0],
    "all_of_them_in_one_call": [1, PAGE + 1, _WALK_WIDTH * PAGE, 0, PAGE,
                                3 * PAGE - 1, 7 * PAGE + 3, PAGE - 1],
}


@pytest.mark.parametrize("case", list(_WALK_CASES))
def test_the_kernel_walks_a_rows_live_pages(case):
    """The kernel against the gathered formulation, over the lengths at
    which its walk changes shape: no loop iteration at all (one page), the
    mask on or off in the last page, the first page ahead, a table longer
    than the slots (a slot holds a second page while the copies ahead are
    in flight), a dummy row (all-zero table, length 0: one masked-harmless
    position of page 0), and rows of all kinds in one call. 1e-5: float32
    sums in another order."""
    lengths = np.array(_WALK_CASES[case], np.int32)
    B = len(lengths)
    key = jax.random.key(B * 100 + int(lengths[0]))
    slab = jax.random.normal(key, (B * _WALK_WIDTH + 1, PAGE, 128))
    q = jax.random.normal(jax.random.fold_in(key, 1), (B, 4, 128)) / 8
    tables = 1 + np.random.default_rng(3).permutation(
        B * _WALK_WIDTH).reshape(B, _WALK_WIDTH).astype(np.int32)
    tables[lengths == 0] = 0
    got = paged_decode_attention_latent(q, slab, tables, lengths, 16)
    want = hybrid._attend_latent_gather(q, slab, jnp.asarray(tables),
                                        jnp.maximum(lengths, 1), 16, PAGE)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5,
                               rtol=0)


def test_the_kernel_reads_nothing_past_a_rows_length_and_no_pad_column():
    """A NaN in a page past the row's length (one the table names and the
    length does not reach, one a shorter row names too), in a table entry
    past it, or (harmless by construction) nowhere in the value's columns:
    the output is that of the clean slab. The pad columns are zeros in the
    slab and in the query, so they add nothing to a score."""
    key = jax.random.key(9)
    q = jax.random.normal(key, (2, 4, 128)).at[..., 24:].set(0.0)
    slab = jax.random.normal(jax.random.fold_in(key, 1), (5, PAGE, 128))
    tables = np.array([[1, 2, 3], [4, 3, 0]], np.int32)
    lengths = np.array([11, 3], np.int32)
    clean = paged_decode_attention_latent(q, slab, tables, lengths, 16)
    dirty = slab.at[3].set(jnp.nan).at[0].set(jnp.nan)
    got = paged_decode_attention_latent(q, dirty, tables, lengths, 16)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(clean))
    with pytest.raises(ValueError, match="value_dim"):
        paged_decode_attention_latent(q, slab, tables, lengths, 129)
    # on the chip a page is copied out of the slab in whole lane tiles
    with pytest.raises(ValueError, match="lane tiles"):
        paged_decode_attention_latent(q[..., :24], slab[..., :24], tables,
                                      lengths, 16, interpret=False)


# through the cache -----------------------------------------------------------


@pytest.mark.parametrize("kernel", ["gather", "pallas"])
def test_paged_prefill_then_decode_match_the_reference_logits(model, kernel):
    """37 prompt tokens (three chunks of 16, five pages of 8) and 22 served
    tokens, past position 16 and 32 where ``tau`` steps, across page and
    chunk boundaries. Tolerance 1e-4 on logits of size ~3: both sides are
    float32 and only the order of the sums differs (absorbed online softmax
    or blocked prefill against one dense unabsorbed softmax; grouped against
    per-expert matmuls); the measured difference is 8e-6."""
    cfg, spec, params = model
    prompt = np.random.default_rng(0).integers(0, 96, 37).astype(np.int32)
    toks, got, _ = _serve_one(spec, params, prompt, 22, kernel)
    want = _ref_logits(params, cfg, toks, 37)
    assert np.abs(want).max() > 1.0
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=0)


@pytest.mark.parametrize("flaw", reference.FLAWS)
def test_the_comparison_sees_each_missing_piece(model, flaw):
    """A reference with one piece of the mathematics wrong (no ``m^2`` on the
    softmax scale, no ``tau`` past ``original_max``, rotate-half for the
    interleaved rotary pairs, softmax for sigmoid scoring, the value read
    from the entry's rotary columns) is far from what the program serves: at
    least 0.5 in logits where the sound one is within 1e-4."""
    cfg, spec, params = model
    prompt = np.random.default_rng(1).integers(0, 96, 29).astype(np.int32)
    toks, got, _ = _serve_one(spec, params, prompt, 12, "gather")
    assert np.abs(got - _ref_logits(params, cfg, toks, 29)).max() < 1e-4
    assert np.abs(got - _ref_logits(params, cfg, toks, 29,
                                    flaw=flaw)).max() > 0.5


@pytest.mark.parametrize("kernel", ["gather", "pallas"])
def test_a_request_that_hits_anothers_cached_pages_gets_its_own_logits(
        model, kernel):
    """Request A prefills a 32-token document (four whole pages) and a
    question. Request B's table begins with A's four document pages; its
    prefill STARTS at position 32, inside the prompt, and attends latent
    pages that another request wrote. Its logits (first token and decode)
    are those of the reference's full forward pass over B's whole prompt,
    to the tolerance of a request that prefills alone."""
    cfg, spec, params = model
    rng = np.random.default_rng(4)
    doc = rng.integers(0, 96, 32).astype(np.int32)
    a = np.concatenate([doc, rng.integers(0, 96, 7).astype(np.int32)])
    b = np.concatenate([doc, rng.integers(0, 96, 13).astype(np.int32)])
    _, _, pages = _serve_one(spec, params, a, 4, kernel)     # pages 1-6
    need = -(-(len(b) + 10) // PAGE)
    table = np.zeros(need + CHUNK // PAGE, np.int32)
    table[:4] = [1, 2, 3, 4]                                 # A's document
    table[4:need] = np.arange(10, 10 + need - 4)
    toks, got, _ = _serve_one(spec, params, b, 10, kernel, pages=pages,
                              table=table, start=32)
    np.testing.assert_allclose(got, _ref_logits(params, cfg, toks, len(b)),
                               atol=1e-4, rtol=0)
    alone, want, _ = _serve_one(spec, params, b, 10, kernel)
    np.testing.assert_array_equal(toks, alone)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)


def test_a_chunk_of_lane_tiles_prefills_through_the_flash_kernel(model):
    """A chunk of 128 tokens takes prefill's flash path (the Pallas panel
    kernel, interpreted here; the queries' position-dependent scale folded
    into them, the keys one product of the stored entry, the value padded to
    the key's head size). First the two formulations on the same queries and
    entries, a chunk that starts at position 256 of 400 gathered positions
    (1e-5, float32 sums in another order); then 300 prompt tokens in three
    such chunks through the program against the reference's full forward
    pass (1e-4 as above; measured 1e-5)."""
    cfg, spec, params = model
    la, H, T = spec.latent, 4, 128
    ks = jax.random.split(jax.random.key(12), 4)
    ctx = jax.random.normal(ks[0], (400, la.entry_width))
    ctx = ctx.at[:, la.entry_dim:].set(0.0)
    q_nope = jax.random.normal(ks[1], (T, H, la.nope_dim))
    q_pe = jax.random.normal(ks[2], (T, H, la.rope_dim))
    wkv_b = jax.random.normal(ks[3], (la.kv_rank, H,
                                      la.nope_dim + la.v_dim)) / 4
    q_pos = 256 + jnp.arange(T)
    scale = la.query_scale(q_pos)
    got = hybrid._attend_latent_flash(q_nope, q_pe, ctx, wkv_b, scale, 256,
                                      la)
    want = hybrid._attend_latent_blocks(q_nope, q_pe, ctx, wkv_b, scale,
                                        q_pos, la, block=64)
    assert got.shape == (T, H, la.v_dim)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5,
                               rtol=0)
    assert hybrid.flash_table_pages(74, 256) == 76      # 19456 positions
    assert hybrid.flash_table_pages(54, 8) == 54        # under one block

    n, chunk = 300, 128
    prompt = np.random.default_rng(5).integers(0, 96, n).astype(np.int32)
    need = -(-n // PAGE)
    table = np.zeros(need + chunk // PAGE, np.int32)
    table[:need] = np.arange(1, need + 1)
    padded = np.zeros(3 * chunk, np.int32)
    padded[:n] = prompt
    pages = init_kv_pages(params, need + 2, PAGE, spec)
    for cs in range(0, 3 * chunk, chunk):
        pages, first, _, logits = lm_prefill_paged(
            params, pages, (table, NO_RING), padded[cs:cs + chunk], cs, n,
            heads=spec, page_len=PAGE)
    want = np.asarray(reference.logits_at(params, cfg, prompt, [n - 1], 384))
    np.testing.assert_allclose(np.asarray(logits), want[0], atol=1e-4, rtol=0)


# the expert layer under sigmoid scoring --------------------------------------


def _expert_layer_by_hand(mp, h, top_k):
    """The uncut expert layer, token by token, pick by pick: sigmoid scores,
    the picks by score + bias, the weights the picks' scores renormalised."""
    h = np.asarray(h, np.float64)
    f = lambda a: np.asarray(a, np.float64)  # noqa: E731
    silu = lambda x: x / (1 + np.exp(-x))  # noqa: E731
    sc = 1 / (1 + np.exp(-(h @ f(mp["router"]))))
    out = (silu(h @ f(mp["s_gate"])) * (h @ f(mp["s_up"]))) @ f(mp["s_down"])
    for t in range(h.shape[0]):
        picks = np.argsort(-(sc[t] + f(mp["e_bias"])), kind="stable")[:top_k]
        for i in picks:
            e = (silu(h[t] @ f(mp["e_gate"][i])) * (h[t] @ f(mp["e_up"][i]))
                 ) @ f(mp["e_down"][i])
            out[t] += sc[t, i] / sc[t, picks].sum() * e
    return out


def _share(mp, first, held):
    cut = dict(mp)
    for k in ("e_gate", "e_up", "e_down"):
        cut[k] = mp[k][first:first + held]
    return cut


@pytest.mark.parametrize("shares", [4, 2, 1])
def test_the_shares_add_up_under_sigmoid_scoring(model, shares):
    """What each of ``shares`` chips computes for its own experts (the router
    and its bias over all 16), with the shared expert, which every chip
    computes alike, counted once, is the uncut layer. The bias is made large
    enough here to change picks (it does for most tokens), and a layer that
    let it into the weights would miss the hand computation."""
    _, spec, params = model
    mp = dict(params["l2"]["moe"])
    mp["e_bias"] = 0.3 * jax.random.normal(jax.random.key(8), (16,))
    h = jax.random.normal(jax.random.key(5), (24, 64), jnp.float32)
    valid = jnp.ones((24,), bool)
    held = 16 // shares
    kw = dict(top_k=4, routed_scale=1.0, scoring="sigmoid")
    outs = [moe_experts_ffn(_share(mp, k * held, held), h, valid,
                            first_expert=k * held, **kw)
            for k in range(shares)]
    shared_only, _ = moe_experts_ffn(_share(mp, 0, held), h,
                                     jnp.zeros((24,), bool), **kw)
    total = sum(np.asarray(o, np.float64) for o, _ in outs) \
        - (shares - 1) * np.asarray(shared_only, np.float64)
    np.testing.assert_allclose(total, _expert_layer_by_hand(mp, h, 4),
                               atol=2e-5)
    counts = np.sum([np.asarray(c) for _, c in outs], axis=0)
    assert counts[1] == 24 * 4          # every assignment fell on one share
    unbiased = _expert_layer_by_hand({**mp, "e_bias": jnp.zeros(16)}, h, 4)
    assert np.abs(total - unbiased).max() > 0.05     # the bias chose
    with pytest.raises(ValueError, match="scoring"):
        moe_experts_ffn(mp, h, valid, top_k=4, scoring="tanh")


# the page class that is one array --------------------------------------------


def _pool(spec, params, num_pages=24, **kw):
    return PagedKVPool(params, spec, num_pages, PAGE, **kw)


def _entry(prompt, steps=4):
    return type("E", (), {"request": Request(
        prompt=np.asarray(prompt, np.int32), steps=steps)})()


def test_a_latent_page_is_one_array_and_is_charged_as_one(model):
    """One slab a layer, ``entry_width`` columns (24 values padded to one
    lane tile of 128); ``kv_page_bytes`` prices what a page id holds over the
    three layers; a request is charged for its pages, no window class."""
    _, spec, params = model
    la = spec.latent
    assert (la.entry_dim, la.entry_width) == (24, 128)
    pages = init_kv_pages(params, 5, PAGE, spec)
    assert all(len(v) == 1 and v[0].shape == (5, PAGE, 128)
               for v in pages.values())
    assert kv_page_bytes(params, spec, PAGE) == 3 * PAGE * 128 * 4
    assert kv_page_bytes(params, spec, PAGE, kind="sliding") == 0
    assert request_pages(37, 22, PAGE) == 8
    with _engine(spec, params, start=False) as eng:
        assert eng._ring == 0 and eng._prefix_cache is True
        long = Request(prompt=np.zeros(37, np.int32), steps=22)
        assert eng._programs["lm"].admission_cost(long, (72, 24)) \
            == 8 * eng._page_bytes == 8 * 3 * PAGE * 128 * 4


def test_copy_on_write_and_the_audit_on_the_one_array_class(model):
    """A row that is about to write a page the prefix cache also holds gets
    a fresh page with the old one's contents, in every layer's one array;
    the audit balances rows, cache and free list before and after, and sees
    a leaked page."""
    _, spec, params = model
    pool = _pool(spec, params)
    assert pool.prefix_cache_enabled and pool.window_pages == 0
    prompt = np.arange(20, dtype=np.int32)
    group = PagedGroup((24, 8), 2, PAGE, CHUNK, ring=0)
    own = pool.alloc(3)
    group.assign(0, _entry(prompt), own, 0, 0)
    pool.pages = {name: (t[0].at[own[1]].set(float(i + 1)),)
                  for i, (name, t) in enumerate(sorted(pool.pages.items()))}
    assert pool.insert_prefix(prompt, group.row_pages[0]) == 2
    assert pool.audit([group])["ok"] and pool.shared_count() == 2
    assert pool.ensure_writable(group.tables[0], 1)
    fresh = int(group.tables[0, 1])
    group.row_pages[0][1] = fresh
    assert fresh != own[1] and pool.cow_copies == 1
    for i, name in enumerate(sorted(pool.pages)):
        (slab,) = pool.pages[name]
        np.testing.assert_array_equal(np.asarray(slab[fresh]),
                                      np.full((PAGE, 128), i + 1.0))
    audit = pool.audit([group])
    assert audit["ok"], audit["errors"]
    leaked = pool.alloc(1)
    assert any("refcount" in e for e in pool.audit([group])["errors"])
    pool.release(leaked)
    pool.release(group.release(0))
    assert pool.audit([group])["ok"] and pool.used_count() == 2  # the cache


def test_rows_and_prefixes_travel_between_pools_of_the_one_array_class(model):
    """``export_rows`` / ``import_rows`` and ``export_prefixes`` /
    ``import_prefixes``: each layer's one array goes into the blob and comes
    out in the target's pages; a second row with the same document
    re-deduplicates against the first; a blob of the (K, V) geometry, or a
    torn one, is refused."""
    cfg, spec, params = model
    src, dst = _pool(spec, params), _pool(spec, params)
    rng = np.random.default_rng(6)
    doc = rng.integers(0, 96, 16).astype(np.int32)
    rows, tables = [], []
    pages = src.pages
    for k in range(2):
        prompt = np.concatenate([doc, rng.integers(0, 96, 5 + k)
                                 .astype(np.int32)])
        shared_len, spages = src.match_prefix(prompt)
        own = src.alloc(request_pages(len(prompt), 4, PAGE) - len(spages))
        table = np.zeros(8, np.int32)
        table[:len(spages) + len(own)] = spages + own
        src.pages, first, _ = _prefill(spec, params, src.pages, table, prompt,
                                       start=shared_len)
        src.insert_prefix(prompt, table)
        rows.append({"rid": k, "pages": spages + own,
                     "prompt": prompt.tolist(), "length": len(prompt),
                     "pf_next": -1, "position": len(prompt),
                     "steps_done": 1, "cur_tok": first, "emitted": [first]})
        tables.append(table)
    assert src.hits == 1 and rows[1]["pages"][:2] == rows[0]["pages"][:2]
    del pages
    blob = src.export_rows(rows)
    got = dst.import_rows(blob)
    assert [r["n_shared"] for r in got] == [0, 2]
    for row, table in zip(got, tables):
        for name in src.pages:
            np.testing.assert_array_equal(
                np.asarray(dst.pages[name][0][np.asarray(row["pages"])]),
                np.asarray(src.pages[name][0][table[:len(row["pages"])]]))
    assert dst.audit()["ok"]
    warm = _pool(spec, params)
    assert warm.import_prefixes(src.export_prefixes(8)) == 2
    assert warm.match_prefix(np.concatenate([doc, [1, 2]]))[0] == 16
    with pytest.raises(MigrationCorruptError):
        dst.import_rows(blob[:-7])
    dense = PagedKVPool(
        {"emb": jnp.zeros((96, 64)),
         "l0": {"wk": jnp.zeros((64, 64))}}, 4, 24, PAGE)
    with pytest.raises(MigrationCorruptError, match="geometry"):
        dense.import_rows(blob)


# through the engine ----------------------------------------------------------


def _engine(spec, params, **kw):
    kw = {"buckets": [(72, 24)], "max_batch": 3, "page_len": PAGE,
          "num_pages": 64, "prefill_chunk": CHUNK, **kw}
    return ServeEngine(params, spec, **kw)


@pytest.mark.parametrize("kernel", ["gather", "pallas"])
def test_the_engine_serves_through_the_prefix_cache(kernel):
    """Nine requests on an expert share (experts 4-7 of 16), each a 32-token
    document (one of two) and a question, through submit, admission, the
    pool with the prefix cache ON, chunked prefill that starts inside the
    prompt, the packed decode call: all but each document's first request hit
    32 cached tokens; every served token is the reference's best (a full
    forward pass over the request's whole prompt); the audit balances while
    rows are resident and after, with the documents left in the cache."""
    cfg = tiny_cfg(n_routed_experts=4, deployment_share={"first_expert": 4})
    spec = hybrid.ModelSpec.from_config(cfg, experts_total=16, first_expert=4)
    params = hybrid.init_params(spec, jax.random.key(1))
    rng = np.random.default_rng(3)
    docs = rng.integers(0, 96, (2, 32)).astype(np.int32)
    reqs = [Request(prompt=np.concatenate(
        [docs[i % 2], rng.integers(0, 96, 3 + 4 * i).astype(np.int32)]),
        steps=6 + i) for i in range(9)]
    with _engine(spec, params, decode_kernel=kernel, start=False) as eng:
        eng.warmup()
        eng.start()
        first = [eng.submit(r) for r in reqs[:2]]
        results = [h.result(timeout=120) for h in first]
        handles = [eng.submit(r) for r in reqs[2:]]
        while not all(h.done() for h in handles):
            eng.kvpool_audit()
            time.sleep(0.005)
        results += [h.result(timeout=120) for h in handles]
        pool = eng._kvpool
        assert (pool.hits, pool.misses) == (7, 2)
        audit = eng.kvpool_audit()
    assert [r.status for r in results] == ["ok"] * 9
    assert audit["ok"], audit["errors"]
    assert audit["used"] == audit["cached"] >= 8     # two documents, leaves
    for r, q in zip(results, reqs):
        gaps = reference.served_gaps(params, cfg, r.tokens, len(q.prompt),
                                     96, 24)["gaps"]
        assert gaps.max() < 1e-4


def test_rows_of_a_latent_model_migrate_mid_decode(model):
    """``freeze_rows`` / ``adopt_rows`` work for a latent model (its pages
    are of the global class): rows frozen mid-decode on engine A finish on B
    with the tokens of an uninterrupted engine."""
    cfg, spec, params = model
    rng = np.random.default_rng(11)
    prompts = [rng.integers(0, 96, 20 + 3 * i).astype(np.int32)
               for i in range(3)]
    with _engine(spec, params, decode_kernel="gather") as ref:
        want = [ref.submit(Request(prompt=p, steps=10)).result(timeout=120)
                .tokens.tolist() for p in prompts]
    a = _engine(spec, params, decode_kernel="gather")
    b = _engine(spec, params, decode_kernel="gather")
    a.warmup(), b.warmup()
    try:
        with faults.injected("serve.decode_step",
                             DelayFault(seconds=0.4, times=1,
                                        schedule=Schedule(fire_on=[2]))):
            hs = [a.submit(Request(prompt=p, steps=10)) for p in prompts]
            time.sleep(0.15)
        frozen = a.freeze_rows()
        assert frozen is not None and frozen["blob"] and not frozen["fallback"]
        res = b.adopt_rows(frozen)
        assert not res["fallback"] and len(res["adopted"]) == 3
        for rid in res["adopted"]:
            a._queue.release(frozen["entries"][rid].cost)
        assert b.adopt_entries(frozen["queued"])
        a.close()
        got = [h.result(timeout=120) for h in hs]
        assert [r.status for r in got] == ["ok"] * 3
        assert [r.tokens.tolist() for r in got] == want
        assert b.metrics.snapshot()["retries"] == 0
        b.drain()
        assert b.kvpool_audit()["ok"]
    finally:
        a.close(), b.close()


def test_the_spans_carry_the_latent_and_the_prefix_fields(model, monkeypatch):
    """``serve.admit`` says how many of a prompt's tokens were shared,
    ``serve.iter`` how many pages have several referents and how many the
    cache holds, ``serve.decode.dispatch`` the latent pages its live rows
    attend and the table's width."""
    from marlin_tpu.serving import engine as engine_mod

    cfg, spec, params = model
    seen = {}

    class Span:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

        def is_enabled(self):
            return True

        def set_metadata(self, **f):
            seen.setdefault(self.name, []).append(f)

    monkeypatch.setattr(engine_mod, "annotate",
                        lambda name, **f: Span(name))
    doc = np.arange(32, dtype=np.int32)
    with _engine(spec, params, decode_kernel="gather") as eng:
        for tail in ([5, 6, 7], [9, 8]):
            r = eng.submit(Request(prompt=np.concatenate([doc, tail])
                                   .astype(np.int32), steps=4))
            assert r.result(timeout=120).status == "ok"
    admits = [f for f in seen["serve.admit"] if "prompt_tokens" in f]
    assert [(f["prompt_tokens"], f["shared_tokens"], f["shared_pages"])
            for f in admits] == [(35, 0, 0), (34, 32, 4)]
    dispatch = [f for f in seen["serve.decode.dispatch"]
                if "latent_kv_pages" in f]
    assert dispatch and all(f["latent_table_width"] == 12 for f in dispatch)
    assert {f["latent_kv_pages"] for f in dispatch} == {5}
    iters = [f for f in seen["serve.iter"] if "shared_pages" in f]
    assert max(f["shared_pages"] for f in iters) == 4
    assert max(f["cached_pages"] for f in iters) == 4


def test_the_spec_of_the_benchmarks_configuration():
    """``benchmarks/configs/mistral-small4-ep4-l6.json``: six latent layers,
    every one an expert layer, at the published widths; the softmax scale
    ``128^-1/2 x 1.4852^2``; ``tau`` 1 below position 8192 and ``1 + 0.1
    ln 3`` from 16384; the cache entry 320 values stored in 384 columns."""
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "mistral-small4-ep4-l6.json")) as f:
        cfg = json.load(f)
    share = cfg["deployment_share"]
    spec = hybrid.ModelSpec.from_config(
        cfg, experts_total=share["experts_total"],
        first_expert=share["first_expert"])
    assert spec.layers == (hybrid.LayerSpec("latent", 32, "moe"),) * 6
    la = spec.latent
    assert (la.q_rank, la.kv_rank, la.nope_dim, la.rope_dim, la.v_dim) \
        == (1024, 256, 64, 64, 128)
    assert (la.entry_dim, la.entry_width) == (320, 384)
    m = 0.1 * np.log(128.0) + 1.0
    assert abs(m - 1.48520) < 1e-5
    assert la.softmax_scale == pytest.approx(128 ** -0.5 * m * m)
    tau = np.asarray(la.query_scale(jnp.asarray([0, 8191, 8192, 16384, 17919]))
                     ) / la.softmax_scale
    np.testing.assert_allclose(tau, [1, 1, 1 + 0.1 * np.log(2),
                                     1 + 0.1 * np.log(3),
                                     1 + 0.1 * np.log(3)], rtol=1e-6)
    rope = spec.rope_full
    assert (rope.kind, rope.rotary_dim, rope.interleave, rope.theta,
            rope.factor, rope.original_max, rope.attention_factor) \
        == ("yarn", 64, True, 10000.0, 128.0, 8192, 1.0)
    assert (spec.d_model, spec.expert_width, spec.shared_width,
            spec.n_experts, spec.experts_held, spec.first_expert, spec.top_k,
            spec.routed_scale, spec.scoring, spec.vocab_held,
            spec.has_window) \
        == (4096, 2048, 2048, 128, 32, 0, 4, 1.0, "sigmoid", 32768, False)
    assert kv_page_bytes({}, spec, 256) == 6 * 256 * 384 * 2
    # group-limited routing is built since PR 56 (tests/test_moe.py,
    # tests/test_dsa.py); groups that do not divide the experts are refused
    grouped = hybrid.ModelSpec.from_config(tiny_cfg(n_group=2, topk_group=1))
    assert (grouped.n_group, grouped.topk_group) == (2, 1)
    assert (spec.n_group, spec.topk_group) == (1, 1)
    with pytest.raises(ValueError, match="whole groups"):
        hybrid.ModelSpec.from_config(tiny_cfg(n_group=3))
