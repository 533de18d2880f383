"""A model of the ``minicpm_sala`` configuration family: attention that reads
a SELECTION of a row's blocks, chosen on the device by a score over
mean-pooled keys kept beside the pages, one such layer to every few lightning
layers (``tests/test_lightning.py`` has those). Here: the selection against
the plain reference's literal steps, the block-walk decode kernel against
gathered blocks, prefill's tile-walk kernel against the mask over every key,
the paged programs against the reference
(``benchmarks/reference/serve_minicpmsala.py``) across ``dense_len``, chunk,
page, block and pooling-window edges, a second row that enters from the first
row's pages and snapshot, each flaw the comparison must catch, and the engine
with its counters.

Small sizes: 4 query heads over 2 KV heads (a group of 2), heads of 16,
pooling windows of 4 tokens 2 apart, blocks of 8 (a page), 6 blocks a query
(block 0 and the last two forced, three chosen), dense below position 40.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import engine_spans
from benchmarks.reference import serve_minicpmsala as reference
from benchmarks.trace_reduce import find_xplane
from marlin_tpu.models import hybrid
from marlin_tpu.models.transformer import (init_kv_pages, lm_decode_paged,
                                           lm_prefill_paged)
from marlin_tpu.ops import paged_attention, sparse_attention
from marlin_tpu.ops.paged_attention import (paged_decode_attention_blocks,
                                            sparse_prefill_attention)
from marlin_tpu.serving import Request, ServeEngine

PAGE, CHUNK = 8, 16
NO_RING = np.zeros(0, np.int32)
#: program against reference, both float32: sums in another order (a running
#: softmax over key blocks, a chunked recurrence); measured 4e-6 on logits of
#: size 3.7
TIGHT = 4e-5
#: the least a flaw may move a logit to count as caught: 25 x TIGHT
CAUGHT = 1e-3
VOCAB = 97
PAD = 128


def tiny_cfg(**over):
    cfg = {
        "model_type": "minicpm_sala", "hidden_size": 48, "head_dim": 16,
        "num_attention_heads": 4, "num_key_value_heads": 2,
        "intermediate_size": 80, "vocab_size": VOCAB, "num_hidden_layers": 4,
        "mixer_types": ["minicpm4", "lightning-attn", "lightning-attn",
                        "minicpm4"],
        "first_layer": 9, "lightning_nh": 4, "lightning_nkv": 4,
        "lightning_head_dim": 16, "lightning_use_rope": True,
        "attn_use_rope": False, "qk_norm": True, "rope_theta": 10000,
        "scale_emb": 12, "scale_depth": 1.4, "mup_denominator": 32,
        "dim_model_base": 12, "rms_norm_eps": 1e-6, "hidden_act": "silu",
        "use_output_gate": True, "use_output_norm": True,
        "attn_use_output_gate": True, "tie_word_embeddings": False,
        "sparse_config": {"kernel_size": 4, "kernel_stride": 2,
                          "block_size": 8, "topk": 6, "init_blocks": 1,
                          "window_size": 16, "dense_len": 40},
        "lightning_chunk_size": 8,
        "param_dtype": "float32", "compute_dtype": "float32"}
    cfg.update(over)
    return cfg


@pytest.fixture(scope="module")
def model():
    cfg = tiny_cfg()
    spec = hybrid.ModelSpec.from_config(cfg)
    return cfg, spec, hybrid.init_params(spec, jax.random.key(3))


def _table(first_page: int, n_pages: int, chunk: int = CHUNK):
    t = np.zeros(n_pages + chunk // PAGE, np.int32)
    t[:n_pages] = np.arange(first_page, first_page + n_pages)
    return t


def serve_one(spec, params, prompt, steps, kernel="gather", pages=None,
              state_id=2, prefill=lm_prefill_paged, between=None,
              chunk=CHUNK, start=0, table=None, snapshots=None,
              decode=lm_decode_paged):
    """Chunked paged prefill of ``prompt`` from position ``start`` into
    state slot ``state_id``, then decode through the cache in a call of
    three rows (the middle one live, the others the dummy row on the dummy
    slot); the tokens and the float32 logits every served token was picked
    from. ``between(pages)`` may tamper with the slabs between two chunks
    (and once more before decode); ``snapshots`` maps a position to the
    slot the state is copied to behind the chunk that ends there."""
    n = len(prompt)
    need = -(-(n + steps) // PAGE)
    if pages is None:
        pages = init_kv_pages(params, 48, PAGE, spec, state_slots=8)
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
    if between is not None:
        pages = between(pages)
    toks, served = list(prompt) + [int(first)], [np.asarray(logits)]
    B = 3
    gt = np.zeros((B, need), np.int32)
    gt[1] = table[:need]
    zeros = np.zeros(B)
    for t in range(steps - 1):
        pages, nxt, _, logits = decode(
            params, pages,
            (gt, np.zeros((B, 0), np.int32), np.array([0, state_id, 0])),
            np.array([0, n + t, 0]), np.array([0, toks[-1], 0]), zeros, zeros,
            zeros, np.ones(B), zeros, heads=spec, page_len=PAGE,
            kernel=kernel)
        toks.append(int(nxt[1]))
        served.append(np.asarray(logits[1]))
    return np.asarray(toks), np.stack(served), pages


def ref_logits(params, cfg, toks, n_prompt, pad=PAD):
    return np.asarray(reference.logits_at(
        params, cfg, toks[:-1], np.arange(n_prompt - 1, len(toks) - 1), pad))


def prompt_of(n: int, seed: int = 0):
    return np.random.default_rng(seed).integers(0, VOCAB, n).astype(np.int32)


def fresh_prefill(monkeypatch, target, name, flawed):
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


def fresh_decode(monkeypatch, target, name, flawed):
    """``lm_decode_paged`` traced anew with ``target.name`` replaced."""
    monkeypatch.setattr(target, name, flawed)
    raw = hybrid._lm_decode_paged_spec_jit.__wrapped__

    def run(*args, spec, page_len, kernel, **kw):
        return raw(*args, spec=spec, page_len=page_len, kernel=kernel, **kw)

    fresh = jax.jit(run, static_argnames=("spec", "page_len", "kernel"))

    def decode(params, pages, tables, *rest, heads, page_len, kernel):
        args, static = hybrid._decode_args(params, pages, tables, *rest,
                                           heads, page_len, kernel)
        return fresh(*args, **static)

    return decode


# the configuration -----------------------------------------------------------


def test_from_config_reads_the_minicpm_sala_keys(model):
    cfg, spec, params = model
    assert [ly.attn for ly in spec.layers] == ["sparse", "lightning",
                                               "lightning", "sparse"]
    assert [ly.owns_pages for ly in spec.layers] == [True, False, False, True]
    assert [ly.has_state for ly in spec.layers] == [False, True, True, False]
    assert spec.has_state and not spec.has_window
    sp = spec.sparse
    assert (sp.stride, sp.block, sp.topk, sp.init_blocks, sp.window_blocks,
            sp.dense_len, sp.per_block) == (2, 8, 6, 1, 2, 40, 4)
    # the published depth scales both branches whatever is held
    assert spec.mults.attention_out == spec.mults.mlp_down \
        == pytest.approx(1.4 / 32 ** 0.5)
    assert spec.mults.embedding == 12 and spec.mults.lm_head == 12 / 48
    assert spec.lightning.first_layer == 9 and spec.lightning.layers_total == 32
    # K and V per KV head and one compressed entry every `stride` tokens, in
    # the two sparse layers; one state a lightning layer
    assert spec.page_values("full", PAGE) == 2 * (2 * PAGE * 32 + PAGE // 2 * 32)
    assert spec.state_slot_bytes() == 2 * 4 * 16 * 16 * 4
    pages = init_kv_pages(params, 5, PAGE, spec, state_slots=3)
    assert [tuple(a.shape for a in pages[f"l{i}"]) for i in range(4)] == [
        ((5, 8, 32), (5, 8, 32), (5, 4, 32)), ((3, 4, 16, 16),),
        ((3, 4, 16, 16),), ((5, 8, 32), (5, 8, 32), (5, 4, 32))]
    assert params["l0"]["w_g"].shape == (48, 64) and "decay" not in params["l0"]
    np.testing.assert_allclose(params["l1"]["decay"],
                               reference.decay(4, 10, 32), rtol=1e-6)


def test_from_config_names_every_missing_key():
    cfg = tiny_cfg()
    for k in ("lightning_nh", "scale_depth", "sparse_config"):
        del cfg[k]
    with pytest.raises(ValueError, match="minicpm_sala") as err:
        hybrid.ModelSpec.from_config(cfg)
    assert all(k in str(err.value)
               for k in ("lightning_nh", "scale_depth", "sparse_config"))
    cfg = tiny_cfg()
    del cfg["sparse_config"]["topk"], cfg["sparse_config"]["dense_len"]
    with pytest.raises(ValueError, match="sparse_config") as err:
        hybrid.ModelSpec.from_config(cfg)
    assert "topk" in str(err.value) and "dense_len" in str(err.value)


@pytest.mark.parametrize("change, match", [
    ({"attn_use_rope": True}, "attn_use_rope"),
    ({"lightning_use_rope": False}, "lightning_use_rope"),
    ({"qk_norm": False}, "qk_norm"),
    ({"attn_use_output_gate": False}, "attn_use_output_gate"),
    ({"tie_word_embeddings": True}, "tie_word_embeddings"),
    ({"lightning_nkv": 2}, "lightning_nkv"),
    ({"mixer_types": ["minicpm4", "mamba", "lightning-attn", "minicpm4"]},
     "mamba"),
    ({"sparse_config": {"kernel_size": 6, "kernel_stride": 2, "block_size": 8,
                        "topk": 6, "init_blocks": 1, "window_size": 16,
                        "dense_len": 40}}, "kernel_size")])
def test_from_config_refuses_what_the_family_does_not_build(change, match):
    with pytest.raises(ValueError, match=match):
        hybrid.ModelSpec.from_config(tiny_cfg(**change))


def test_a_page_length_that_is_not_whole_blocks_is_refused(model):
    _, spec, params = model
    with pytest.raises(ValueError, match="whole blocks"):
        init_kv_pages(params, 5, 12, spec, state_slots=3)


# the selection ---------------------------------------------------------------


def _selection_case(seed: int, T: int = 96):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(T, 2, 16)) * 2, jnp.float32)
    k = jnp.asarray(rng.normal(size=(T, 16)) * 2, jnp.float32)
    return q, k


@pytest.mark.parametrize("topk, window, init, dense_len", [
    (6, 16, 1, 40), (6, 16, 1, 0), (4, 8, 2, 24), (12, 16, 1, 40),
    (5, 24, 1, 16)])
def test_the_selection_is_the_references_steps(topk, window, init, dense_len):
    """Compressed keys numbered by the token that ENDS their window, a pooled
    top-k through ``lax.top_k``: the same blocks, query by query, as the
    reference's literal steps (windows numbered by their start, a stable
    sort), in both regimes and where fewer than ``topk`` blocks exist."""
    sp = sparse_attention.SparseSpec(stride=2, block=8, topk=topk,
                                     init_blocks=init, window=window,
                                     dense_len=dense_len)
    q, k = _selection_case(1)
    T = q.shape[0]
    pos = jnp.arange(T)
    ext = jnp.concatenate([jnp.zeros((2, 16)), k])
    ck = sparse_attention.compress_keys(ext, 2)               # entries 0..T/2-1
    idx, taken = sparse_attention.select_blocks(q, ck, pos, sp)
    got = np.asarray(sparse_attention.block_mask(idx, taken, T // 8))
    m = {"kernel": 4, "stride": 2, "block": 8, "topk": topk,
         "init_blocks": init, "window_blocks": window // 8,
         "dense_len": dense_len, "n_blocks": T // 8}
    c = jnp.stack([k[2 * j:2 * j + 4].mean(0) for j in range((T - 4) // 2 + 1)])
    want = np.asarray(reference.taken_blocks(q.transpose(1, 0, 2), c, pos, m))
    np.testing.assert_array_equal(got, want)
    # the lists: taken entries first, each block once, the count the rule's
    n_taken = np.asarray(taken).sum(-1)
    assert (np.asarray(taken)[:, :-1] >= np.asarray(taken)[:, 1:]).all()
    exists = np.arange(T) // 8 + 1
    np.testing.assert_array_equal(
        n_taken, np.where(np.arange(T) < dense_len, exists,
                          np.minimum(exists, topk)))


def test_a_pages_entries_are_a_function_of_the_tokens_up_to_its_end(model):
    """Two prompts that share their first three pages and differ from the
    next token on leave the same compressed keys in those pages, entry for
    entry: an entry belongs to the page in which its window ENDS. (Owned by
    the page in which it begins, the third page's last entry would hold the
    first tokens of whichever prompt wrote it.)"""
    _, spec, params = model
    a = prompt_of(45, seed=7)
    b = np.concatenate([a[:24], prompt_of(21, seed=8)])
    with jax.default_matmul_precision("highest"):
        pa = serve_one(spec, params, a, 2)[2]
        pb = serve_one(spec, params, b, 2)[2]
    for name in ("l0", "l3"):
        np.testing.assert_array_equal(pa[name][2][1:4], pb[name][2][1:4])
        assert np.abs(pa[name][2][4] - pb[name][2][4]).max() > 1e-3


# the decode kernel -----------------------------------------------------------


@pytest.mark.parametrize("dtype, S", [("float32", 5), ("float32", 20),
                                      ("bfloat16", 5)])
def test_the_block_walk_kernel_is_the_gathered_blocks_attention(dtype, S):
    """Each (row, KV head) walks ITS list: lists of different lengths, a
    partly filled last block, a row of one token, more slots than a matmul
    step holds."""
    rng = np.random.default_rng(0)
    B, kvh, g, dh, page_len, block, W, P = 3, 2, 4, 16, 16, 8, 12, 50
    dt = jnp.dtype(dtype)
    pk, pv = (jnp.asarray(rng.normal(size=(P, page_len, kvh * dh)), dt)
              for _ in range(2))
    q = jnp.asarray(rng.normal(size=(B, kvh, g, dh)), dt)
    tables = jnp.asarray(rng.permutation(np.arange(1, P))[:B * W]
                         .reshape(B, W), jnp.int32)
    lengths = np.array([187, 37, 1], np.int32)
    idx = np.zeros((B, kvh, S), np.int32)
    taken = np.zeros((B, kvh, S), bool)
    for b in range(B):
        nb = (lengths[b] - 1) // block + 1
        for h in range(kvh):
            c = max(1, min(S, nb) - h)
            pick = rng.permutation(nb)[:c]
            if nb - 1 not in pick:
                pick[0] = nb - 1
            idx[b, h, :c], taken[b, h, :c] = pick, True
    want = sparse_attention.attend_blocks_gather(
        q, pk, pv, tables, jnp.asarray(idx), jnp.asarray(taken),
        jnp.asarray(lengths), block)
    got = paged_decode_attention_blocks(q, pk, pv, tables, idx,
                                        taken.sum(-1), lengths, block)
    tol = 2e-6 if dtype == "float32" else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol)


# the prefill kernel ----------------------------------------------------------


_SP = sparse_attention.SparseSpec(stride=2, block=8, topk=6, init_blocks=1,
                                  window=16, dense_len=40)
#: name: (tile tokens, first position, tokens that are not padding, blocks of
#: the context, dtype, the free blocks: "chosen" by the selection, or drawn
#: by hand so that a tile's tokens share "none" or "all" of them)
TILE_CASES = {
    "tq2": (2, 100, 32, 20, "float32", "chosen"),
    "tq4": (4, 100, 32, 20, "float32", "chosen"),
    "tq8": (8, 100, 32, 20, "float32", "chosen"),
    "tq16": (16, 100, 32, 20, "float32", "chosen"),
    "tq32": (32, 100, 32, 20, "float32", "chosen"),
    "wholly_below_dense_len": (8, 0, 32, 20, "float32", "chosen"),
    "straddles_dense_len": (8, 24, 32, 20, "float32", "chosen"),
    "last_tiles_hold_no_token": (4, 100, 13, 20, "float32", "chosen"),
    "a_tile_of_padding_between_tokens": (4, 100, -8, 20, "float32", "chosen"),
    "tokens_share_no_free_block": (4, 100, 32, 20, "float32", "none"),
    "tokens_share_every_free_block": (8, 100, 32, 20, "float32", "all"),
    "context_not_whole_rounds": (8, 130, 30, 21, "float32", "chosen"),
    "bfloat16": (8, 100, 27, 20, "bfloat16", "chosen"),
}


def _tile_case(name: str, T: int = 32, kvh: int = 2, g: int = 2,
               dh: int = 16):
    """A chunk of ``T`` queries from ``start`` over a context of ``nb``
    blocks, the two KV heads' selections drawn apart; ``tokens`` < 0: the
    SECOND eight rows are the padding."""
    tq, start, tokens, nb, dtype, free = TILE_CASES[name]
    sp, rng = _SP, np.random.default_rng(len(name))
    L, dt = nb * sp.block, jnp.dtype(dtype)
    q = jnp.asarray(rng.normal(size=(T, kvh, g, dh)) * 2, dt)
    k, v = (jnp.asarray(rng.normal(size=(L, kvh * dh)) * s, dt)
            for s in (2, 1))
    q_pos = start + jnp.arange(T)
    valid = np.arange(T) < tokens if tokens > 0 else \
        (np.arange(T) < 8) | (np.arange(T) >= 16)
    own = np.asarray(q_pos) // sp.block
    masks = []
    for h in range(kvh):
        if free == "chosen":
            ext = jnp.concatenate([jnp.zeros((sp.stride, dh), dt),
                                   k.reshape(L, kvh, dh)[:, h]])
            idx, taken = sparse_attention.select_blocks(
                q[:, h], sparse_attention.compress_keys(ext, sp.stride),
                q_pos, sp)
            masks.append(np.asarray(sparse_attention.block_mask(idx, taken,
                                                                nb)))
            continue
        b = np.arange(nb)
        m = (b[None] == 0) | ((b[None] > own[:, None] - sp.window_blocks)
                              & (b[None] <= own[:, None]))
        for t in range(T):   # three free blocks a token, of blocks 1..9
            pick = [1 + h, 4 + h, 7 + h] if free == "all" else \
                [1 + (3 * (t % tq) + j + h) % 9 for j in range(3)]
            m[t, pick] = True
        masks.append(m)
    return q, k, v, q_pos, jnp.asarray(valid), jnp.asarray(np.stack(masks)), tq


def _masked_attention(q, k, v, q_pos, mask):
    L, kvh = k.shape[0], q.shape[1]
    heads = lambda x: x.reshape(L, kvh, -1)  # noqa: E731
    return np.asarray(jnp.stack([sparse_attention.attend_selected(
        q[:, h], heads(k)[:, h], heads(v)[:, h], q_pos, mask[h], _SP.block, 24)
        for h in range(kvh)], axis=1).astype(jnp.float32))


@pytest.mark.parametrize("case", TILE_CASES)
def test_the_tile_walk_kernel_is_the_masked_attention(case):
    """Prefill's kernel meets the UNION of a tile's tokens' blocks and shows
    each token its own: the mask over every key
    (:func:`sparse_attention.attend_selected`), for every tile size the code
    may pick, in both regimes and across ``dense_len``, with tiles (trailing
    or not) that hold no token, unions of one block and of every block,
    a context that is not whole rounds of 16 blocks, lists that differ
    between the KV heads; a row of padding is zeros."""
    q, k, v, q_pos, valid, mask, tq = _tile_case(case)
    lists, rounds, words, met, taken = sparse_attention.tile_lists(
        mask, q_pos, valid, _SP.block, tq)
    got = np.asarray(sparse_prefill_attention(
        q, k, v, q_pos, lists, rounds, words, _SP.block).astype(jnp.float32))
    want = _masked_attention(q, k, v, q_pos, mask)
    live = np.asarray(valid)
    tol = TIGHT if q.dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(got[live], want[live], atol=tol)
    assert not got[~live].any()
    # the counters: what the tokens took (the blocks they can see), and the
    # unions the tiles list, KV head by KV head
    seen = np.asarray(mask) & live[None, :, None] & (
        np.arange(mask.shape[2])[None, None, :]
        <= (np.asarray(q_pos) // _SP.block)[None, :, None])
    union = seen.reshape(2, -1, tq, mask.shape[2]).any(axis=2)
    assert int(taken) == seen.sum() and int(met) == union.sum()
    np.testing.assert_array_equal(np.asarray(rounds), -(-union.sum(-1) // 16))
    # the KV heads chose apart: another list, or other tokens a block
    assert case == "wholly_below_dense_len" or any(
        (np.asarray(x)[0] != np.asarray(x)[1]).any() for x in (lists, words))
    if case == "tokens_share_every_free_block":
        assert int(taken) > 5 * int(met)
    if case == "tokens_share_no_free_block":   # 3 + 3 tq of them a tile
        assert union[:, 0].sum(-1).min() >= 3 + 9


_tile_lists = sparse_attention.tile_lists   # the flaws below replace it


def _every_tile_mate_sees_it(*args):
    lists, rounds, words, met, taken = _tile_lists(*args)
    return lists, rounds, jnp.where(words != 0, -1, 0), met, taken


def _no_step_is_passed_over(rounds):
    return jnp.arange(1, rounds.shape[0] + 2, dtype=jnp.int32).at[0].set(0)


@pytest.mark.parametrize("flaw", ["a_tile_mates_block_is_seen",
                                  "a_tile_of_padding_is_met_by_a_live_row"])
def test_the_tile_walk_kernel_catches_its_flaws(flaw, monkeypatch):
    """The two flaws the tiled form invites, each far outside the agreement
    above: a token that sees a block only its tile-mate took, and the round
    of a tile that holds no token landing in a live tile's softmax (the
    stream of copies not passing over the grid step without a round)."""
    q, k, v, q_pos, valid, mask, tq = _tile_case(
        "a_tile_of_padding_between_tokens")
    args = (mask, q_pos, valid, _SP.block, tq)
    call = paged_attention._sparse_prefill_attention_call
    if flaw == "a_tile_mates_block_is_seen":
        lists, rounds, words, _, _ = _every_tile_mate_sees_it(*args)
    else:
        lists, rounds, words, _, _ = sparse_attention.tile_lists(
            mask, q_pos, jnp.ones_like(valid), _SP.block, tq)
        dead = ~np.asarray(valid).reshape(-1, tq).any(axis=1)
        rounds = jnp.where(dead[None], 0, rounds)
        monkeypatch.setattr(paged_attention, "_next_with_a_round",
                            _no_step_is_passed_over)
        call.clear_cache()
    try:
        got = np.asarray(sparse_prefill_attention(
            q, k, v, q_pos, lists, rounds, words, _SP.block))
    finally:
        monkeypatch.undo()
        call.clear_cache()
    live = np.asarray(valid)
    want = _masked_attention(q, k, v, q_pos, mask)
    assert np.abs(got - want)[live].max() > CAUGHT, flaw


# the programs against the reference ------------------------------------------


@pytest.mark.parametrize("kernel, n, steps", [
    ("gather", 101, 12),   # six chunks and a partial one, deep in the regime
    ("pallas", 101, 12),
    ("gather", 33, 12),    # dense prompt; decode crosses dense_len at 40
    ("pallas", 35, 14),
    ("gather", 64, 10),    # ends on a chunk, page, block and window edge
    ("gather", 47, 11),    # the last pooling window ends with the prompt
    ("pallas", 78, 20)])   # decode completes windows across a page edge
def test_chunked_prefill_then_decode_agree_with_the_reference(model, kernel,
                                                              n, steps):
    cfg, spec, params = model
    with jax.default_matmul_precision("highest"):
        toks, served, _ = serve_one(spec, params, prompt_of(n), steps,
                                    kernel=kernel)
        want = ref_logits(params, cfg, toks, n)
    np.testing.assert_allclose(served, want, atol=TIGHT)


def test_a_dirty_pool_changes_nothing(model):
    """A second row through pages (compressed keys among them) and a slot
    that a first row left full."""
    cfg, spec, params = model
    with jax.default_matmul_precision("highest"):
        _, _, dirty = serve_one(spec, params, prompt_of(90, seed=5), 6)
        toks, served, _ = serve_one(spec, params, prompt_of(75), 8,
                                    pages=dirty)
        want = ref_logits(params, cfg, toks, 75)
    np.testing.assert_allclose(served, want, atol=TIGHT)


def shared(spec, params, boundary: int = 64, enter: str = "snapshot",
           between=None, copy=None):
    """Row A (85 tokens, slot 2, pages 1..) prefills in chunks of one page
    and leaves snapshots of its state behind the chunks that end at 56, 64
    and 72 (slots 5, 6, 7). Row B (its first 64 tokens A's, then its own, 81
    in all; slot 3) takes A's first eight pages (or, with ``copy``, copies
    of them made by it), copies the snapshot of ``boundary`` into its slot
    (``enter`` ``zeros``: copies nothing) and prefills from 64. Returns B's
    tokens and logits."""
    a = prompt_of(85, seed=7)
    b = np.concatenate([a[:64], prompt_of(17, seed=8)])
    snaps = {56: 5, 64: 6, 72: 7}
    _, _, pages = serve_one(spec, params, a, 2, chunk=PAGE, snapshots=snaps)
    table = _table(20, 12, PAGE)      # 81 + 8 tokens: twelve pages
    table[:8] = np.arange(1, 9)       # A's first eight, shared
    if copy is not None:
        for p in range(1, 9):
            pages = copy(pages, p, 38 + p)
        table[:8] = np.arange(39, 47)
    if enter == "snapshot":
        pages = hybrid.state_slot_copy(pages, snaps[boundary], 3, spec)
    toks, served, _ = serve_one(spec, params, b, 8, pages=pages, state_id=3,
                                chunk=PAGE, start=64, table=table,
                                between=between)
    return toks, served


@pytest.mark.parametrize("copied", [False, True])
def test_a_row_that_enters_from_pages_and_a_snapshot(model, copied):
    """A request that shares a whole-page prefix with another (the pages
    themselves, or copy-on-write copies of them) and enters from that one's
    snapshot agrees with the reference's full pass, which never saw a
    cache: its first window reaches back into the shared page, its
    selection reads the shared pages' compressed keys."""
    cfg, spec, params = model
    copy = (lambda pages, s, d: hybrid.kv_page_copy(pages, s, d, spec)) \
        if copied else None
    with jax.default_matmul_precision("highest"):
        toks, served = shared(spec, params, copy=copy)
        want = ref_logits(params, cfg, toks, 81)
    np.testing.assert_allclose(served, want, atol=TIGHT)


# each flaw fails ---------------------------------------------------------------


def _roped_sparse_layer():
    real = hybrid._sala_layer

    def layer(spec, ly, lp, x, positions, valid, attend, mix):
        def roped(q, k, v):
            T = q.shape[0]
            qr = hybrid._rope(q.reshape(T, -1, q.shape[-1]), positions,
                              spec.rope_full).reshape(q.shape)
            return attend(qr, hybrid._rope(k, positions, spec.rope_full), v)
        return real(spec, ly, lp, x, positions, valid,
                    roped if ly.attn == "sparse" else attend, mix)
    return layer


def _sum_before_softmax(q, ck, complete):
    s = jnp.einsum("tgd,md->gtm", q, ck) / q.shape[-1] ** 0.5
    p = jax.nn.softmax(jnp.where(complete, s.sum(0), -1e30), axis=-1)
    return jnp.where(complete, p * q.shape[1], 0.0)


def _windows_that_do_not_overlap(k_ext, stride):
    halves = k_ext.reshape(-1, stride, k_ext.shape[-1]).mean(axis=1)
    return halves[1:]


def _tamper_sparse(spec, fn):
    """``fn(k, v, cc) -> (k, v, cc)`` over every sparse layer's arrays."""
    def between(pages):
        return {f"l{i}": fn(*pages[f"l{i}"]) if ly.attn == "sparse"
                else pages[f"l{i}"] for i, ly in enumerate(spec.layers)}
    return between


def _padding_met_by_the_last(mask, q_pos, valid, block, tq):
    """The flaw of a tile past the length computed INTO a valid row: the
    rows of padding keep their tiles, and what they took the last token of
    the prompt takes too."""
    last = jnp.argmin(valid) - 1          # valid is a prefix of the chunk
    padding = (mask & ~valid[None, :, None]).any(axis=1)        # (kvh, NB)
    mask = mask.at[:, last].set(jnp.where(valid.all(), mask[:, last],
                                          mask[:, last] | padding))
    return _tile_lists(mask, q_pos, valid, block, tq)


FLAWS = ["selection_dropped", "forced_blocks_left_out",
         "forced_blocks_outside_the_topk", "group_summed_before_the_softmax",
         "windows_not_overlapping", "rope_in_the_sparse_layer",
         "branch_scale_of_the_held_depth", "qk_norm_gain_lost",
         "gate_dropped", "entries_not_written_in_decode",
         "a_tile_mates_block_is_seen", "a_tile_past_the_length_is_met"]


@pytest.mark.parametrize("flaw", FLAWS)
def test_each_flaw_fails_the_comparison(flaw, model, monkeypatch):
    """What the comparison must catch: every piece of the selection and of
    the family's block, left out or bent one at a time, moves a logit by far
    more than the agreement above allows."""
    cfg, spec, params = model
    kw, sp = {}, spec.sparse
    if flaw == "selection_dropped":      # dense attention in its place
        spec = dataclasses.replace(spec, sparse=dataclasses.replace(
            sp, dense_len=10 ** 6))
    elif flaw == "forced_blocks_left_out":  # all but the query's own
        kw["prefill"] = fresh_prefill(
            monkeypatch, sparse_attention, "_forced",
            lambda sp, b_idx, own: b_idx[None, :] == own[:, None])
    elif flaw == "forced_blocks_outside_the_topk":
        spec = dataclasses.replace(spec, sparse=dataclasses.replace(
            sp, topk=sp.topk + sp.init_blocks + sp.window_blocks))
    elif flaw == "group_summed_before_the_softmax":
        kw["prefill"] = fresh_prefill(monkeypatch, sparse_attention,
                                      "_entry_scores", _sum_before_softmax)
    elif flaw == "windows_not_overlapping":
        kw["prefill"] = fresh_prefill(monkeypatch, sparse_attention,
                                      "compress_keys",
                                      _windows_that_do_not_overlap)
    elif flaw == "rope_in_the_sparse_layer":
        kw["prefill"] = fresh_prefill(monkeypatch, hybrid, "_sala_layer",
                                      _roped_sparse_layer())
    elif flaw == "branch_scale_of_the_held_depth":   # sqrt(4), not sqrt(32)
        r = 1.4 / spec.n_layers ** 0.5
        spec = dataclasses.replace(spec, mults=dataclasses.replace(
            spec.mults, attention_out=r, mlp_down=r))
    elif flaw == "qk_norm_gain_lost":
        params = dict(params, l0=dict(params["l0"],
                                      q_norm=jnp.ones((16,), jnp.float32)))
    elif flaw == "gate_dropped":
        params = dict(params, l3=dict(params["l3"],
                                      w_g=jnp.zeros_like(params["l3"]["w_g"])))
    elif flaw == "entries_not_written_in_decode":
        kw["steps"] = 30   # far enough that a stale entry is in reach
        kw["decode"] = fresh_decode(monkeypatch, hybrid, "_complete_entries",
                                    lambda pk, pc, *a: pc)
    elif flaw == "a_tile_mates_block_is_seen":   # a tile's union for all
        kw["prefill"] = fresh_prefill(monkeypatch, sparse_attention,
                                      "tile_lists", _every_tile_mate_sees_it)
    elif flaw == "a_tile_past_the_length_is_met":
        # tiles of four tokens; the last chunk's (five tokens of sixteen)
        # two tiles of padding are listed like the others, and what their
        # tokens took is shown to the prompt's last token
        monkeypatch.setattr(sparse_attention, "tile_tokens", lambda T, g: 4)
        kw["prefill"] = fresh_prefill(monkeypatch, sparse_attention,
                                      "tile_lists", _padding_met_by_the_last)
    with jax.default_matmul_precision("highest"):
        toks, served, _ = serve_one(spec, params, prompt_of(101),
                                    kw.pop("steps", 8), **kw)
        want = ref_logits(params, cfg, toks, 101, pad=PAD + 8)
    assert np.abs(served - want).max() > CAUGHT, flaw


def test_a_compressed_entry_owned_by_the_page_it_begins_in_fails(model):
    """Two requests that share a document and differ in the next tokens: the
    window that straddles the document's end ends in each request's OWN
    first page. Were it owned by the page in which it begins, the shared
    page would hold it for both, with the first writer's tokens: here row
    B's entry is overwritten with row A's, which is that state."""
    cfg, spec, params = model

    def first_writers(k, v, cc):   # A's page 9 is B's page 28: entry 0
        return k, v, cc.at[28, 0].set(cc[9, 0])

    with jax.default_matmul_precision("highest"):
        toks, served = shared(spec, params,
                              between=_tamper_sparse(spec, first_writers))
        want = ref_logits(params, cfg, toks, 81)
    assert np.abs(served - want).max() > CAUGHT


def test_a_copied_page_without_its_compressed_keys_fails(model):
    cfg, spec, params = model

    def copy(pages, src, dst):     # K and V move, the entries stay behind
        moved = hybrid.kv_page_copy(pages, src, dst, spec)
        return {name: arrays[:2] + (arrays[2].at[dst].set(0),)
                if len(arrays) == 3 else arrays
                for name, arrays in moved.items()}

    with jax.default_matmul_precision("highest"):
        toks, served = shared(spec, params, copy=copy)
        want = ref_logits(params, cfg, toks, 81)
    assert np.abs(served - want).max() > CAUGHT


# the engine --------------------------------------------------------------------


BUCKETS = ((96, 16),)
#: (document, question tokens, steps)
SESSIONS = ((0, 5, 4), (1, 9, 6), (0, 11, 8), (1, 3, 3), (0, 20, 16),
            (1, 30, 4), (0, 2, 9), (1, 14, 5))


def _sessions():
    docs = [prompt_of(64, seed=100 + d) for d in range(2)]
    return [Request(prompt=np.concatenate([docs[d], prompt_of(turn, seed=i)]),
                    steps=steps, temperature=0.0)
            for i, (d, turn, steps) in enumerate(SESSIONS)]


class _Records:
    def __init__(self):
        self.sparse = []

    def event(self, kind, **f):
        if f.get("ev") == "sparse":
            self.sparse.append(f)


@pytest.fixture(scope="module")
def served(model, tmp_path_factory):
    """Eight questions over two documents through a ServeEngine with the
    prefix cache on, under a profiler capture."""
    cfg, spec, params = model
    log = _Records()
    eng = ServeEngine(params, spec, buckets=BUCKETS, max_batch=3,
                      page_len=PAGE, prefill_chunk=CHUNK, num_pages=96,
                      prefix_cache=True, log=log, start=False)
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
    eng.close()
    return {"requests": reqs, "results": results, "audit": audit,
            "spans": engine_spans.load(find_xplane(where))["spans"],
            "records": log.sparse}


def test_the_engine_serves_the_reference_from_shared_documents(served, model):
    """Every request ok and every served token the reference's first choice
    (float32, greedy), though most rows entered from another row's pages,
    compressed keys and snapshot and never prefilled their document."""
    cfg, spec, params = model
    assert [r.status for r in served["results"]] == ["ok"] * len(SESSIONS)
    with jax.default_matmul_precision("highest"):
        for req, res in zip(served["requests"], served["results"]):
            n = len(req.prompt)
            assert len(res.tokens) == n + req.steps
            want = ref_logits(params, cfg, np.asarray(res.tokens), n)
            gap = want.max(-1) - want[np.arange(req.steps), res.tokens[n:]]
            assert gap.max() < 1e-4, (n, gap)
    hits = [r.metrics["shared_pages"] for r in served["results"]]
    assert sum(s == 8 for s in hits) >= 4 and set(hits) <= {0, 8}, hits
    assert served["audit"]["ok"], served["audit"]["errors"]


def test_the_spans_and_records_carry_the_sparse_counters(served, model):
    cfg, spec, params = model
    by = {}
    for s in served["spans"]:
        by.setdefault(s.name, []).append(s.fields)
    chunks = by["serve.prefill.dispatch"]
    assert all(f["lightning_tokens"] == f["tokens"] for f in chunks)
    calls = {f["seq"]: f for f in by["serve.decode.dispatch"] if f.get("rows")}
    syncs = [f for f in by["serve.decode.sync"]
             if "sparse_blocks_held" in f and f["seq"] in calls]
    assert syncs
    for f in syncs:
        rows = calls[f["seq"]]["rows"]
        # every prompt is past dense_len: each row in the sparse regime,
        # 6 of its 9-13 blocks, in 2 KV heads of 2 sparse layers
        assert f["sparse_rows"] == rows
        assert f["sparse_blocks_attended"] == rows * 6 * 4
        assert rows * 9 * 4 <= f["sparse_blocks_held"] <= rows * 13 * 4
    # a final chunk's landing carries what its tiles met and what their
    # tokens took, summed over the 2 KV heads of the 2 sparse layers: every
    # question lies past dense_len (6 blocks a token, the n tokens of a
    # question's LAST chunk 6 n x 4), and a tile lists a block once however
    # many of its tokens took it
    firsts = [f for f in by["serve.prefill.sync"] if f.get("final")]
    assert len(firsts) == len(SESSIONS)
    assert all(0 < f["sparse_blocks_met"] <= f["sparse_blocks_taken"]
               for f in firsts)
    assert sorted(f["sparse_blocks_taken"] for f in firsts) == sorted(
        24 * ((turn - 1) % CHUNK + 1) for _, turn, _ in SESSIONS)
    # the log= records carry the same counts, one a landed call
    assert sum(f["sparse_blocks_held"] for f in syncs) == sum(
        r["blocks_held"] for r in served["records"])
    assert sum(f["sparse_blocks_attended"] for f in syncs) == sum(
        r["blocks_attended"] for r in served["records"])
    assert sum(f["sparse_rows"] for f in syncs) == sum(
        r["rows"] for r in served["records"])
