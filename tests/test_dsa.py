"""A model of the ``deepseek_v32`` configuration family: latent attention over
the TOKENS a lightning indexer selects (``marlin_tpu/ops/dsa.py``), the index
keys in a second page-indexed array beside the latent slab, group-limited
routing over experts of which a share is held. Here: the selection against
``lax.top_k`` with constructed ties, the kernels (in the interpreter) against
the gather forms, the paged programs against the plain reference
(``benchmarks/reference/serve_deepseekv32.py``) on logits AND on the selected
sets across ``index_topk``, chunk and page edges, a second row that enters
from the first row's pages and index keys (shared, copied, and after part of
them was lost), each flaw the comparison must catch, and the engine with its
counters.

Small sizes: 4 heads (16 + 8 columns a query, latents of 32), 4 index heads
of 16, ``index_topk`` 16, pages of 8, chunks of 16, contexts past 100; 16
experts in 4 groups of which 2 stay, 4 held.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import engine_spans
from benchmarks.reference import serve_deepseekv32 as reference
from benchmarks.trace_reduce import find_xplane
from marlin_tpu.models import hybrid
from marlin_tpu.models.transformer import (init_kv_pages, lm_decode_paged,
                                           lm_prefill_paged)
from marlin_tpu.ops import dsa
from marlin_tpu.serving import Request, ServeEngine

PAGE, CHUNK, TOPK = 8, 16, 16
NO_RING = np.zeros(0, np.int32)
#: program against reference, both float32: sums in another order (the
#: absorbed form, a list in place of a mask); measured 3.3e-6 on logits of
#: size 3.5
TIGHT = 1e-5
#: the least a flaw may move a logit to count as caught: 100 x TIGHT
CAUGHT = 1e-3
VOCAB, PAD, SEG = 97, 160, 32


def tiny_cfg(**over):
    cfg = {
        "model_type": "deepseek_v32", "hidden_size": 48,
        "num_attention_heads": 4, "num_key_value_heads": 4,
        "q_lora_rank": 24, "kv_lora_rank": 32, "qk_nope_head_dim": 16,
        "qk_rope_head_dim": 8, "v_head_dim": 16, "index_n_heads": 4,
        "index_head_dim": 16, "index_topk": TOPK, "rope_interleave": True,
        "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                         "mscale": 1, "mscale_all_dim": 1,
                         "original_max_position_embeddings": 64,
                         "type": "yarn"},
        "rope_theta": 10000, "first_k_dense_replace": 1,
        "intermediate_size": 80, "moe_intermediate_size": 24,
        "n_shared_experts": 1, "n_routed_experts": 4,
        "num_experts_per_tok": 4, "n_group": 4, "topk_group": 2,
        "norm_topk_prob": True, "routed_scaling_factor": 2.5,
        "scoring_func": "sigmoid", "vocab_size": VOCAB,
        "num_hidden_layers": 3, "rms_norm_eps": 1e-6,
        "deployment_share": {"experts_total": 16, "first_expert": 0},
        "param_dtype": "float32", "compute_dtype": "float32"}
    cfg.update(over)
    return cfg


@pytest.fixture(scope="module")
def model():
    cfg = tiny_cfg()
    spec = hybrid.ModelSpec.from_config(cfg, experts_total=16, first_expert=0)
    params = hybrid.init_params(spec, jax.random.key(3))
    for i in range(spec.n_layers):   # a gain and a bias that are not 1 and 0
        k = jax.random.key(100 + i)
        params[f"l{i}"]["ix_k_gain"] = 1 + 0.3 * jax.random.normal(k, (16,))
        params[f"l{i}"]["ix_k_bias"] = 0.3 * jax.random.normal(
            jax.random.fold_in(k, 1), (16,))
    return cfg, spec, params


def _table(first_page: int, n_pages: int, chunk: int = CHUNK):
    t = np.zeros(n_pages + chunk // PAGE, np.int32)
    t[:n_pages] = np.arange(first_page, first_page + n_pages)
    return t


def serve_one(spec, params, prompt, steps, kernel="gather", pages=None,
              between=None, chunk=CHUNK, start=0, table=None):
    """Chunked paged prefill of ``prompt`` from position ``start``, then
    decode through the cache in a call of three rows (the middle one live,
    the others dummy rows); the tokens and the float32 logits every served
    token was picked from. ``between(pages)`` may tamper with the slabs
    before decode."""
    n = len(prompt)
    need = -(-(n + steps) // PAGE)
    if pages is None:
        pages = init_kv_pages(params, 48, PAGE, spec)
    if table is None:
        table = _table(1, need, chunk)
    padded = np.zeros(-(-n // chunk) * chunk + chunk, np.int32)
    padded[:n] = prompt
    for cs in range(start, n, chunk):
        pages, first, _, logits = lm_prefill_paged(
            params, pages, (table, NO_RING), padded[cs:cs + chunk], cs, n,
            heads=spec, page_len=PAGE)
    if between is not None:
        pages = between(pages)
    toks, served = list(prompt) + [int(first)], [np.asarray(logits)]
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


def ref_logits(params, cfg, toks, n_prompt, **kw):
    return reference.logits_at(
        params, cfg, toks[:-1], np.arange(n_prompt - 1, len(toks) - 1), PAD,
        segment=SEG, **kw)


def prompt_of(n: int, seed: int = 0):
    return np.random.default_rng(seed).integers(0, VOCAB, n)


# the configuration -----------------------------------------------------------


def test_from_config_reads_the_deepseek_v32_keys(model):
    cfg, spec, _ = model
    ix = spec.latent.indexer
    assert (ix.heads, ix.head_dim, ix.topk, ix.rope_dim) == (4, 16, 16, 8)
    assert (spec.n_group, spec.topk_group) == (4, 2)
    assert [ly.ffn for ly in spec.layers] == ["dense", "moe", "moe"]
    assert spec.rope_full.kind == "yarn" and spec.rope_full.interleave
    assert spec.rope_full.attention_factor == 1.0
    m = 0.1 * np.log(40) + 1
    assert spec.latent.softmax_scale == pytest.approx(24 ** -0.5 * m * m)
    # a page id's bytes count both arrays of every layer
    assert spec.page_values("full", PAGE) == 3 * PAGE * (128 + 16)
    pages = hybrid.init_kv_pages(spec, 5, 0, PAGE)
    assert [a.shape for a in pages["l0"]] == [(5, PAGE, 128), (5, PAGE, 16)]


def test_a_file_without_an_indexer_has_none_and_no_groups():
    cfg = tiny_cfg()
    for k in ("index_topk", "index_n_heads", "index_head_dim", "n_group",
              "topk_group"):
        del cfg[k]
    spec = hybrid.ModelSpec.from_config(cfg, experts_total=16)
    assert spec.latent.indexer is None and spec.n_group == 1
    pages = hybrid.init_kv_pages(spec, 5, 0, PAGE)
    assert len(pages["l0"]) == 1
    assert "ix_wk" not in hybrid.init_params(spec, jax.random.key(0))["l0"]


@pytest.mark.parametrize("change, match", [
    ({"n_group": 3}, "whole groups"),
    ({"topk_group": 5}, "whole groups"),
    ({"norm_topk_prob": False}, "not renormalised")])
def test_from_config_refuses_what_the_family_does_not_build(change, match):
    with pytest.raises(ValueError, match=match):
        hybrid.ModelSpec.from_config(tiny_cfg(**change), experts_total=16)


def test_from_config_names_the_missing_rope_keys():
    cfg = tiny_cfg()
    del cfg["rope_scaling"]
    with pytest.raises(ValueError, match="rope_scaling"):
        hybrid.ModelSpec.from_config(cfg, experts_total=16)


# the selection ---------------------------------------------------------------


@pytest.mark.parametrize("k, R, L, kernel", [
    (1, 12, 512, False), (16, 12, 512, False), (40, 12, 512, False),
    (128, 12, 512, False),       # 12 rows are not whole groups of 8
    (128, 8, 512, True), (128, 32, 1408, True),
    (256, 8, 2048, True), (256, 32, 4224, True),
    (2048, 8, 4480, True), (2048, 32, 8192, True), (2048, 8, 2048, True),
    (128, 8, 8192, True), (256, 16, 17408, True),   # a last piece in part
    (256, 20, 1024, False),
    (120, 8, 512, False),        # a list that is not whole lane tiles
    (128, 8, 128 * 16411, False)])   # a group's blocks past the VMEM asked for
def test_the_selection_is_top_k_with_ties_to_the_lower_position(k, R, L,
                                                                kernel):
    """Constructed ties (a seventh of the scores one value, a row of zeros, a
    row of negative zeros beside positive ones, whole lane tiles of one
    value, ``-inf`` inside the row's length) and every kind of row length (0,
    1, ``k - 1``, ``k``, ``k + 1``, L): the list is ``lax.top_k``'s set,
    ascending, and the places past the count name 0. Where the sizes allow
    (:func:`dsa.select_kernel_supported`) the list comes from the ONE kernel
    (the interpreter here) and equals XLA's form to the bit; where they do
    not, XLA's form runs."""
    assert dsa.select_kernel_supported(R, L, k) == kernel
    if L > 1 << 20:     # only the choice is asked of a row this long
        text = str(jax.make_jaxpr(lambda s, n: dsa.select_tokens(s, n, k))(
            jax.ShapeDtypeStruct((R, L), jnp.float32),
            jax.ShapeDtypeStruct((R,), jnp.int32)))
        assert "pallas_call" not in text
        return
    rng = np.random.default_rng(k + R + L)
    s = rng.standard_normal((R, L)).astype(np.float32)
    s[:, ::7] = 0.5
    s[3] = 0.0
    s[4, :100] = -0.0
    s[4, 1:100:2] = 0.0
    s[5, 200:] = s[5, 199]
    s[6, 128:384] = 3.0          # two whole lane tiles of the largest value
    s[7, 5:L // 2] = -np.inf     # fewer finite scores than the list is long
    lengths = [0, 1, k - 1, k, k + 1, L, L, L, 5, 39, L - 1, 64]
    n_valid = np.clip(np.array([lengths[r % 12] for r in range(R)]), 0,
                      L).astype(np.int32)
    sm = np.where(np.arange(L)[None] < n_valid[:, None], s, -np.inf)
    select = jax.jit(lambda s, n: dsa.select_tokens(s, n, k))
    assert ("pallas_call" in str(jax.make_jaxpr(select)(sm, n_valid))
            ) == kernel
    idx, count = select(jnp.asarray(sm), jnp.asarray(n_valid))
    mask, mcount = dsa.selection_mask(jnp.asarray(sm), jnp.asarray(n_valid),
                                      k)
    np.testing.assert_array_equal(idx, dsa.compact(mask, mcount, k))
    np.testing.assert_array_equal(count, mcount)
    # (a -0.0 counts as the +0.0 beside it: the sort of `lax.top_k` on a
    # CPU ranks it lower, so it is shown the sum with +0.0)
    _, want = jax.lax.top_k(jnp.asarray(sm + np.float32(0.0)), k)
    for r in range(R):
        c = min(k, n_valid[r])
        assert count[r] == c
        assert np.asarray(idx[r, :c]).tolist() == sorted(
            np.asarray(want[r, :c]).tolist()), r
        assert not np.asarray(idx[r, c:]).any()


@pytest.mark.parametrize("start", [0, 40, 200])
def test_the_chunk_kernel_is_the_index_scores(start):
    rng = np.random.default_rng(start)
    T, J, D = 32, 4, 16
    qi = jnp.asarray(rng.standard_normal((T, J, D)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((T, J)), jnp.float32)
    keys = jnp.asarray(rng.standard_normal((256, D)), jnp.float32)
    want = np.asarray(dsa.index_scores(qi, w, keys, start + jnp.arange(T)))
    got = np.asarray(dsa.index_scores_chunk(qi, w, keys, start, tq=8, tk=64,
                                            interpret=True))
    seen = np.isfinite(want)
    assert (np.isfinite(got) == seen).all()
    np.testing.assert_allclose(got[seen], want[seen], atol=1e-5)
    # a short chunk: the tiles of queries wholly past its 11 tokens are not
    # computed and read -inf; the tile that holds the last token is whole
    short = np.asarray(dsa.index_scores_chunk(qi, w, keys, start, 11, tq=8,
                                              tk=64, interpret=True))
    np.testing.assert_array_equal(short[:16], got[:16])
    assert np.isneginf(short[16:]).all()
    short = np.asarray(dsa.index_scores_chunk(qi, w, keys, start, 3, tq=8,
                                              tk=64, interpret=True))
    np.testing.assert_array_equal(short[:8], got[:8])
    assert np.isneginf(short[8:]).all()


def test_the_page_walk_kernel_is_the_gathered_index_scores():
    rng = np.random.default_rng(1)
    B, W, J, D = 4, 6, 4, 16
    qi = jnp.asarray(rng.standard_normal((B, J, D)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((B, J)), jnp.float32)
    slab = jnp.asarray(rng.standard_normal((20, 16, D)), jnp.float32)
    tables = jnp.asarray(rng.integers(1, 20, (B, W)), jnp.int32)
    lengths = jnp.asarray([1, 50, 96, 16], jnp.int32)
    want = np.asarray(dsa.index_scores_gather(qi, w, slab, tables, lengths))
    got = np.asarray(dsa.index_scores_paged(qi, w, slab, tables, lengths,
                                            interpret=True))
    seen = np.isfinite(want)
    assert (np.isfinite(got) == seen).all()
    assert seen.sum(axis=1).tolist() == [1, 50, 96, 16]
    np.testing.assert_allclose(got[seen], want[seen], atol=1e-5)


# programs against the reference ----------------------------------------------


@pytest.fixture
def recorded(monkeypatch):
    """Every selection the programs make, as ``(positions, lists, counts)``
    in the order the layers made them."""
    made = []
    inner = hybrid._attend_selected_tokens

    def recording(q, ctx, scores, n_valid, la, tile, entry_of=None,
                  live=None):
        L = scores.shape[1]
        padded = jnp.pad(scores, ((0, 0), (0, -L % 128)),
                         constant_values=-jnp.inf)
        idx, count = dsa.select_tokens(padded, n_valid,
                                       min(la.indexer.topk, padded.shape[1]))
        jax.debug.callback(lambda *a: made.append([np.asarray(x) for x in a]),
                           n_valid - 1, idx, count, ordered=True)
        return inner(q, ctx, scores, n_valid, la, tile, entry_of, live)

    monkeypatch.setattr(hybrid, "_attend_selected_tokens", recording)
    jax.clear_caches()
    yield made
    jax.clear_caches()


@pytest.mark.parametrize("kernel, n, steps", [
    ("gather", 109, 12),   # past index_topk, a chunk and a page in decode
    ("pallas", 109, 12),
    ("gather", 13, 8),     # decode crosses index_topk and a page
    ("pallas", 40, 10)])   # the chunk that holds position index_topk
def test_chunked_prefill_then_decode_agree_with_the_reference(
        model, recorded, kernel, n, steps):
    """Logits within float32's sums AND every selection the reference's:
    prefill in chunks (the first wholly below ``index_topk``: every position;
    the others by the index scores), then decode through the cache."""
    cfg, spec, params = model
    with jax.default_matmul_precision("highest"):
        toks, served, _ = serve_one(spec, params, prompt_of(n), steps, kernel)
        want = ref_logits(params, cfg, toks, n)
        sets = reference.selections(params, cfg, toks[:-1], PAD, segment=SEG)
    np.testing.assert_allclose(served, want, atol=TIGHT)
    jax.effects_barrier()
    assert len(recorded) % spec.n_layers == 0 and recorded
    compared = 0
    for i, (positions, idx, count) in enumerate(recorded):
        layer = i % spec.n_layers
        # a chunk's rows past the prompt are padding; of a decode call's
        # three rows the middle one is live
        rows = range(CHUNK) if len(positions) == CHUNK else (1,)
        for r in rows:
            p, c = positions[r], count[r]
            if len(positions) == CHUNK and p >= n:
                continue
            assert idx[r][:c].tolist() == sets[layer][p], (layer, p)
            compared += 1
    assert compared >= spec.n_layers * (steps - 1)


def test_the_reference_gathered_form_is_its_masked_form(model):
    cfg, _, params = model
    toks = prompt_of(120, seed=5)
    at = np.arange(90, 119)
    with jax.default_matmul_precision("highest"):
        a = reference.logits_at(params, cfg, toks, at, PAD, segment=SEG)
        b = reference.logits_at(params, cfg, toks, at, PAD, segment=SEG,
                                gathered=True)
        memo = {}
        c = reference.logits_at(params, cfg, toks, at, PAD, segment=SEG,
                                memo=memo)
        d = reference.logits_at(params, cfg, toks, at, PAD, segment=SEG,
                                memo=memo)   # its first segments from memo
    np.testing.assert_allclose(a, b, atol=TIGHT)
    assert len(memo) == 3
    np.testing.assert_array_equal(a, c)
    np.testing.assert_allclose(c, d, atol=1e-6)


def test_a_dirty_pool_changes_nothing(model):
    cfg, spec, params = model
    dirty = jax.tree.map(lambda a: a + 3.0,
                         init_kv_pages(params, 48, PAGE, spec))
    with jax.default_matmul_precision("highest"):
        toks, served, _ = serve_one(spec, params, prompt_of(70, 2), 6,
                                    "pallas", pages=dirty)
        want = ref_logits(params, cfg, toks, 70)
    np.testing.assert_allclose(served, want, atol=TIGHT)


def shared(spec, params, copy=None, lose=False):
    """Row A (85 tokens, pages 1..) prefills. Row B (its first 64 tokens
    A's, then its own, 81 in all) takes A's first eight pages (or, with
    ``copy``, copies of them made by it) and prefills from 64; with ``lose``
    the last four of them are gone (overwritten, as an evicted page is), so
    it shares four and prefills from 32. Returns B's tokens and logits."""
    a = prompt_of(85, seed=7)
    b = np.concatenate([a[:64], prompt_of(17, seed=8)])
    _, _, pages = serve_one(spec, params, a, 2, chunk=PAGE)
    keep = 4 if lose else 8
    table = _table(20, 12, PAGE)      # 81 + 8 tokens: twelve pages
    table[:keep] = np.arange(1, keep + 1)
    if lose:
        pages = {name: tuple(t.at[5:9].set(7.0) for t in arrays)
                 for name, arrays in pages.items()}
    if copy is not None:
        for p in range(1, 9):
            pages = copy(pages, p, 38 + p)
        table[:8] = np.arange(39, 47)
    toks, served, _ = serve_one(spec, params, b, 8, pages=pages, chunk=PAGE,
                                start=keep * PAGE, table=table)
    return toks, served


@pytest.mark.parametrize("how", ["shared", "copied", "evicted"])
def test_a_row_that_enters_from_pages_and_their_index_keys(model, how):
    """A request that shares a whole-page prefix with another (the pages
    themselves, copy-on-write copies of them, or what is left after half of
    them was evicted) agrees with the reference's full pass, which never saw
    a cache: its selection reads the shared pages' index keys."""
    cfg, spec, params = model
    copy = (lambda pages, s, d: hybrid.kv_page_copy(pages, s, d, spec)) \
        if how == "copied" else None
    with jax.default_matmul_precision("highest"):
        toks, served = shared(spec, params, copy=copy, lose=how == "evicted")
        want = ref_logits(params, cfg, toks, 81)
    np.testing.assert_allclose(served, want, atol=TIGHT)


# each flaw fails -------------------------------------------------------------


@pytest.fixture(scope="module")
def served_once(model):
    cfg, spec, params = model
    with jax.default_matmul_precision("highest"):
        toks, served, _ = serve_one(spec, params, prompt_of(109), 12)
    return toks, served


@pytest.mark.parametrize("flaw", reference.FLAWS)
def test_each_flaw_fails_the_comparison(flaw, model, served_once):
    """Every flaw of the reference's table, put into the reference's place:
    the programs' logits then miss it by more than ``CAUGHT``."""
    cfg, _, params = model
    toks, served = served_once
    with jax.default_matmul_precision("highest"):
        bent = ref_logits(params, cfg, toks, 109, flaw=flaw)
    assert np.abs(served - bent).max() > CAUGHT


def test_a_copied_page_without_its_index_keys_fails(model):
    cfg, spec, params = model

    def copy(pages, src, dst):     # the latents move, the index keys stay
        moved = hybrid.kv_page_copy(pages, src, dst, spec)
        return {name: (arrays[0], arrays[1].at[dst].set(0))
                for name, arrays in moved.items()}

    with jax.default_matmul_precision("highest"):
        toks, served = shared(spec, params, copy=copy)
        want = ref_logits(params, cfg, toks, 81)
    assert np.abs(served - want).max() > CAUGHT


# the engine ------------------------------------------------------------------


BUCKETS = ((96, 16),)
#: (document, question tokens, steps)
SESSIONS = ((0, 5, 4), (1, 9, 6), (0, 11, 8), (1, 3, 3), (0, 20, 16),
            (1, 30, 4), (0, 2, 9), (1, 14, 5))


def _sessions():
    docs = [prompt_of(64, seed=100 + d) for d in range(2)]
    return [Request(prompt=np.concatenate([docs[d], prompt_of(turn, seed=i)]),
                    steps=steps, temperature=0.0)
            for i, (d, turn, steps) in enumerate(SESSIONS)]


@pytest.fixture(scope="module")
def served(model, tmp_path_factory):
    """Eight questions over two documents through a ServeEngine with the
    prefix cache on, under a profiler capture."""
    cfg, spec, params = model
    eng = ServeEngine(params, spec, buckets=BUCKETS, max_batch=3,
                      page_len=PAGE, prefill_chunk=CHUNK, num_pages=64,
                      prefix_cache=True, start=False)
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
            "spans": engine_spans.load(find_xplane(where))["spans"]}


def test_the_engine_serves_the_reference_from_shared_documents(served, model):
    """Every request ok and every served token the reference's first choice
    (float32, greedy), though most rows entered from another row's pages and
    index keys and never prefilled their document."""
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


def test_the_spans_carry_the_dsa_counters(served, model):
    cfg, spec, params = model
    by = {}
    for s in served["spans"]:
        by.setdefault(s.name, []).append(s.fields)
    calls = {f["seq"]: f for f in by["serve.decode.dispatch"] if f.get("rows")}
    syncs = [f for f in by["serve.decode.sync"]
             if "dsa_tokens_held" in f and f["seq"] in calls]
    assert syncs
    for f in syncs:
        rows = calls[f["seq"]]["rows"]
        # every prompt is past index_topk: 16 of a row's 66-110 tokens, in
        # each of the 3 layers
        assert f["dsa_rows"] == rows
        assert f["dsa_tokens_attended"] == rows * TOPK * 3
        assert rows * 66 * 3 <= f["dsa_tokens_held"] <= rows * 110 * 3
    # a final chunk's landing: the tokens of a question's LAST chunk selected
    # (the chunk lies past index_topk), and each scored its whole context in
    # every layer
    firsts = [f for f in by["serve.prefill.sync"] if f.get("final")]
    assert len(firsts) == len(SESSIONS)
    assert sorted(f["dsa_queries"] for f in firsts) == sorted(
        (turn - 1) % CHUNK + 1 for _, turn, _ in SESSIONS)
    for f in firsts:
        q = f["dsa_queries"]
        assert 3 * q * 64 < f["dsa_pairs_scored"] <= 3 * q * 96


def test_padding_is_skipped_a_tile_at_a_time_and_the_lists_are_counted(model):
    """A tile of rows none of which is live is not computed: it reads zeros
    and attends nothing; every other row reports its own list's length,
    ``min(n_valid, index_topk)``; the live rows' output is what all rows
    computed give."""
    cfg, spec, params = model
    la, rng = spec.latent, np.random.default_rng(3)
    R, L, H = 12, 40, 4
    q = jnp.asarray(rng.standard_normal((R, H, la.entry_width)), jnp.float32)
    ctx = jnp.asarray(rng.standard_normal((L, la.entry_width)), jnp.float32)
    n_valid = jnp.asarray([3, 20, 40, 17, 9, 30, 1, 1, 1, 1, 1, 1], jnp.int32)
    scores = jnp.where(jnp.arange(L)[None, :] < n_valid[:, None],
                       jnp.asarray(rng.standard_normal((R, L)), jnp.float32),
                       -jnp.inf)
    live = jnp.arange(R) < 5       # tiles of 4: the third one is padding
    out, took = hybrid._attend_selected_tokens(q, ctx, scores, n_valid, la, 4,
                                               live=live)
    full, all_took = hybrid._attend_selected_tokens(q, ctx, scores, n_valid,
                                                    la, 4)
    assert np.asarray(all_took).tolist() == np.minimum(n_valid, TOPK).tolist()
    assert np.asarray(took).tolist() == np.minimum(n_valid, TOPK).tolist()[:8] \
        + [0] * 4
    np.testing.assert_array_equal(np.asarray(out[:8]), np.asarray(full[:8]))
    assert not np.asarray(out[8:]).any() and np.asarray(full[8:]).any()


def test_a_decode_call_returns_what_its_live_rows_read(model):
    """The decode program counts on the device: the entries its lists held
    and the tokens the contexts hold, summed over the live rows and the
    layers, and the rows past ``index_topk``; a dummy row counts nothing."""
    cfg, spec, params = model
    layers = len(spec.layer_names("latent"))
    for n in (29, 9):       # past index_topk, and below it
        toks, _, pages = serve_one(spec, params, prompt_of(n), 1)
        need = -(-(n + 1) // PAGE)
        gt = np.zeros((3, need), np.int32)
        gt[1] = _table(1, need)[:need]
        zeros = np.zeros(3)
        _, _, counts, _ = lm_decode_paged(
            params, pages, (gt, np.zeros((3, 0), np.int32)),
            np.array([0, n, 0]), np.array([0, toks[-1], 0]), zeros, zeros,
            zeros, np.ones(3), zeros, heads=spec, page_len=PAGE,
            kernel="gather")
        assert np.asarray(counts)[3:].tolist() == [
            min(n + 1, TOPK) * layers, (n + 1) * layers, int(n + 1 > TOPK)]


@pytest.mark.parametrize("Q, L, k", [(8, 256, 16), (5, 96, 16),
                                     (4, 1024, 300), (3, 40, 64)])
def test_the_references_selection_is_top_k_without_the_sort(Q, L, k):
    """``reference.largest`` + ``reference.listed`` give ``lax.top_k``'s set,
    ascending, on scores full of ties (a whole row of one value too)."""
    rng = np.random.default_rng(L)
    sc = (np.round(rng.standard_normal((Q, L)) * 2) / 2).astype(np.float32)
    sc[0] = 0.0
    pos = rng.integers(0, L, Q)
    pos[0] = L - 1
    can = np.arange(L)[None, :] <= pos[:, None]
    k = min(k, L)
    vals, idx = jax.lax.top_k(jnp.where(can, sc, -jnp.inf), k)
    want = [sorted(i[np.isfinite(v)].tolist())
            for i, v in zip(np.asarray(idx), np.asarray(vals))]
    mask = reference.largest(jnp.asarray(sc), jnp.asarray(can), k)
    got, took = (np.asarray(a) for a in reference.listed(mask, k))
    assert [g[t].tolist() for g, t in zip(got, took)] == want
    assert [np.nonzero(m)[0].tolist() for m in np.asarray(mask)] == want
