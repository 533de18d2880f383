"""The config-built decoder (models/hybrid.py) on the CPU at a small size:
hidden 64, head size 16, 2 KV heads, 4 and 6 query heads, window 16, 5
layers ``F(dense) S S S F``, 16 experts top-4, vocabulary 96, seeded weights.
Everything is compared with ``benchmarks/reference/serve_laguna.py`` (the
equations in plain float32) in LOGITS, not tokens."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import serve_laguna as reference
from marlin_tpu.models import hybrid
from marlin_tpu.models.moe import moe_experts_ffn
from marlin_tpu.models.planner import kv_page_bytes, request_pages
from marlin_tpu.models.transformer import (init_kv_pages, lm_decode_paged,
                                           lm_generate, lm_prefill_paged)
from marlin_tpu.serving import Request, ServeEngine
from marlin_tpu.serving.engine import MigrationError
from marlin_tpu.serving.kvpool import (PagedGroup, PagedKVPool,
                                       decode_inputs)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PAGE, CHUNK = 8, 16


def tiny_cfg(**over):
    cfg = {
        "hidden_size": 64, "head_dim": 16, "num_key_value_heads": 2,
        "num_hidden_layers": 5, "num_attention_heads": 4,
        "layer_types": ["full_attention"] + ["sliding_attention"] * 3
        + ["full_attention"],
        "num_attention_heads_per_layer": [4, 6, 6, 6, 4],
        "mlp_layer_types": ["dense"] + ["sparse"] * 4,
        "sliding_window": 16, "intermediate_size": 128,
        "moe_intermediate_size": 32, "shared_expert_intermediate_size": 32,
        "num_experts": 16, "num_experts_per_tok": 4,
        "moe_routed_scaling_factor": 2.5, "vocab_size": 96,
        "rms_norm_eps": 1e-6,
        "rope_parameters": {
            "full_attention": {
                "rope_theta": 500000, "rope_type": "yarn", "factor": 128,
                "original_max_position_embeddings": 8192, "beta_slow": 1,
                "beta_fast": 32, "attention_factor": 1.4852030263919618,
                "partial_rotary_factor": 0.5},
            "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                                  "partial_rotary_factor": 1}},
        "param_dtype": "float32", "compute_dtype": "float32"}
    cfg.update(over)
    return cfg


@pytest.fixture(scope="module")
def model():
    cfg = tiny_cfg()
    spec = hybrid.ModelSpec.from_config(cfg)
    return cfg, spec, hybrid.init_params(spec, jax.random.key(0))


def _serve_one(spec, params, prompt, steps, kernel):
    """Chunked paged prefill, then decode through the cache in a bucket of
    three rows (the middle one live); returns the tokens and the float32
    logits every served token was picked from."""
    n = len(prompt)
    ring = hybrid.window_ring_pages(spec.window, CHUNK, PAGE)
    need = -(-(n + steps) // PAGE)
    gtable = np.zeros(need + CHUNK // PAGE, np.int32)
    gtable[:need] = np.arange(1, need + 1)
    wtable = np.arange(1, ring + 1, dtype=np.int32)
    pages = init_kv_pages(params, 32, PAGE, spec, window_pages=16)
    # a token's KV heads side by side in one row, in both page classes
    row = spec.kv_heads * spec.head_dim
    assert [pages[name][0].shape for name in ("l0", "l1")] == [
        (32, PAGE, row), (16, PAGE, row)]
    padded = np.zeros(-(-n // CHUNK) * CHUNK, np.int32)
    padded[:n] = prompt
    for cs in range(0, len(padded), CHUNK):
        pages, first, _, logits = lm_prefill_paged(
            params, pages, (gtable, wtable), padded[cs:cs + CHUNK], cs, n,
            heads=spec, page_len=PAGE)
    toks, served = list(prompt) + [int(first)], [np.asarray(logits)]
    B = 3
    gt = np.zeros((B, need), np.int32)
    gt[1] = gtable[:need]
    wt = np.zeros((B, ring), np.int32)
    wt[1] = wtable
    zeros = np.zeros(B)
    for t in range(steps - 1):
        pages, nxt, _, logits = lm_decode_paged(
            params, pages, (gt, wt), np.array([0, n + t, 0]),
            np.array([0, toks[-1], 0]), zeros, zeros, zeros, np.ones(B),
            zeros, heads=spec, page_len=PAGE, kernel=kernel)
        toks.append(int(nxt[1]))
        served.append(np.asarray(logits[1]))
    return np.asarray(toks), np.stack(served)


# (a) ---------------------------------------------------------------------


@pytest.mark.parametrize("kernel", ["gather", "pallas"])
def test_paged_prefill_then_decode_match_the_reference_logits(model, kernel):
    """37 prompt tokens (three chunks of 16, five pages of 8) and 22 served
    tokens: longer than the window of 16, across page and chunk boundaries,
    the window ring wrapping several times. Tolerance 1e-4 on logits of
    size ~4: both sides are float32, and only the order of the sums differs
    (chunked softmax over a gathered context or the kernel's online softmax
    against one dense softmax; grouped against per-expert matmuls); the
    measured difference is 1e-5."""
    cfg, spec, params = model
    prompt = np.random.default_rng(0).integers(0, 96, 37).astype(np.int32)
    toks, got = _serve_one(spec, params, prompt, 22, kernel)
    want = np.asarray(reference.logits_at(
        params, cfg, toks[:-1], np.arange(36, len(toks) - 1), 96))
    assert np.abs(want).max() > 1.0
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=0)


@pytest.mark.parametrize("flaw", reference.FLAWS)
def test_the_comparison_sees_each_missing_piece(model, flaw):
    """A reference with one piece of the mathematics left out (the x 2.5, the
    gate, a window off by one, the two RoPEs swapped, one expert pick
    dropped) is far from what the program serves: at least 0.5 in logits."""
    cfg, spec, params = model
    prompt = np.random.default_rng(1).integers(0, 96, 29).astype(np.int32)
    toks, got = _serve_one(spec, params, prompt, 12, "gather")
    at = np.arange(28, len(toks) - 1)
    sound = np.asarray(reference.logits_at(params, cfg, toks[:-1], at, 64))
    flawed = np.asarray(reference.logits_at(params, cfg, toks[:-1], at, 64,
                                            flaw=flaw))
    assert np.abs(got - sound).max() < 1e-4
    assert np.abs(got - flawed).max() > 0.5


def test_a_bfloat16_model_stays_near_the_reference(model):
    """bfloat16 parameters and compute: the reference (float32 arithmetic on
    the same bfloat16 weights) is met to a median 0.05 in logits of size ~4
    (8 bits of mantissa through five layers). At hidden 64 a near-tie in the
    router now and then picks another fourth expert and moves a logit by
    tenths, so a served token may lie up to 0.6 under the reference's best;
    most are the reference's best."""
    cfg = tiny_cfg(param_dtype="bfloat16", compute_dtype="bfloat16")
    spec = hybrid.ModelSpec.from_config(cfg)
    params = hybrid.init_params(spec, jax.random.key(0))
    prompt = np.random.default_rng(2).integers(0, 96, 21).astype(np.int32)
    toks, got = _serve_one(spec, params, prompt, 8, "pallas")
    want = np.asarray(reference.logits_at(
        params, cfg, toks[:-1], np.arange(20, len(toks) - 1), 32))
    assert np.median(np.abs(got - want)) < 0.05
    gaps = reference.served_gaps(params, cfg, toks, 21, 32, 8)["gaps"]
    assert gaps.max() < 0.6 and np.median(gaps) == 0.0


# (b), (c) ------------------------------------------------------------------


def _expert_layer_by_hand(mp, h, top_k, scale, scoring="softmax"):
    """The uncut expert layer, token by token, pick by pick. ``sigmoid``:
    the picks by score + ``e_bias``, the weights from the scores alone."""
    h = np.asarray(h, np.float64)
    f = lambda a: np.asarray(a, np.float64)  # noqa: E731
    silu = lambda x: x / (1 + np.exp(-x))  # noqa: E731
    logits = h @ f(mp["router"])
    if scoring == "sigmoid":
        s = 1 / (1 + np.exp(-logits))
        by = s + f(mp["e_bias"])
    else:
        s = np.exp(logits - logits.max(-1, keepdims=True))
        s /= s.sum(-1, keepdims=True)
        by = s
    out = (silu(h @ f(mp["s_gate"])) * (h @ f(mp["s_up"]))) @ f(mp["s_down"])
    for t in range(h.shape[0]):
        picks = np.argsort(-by[t], kind="stable")[:top_k]
        for i in picks:
            e = (silu(h[t] @ f(mp["e_gate"][i])) * (h[t] @ f(mp["e_up"][i]))
                 ) @ f(mp["e_down"][i])
            out[t] += scale * s[t, i] / s[t, picks].sum() * e
    return out


def _share(mp, first, held):
    cut = {k: v for k, v in mp.items()}
    for k in ("e_gate", "e_up", "e_down"):
        cut[k] = mp[k][first:first + held]
    return cut


@pytest.mark.parametrize("shares, scoring", [
    (4, "softmax"), (2, "softmax"), (1, "softmax"),
    (8, "sigmoid")])    # the solar_open2 family's: eight chips share a layer
def test_the_shares_of_the_expert_layer_add_up(model, shares, scoring):
    """What each of ``shares`` chips computes for its own experts, with the
    shared expert (which every chip computes alike) counted once, is the
    uncut layer; under sigmoid scoring with a bias that only selects as
    under the softmax."""
    _, spec, params = model
    mp = params["l2"]["moe"]
    if scoring == "sigmoid":
        mp = dict(mp, e_bias=0.05 * jax.random.normal(jax.random.key(6),
                                                      (16,), jnp.float32))
    h = jax.random.normal(jax.random.key(5), (24, 64), jnp.float32)
    valid = jnp.ones((24,), bool)
    held = 16 // shares
    kw = dict(top_k=4, routed_scale=2.5, scoring=scoring)
    outs = [moe_experts_ffn(_share(mp, k * held, held), h, valid,
                            first_expert=k * held, **kw)
            for k in range(shares)]
    shared_only, _ = moe_experts_ffn(_share(mp, 0, held), h,
                                     jnp.zeros((24,), bool), first_expert=0,
                                     **kw)
    total = sum(np.asarray(o, np.float64) for o, _ in outs) \
        - (shares - 1) * np.asarray(shared_only, np.float64)
    np.testing.assert_allclose(
        total, _expert_layer_by_hand(mp, h, 4, 2.5, scoring), atol=2e-5)
    counts = np.sum([np.asarray(c) for _, c in outs], axis=0)
    assert counts[1] == 24 * 4          # every assignment fell on one share
    assert all(int(c[0]) == 24 * 4 for _, c in outs)


def test_a_decode_bucket_and_a_prefill_chunk_route_alike_and_drop_nothing(
        model):
    """A router skewed to one expert (every token's first pick): the GShard
    layer would overflow that expert's capacity; here all 16 x 4 assignments
    are computed, as a chunk of 16 tokens or as a bucket of 24 rows whose
    other 8 are dummies that touch no expert."""
    _, spec, params = model
    mp = dict(params["l1"]["moe"])
    mp["router"] = mp["router"].at[:, 7].add(5.0 * jnp.ones((64,)) / 8)
    h = jax.random.normal(jax.random.key(6), (16, 64), jnp.float32)
    h = h + 1.0                      # a positive mean: the skew bites
    chunk, c_counts = moe_experts_ffn(mp, h, jnp.ones((16,), bool), top_k=4,
                                      routed_scale=2.5)
    rows = np.arange(24) % 3 != 2    # 16 live rows among 24
    bucket_h = jnp.zeros((24, 64)).at[np.flatnonzero(rows)].set(h)
    bucket, b_counts = moe_experts_ffn(mp, bucket_h, jnp.asarray(rows),
                                       top_k=4, routed_scale=2.5)
    np.testing.assert_allclose(np.asarray(bucket)[rows], np.asarray(chunk),
                               atol=1e-6)
    np.testing.assert_array_equal(np.asarray(b_counts), np.asarray(c_counts))
    assert list(np.asarray(c_counts)[:2]) == [64, 64]   # none dropped
    np.testing.assert_allclose(np.asarray(chunk, np.float64),
                               _expert_layer_by_hand(mp, h, 4, 2.5),
                               atol=2e-5)
    none, counts = moe_experts_ffn(mp, h, jnp.zeros((16,), bool), top_k=4)
    assert list(np.asarray(counts)) == [0, 0, 0]


# (d), (e), (f) -------------------------------------------------------------


def _engine(spec, params, **kw):
    kw = {"buckets": [(24, 8), (72, 24)], "max_batch": 3, "page_len": PAGE,
          "num_pages": 64, "window_pages": 24, "prefill_chunk": CHUNK, **kw}
    return ServeEngine(params, spec, **kw)


@pytest.mark.parametrize("kernel", ["gather", "pallas"])
def test_the_engine_serves_a_share_and_both_page_classes_balance(kernel):
    """Seven requests (5-70 prompt tokens, up to 24 steps) through submit,
    admission, the paged pool, chunked prefill, decode and the sampling
    tail, on an expert share (experts 4-7 of 16): every served token is the
    reference's best; a row's ring never holds more than its bound; the
    audit balances both classes while rows are resident and after."""
    cfg = tiny_cfg(num_experts=4, deployment_share={"first_expert": 4})
    spec = hybrid.ModelSpec.from_config(cfg, experts_total=16, first_expert=4)
    params = hybrid.init_params(spec, jax.random.key(1))
    rng = np.random.default_rng(3)
    sizes = [(5, 8), (20, 6), (33, 24), (70, 20), (41, 3), (17, 8), (64, 24)]
    with _engine(spec, params, decode_kernel=kernel, start=False) as eng:
        ring = eng._ring
        assert ring == hybrid.window_ring_pages(16, CHUNK, PAGE) == 3
        reqs = [Request(prompt=rng.integers(0, 96, n).astype(np.int32),
                        steps=s) for n, s in sizes]
        handles = [eng.submit(r) for r in reqs]
        eng.start()
        seen = 0
        while not all(h.done() for h in handles):
            audit = eng.kvpool_audit()
            if audit["ok"]:  # advisory while the worker runs
                seen = max(seen, audit.get("window_used", 0))
            for g in list(eng._pools.values()):
                assert all(len(p or ()) <= ring for p in g.window_row_pages)
        results = [h.result(timeout=120) for h in handles]
    assert [r.status for r in results] == ["ok"] * 7
    assert 0 < seen <= 3 * 2 * ring
    audit = eng.kvpool_audit()
    assert audit["ok"], audit["errors"]
    assert audit["used"] == 0 and audit["window_used"] == 0
    for r, q in zip(results, reqs):
        gaps = reference.served_gaps(params, cfg, r.tokens, len(q.prompt),
                                     96, 24)["gaps"]
        assert gaps.max() < 1e-4


@pytest.mark.parametrize("kernel", ["gather", "pallas"])
def test_rows_with_rings_in_two_buckets_share_one_decode_call(
        model, monkeypatch, kernel):
    """One row in the small bucket and two in the large one, live together:
    every decode call carries the rows of both (their global tables laid
    into the wide bucket's width, their rings side by side), one call a
    step, and every served token is the reference's best."""
    from marlin_tpu.models import transformer

    cfg, spec, params = model
    handed = []
    decode = transformer.lm_decode_paged

    def spy(*args, **kw):
        gtables, rings = (np.asarray(t) for t in args[2])
        live = gtables[:, 0] != 0
        handed.append((gtables.shape, rings.shape, int(live.sum()),
                       bool((rings[live] != 0).any(axis=1).all())))
        return decode(*args, **kw)

    rng = np.random.default_rng(5)
    sizes = [(9, 8), (40, 12), (14, 20)]  # buckets (24, 8), (72, 24) twice
    reqs = [Request(prompt=rng.integers(0, 96, n).astype(np.int32), steps=s)
            for n, s in sizes]
    with _engine(spec, params, decode_kernel=kernel, prefill_chunk=64,
                 start=False) as eng:
        eng.warmup()
        monkeypatch.setattr(transformer, "lm_decode_paged", spy)
        handles = eng.submit_many(reqs)
        eng.start()
        results = [h.result(timeout=120) for h in handles]
        audit = eng.kvpool_audit()
        ring, width = eng._ring, eng._decode_pages
    assert [r.status for r in results] == ["ok"] * 3
    assert width == -(-(72 + 24) // PAGE)
    # 19 steps of the longest request, a call each; the first seven carry
    # all three rows, which no one bucket holds
    assert len(handed) == 19
    assert {h[:2] for h in handed} == {((3, width), (3, ring))}
    assert [h[2] for h in handed[:7]] == [3] * 7
    assert all(h[3] for h in handed)  # every live row came with its ring
    assert audit["ok"], audit["errors"]
    for r, q in zip(results, reqs):
        gaps = reference.served_gaps(params, cfg, r.tokens, len(q.prompt),
                                     96, 24)["gaps"]
        assert gaps.max() < 1e-4


def test_warmup_compiles_each_bucket_program_once_side_by_side(
        model, monkeypatch):
    """``warmup`` lowers and compiles the engine's programs at once
    (``hybrid.precompile_paged``: a prefill program for each of the two
    buckets and the ONE decode program their rows share) and then runs them:
    the runs must find those executables, so each of the three is compiled
    exactly once (a second compile would double a cold start instead of
    cutting it), and traffic after the warm-up compiles nothing."""
    _, spec, params = model
    compiled, at_end = [], []
    precompile = hybrid.precompile_paged

    def noting(*args):
        precompile(*args)
        at_end.append(len(compiled))

    monkeypatch.setattr(hybrid, "precompile_paged", noting)

    def on(event, *_, **__):
        if event == "/jax/core/compile/backend_compile_duration":
            compiled.append(event)

    hybrid._lm_prefill_paged_spec_jit.clear_cache()
    hybrid._lm_decode_paged_spec_jit.clear_cache()
    jax.monitoring.register_event_duration_secs_listener(on)
    try:
        with _engine(spec, params, decode_kernel="gather",
                     start=False) as eng:
            sizes = (hybrid.prefill_paged._cache_size(),
                     hybrid.decode_paged._cache_size())
            compiled.clear()
            eng.warmup()
            mine = [hybrid.prefill_paged._cache_size() - sizes[0],
                    hybrid.decode_paged._cache_size() - sizes[1]]
            n_warm = len(compiled)
            eng.start()
            res = eng.submit(Request(
                prompt=np.arange(30, dtype=np.int32) % 96, steps=6)
            ).result(timeout=120)
    finally:
        jax.monitoring.unregister_event_duration_listener(on)
    assert res.status == "ok"
    assert mine == [2, 1]
    assert len(compiled) == n_warm  # nothing compiled under traffic
    # the three programs side by side; their runs then compile nothing: what
    # is left is the page copy (three more would mean that the runs did not
    # find the lowered programs)
    assert len(at_end) == 1 and at_end[0] >= 3
    assert n_warm - at_end[0] < 3, (at_end, n_warm)


def test_the_audit_catches_a_leaked_and_an_overlong_ring(model):
    _, spec, params = model
    pool = PagedKVPool(params, spec, 16, PAGE, window_pages=9, ring=3)
    assert pool.stats()["window_total"] == 8 and not pool.prefix_cache_enabled
    group = PagedGroup((24, 8), 2, PAGE, CHUNK, ring=3)
    entry = type("E", (), {"request": Request(
        prompt=np.arange(5, dtype=np.int32), steps=3)})()
    group.assign(0, entry, pool.alloc(1), 0, 0, pool.alloc_window(1))
    assert pool.audit([group])["ok"]
    leaked = pool.alloc_window(2)        # held by no row
    assert any("window page" in e for e in pool.audit([group])["errors"])
    pool.release_window(leaked)
    group.window_row_pages[0] = group.window_row_pages[0] * 4
    assert any("over its bound" in e for e in pool.audit([group])["errors"])
    group.window_row_pages[0] = group.window_row_pages[0][:1]
    ring = group.window_row_pages[0]
    pool.release(group.release(0))
    pool.release_window(ring)
    audit = pool.audit([group])
    assert audit["ok"] and audit["window_used"] == 0
    with pytest.raises(Exception, match="window pages"):
        pool.alloc_window(9)


def test_each_page_class_is_charged_for_what_a_row_can_pin(model):
    _, spec, params = model
    assert kv_page_bytes(params, spec, PAGE) == 2 * 2 * PAGE * 2 * 16 * 4
    assert kv_page_bytes(params, spec, PAGE, kind="sliding") \
        == 3 * 2 * PAGE * 2 * 16 * 4
    assert request_pages(70, 20, PAGE) == 12
    assert request_pages(70, 20, PAGE, ring=3) == 3
    assert request_pages(5, 3, PAGE, ring=3) == 1
    with _engine(spec, params, start=False) as eng:
        lm = eng._programs["lm"]
        long = Request(prompt=np.zeros(70, np.int32), steps=20)
        short = Request(prompt=np.zeros(5, np.int32), steps=3)
        assert lm.admission_cost(long, (72, 24)) \
            == 12 * eng._page_bytes + 3 * eng._window_page_bytes
        assert lm.admission_cost(short, (24, 8)) \
            == eng._page_bytes + eng._window_page_bytes


def test_prefix_cache_and_migration_refuse_a_window_model(model):
    _, spec, params = model
    with pytest.raises(ValueError, match="prefix_cache"):
        _engine(spec, params, prefix_cache=True, start=False)
    with _engine(spec, params, start=False) as eng:
        assert eng._prefix_cache is False
        for call in (eng.freeze_rows, lambda: eng.export_prefixes(4),
                     lambda: eng.import_prefixes(b"x"),
                     lambda: eng.adopt_rows({"entries": {1: None},
                                             "blob": b"x"})):
            with pytest.raises(MigrationError, match="sliding-window"):
                call()
        pool = eng._ensure_kvpool()
        for call in (lambda: pool.export_rows([]),
                     lambda: pool.import_rows(b""),
                     lambda: pool.export_prefixes(1),
                     lambda: pool.import_prefixes(b"")):
            with pytest.raises(NotImplementedError, match="sliding-window"):
                call()


@pytest.mark.parametrize("what", ["lm_generate", "moe knob"])
def test_the_paths_that_cannot_run_a_spec_say_so(model, what):
    _, spec, params = model
    if what == "lm_generate":
        with pytest.raises(TypeError, match="heads: int"):
            lm_generate(params, np.zeros(4, np.int32), jax.random.key(0),
                        heads=spec, max_len=8, steps=2)
    else:
        with pytest.raises(ValueError, match="moe"):
            ServeEngine(params, spec, moe=(2, 1.25, 64), start=False)


def test_the_dense_block_keeps_its_tables_and_its_kernel():
    """`ServeEngine(params, heads: int)`: a group hands the programs ONE
    table as before, and the window variant of the kernel with a window
    that hides nothing equals the plain kernel bit for bit."""
    from marlin_tpu.ops.paged_attention import paged_decode_attention

    g = PagedGroup((24, 8), 2, PAGE, CHUNK)
    tables = decode_inputs([(g, [])], 2, g.pages_per_row, g.ring)[0]
    assert g.ring is None and isinstance(tables, np.ndarray)
    ringed = PagedGroup((24, 8), 2, PAGE, CHUNK, ring=3)
    both = decode_inputs([(ringed, [])], 2, g.pages_per_row, 3)[0]
    assert [t.shape for t in both] == [(2, g.pages_per_row), (2, 3)]
    assert g.prefill_tables(0) is not None and g.prefill_tables(0).ndim == 1
    key = jax.random.key(3)
    q = jax.random.normal(key, (2, 2, 3, 16), jnp.float32)
    slab = jax.random.normal(jax.random.fold_in(key, 1), (9, PAGE, 2, 16))
    tables = np.array([[1, 2, 3, 4], [5, 6, 0, 0]], np.int32)
    lengths = np.array([27, 11], np.int32)
    plain = paged_decode_attention(q, slab, slab, tables, lengths)
    windowed = paged_decode_attention(q, slab, slab, tables, lengths,
                                      first_page=np.zeros(2, np.int32),
                                      lower=np.zeros(2, np.int32))
    np.testing.assert_array_equal(np.asarray(plain), np.asarray(windowed))


def test_the_spec_of_the_benchmarks_configuration():
    """The committed configuration file, read as the program reads it:
    published widths, 9 layers ``F S S S F S S S F``, 64 of 256 experts."""
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "laguna-s21-ep4-l9.json")) as f:
        cfg = json.load(f)
    spec = hybrid.ModelSpec.from_config(
        cfg, experts_total=cfg["deployment_share"]["experts_total"])
    assert hash(spec) == hash(hybrid.ModelSpec.from_config(
        cfg, experts_total=256))
    assert [ly.attn[0] for ly in spec.layers] == list("fsssfsssf")
    assert [ly.q_heads for ly in spec.layers] == [48, 72, 72, 72] * 2 + [48]
    assert [ly.ffn for ly in spec.layers] == ["dense"] + ["moe"] * 8
    assert (spec.d_model, spec.head_dim, spec.kv_heads, spec.window) \
        == (3072, 128, 8, 512)
    assert (spec.n_experts, spec.experts_held, spec.top_k,
            spec.routed_scale) == (256, 64, 10, 2.5)
    assert (spec.dense_width, spec.expert_width, spec.vocab_held) \
        == (12288, 1024, 25088)
    full, sliding = spec.rope_full, spec.rope_sliding
    assert (full.kind, full.rotary_dim, sliding.rotary_dim) \
        == ("yarn", 64, 128)
    f = full.inv_freq()
    assert f.shape == (32,) and np.isclose(f[0], 1.0)     # extrapolated
    assert np.isclose(f[-1], 500000 ** (-62 / 64) / 128)  # interpolated
    np.testing.assert_allclose(sliding.inv_freq(),
                               10000.0 ** (-np.arange(0, 128, 2) / 128),
                               rtol=1e-6)
    np.testing.assert_allclose(
        f, reference.inv_freq(cfg["rope_parameters"]["full_attention"], 128))
    assert hybrid.window_ring_pages(512, 512, 128) == 5
    with pytest.raises(ValueError, match="divide"):
        hybrid.window_ring_pages(512, 512, 96)
