"""A state-space (Mamba-2) mixer beside the attention of every block
(models/hybrid.py, ``LayerSpec.ssm``; ops/ssm.py) and the recurrent-state
slot a row holds beside its pages (serving/kvpool.py), on the CPU at a small
size: hidden 64, 4 query heads over 2 KV heads of 16, a mixer of 4 heads x 8
channels with a state of 16 columns in 2 groups, 4 convolution taps, scan
blocks of 8, the ``falcon_h1`` family's published multipliers, 2 layers,
vocabulary 97, seeded float32 weights. Everything is compared with
``benchmarks/reference/serve_falconh1.py`` (the recurrence token by token,
plain float32) in LOGITS, not tokens."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import engine_spans
from benchmarks.reference import serve_falconh1 as reference
from benchmarks.trace_reduce import find_xplane
from marlin_tpu.models import hybrid
from marlin_tpu.models.transformer import (init_kv_pages, lm_decode_paged,
                                           lm_prefill_paged)
from marlin_tpu.ops import ssm
from marlin_tpu.serving import Request, ServeEngine
from marlin_tpu.serving.engine import MigrationError
from marlin_tpu.serving.kvpool import (PagedGroup, PagedKVPool,
                                       PagePoolExhausted)

PAGE, CHUNK = 8, 16
NO_RING = np.zeros(0, np.int32)
#: program against reference, both float32: sums in another order (blocks of
#: 8 tokens against one token at a time); measured 2e-6 on logits of size 3
TIGHT = 2e-5
#: the least a flaw may move a logit to count as caught: 50 x TIGHT
CAUGHT = 1e-3


def tiny_cfg(**over):
    cfg = {
        "model_type": "falcon_h1", "hidden_size": 64, "head_dim": 16,
        "num_attention_heads": 4, "num_key_value_heads": 2,
        "intermediate_size": 96, "vocab_size": 97, "num_hidden_layers": 2,
        "mamba_n_heads": 4, "mamba_d_head": 8, "mamba_d_ssm": 32,
        "mamba_d_state": 16, "mamba_n_groups": 2, "mamba_d_conv": 4,
        "mamba_chunk_size": 8, "mamba_rms_norm": True,
        "mamba_norm_before_gate": False, "mamba_conv_bias": True,
        "rope_theta": 1e11, "rms_norm_eps": 1e-5,
        "attention_in_multiplier": 1,
        "attention_out_multiplier": 0.0375,
        "embedding_multiplier": 5.656854249492381,
        "key_multiplier": 0.011048543456039804,
        "lm_head_multiplier": 0.0078125,
        "mlp_multipliers": [0.1767766952966369, 0.011160714285714284],
        "ssm_in_multiplier": 0.25,
        "ssm_multipliers": [0.3535533905932738, 0.25, 0.1767766952966369,
                            0.5, 0.3535533905932738],
        "ssm_out_multiplier": 0.08838834764831845,
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
    """A mixer the Pallas update takes: 16 heads of 128 channels, a state of
    128 columns (whole 128 x 128 tiles, 8 heads a group); one layer."""
    cfg = tiny_cfg(mamba_n_heads=16, mamba_d_head=128, mamba_d_ssm=2048,
                   mamba_d_state=128, num_hidden_layers=1)
    spec = hybrid.ModelSpec.from_config(cfg)
    return cfg, spec, hybrid.init_params(spec, jax.random.key(4))


def _table(first_page: int, n_pages: int):
    t = np.zeros(n_pages + CHUNK // PAGE, np.int32)
    t[:n_pages] = np.arange(first_page, first_page + n_pages)
    return t


def _serve_one(spec, params, prompt, steps, kernel="gather", pages=None,
               state_id=2, prefill=lm_prefill_paged, between=None):
    """Chunked paged prefill of ``prompt`` into state slot ``state_id``, then
    decode through the cache in a call of three rows (the middle one live,
    the others the dummy row on the dummy slot); the tokens and the float32
    logits every served token was picked from. ``between(pages)`` may
    tamper with the slabs between two chunks."""
    n = len(prompt)
    need = -(-(n + steps) // PAGE)
    if pages is None:
        pages = init_kv_pages(params, 40, PAGE, spec, state_slots=4)
    table = _table(1, need)
    padded = np.zeros(-(-n // CHUNK) * CHUNK, np.int32)
    padded[:n] = prompt
    for cs in range(0, n, CHUNK):
        if cs and between is not None:
            pages = between(pages)
        pages, first, _, logits = prefill(
            params, pages, (table, NO_RING, state_id), padded[cs:cs + CHUNK],
            cs, n, heads=spec, page_len=PAGE)
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
    return np.random.default_rng(seed).integers(0, 97, n).astype(np.int32)


# ops/ssm.py ------------------------------------------------------------------


def _naive(x, dt, A, Bm, Cm, D, S):
    """The recurrence a token and a head at a time, in numpy."""
    T, H, _ = x.shape
    hpg = H // Bm.shape[1]
    S, ys = S.copy(), np.zeros_like(x)
    for t in range(T):
        for h in range(H):
            g = h // hpg
            S[h] = (np.exp(dt[t, h] * A[h]) * S[h]
                    + dt[t, h] * np.outer(Bm[t, g], x[t, h]))
            ys[t, h] = S[h].T @ Cm[t, g] + D[h] * x[t, h]
    return ys, S


def _operands(rng, T, H, P, G, N):
    return (rng.normal(size=(T, H, P)).astype(np.float32),
            np.exp(rng.normal(size=(T, H)) - 2).astype(np.float32),
            -np.exp(rng.uniform(0, 2, H)).astype(np.float32),
            rng.normal(size=(T, G, N)).astype(np.float32),
            rng.normal(size=(T, G, N)).astype(np.float32),
            rng.normal(size=H).astype(np.float32))


def test_the_chunked_scan_is_the_token_by_token_recurrence():
    """Blocks of 8 over 32 tokens, entered with a state; the last 12
    positions are padding (``dt`` 0) and move nothing."""
    rng = np.random.default_rng(0)
    x, dt, A, Bm, Cm, D = _operands(rng, 32, 4, 8, 2, 16)
    dt[20:] = 0
    S0 = rng.normal(size=(4, 16, 8)).astype(np.float32)
    want_y, want_S = _naive(x[:20], dt[:20], A, Bm[:20], Cm[:20], D, S0)
    with jax.default_matmul_precision("highest"):
        y, S = ssm.ssd_chunk_scan(*map(jnp.asarray, (x, dt, A, Bm, Cm, D,
                                                     S0)), block=8)
    np.testing.assert_allclose(np.asarray(y)[:20], want_y, atol=2e-5)
    np.testing.assert_allclose(np.asarray(S), want_S, atol=2e-5)
    with pytest.raises(ValueError, match="whole blocks"):
        ssm.ssd_chunk_scan(*map(jnp.asarray, (x[:30], dt[:30], A, Bm[:30],
                                              Cm[:30], D, S0)), block=8)


def test_the_decode_kernel_updates_the_live_slots_in_place_and_no_other():
    """Five rows, two of them the dummy row on slot 0: the Pallas update
    (interpreted) and the gathered form give the recurrence's one step, the
    live rows' slots change and no slot but theirs and the dummy's does."""
    rng = np.random.default_rng(1)
    H, P, G, N = 16, 128, 2, 128
    x, dt, A, Bm, Cm, D = _operands(rng, 5, H, P, G, N)
    slab = rng.normal(size=(7, H, N, P)).astype(np.float32)
    slots = np.array([3, 0, 5, 0, 1], np.int32)
    assert ssm.decode_update_supported(H, G, N, P)
    assert not ssm.decode_update_supported(4, 2, 16, 8)
    got = {k: ssm.ssd_decode_update(jnp.asarray(slab), jnp.asarray(slots), x,
                                    dt, A, Bm, Cm, D, kernel=k,
                                    interpret=True)
           for k in ("gather", "pallas")}
    for b, slot in ((0, 3), (2, 5), (4, 1)):
        want_y, want_S = _naive(x[b:b + 1], dt[b:b + 1], A, Bm[b:b + 1],
                                Cm[b:b + 1], D, slab[slot])
        for new, y in got.values():
            np.testing.assert_allclose(np.asarray(y)[b], want_y[0], atol=5e-5)
            np.testing.assert_allclose(np.asarray(new)[slot], want_S,
                                       atol=5e-6)
    for new, _ in got.values():
        np.testing.assert_array_equal(np.asarray(new)[[2, 4, 6]],
                                      slab[[2, 4, 6]])


def test_the_convolutions_tail_ends_at_the_last_valid_input():
    rng = np.random.default_rng(2)
    u = rng.normal(size=(16, 6)).astype(np.float32)
    tail = rng.normal(size=(3, 6)).astype(np.float32)
    w = rng.normal(size=(4, 6)).astype(np.float32)
    b = rng.normal(size=6).astype(np.float32)
    ext = np.concatenate([tail, u])
    want = b + sum(ext[k:k + 16] * w[k] for k in range(4))
    for n_valid, new in ((16, u[13:]), (5, u[2:5]), (2, ext[2:5]),
                         (0, tail)):
        out, t1 = ssm.causal_conv(jnp.asarray(u), jnp.asarray(tail), w, b,
                                  n_valid)
        np.testing.assert_allclose(np.asarray(out), want, atol=1e-5)
        np.testing.assert_array_equal(np.asarray(t1), new)
    out, t1 = ssm.conv_step(jnp.asarray(u[:2]), jnp.stack([tail, tail]), w, b)
    np.testing.assert_allclose(np.asarray(out)[0], want[0], atol=1e-5)
    np.testing.assert_array_equal(np.asarray(t1)[1],
                                  np.concatenate([tail[1:], u[1:2]]))


# the spec --------------------------------------------------------------------


def test_from_config_reads_the_falcon_h1_keys(model):
    cfg, spec, params = model
    assert spec.has_state and not spec.has_window and spec.latent is None
    assert spec.layers == (hybrid.LayerSpec("full", 4, "dense", ssm=True),) * 2
    sm = spec.ssm
    assert (sm.heads, sm.head_dim, sm.state, sm.groups, sm.conv, sm.chunk) \
        == (4, 8, 16, 2, 4, 8)
    assert sm.d_inner == 32 and sm.conv_dim == 96
    assert sm.segments == (32, 32, 32, 32, 4)
    assert sm.state_dtype == "float32"
    assert spec.mults.key == cfg["key_multiplier"]
    assert spec.mults.mlp_down == cfg["mlp_multipliers"][1]
    assert params["l0"]["ssm"]["w_in"].shape == (64, 32 + 96 + 4)
    assert "wgate" not in params["l0"]  # the family has no head gate
    # a state of 4 x 16 x 8 float32 and a tail of 3 x 96 in the compute
    # dtype, a layer
    assert spec.state_slot_bytes() == 2 * (512 * 4 + 288 * 4)
    assert spec.state_slot_bytes("bfloat16") == 2 * (512 * 4 + 288 * 2)
    pages = init_kv_pages(params, 5, PAGE, spec, state_slots=3)
    # K and V: a token's 2 KV heads x 16 side by side in one row
    assert [a.shape for a in pages["l1"]] == [
        (5, 8, 2 * 16), (5, 8, 2 * 16), (3, 4, 16, 8), (3, 3, 96)]
    with pytest.raises(ValueError, match="state slots"):
        init_kv_pages(params, 5, PAGE, spec)


@pytest.mark.parametrize("family, cfg, missing", [
    ("falcon_h1", {"mamba_d_ssm": 32, "hidden_size": 64},
     ["num_hidden_layers", "head_dim", "mamba_d_state"]),
    ("latent", {"kv_lora_rank": 16, "hidden_size": 64},
     ["num_hidden_layers", "q_lora_rank"]),
    ("layer_types", {"hidden_size": 64, "num_hidden_layers": 2},
     ["layer_types", "sliding_window", "num_experts"])])
def test_from_config_names_the_keys_it_could_not_read(family, cfg, missing):
    with pytest.raises(ValueError, match=family) as err:
        hybrid.ModelSpec.from_config(cfg)
    for key in missing:
        assert repr(key) in str(err.value)
    assert "'hidden_size'" not in str(err.value)


def test_every_branch_reaches_the_stream_at_order_one(model):
    """The weights' laws under the published multipliers: attention, mixer
    and FFN each add to the residual stream at the order of the embedding,
    and the logits spread by order 1 (at N(0, 1/fan_in) the branches behind
    a multiplier of 0.01-0.09 would be lost to rounding, and a dropped
    mixer would pass every comparison)."""
    cfg, spec, params = model
    toks = _prompt(48)
    x = hybrid._embed(spec, params, toks)
    seen = {"emb": float(jnp.std(x))}
    pos = jnp.arange(48)

    def attend(q, k, v):
        return hybrid._attend_dense(q, k, v, pos, pos, jnp.ones(48, bool),
                                    None)

    def mix(xbc, dt, sp):
        conv, _ = ssm.causal_conv(xbc, jnp.zeros((3, 96)), sp["conv_w"],
                                  sp["conv_b"], 48)
        xs, Bm, Cm = hybrid._scan_operands(spec.ssm, conv, xbc.dtype)
        y, _ = ssm.ssd_chunk_scan(xs, dt, -jnp.exp(sp["A_log"]), Bm, Cm,
                                  sp["D"], jnp.zeros((4, 16, 8)), block=8)
        return y.reshape(48, 32)

    lp = params["l0"]
    u = hybrid._rmsnorm(x, lp["ln1"], spec.norm_eps)
    seen["ssm"] = float(jnp.std(hybrid._ssm_mixer(spec, lp["ssm"], u, mix)))
    both = hybrid._parallel_mixers(spec, spec.layers[0], lp, x, pos, attend,
                                   mix)
    seen["attn"] = float(jnp.std(both - x) ** 2 - seen["ssm"] ** 2) ** 0.5
    out, _ = hybrid._ffn_half(spec, spec.layers[0], lp, both,
                              jnp.ones(48, bool))
    seen["ffn"] = float(jnp.std(out - both))
    logits = hybrid._head_logits(spec, params, out)
    seen["logits"] = float(jnp.std(logits))
    for name, std in seen.items():
        assert 0.2 < std < 5.0, (name, seen)


# programs against the reference ----------------------------------------------


@pytest.mark.parametrize("kernel, which", [("gather", "model"),
                                           ("pallas", "kernel_model")])
def test_chunked_prefill_then_decode_agree_with_the_reference(
        kernel, which, request):
    """A prompt of 37 tokens in chunks of 16 (the prompt ends 5 tokens into
    its third chunk, inside a scan block of 8), then 7 decode steps through
    the pages and the state slot, against the reference's one full pass with
    its token-by-token recurrence: float32, tightly."""
    cfg, spec, params = request.getfixturevalue(which)
    with jax.default_matmul_precision("highest"):
        toks, served, pages = _serve_one(spec, params, _prompt(37), 8, kernel)
        want = _ref_logits(params, cfg, toks, 37)
    assert np.abs(want).max() > 1.0
    np.testing.assert_allclose(served, want, atol=TIGHT)
    # the row's slot holds its state; the slots no row was given hold none
    state = np.asarray(pages["l0"][2])
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


def _zero_arrays(index: int):
    def between(pages):
        return {name: tuple(jnp.zeros_like(a) if i == index else a
                            for i, a in enumerate(arrays))
                for name, arrays in pages.items()}
    return between


FLAWS = ["mixer_dropped", "state_not_carried", "slot_not_zeroed",
         "tail_lost_at_a_chunk_edge", "padding_advances_the_state",
         "no_key_multiplier", "no_ssm_multiplier_x", "no_ssm_multiplier_dt",
         "norm_before_gate"]


@pytest.mark.parametrize("flaw", FLAWS)
def test_each_flaw_fails_the_comparison(flaw, model, monkeypatch):
    """What the comparison must catch: every piece of the mixer's
    arithmetic and of the state's way through the cache, left out one at a
    time, moves a logit by far more than the agreement above allows."""
    cfg, spec, params = model
    kw = {}
    if flaw == "mixer_dropped":
        real = hybrid._ssm_mixer
        kw["prefill"] = _fresh_prefill(
            monkeypatch, hybrid, "_ssm_mixer",
            lambda *a: jnp.zeros_like(real(*a)))
    elif flaw == "state_not_carried":
        kw["between"] = _zero_arrays(2)
    elif flaw == "tail_lost_at_a_chunk_edge":
        kw["between"] = _zero_arrays(3)
    elif flaw == "slot_not_zeroed":
        with jax.default_matmul_precision("highest"):
            kw["pages"] = _serve_one(spec, params, _prompt(40, seed=5), 6)[2]
        kw["prefill"] = _fresh_prefill(
            monkeypatch, hybrid, "_enter_state",
            lambda fresh, state, tail: (state, tail))
    elif flaw == "padding_advances_the_state":
        real = ssm.ssd_chunk_scan
        kw["prefill"] = _fresh_prefill(
            monkeypatch, ssm, "ssd_chunk_scan",
            lambda x, dt, *a, **k: real(x, jnp.where(dt == 0, 0.05, dt), *a,
                                        **k))
    elif flaw == "no_key_multiplier":
        spec = dataclasses.replace(spec, mults=dataclasses.replace(
            spec.mults, key=1.0))
    elif flaw.startswith("no_ssm_multiplier"):
        at = {"x": 1, "dt": 4}[flaw.rsplit("_", 1)[1]]
        mup = tuple(1.0 if i == at else v
                    for i, v in enumerate(spec.ssm.mup))
        spec = dataclasses.replace(spec, ssm=dataclasses.replace(
            spec.ssm, mup=mup))
    elif flaw == "norm_before_gate":
        spec = dataclasses.replace(spec, ssm=dataclasses.replace(
            spec.ssm, norm_before_gate=True))
    with jax.default_matmul_precision("highest"):
        toks, served, _ = _serve_one(spec, params, _prompt(37), 8, **kw)
        want = _ref_logits(params, cfg, toks, 37)
    assert np.abs(served - want).max() > CAUGHT, flaw


# the pool --------------------------------------------------------------------


def test_the_pool_hands_out_state_slots_and_audits_them(model):
    cfg, spec, params = model
    pool = PagedKVPool(params, spec, 9, PAGE, state_slots=3)
    assert pool.stats()["state_total"] == 2 and not pool.prefix_cache_enabled
    a, b = pool.alloc_state(), pool.alloc_state()
    assert {a, b} == {1, 2} and pool.state_used_count() == 2
    with pytest.raises(PagePoolExhausted, match="state slot"):
        pool.alloc_state()
    # held by no row the audit can see: reported
    group = PagedGroup((16, 8), 2, PAGE, CHUNK, ring=0, stateful=True)
    report = pool.audit([group])
    assert not report["ok"] and "state slot" in report["errors"][0]
    pool.release_state(a)
    pool.release_state(b)
    assert pool.audit([group])["ok"] and pool.state_used_count() == 0
    with pytest.raises(AssertionError, match="twice"):
        pool.release_state(a)
    for entry in (pool.export_rows, pool.import_rows, pool.export_prefixes,
                  pool.import_prefixes):
        with pytest.raises(NotImplementedError, match="recurrent mixers"):
            entry(b"" if "import" in entry.__name__ else [])


# the engine ------------------------------------------------------------------

BUCKETS = ((16, 8), (32, 8), (48, 16))
#: (prompt, steps): rows in all three buckets, more of them than the slots of
#: a bucket (3), so rows end and their slots and state slots refill
SCHEDULE = ((5, 4), (37, 9), (12, 8), (40, 16), (9, 3), (30, 7), (16, 8),
            (3, 2), (44, 5), (20, 6), (14, 2), (33, 12))


def _engine(spec, params, **kw):
    kw.setdefault("buckets", BUCKETS)
    kw.setdefault("max_batch", 3)
    kw.setdefault("page_len", PAGE)
    kw.setdefault("prefill_chunk", CHUNK)
    kw.setdefault("num_pages", 64)
    return ServeEngine(params, spec, **kw)


def _requests(schedule=SCHEDULE):
    return [Request(prompt=_prompt(n, seed=i), steps=steps, temperature=0.0)
            for i, (n, steps) in enumerate(schedule)]


@pytest.fixture(scope="module")
def served(model, tmp_path_factory):
    """The schedule through a ServeEngine under a profiler capture: results,
    spans, and the pool's audit afterwards."""
    cfg, spec, params = model
    eng = _engine(spec, params, start=False)
    eng.warmup()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    where = str(tmp_path_factory.mktemp("capture"))
    jax.profiler.start_trace(where, profiler_options=opts)
    try:
        reqs = _requests()
        handles = eng.submit_many(reqs)
        eng.start()
        results = [h.result(timeout=300) for h in handles]
    finally:
        jax.profiler.stop_trace()
    audit = eng.kvpool_audit()
    eng.close()
    spans = engine_spans.load(find_xplane(where))["spans"]
    return {"requests": reqs, "results": results, "audit": audit,
            "spans": spans, "engine": eng}


def test_the_engine_serves_the_reference_through_reused_slots(served, model):
    """Twelve rows over three buckets of three slots under the pipelined
    decode: every request ok, every served token the reference's first
    choice (float32, greedy), and more rows served than there are state
    slots, so slots were reused behind calls in flight."""
    cfg, spec, params = model
    assert [r.status for r in served["results"]] == ["ok"] * len(SCHEDULE)
    with jax.default_matmul_precision("highest"):
        for req, res in zip(served["requests"], served["results"]):
            n = len(req.prompt)
            assert len(res.tokens) == n + req.steps
            want = _ref_logits(params, cfg, res.tokens, n)
            gap = want.max(-1) - want[np.arange(req.steps), res.tokens[n:]]
            assert gap.max() < 1e-4, (n, gap)
    ahead = [s for s in served["spans"] if s.name == "serve.decode.dispatch"
             and s.fields.get("ahead")]
    assert ahead, "no decode call was dispatched ahead of a landing"


def test_slots_are_freed_with_the_pages_and_audited(served):
    audit = served["audit"]
    assert audit["ok"], audit["errors"]
    assert audit["used"] == 0 and audit["state_used"] == 0
    assert audit["state_total"] == len(BUCKETS) * 3


def test_the_spans_carry_the_state_fields(served, model):
    cfg, spec, params = model
    by = {}
    for s in served["spans"]:
        by.setdefault(s.name, []).append(s.fields)
    slot_bytes = spec.state_slot_bytes()
    iters = [f for f in by["serve.iter"] if "state_rows" in f]
    assert iters and all(f["state_slots"] == 9 for f in iters)
    assert all(f["state_bytes"] == f["state_rows"] * slot_bytes
               and f["state_rows"] == f["resident_rows"] for f in iters)
    assert max(f["state_rows"] for f in iters) > 3
    admits = by["serve.admit"]
    assert len(admits) == len(SCHEDULE)
    # what a slot costs is a constant of the engine: an admission's span
    # does not repeat it, the iteration's counts the slots and the rows
    assert all("state_bytes" not in f for f in admits)
    assert all(0 <= f["state_rows"] <= f["state_slots"] for f in iters)
    chunks = by["serve.prefill.dispatch"]
    assert sum(f["ssm_tokens"] for f in chunks) == sum(n for n, _ in SCHEDULE)
    assert all(f["ssm_tokens"] == f["tokens"] for f in chunks)
    calls = [f for f in by["serve.decode.dispatch"] if f.get("rows")]
    assert calls and all(f["state_rows"] == f["rows"] for f in calls)


def test_admission_charges_the_state_slot_with_the_pages(model):
    cfg, spec, params = model
    eng = _engine(spec, params, start=False)
    try:
        prog = eng._programs["lm"]
        req = Request(prompt=_prompt(20), steps=5)
        pages = -(-(20 + 5 - 1) // PAGE)
        assert prog.admission_cost(req, (32, 8)) \
            == pages * eng._page_bytes + spec.state_slot_bytes()
        assert eng._state_slots == 1 + len(BUCKETS) * 3
    finally:
        eng.close()


def test_a_pool_out_of_state_slots_fails_the_request_not_the_worker(model):
    """Two slots for four rows of one bucket: the rows that find none are
    answered with an error (no retry budget), the others are served, and
    nothing leaks."""
    cfg, spec, params = model
    eng = _engine(spec, params, buckets=((16, 8),), max_batch=4,
                  state_slots=3, start=False)
    try:
        handles = eng.submit_many(_requests(((9, 6),) * 4))
        eng.start()
        results = [h.result(timeout=300) for h in handles]
        statuses = sorted(r.status for r in results)
        assert statuses == ["error", "error", "ok", "ok"], statuses
        assert all("state slot" in r.reason for r in results
                   if r.status == "error")
        late = eng.submit(_requests(((7, 3),))[0]).result(timeout=300)
        assert late.status == "ok"
        audit = eng.kvpool_audit()
        assert audit["ok"] and audit["state_used"] == 0, audit
    finally:
        eng.close()


def test_migration_is_refused_for_a_spec_with_state_and_sharing_opt_in(model):
    """Sharing a prefix is opt-in for a model with state (it rests on
    snapshot slots: ``tests/test_delta_rule.py`` drives it); migration is
    refused."""
    cfg, spec, params = model
    shared = _engine(spec, params, prefix_cache=True, start=False)
    assert shared._prefix_cache and shared._snapshot_slots == 3 * 3
    shared.close()
    with pytest.raises(ValueError, match="mamba_chunk_size"):
        _engine(hybrid.ModelSpec.from_config(tiny_cfg(mamba_chunk_size=6)),
                params, start=False)
    eng = _engine(spec, params)
    try:
        assert not eng._prefix_cache
        for entry, args in ((eng.freeze_rows, ()),
                            (eng.adopt_rows, ({"entries": {1: None},
                                               "blob": b"x"},)),
                            (eng.export_prefixes, (4,)),
                            (eng.import_prefixes, (b"x",))):
            with pytest.raises(MigrationError, match="recurrent"):
                entry(*args)
        ok = eng.submit(_requests(((7, 3),))[0]).result(timeout=300)
        assert ok.status == "ok"  # refused, not broken
    finally:
        eng.close()
