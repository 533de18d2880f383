"""plan_context: knob escalation driven by compiler memory accounting.

Ladder/budget logic runs against a fake measurer (fast, deterministic); one
integration test compiles for real through the AOT channel (libtpu, no chip)
and lives with the other compile-only evidence in test_aot_tpu.py.
"""

import json

import pytest

from marlin_tpu.models import TransformerLM, plan_context, usable_hbm_bytes
from marlin_tpu.models.planner import DEFAULT_RESERVE_BYTES, GIB, _ladder


def _measure_table(table):
    """Fake measurer: peak by frozenset of escalated knob names."""
    def measure(m):
        key = frozenset(
            k for k in ("remat", "loss_chunk", "mlp_chunk", "compute_dtype",
                        "offload_residuals")
            if getattr(m, k) not in (None, False))
        return table[key], ""
    return measure


def test_ladder_is_cumulative_and_respects_preset_knobs():
    lm = TransformerLM(vocab=64, d_model=32, heads=2, layers=1)
    rungs = _ladder(lm, seq=100_000)
    assert rungs[0] == {}
    assert rungs[1] == {"remat": True}
    assert rungs[-1] == {"remat": True, "loss_chunk": 16384,
                         "mlp_chunk": 16384, "compute_dtype": "bfloat16",
                         "offload_residuals": True}
    # knobs already set by the user are never re-proposed (or weakened)
    lm2 = TransformerLM(remat=True, loss_chunk=4096)
    rungs2 = _ladder(lm2, seq=100_000)
    assert rungs2 == [{}, {"mlp_chunk": 16384},
                      {"mlp_chunk": 16384, "compute_dtype": "bfloat16"},
                      {"mlp_chunk": 16384, "compute_dtype": "bfloat16",
                       "offload_residuals": True}]
    # chunk sizes never exceed the sequence
    assert _ladder(lm, seq=1000)[2]["loss_chunk"] == 1000


def test_plan_stops_at_first_fitting_rung():
    lm = TransformerLM(vocab=64, d_model=32, heads=2, layers=1)
    table = {frozenset(): 10 * GIB,
             frozenset({"remat"}): 6 * GIB,
             frozenset({"remat", "loss_chunk"}): 4 * GIB}
    plan = plan_context(50_000, lm, hbm_budget=7 * GIB,
                        measure=_measure_table(table))
    assert plan.fits and plan.knobs == {"remat": True}
    assert plan.peak_bytes == 6 * GIB
    assert plan.model.remat is True and plan.model.loss_chunk is None
    assert len(plan.trail) == 2  # stopped before probing loss_chunk
    # the chosen model is the input plus exactly the escalated knobs
    assert plan.model.d_model == 32 and plan.model.vocab == 64
    # a generous budget keeps the user's config untouched
    plan0 = plan_context(50_000, lm, hbm_budget=11 * GIB,
                         measure=_measure_table(table))
    assert plan0.fits and plan0.knobs == {} and plan0.model is not None
    assert plan0.model.remat is False


def test_plan_reports_no_fit_with_best_rung():
    lm = TransformerLM(vocab=64, d_model=32, heads=2, layers=1)
    table = {
        frozenset(): 40 * GIB,
        frozenset({"remat"}): 30 * GIB,
        frozenset({"remat", "loss_chunk"}): 28 * GIB,
        frozenset({"remat", "loss_chunk", "mlp_chunk"}): 27 * GIB,
        frozenset({"remat", "loss_chunk", "mlp_chunk", "compute_dtype"}):
            18 * GIB,
        frozenset({"remat", "loss_chunk", "mlp_chunk", "compute_dtype",
                   "offload_residuals"}): 19 * GIB,  # offload nets NEGATIVE
    }
    plan = plan_context(2_000_000, lm, hbm_budget=15 * GIB,
                        measure=_measure_table(table))
    assert not plan.fits
    assert plan.peak_bytes == 18 * GIB  # the best (lowest-peak) rung
    assert plan.knobs["compute_dtype"] == "bfloat16"
    assert "offload_residuals" not in plan.knobs  # a worse rung never wins
    assert len(plan.trail) == 6  # the whole ladder was probed
    assert "DOES NOT FIT" in plan.describe()


def test_usable_hbm_budget_sources(tmp_path):
    # no on-chip report: raw minus the documented reserve
    assert usable_hbm_bytes(onchip_report=str(tmp_path / "absent.json")) == \
        16 * GIB - DEFAULT_RESERVE_BYTES
    # measured bytes_limit wins when the probe has run
    rep = tmp_path / "HBM_ONCHIP.json"
    rep.write_text(json.dumps({"bytes_limit": 14 * GIB}))
    assert usable_hbm_bytes(onchip_report=str(rep)) == 14 * GIB
    # a corrupt/zero report falls back to the policy
    rep.write_text(json.dumps({"bytes_limit": 0}))
    assert usable_hbm_bytes(onchip_report=str(rep)) == \
        16 * GIB - DEFAULT_RESERVE_BYTES


def test_compile_failure_notes_do_not_abort_the_ladder():
    lm = TransformerLM(vocab=64, d_model=32, heads=2, layers=1)
    calls = []

    def measure(m):
        calls.append(m)
        if len(calls) == 1:
            return None, "compile failed: boom"  # e.g. Mosaic rejection
        return 2 * GIB, ""

    plan = plan_context(50_000, lm, hbm_budget=4 * GIB, measure=measure)
    assert plan.fits and plan.trail[0][1] is None
    assert "boom" in plan.trail[0][3]


def test_chips_topology_validation():
    lm = TransformerLM(vocab=64, d_model=32, heads=2, layers=1)
    with pytest.raises(ValueError, match="chips"):
        plan_context(1000, lm, chips=3)
    # an explicit measure bypasses topology construction entirely
    plan = plan_context(1000, lm, chips=3, hbm_budget=GIB,
                        measure=lambda m: (GIB // 2, ""))
    assert plan.fits


# ------------------------------- the admission unit: a page, a slot, as held
#
# Admission charges one number (no ratio stands on it): a request's pages of
# each class times ``kv_page_bytes`` of that class, plus its state slot. The
# cases hold that number to the bytes ``init_kv_pages`` really allocates a
# page id / a slot, for the dense block and one tiny model of every served
# family (tests/test_ctx_gather.py's five and the ``minicpm_sala`` one).

PAGE = 8


def _dense(**kw):
    lm = TransformerLM(vocab=32, d_model=32, heads=4, layers=3, seed=2, **kw)
    return lm.init_params(), lm.heads


def _spec_model(cfg):
    import jax

    from marlin_tpu.models import hybrid

    spec = hybrid.ModelSpec.from_config(cfg)
    return hybrid.init_params(spec, jax.random.key(0)), spec


def _family(name):
    from tests import test_delta_rule as olmo
    from tests import test_kda as solar
    from tests.test_hybrid_model import tiny_cfg as laguna_cfg
    from tests.test_latent_attention import tiny_cfg as mistral_cfg
    from tests.test_short_conv import tiny_cfg as lfm2_cfg
    from tests.test_sparse_attention import tiny_cfg as sala_cfg
    from tests.test_state_space import tiny_cfg as falcon_cfg

    if name == "dense":
        return _dense()
    if name == "dense-gqa":
        return _dense(kv_heads=2)
    if name == "kda+experts":   # a state slot AND a held share of experts
        import jax

        from marlin_tpu.models import hybrid

        spec = solar._spec(solar.tiny_cfg())
        return hybrid.init_params(spec, jax.random.key(0)), spec
    return _spec_model({"window-ring+experts": laguna_cfg,
                        "latent+experts": mistral_cfg,
                        "ssm": falcon_cfg, "delta-rule": olmo.tiny_cfg,
                        "short-conv": lfm2_cfg,
                        "sparse+lightning": sala_cfg}[name]())


def _held_bytes(pages, heads):
    """(bytes one GLOBAL page id names over all layers, one WINDOW page id,
    one state slot), from the arrays' own ``nbytes``: a layer's page slabs
    come first in its tuple (two, ONE for a latent layer, THREE for a sparse
    layer, whose compressed keys ride with the page, none for a layer that
    owns no page), its slot's arrays after them."""
    full = sliding = slot = 0
    for i in range(len(pages)):
        arrays = pages[f"l{i}"]
        ly = None if isinstance(heads, int) else heads.layers[i]
        n_slabs = (2 if ly is None else 0 if not ly.owns_pages
                   else {"latent": 1, "sparse": 3}.get(ly.attn, 2))
        for a in arrays[:n_slabs]:
            if ly is not None and ly.attn == "sliding":
                sliding += a.nbytes // a.shape[0]
            else:
                full += a.nbytes // a.shape[0]
        slot += sum(a.nbytes // a.shape[0] for a in arrays[n_slabs:])
    return full, sliding, slot


FAMILIES = ["dense", "dense-gqa", "window-ring+experts", "latent+experts",
            "ssm", "delta-rule", "short-conv", "sparse+lightning",
            "kda+experts"]


@pytest.mark.parametrize("family", FAMILIES)
def test_admission_charges_the_pages_and_the_slot_the_pool_holds(family):
    from marlin_tpu.models.planner import kv_page_bytes, request_pages
    from marlin_tpu.serving import Request, ServeEngine

    params, heads = _family(family)
    spec = None if isinstance(heads, int) else heads
    eng = ServeEngine(params, heads, buckets=((24, 8), (72, 24)),
                      max_batch=2, page_len=PAGE, prefill_chunk=16,
                      num_pages=40, queue_depth=8, start=False)
    try:
        full, sliding, slot = _held_bytes(eng._ensure_kvpool().pages, heads)
        # the planner's unit is what a page id / a slot holds, class by class
        assert kv_page_bytes(params, heads, PAGE, kind="full") == full > 0
        if spec is not None:
            assert kv_page_bytes(params, heads, PAGE,
                                 kind="sliding") == sliding
            assert spec.state_slot_bytes() == slot
        assert (sliding > 0) == (family == "window-ring+experts")
        assert (slot > 0) == (family in ("ssm", "delta-rule", "short-conv",
                                         "sparse+lightning", "kda+experts"))
        # ... and a request is charged exactly that, times what it can pin
        charged = []
        for n, steps in ((5, 3), (40, 20)):
            before = eng._queue.bytes_in_flight
            h = eng.submit(Request(prompt=[1 + i % 30 for i in range(n)],
                                   steps=steps))
            assert not h.done(), h.result(timeout=0).reason
            pages = request_pages(n, steps, PAGE)
            ring = min(pages, eng._ring) if eng._ring else 0
            want = pages * full + ring * sliding + slot
            assert eng._queue.bytes_in_flight - before == want
            charged.append(want)
        assert charged[1] > charged[0]
        if sliding:  # the long request pins a whole ring, no more
            assert eng._ring < request_pages(40, 20, PAGE)
    finally:
        eng.close()
    assert eng._queue.bytes_in_flight == 0


@pytest.mark.parametrize("family,kind,values_a_position", [
    # layers x {k, v} x kv_heads x head_dim, by hand from each tiny model
    ("dense", "full", 3 * 2 * 4 * 8),
    ("dense-gqa", "full", 3 * 2 * 2 * 8),
    # F S S S F: a page id of a class names a page in that class's layers
    ("window-ring+experts", "full", 2 * 2 * 2 * 16),
    ("window-ring+experts", "sliding", 3 * 2 * 2 * 16),
    # three latent layers: ONE entry of 16 + 8 columns, stored 128 wide
    ("latent+experts", "full", 3 * 128),
    ("latent+experts", "sliding", 0),
])
def test_kv_page_bytes_of_a_page_kind_is_its_slabs_row(family, kind,
                                                       values_a_position):
    """At another page length and a compute dtype handed in (bfloat16 over
    float32 parameters): the unit follows the slabs' shapes, not the
    parameters' dtype."""
    from marlin_tpu.models.planner import kv_page_bytes
    from marlin_tpu.models.transformer import init_kv_pages

    params, heads = _family(family)
    got = kv_page_bytes(params, heads, 16, compute_dtype="bfloat16",
                        kind=kind)
    assert got == values_a_position * 16 * 2
    pages = init_kv_pages(params, 3, 16, heads, compute_dtype="bfloat16",
                          window_pages=5)
    full, sliding, slot = _held_bytes(pages, heads)
    assert got == (sliding if kind == "sliding" else full) and slot == 0
    for i in range(len(pages)):  # a class's slabs hold that class's pages
        for a in pages[f"l{i}"]:
            sl = not isinstance(heads, int) \
                and heads.layers[i].attn == "sliding"
            assert a.shape[:2] == (5 if sl else 3, 16)
            assert a.dtype == "bfloat16"
