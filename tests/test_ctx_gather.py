"""How a spec model's prefill chunk fetches a row's context from the page
slabs: a page at a time through the copy kernel
``ops.paged_attention.fetch_pages``, at every row width and for every kind
of layer that owns pages, because XLA's gather of a row wider than 1024
lanes passes over the WHOLE slab (PERF.md section 6, PR 45). The assembled
context is what ``slab[table]`` holds, bit for bit, at every row width and
table the cells hand in; the traced program holds no gather and no slice of
a slab: the kernel is the one operation that reads it.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from marlin_tpu.models import hybrid
from marlin_tpu.ops import paged_attention
from marlin_tpu.ops.paged_attention import fetch_pages
from tests import test_delta_rule as olmo
from tests.test_hybrid_model import tiny_cfg as laguna_cfg
from tests.test_latent_attention import tiny_cfg as mistral_cfg
from tests.test_short_conv import tiny_cfg as lfm2_cfg
from tests.test_state_space import tiny_cfg as falcon_cfg

PAGE, CHUNK = 8, 16
#: pages of the slabs below: a count no other array of the programs leads with
NUM_PAGES = 37


def _tables(kind: str):
    """A row's table as prefill hands it to the fetch, by the cell that
    makes it so."""
    if kind == "distinct":          # a full layer's, every entry its own page
        return jnp.asarray([5, 3, 11, 7, 2, 9, 1], jnp.int32)
    if kind == "dummy-tail":        # the entries past the row's pages name 0
        return jnp.asarray([4, 8, 6, 0, 0, 0, 0], jnp.int32)
    if kind == "shared":            # two rows of one history: a page twice
        return jnp.asarray([4, 4, 8, 8, 6, 0], jnp.int32)
    if kind == "ring":
        # a sliding layer's: the ring slots that hold the window's pages
        # before the chunk, in position order (wraps past the ring's end)
        wtable = jnp.asarray([10, 12, 14, 16], jnp.int32)
        s_page, wp = jnp.int32(5), 2
        return wtable[jnp.mod(s_page - wp + jnp.arange(wp), 4)]
    if kind == "latent-padded":
        # a latent layer's: the table padded with the dummy page to whole
        # key blocks of the flash kernel (1024 positions at pages of 256)
        gtable = jnp.asarray([3, 1, 2, 9, 5], jnp.int32)
        width = hybrid.flash_table_pages(gtable.shape[0], 256)
        assert width == 8
        return jnp.pad(gtable, (0, width - gtable.shape[0]))
    raise ValueError(kind)


@pytest.mark.parametrize("kind", ["distinct", "dummy-tail", "shared", "ring",
                                  "latent-padded"])
@pytest.mark.parametrize("width", [3840, 1024, 512, 384])
def test_the_fetched_context_is_the_gathered_one_bit_for_bit(width, kind):
    # 3840: Olmo-Hybrid's row, which the gather cut into four pieces;
    # 1024: Laguna's; 512: Falcon-H1's and LFM2's; 384: a latent entry as
    # stored (a page is one copy at every width)
    slab = jax.random.normal(jax.random.key(width), (NUM_PAGES, PAGE, width),
                             jnp.bfloat16)
    table = _tables(kind)
    want = slab[table].reshape(-1, width)
    for fetch in (fetch_pages, jax.jit(fetch_pages)):
        got = fetch(slab, table).reshape(-1, width)
        assert got.dtype == want.dtype and got.shape == want.shape
        np.testing.assert_array_equal(np.asarray(got, np.float32),
                                      np.asarray(want, np.float32))


def _equations(jaxpr):
    """Every equation of ``jaxpr`` and of the jaxprs inside it (a jitted
    call, a loop's body, a branch)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _equations(sub)


def _slab_like(var) -> bool:
    shape = getattr(getattr(var, "aval", None), "shape", ())
    return len(shape) >= 3 and shape[0] == NUM_PAGES


def _wide_olmo_cfg(lanes):
    # three heads whose rows together are ``lanes`` wide (the tiny one's 48)
    return olmo.tiny_cfg(head_dim=lanes // 3)


SPECS = {
    # three linear layers to one full one; a row of 1152 lanes, which the
    # gather cut into pieces sliced off the whole slab
    "olmo-like-1152": lambda: _wide_olmo_cfg(1152),
    # full and sliding layers, the ring's slots beside the global table
    "laguna-like": laguna_cfg,
    # latent entries, the table padded to the flash kernel's key blocks
    "mistral-like": mistral_cfg,
    # state-space mixers beside attention in every layer
    "falcon-like": falcon_cfg,
    # short-convolution layers, three to one attention layer
    "lfm2-like": lfm2_cfg,
}


def _traced_prefill(cfg):
    """The prefill program of ``cfg``'s spec, traced over abstract
    parameters and slabs of NUM_PAGES pages (the state slabs lead with 5)."""
    spec = hybrid.ModelSpec.from_config(cfg)
    params = jax.eval_shape(
        lambda: hybrid.init_params(spec, jax.random.key(0)))
    ring = (hybrid.window_ring_pages(spec.window, CHUNK, PAGE)
            if spec.has_window else 0)
    pages = jax.eval_shape(lambda: hybrid.init_kv_pages(
        spec, NUM_PAGES, NUM_PAGES if ring else 0, PAGE,
        **({"state_slots": 5} if spec.has_state else {})))
    table = np.arange(1, 7, dtype=np.int32)
    args, static = hybrid._prefill_args(
        params, pages,
        (table, np.arange(1, 1 + ring, dtype=np.int32))
        + ((2,) if spec.has_state else ()),
        np.zeros(CHUNK, np.int32), 2 * CHUNK, 3 * CHUNK - 3, spec, PAGE)
    return spec, pages, table, args, static


@pytest.mark.parametrize("family", sorted(SPECS))
def test_the_prefill_program_reads_a_slab_through_the_kernel_alone(family):
    spec, pages, table, args, static = _traced_prefill(SPECS[family]())
    slabs = [t for i, ly in enumerate(spec.layers)
             for t in hybrid._kv_slabs(ly, pages[f"l{i}"])]
    assert slabs and all(t.shape[0] == NUM_PAGES for t in slabs)
    jaxpr = jax.make_jaxpr(
        lambda *a: hybrid._lm_prefill_paged_spec_jit(*a, **static))(*args)
    fetched = 0
    for eqn in _equations(jaxpr.jaxpr):
        for var in filter(_slab_like, eqn.invars):
            # a page write or the call of a jitted function reads no slab
            assert eqn.primitive.name not in ("gather", "dynamic_slice"), eqn
            if eqn.primitive.name == "pallas_call":
                # the slab and the table in, the row's pages out
                (out,) = eqn.outvars
                assert out.aval.shape[1:] == var.aval.shape[1:], eqn
                assert out.aval.shape[0] <= hybrid.flash_table_pages(
                    len(table), PAGE), eqn
                fetched += 1
    assert fetched == len(slabs)        # each slab ONE kernel


def test_the_fetch_is_traced_under_its_own_scope():
    # the next self-time-by-scope reading of a chunk attributes it (PR 43's
    # reading found 15.5 ms of a chunk under no scope)
    *_, args, static = _traced_prefill(_wide_olmo_cfg(1152))
    text = hybrid._lm_prefill_paged_spec_jit.lower(
        *args, **static).as_text(debug_info=True)
    assert "ctx_gather/" in text and "_fetch_pages_call" in text


@pytest.fixture(scope="module")
def wide_model():
    cfg = _wide_olmo_cfg(1152)
    spec = hybrid.ModelSpec.from_config(cfg)
    return cfg, spec, hybrid.init_params(spec, jax.random.key(5))


def test_a_wide_rowed_model_agrees_with_the_reference(wide_model):
    """The whole-program equality of ``test_delta_rule`` at a row the
    programs fetch through the kernel: a prompt of 37 tokens in chunks of 16,
    then decode, against the reference's one full pass, float32, tightly."""
    cfg, spec, params = wide_model
    with jax.default_matmul_precision("highest"):
        toks, served, _ = olmo._serve_one(spec, params, olmo._prompt(37), 4)
        want = olmo._ref_logits(params, cfg, toks, 37)
    assert np.abs(want).max() > 1.0
    np.testing.assert_allclose(served, want, atol=olmo.TIGHT)


def test_the_program_is_the_gathered_one_bit_for_bit(wide_model,
                                                     monkeypatch):
    """Three chunks of one prompt over scattered pages, through the program
    as it is and with the fetch put back to ``slab[table]``: the logits, the
    pick, the counts and every written slab are the same bits."""
    _, spec, params = wide_model
    prompt = olmo._prompt(41, seed=3)
    table = np.asarray([9, 4, 17, 2, 11, 6, 0, 0], np.int32)
    padded = np.zeros(64, np.int32)
    padded[:41] = prompt

    def run():
        def chunk(*a, **kw):    # a new function a run: jit's cache is by it
            return hybrid._lm_prefill_paged_spec_jit.__wrapped__(*a, **kw)

        prefill = jax.jit(chunk, static_argnames=("spec", "page_len"))
        pages = hybrid.init_kv_pages(spec, NUM_PAGES, 0, PAGE, state_slots=5)
        outs = []
        for cs in range(0, 41, CHUNK):
            args, static = hybrid._prefill_args(
                params, pages, (table, np.zeros(0, np.int32), 2),
                padded[cs:cs + CHUNK], cs, 41, spec, PAGE)
            pages, *rest = prefill(*args, **static)
            outs.append(rest)
        return jax.tree.leaves((pages, outs))

    got = run()
    gathered = []
    monkeypatch.setattr(paged_attention, "fetch_pages",
                        lambda t, tb: gathered.append(t) or t[tb])
    want = run()
    assert gathered             # the second run was traced anew
    assert len(got) == len(want)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
