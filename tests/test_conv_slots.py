"""The convolution's decode step on tails that live in a slab
(``ops/ssm.py:conv_step_slots``): each live row's tail read and written in
its slot, against :func:`~marlin_tpu.ops.ssm.conv_step` on the gathered
tails, at the three tailed families' sizes scaled down (a row of 24576,
11520 and 5120 channels: 192, 90 and 40 lane tiles a tap, here 6, 10 and 5:
18, 30 and 15 rows a slot, the last two padded to whole sublane tiles), the
kernel through Pallas' interpreter; and the paged programs of a model whose
tails are whole lane tiles (the slab then holds ``((taps - 1) * channels /
128`` rounded up to 8``, 128)`` a slot) against the plain reference through
a snapshot, both forms.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from marlin_tpu.models import hybrid
from marlin_tpu.ops import ssm
from tests.test_kda import _ref_logits, _shared, kernel_model  # noqa: F401

#: (taps, channels, slots, rows): Solar-Open2's, Olmo-Hybrid's, Falcon-H1's
SIZES = {"solaropen2": (4, 768, 13, 8), "olmohybrid": (4, 1280, 7, 3),
         "falconh1": (4, 640, 9, 8)}


def _operands(name, dtype, seed=0):
    taps, ch, S, B = SIZES[name]
    rng = np.random.default_rng(seed)
    cast = lambda x: jnp.asarray(x, jnp.float32).astype(dtype)  # noqa: E731
    return (cast(rng.normal(size=(S, taps - 1, ch))),
            cast(rng.normal(size=(B, ch))), cast(rng.normal(size=(taps, ch))),
            cast(rng.normal(size=(ch,))))


def _slab(tails, taps, ch):
    return ssm.tails_slots(tails, taps, ch)


def _tails(slab, taps, ch):
    return np.asarray(ssm.slot_tails(slab, taps, ch).astype(jnp.float32))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("kernel", ["gather", "pallas"])
@pytest.mark.parametrize("name", list(SIZES))
def test_the_slot_step_is_conv_step_on_the_gathered_tails(name, kernel,
                                                          dtype):
    """Rows on scattered slots, in no order: the output is ``conv_step``'s
    on ``tails[slots]`` (float32 sums of ``taps`` products: to an ulp or
    two, whatever their order), the slab ``tails.at[slots].set`` of its new
    tails, bit for bit."""
    taps, ch, S, B = SIZES[name]
    tails, u, w, b = _operands(name, dtype)
    slots = jnp.asarray(np.random.default_rng(1).permutation(
        np.arange(1, S))[:B], jnp.int32)
    want_out, t1 = ssm.conv_step(u, tails[slots], w, b)
    out, slab = ssm.conv_step_slots(_slab(tails, taps, ch), slots, u, w, b,
                                    kernel=kernel)
    assert out.dtype == jnp.float32 and out.shape == (B, ch)
    assert slab.dtype == tails.dtype
    np.testing.assert_allclose(out, want_out, rtol=2e-6, atol=2e-6)
    np.testing.assert_array_equal(
        _tails(slab, taps, ch),
        np.asarray(tails.at[slots].set(t1).astype(jnp.float32)))
    # what pads a slot to whole sublane tiles stays zero
    np.testing.assert_array_equal(
        np.asarray(slab.astype(jnp.float32)),
        np.asarray(_slab(tails.at[slots].set(t1), taps,
                         ch).astype(jnp.float32)))


@pytest.mark.parametrize("kernel", ["gather", "pallas"])
@pytest.mark.parametrize("name", list(SIZES))
def test_the_slot_step_moves_the_live_slots_and_the_dummys_and_no_other(
        name, kernel):
    """Two live rows among dummy rows (slot 0): the live slots hold the old
    tail shifted by one with the row's input behind it, the dummy slot
    whatever a dummy row left, every other slot what it held."""
    taps, ch, S, B = SIZES[name]
    tails, u, w, b = _operands(name, jnp.float32, seed=2)
    slots = np.zeros(B, np.int32)
    slots[[0, B - 1]] = [S - 1, 2]
    out, slab = ssm.conv_step_slots(_slab(tails, taps, ch),
                                    jnp.asarray(slots), u, w, b,
                                    kernel=kernel)
    got = _tails(slab, taps, ch)
    idle = [s for s in range(1, S) if s not in (2, S - 1)]
    np.testing.assert_array_equal(got[idle], np.asarray(tails)[idle])
    for row in (0, B - 1):
        np.testing.assert_array_equal(
            got[slots[row]], np.concatenate(
                [np.asarray(tails)[slots[row], 1:], np.asarray(u)[row][None]]))
        np.testing.assert_allclose(
            out[row], np.asarray(b) + np.einsum(
                "kc,kc->c", np.concatenate([np.asarray(tails)[slots[row]],
                                            np.asarray(u)[row][None]]),
                np.asarray(w)), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("kernel", ["gather", "pallas"])
def test_rows_that_all_name_the_dummy_slot_leave_every_other_slot_as_it_was(
        kernel):
    taps, ch, S, B = SIZES["falconh1"]
    tails, u, w, b = _operands("falconh1", jnp.bfloat16, seed=3)
    _, slab = ssm.conv_step_slots(_slab(tails, taps, ch),
                                  jnp.zeros((B,), jnp.int32), u, w, b,
                                  kernel=kernel)
    np.testing.assert_array_equal(
        _tails(slab, taps, ch)[1:],
        np.asarray(tails.astype(jnp.float32))[1:])


@pytest.mark.parametrize("channels, whole", [(128, True), (24576, True),
                                             (11520, True), (5120, True),
                                             (132, False), (108, False),
                                             (64, False), (200, False)])
def test_the_predicate_takes_whole_lane_tiles_and_nothing_else(channels,
                                                               whole):
    assert ssm.conv_slots_supported(channels) == whole
    rows = -(-3 * channels // 128 // 8) * 8   # whole sublane tiles of 8
    assert ssm.tail_slot_shape(4, channels) == (
        (rows, 128) if whole else (3, channels))
    tails = jnp.arange(2 * 3 * channels, dtype=jnp.float32).reshape(
        2, 3, channels)
    slab = ssm.tails_slots(tails, 4, channels)
    assert slab.shape == (2, *ssm.tail_slot_shape(4, channels))
    np.testing.assert_array_equal(ssm.slot_tails(slab, 4, channels), tails)
    if whole:       # tap k the rows k * channels / 128 onward, then zeros
        np.testing.assert_array_equal(
            slab[1, channels // 128], tails[1, 1, :128])
        assert not np.asarray(slab[:, 3 * channels // 128:]).any()


def test_a_tail_that_is_not_whole_lane_tiles_takes_the_gather_form(
        monkeypatch):
    """132 channels (the tests' tiny delta-rule model): the slab stays
    ``(slots, taps - 1, channels)``, ``kernel="pallas"`` never reaches the
    kernel, and the step is ``conv_step``'s all the same."""
    def refuse(*a, **kw):
        raise AssertionError("the kernel was called")

    monkeypatch.setattr(ssm, "_conv_slots_call", refuse)
    rng = np.random.default_rng(4)
    tails = jnp.asarray(rng.normal(size=(5, 3, 132)), jnp.float32)
    u, w, b = (jnp.asarray(rng.normal(size=s), jnp.float32)
               for s in ((2, 132), (4, 132), (132,)))
    slots = jnp.asarray([3, 1], jnp.int32)
    want_out, t1 = ssm.conv_step(u, tails[slots], w, b)
    out, slab = ssm.conv_step_slots(tails, slots, u, w, b, kernel="pallas")
    np.testing.assert_array_equal(out, want_out)
    np.testing.assert_array_equal(slab, tails.at[slots].set(t1))
    # and a slab of whole lane tiles does reach it
    with pytest.raises(AssertionError, match="the kernel was called"):
        ssm.conv_step_slots(jnp.zeros((5, 8, 128)), slots,
                            jnp.zeros((2, 128)), jnp.zeros((4, 128)),
                            jnp.zeros((128,)), kernel="pallas")
    # a slab in another shape is refused, whatever the form
    with pytest.raises(ValueError, match="tail_slot_shape"):
        ssm.conv_step_slots(jnp.zeros((5, 3, 128)), slots,
                            jnp.zeros((2, 128)), jnp.zeros((4, 128)),
                            jnp.zeros((128,)))


# the programs, a slot of whole lane tiles ------------------------------------


@pytest.mark.parametrize("kernel", ["gather", "pallas"])
def test_a_model_whose_tails_are_whole_lane_tiles_serves_the_reference(
        kernel_model, kernel, monkeypatch):  # noqa: F811
    """Two KDA heads of 128 x 128 (768 channels through the convolution: a
    slot of the tails array is ``(24, 128)``: 18 rows and their padding): a
    row prefills through a snapshot, a second row enters from it (the slot-to-slot copy, the
    chunk's reshape in and out) and decodes, each row's tail advanced in
    its slot by the form ``kernel`` names: the reference's logits, float32,
    tightly."""
    from tests import test_delta_rule

    cfg, spec, params = kernel_model
    pages = hybrid.init_kv_pages(spec, 5, 0, test_delta_rule.PAGE,
                                 state_slots=3)
    assert [a.shape for a in pages["l1"]] == [(3, 128, 256), (3, 24, 128)]
    serve_one = test_delta_rule._serve_one
    monkeypatch.setattr(
        "tests.test_kda._serve_one",
        lambda *a, **kw: serve_one(*a, **{"kernel": kernel, **kw}))
    with jax.default_matmul_precision("highest"):
        toks, served = _shared(spec, params)
        want = _ref_logits(params, cfg, toks, 41)
    np.testing.assert_allclose(served, want, atol=test_delta_rule.TIGHT)
