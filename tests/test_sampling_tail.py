"""The sampling tail of the decode programs: one conditional for the batch.

``_pick_token_rows`` chooses between "sort the vocabulary and sample" and
"argmax" once per dispatch, on a scalar the program computes from the
temperatures it is handed. For every mix of greedy and sampled rows both
decode programs must give, bit for bit, the tokens of the per-row function
called row by row on unbatched logits; and in their jaxprs no ``sort`` may
stand outside a branch of a ``cond`` with a scalar predicate (under ``vmap``
the per-row ``cond`` is a select, and the sort ran for greedy rows too).
"""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from marlin_tpu.models import TransformerLM
from marlin_tpu.models import transformer as tf
from marlin_tpu.serving.kvpool import PagedGroup, decode_inputs

HEADS, PAGE_LEN, B, P, STEPS = 2, 8, 4, 8, 4
W = (P + 8) // PAGE_LEN

#: per-row (temperature, top_p, top_k); top_p 1.0 and top_k 0 are "off"
MIXES = {
    "all-greedy": ((0.0, 0.0, 0.0, 0.0), (1.0, 1.0, 1.0, 1.0), (0, 0, 0, 0)),
    "all-sampled": ((0.7, 1.0, 1.3, 0.9), (1.0, 1.0, 1.0, 1.0), (0, 0, 0, 0)),
    "mixed-knobs": ((0.0, 0.8, 0.0, 1.1), (1.0, 0.6, 0.5, 1.0), (0, 0, 3, 5)),
}
PROGRAMS = ("paged-gather", "paged-pallas")


@pytest.fixture(scope="module")
def params():
    return TransformerLM(vocab=32, d_model=16, heads=HEADS, layers=2,
                         seed=5).init_params()


def _row_by_row(temperature, top_p, top_k, logits, seeds, steps_done):
    """The oracle: the per-row function on each row's own, unbatched,
    logits and stream — a real conditional on a scalar temperature."""
    return jnp.stack([
        tf._pick_token_row(temperature[b], top_p[b], top_k[b], logits[b],
                           tf._row_key(seeds[b], steps_done[b]))
        for b in range(logits.shape[0])])


def _prompt(b):
    return (np.arange(P, dtype=np.int32) * (b + 3) + b) % 32


def _knobs(mix):
    temperature, top_p, top_k = MIXES[mix]
    return (jnp.arange(40, 40 + B, dtype=jnp.uint32),
            jnp.asarray(temperature, jnp.float32),
            jnp.asarray(top_p, jnp.float32), jnp.asarray(top_k, jnp.int32))


def _paged_state(params):
    """Four rows of eight prompt tokens in the page slab, greedy first
    tokens: the state a bucket's first decode dispatch finds."""
    pages = tf.init_kv_pages(params, 1 + B * W, PAGE_LEN, HEADS)
    tables = 1 + np.arange(B * W, dtype=np.int32).reshape(B, W)
    cur = np.zeros(B, np.int32)
    for b in range(B):
        pages, first = tf.lm_prefill_paged(
            params, pages, np.append(tables[b], np.zeros(1, np.int32)),
            _prompt(b), 0, P, heads=HEADS, page_len=PAGE_LEN)
        cur[b] = int(first)
    return pages, tables, cur


def _paged_tokens(step, params, mix):
    pages, tables, cur = _paged_state(params)
    pos, done, out = np.full(B, P, np.int32), np.ones(B, np.int32), []
    for _ in range(STEPS):
        pages, nxt = step(params, pages, tables, pos, cur, done, *_knobs(mix))
        cur = np.asarray(nxt)
        out.append(cur.tolist())
        pos, done = pos + 1, done + 1
    return out


def _program(name):
    """The jitted program's Python body with its static arguments bound."""
    return functools.partial(
        tf._lm_decode_paged_jit.__wrapped__, heads=HEADS, page_len=PAGE_LEN,
        compute_dtype=None, kernel=name.split("-")[1])


@pytest.mark.parametrize("mix", sorted(MIXES))
@pytest.mark.parametrize("program", PROGRAMS)
def test_decode_tokens_equal_the_per_row_oracle(params, monkeypatch,
                                                program, mix):
    body = _program(program)
    got = _paged_tokens(jax.jit(body), params, mix)
    # the same program with the tail replaced by the row-by-row oracle,
    # traced anew so that the replacement is what gets compiled
    monkeypatch.setattr(tf, "_pick_token_rows", _row_by_row)
    want = _paged_tokens(jax.jit(lambda *a: body(*a)), params, mix)
    assert got == want
    if mix == "all-sampled":  # and the draw is not the argmax in disguise
        monkeypatch.undo()
        assert got != _paged_tokens(jax.jit(body), params, "all-greedy")


def _sorts(jaxpr, under_scalar_cond=False):
    """Every ``sort`` equation below ``jaxpr``, each with whether it lies
    inside a branch of a ``cond`` whose predicate is a scalar."""
    for eqn in jaxpr.eqns:
        inside = under_scalar_cond
        if eqn.primitive.name == "sort":
            yield eqn, inside
        if eqn.primitive.name == "cond":
            inside = inside or eqn.invars[0].aval.shape == ()
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _sorts(sub, inside)


@pytest.mark.parametrize("program", PROGRAMS)
def test_every_sort_lies_behind_a_scalar_cond(params, program):
    body = _program(program)
    i32 = jnp.zeros(B, jnp.int32)
    state = (tf.init_kv_pages(params, 1 + B * W, PAGE_LEN, HEADS),
             jnp.zeros((B, W), jnp.int32), i32, i32, i32)
    jaxpr = jax.make_jaxpr(body)(params, *state, *_knobs("mixed-knobs"))
    found = list(_sorts(jaxpr.jaxpr))
    assert len(found) == 2, "the sampler's two argsorts"
    assert all(inside for _, inside in found), found


def test_a_prefilling_row_is_handed_to_decode_as_greedy():
    """A decode call is packed from LIVE rows only: a row still prefilling
    stays out of it with its table, its position and its temperature (its
    token would be discarded, and its temperature would switch the
    vocabulary sort on for every row of the call), and the rows no live row
    fills are greedy dummies on page 0."""
    class Entry:
        def __init__(self, **kw):
            self.request = type("R", (), dict(
                seed=1, top_p=None, top_k=None, **kw))()

    group = PagedGroup((32, 8), B, PAGE_LEN, 8)
    group.assign(0, Entry(prompt=np.arange(3), temperature=0.0), [1], 0, 0)
    group.assign(1, Entry(prompt=np.arange(30), temperature=0.9),
                 [2, 3, 4, 5], 0, 0)
    group.begin_decode(0)
    group.land_first(0, first=7)
    assert group.live_slots() == [0] and group.prefilling_slots() == [1]

    def packed():
        return decode_inputs([(group, group.live_slots())], B,
                             group.pages_per_row)[:-1]  # (not prev_index)

    tables, positions, cur, *_, temperature, top_p, top_k = packed()
    assert temperature.dtype == np.float32
    assert temperature.tolist() == [0.0, 0.0, 0.0, 0.0]
    assert tables[:, 0].tolist() == [1, 0, 0, 0]  # row 1's pages stay out
    assert positions.tolist() == [3, 0, 0, 0] and cur.tolist() == [7, 0, 0, 0]
    assert top_p.tolist() == [1.0] * 4 and top_k.tolist() == [0] * 4
    group.begin_decode(1)
    group.land_first(1, first=9)
    tables, *_, temperature, _, _ = packed()
    assert temperature.tolist() == [0.0, np.float32(0.9), 0.0, 0.0]
    assert tables[:, :4].tolist() == [[1, 0, 0, 0], [2, 3, 4, 5],
                                      [0] * 4, [0] * 4]
