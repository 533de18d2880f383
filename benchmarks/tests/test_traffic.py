"""The request generator: every seed gets the same multiset of sizes, in
another order; open-loop schedules come from the seed."""

import json
import os

import numpy as np

from benchmarks.generators import requests as gen

HERE = os.path.dirname(os.path.abspath(__file__))


def _plan(seed, name="closed-ragged"):
    with open(os.path.join(HERE, "..", "traffic", name + ".json")) as f:
        params = json.load(f)
    return gen.plan(params, seed, {"vocab_size": 50432}), params


def test_sizes_are_the_same_for_every_seed_and_fit_the_buckets():
    a, params = _plan(1)
    b, _ = _plan(3_000_000_123)
    assert a["sizes"] == b["sizes"] and len(a["sizes"]) == params["pool"]
    buckets = [(256, 128), (768, 256), (1536, 384)]
    for p, o in a["sizes"]:
        assert 32 <= p <= 1536 and 16 <= o <= 384 and p + o <= 2048
        assert any(p <= bp and o <= bo for bp, bo in buckets)
    d = gen.describe(a)
    assert 230 <= d["prompt_len"]["median"] <= 290
    assert 85 <= d["output_len"]["median"] <= 110


def test_order_and_tokens_come_from_the_seed():
    def first(seed, n=70):
        p, _ = _plan(seed)
        s = gen.stream(p)
        return [next(s) for _ in range(n)]

    x, y, z = first(5), first(5), first(6)
    assert all(np.array_equal(a[1], b[1]) for a, b in zip(x, y))
    assert [len(a[1]) for a in x] != [len(a[1]) for a in z]
    # one cycle is a permutation of the whole pool
    pool = sorted(_plan(5)[0]["sizes"])
    assert sorted((len(t), o) for _, t, o in x[:len(pool)]) == pool
    assert sorted((len(t), o) for _, t, o in x[len(pool):2 * len(pool)]) == pool
    assert all(t.dtype == np.int32 and t.max() < 50432 for _, t, _ in x)
    # every run of four requests takes one pair from each quartile of prompt
    # length: any stretch of the stream holds nearly the same prefill work
    cuts = [sorted(p for p, _ in pool)[i] for i in (8, 16, 24)]
    for seed_run in (x, z):
        for r in range(0, 64, 4):
            quartiles = sorted(sum(len(t) >= c for c in cuts)
                               for _, t, _ in seed_run[r:r + 4])
            assert quartiles == [0, 1, 2, 3]


def test_open_loop_schedule_and_shared_prefixes():
    params = {"arrival": {"kind": "poisson", "rate_per_s": 20.0,
                          "burst": {"every_s": 1.0, "size": 3}},
              "pool": 8, "max_total_len": 64,
              "prompt_len": {"dist": "fixed", "value": 24},
              "output_len": {"dist": "fixed", "value": 8},
              "shared_prefix": {"count": 1, "length": 16, "share": 1.0}}
    p = gen.plan(params, 9, {"vocab_size": 100})
    dues = gen.due_times(p, 10.0)
    assert dues == sorted(dues) and dues == gen.due_times(p, 10.0)
    assert 150 < len(dues) < 290 and dues.count(3.0) == 3
    s = gen.stream(p)
    a, b = next(s)[1], next(s)[1]
    assert np.array_equal(a[:16], b[:16]) and not np.array_equal(a, b)
