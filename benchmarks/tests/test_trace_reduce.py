"""The reduction from a trace to numbers: interval arithmetic by hand, then
the recorded chip trace kept beside this file."""

import os

import pytest

from benchmarks import trace_reduce as tr
from benchmarks.trace_reduce import DeviceTrace, Event

HERE = os.path.dirname(os.path.abspath(__file__))


def test_union_clip_subtract_intersect_gaps():
    assert tr.union([(3, 4), (0, 1), (0.5, 2), (2, 2)]) == [(0, 2), (3, 4)]
    assert tr.total([(0, 2), (3, 4)]) == 3
    assert tr.clip([(0, 2), (3, 4)], 1, 3.5) == [(1, 2), (3, 3.5)]
    assert tr.subtract([(0, 10)], [(1, 2), (4, 6), (9, 12)]) == \
        [(0, 1), (2, 4), (6, 9)]
    assert tr.subtract([(0, 1), (5, 6)], [(0.5, 5.5)]) == [(0, 0.5), (5.5, 6)]
    assert tr.intersect([(0, 3), (5, 8)], [(2, 6), (7, 9)]) == \
        [(2, 3), (5, 6), (7, 8)]
    assert tr.gaps([(1, 2), (4, 6)], 0, 7) == [(0, 1), (2, 4), (6, 7)]


FUSION = ("%fusion.1 = f32[8,8]{1,0:T(8,128)} fusion(f32[8,8]{1,0} %a, "
          "f32[8,8]{1,0} %b), kind=kOutput, calls=%fused_computation")


def _dev():
    ops = [Event(FUSION, 0.0, 4.0, "fusion"),
           Event("%all-reduce.2 = f32[8,8]{1,0} all-reduce(f32[8,8] %fusion.1)",
                 4.0, 6.0, "all-reduce"),
           Event("%copy.3 = f32[8,8]{1,0} copy(f32[8,8] %x)", 5.0, 5.5, "copy"),
           Event(FUSION, 8.0, 10.0, "fusion")]
    mods = [Event("jit_f(1)", 0.0, 6.0), Event("jit_g(2)", 8.0, 10.0)]
    return DeviceTrace("/device:TPU:0", ops, mods)


def test_busy_idle_and_exposed_collective_by_hand():
    d = _dev()
    assert tr.busy_seconds(d, 0, 10) == 8.0
    assert tr.idle_share(d, 0, 10) == pytest.approx(0.2)
    assert tr.busy_seconds(d, 3, 9) == 4.0
    # the all-reduce runs 4..6, a copy covers 5..5.5: 1.5 s exposed
    assert tr.exposed_collective_seconds(d, 0, 10) == pytest.approx(1.5)
    assert tr.seconds_by_name(d.ops, 0, 9)[FUSION] == pytest.approx(5.0)
    assert [e.name for e in tr.module_events(d, r"jit_f", 0, 10)] == ["jit_f(1)"]
    assert tr.module_events(d, r"jit_g", 0, 9) == []  # not wholly inside
    assert tr.busy_inside(d, [(0.0, 6.0)], 0, 10) == 6.0


def test_breakdown_names_gaps_by_the_host_span_before_them():
    d = _dev()
    trace = tr.Trace([d], [Event("window", 0.0, 10.0)])
    assert tr.window_of(trace) == (0.0, 10.0)
    assert tr.top_ops(trace, 0, 10, n=2) == [
        ["f/%fusion.1 fusion[kOutput] f32[8,8]", 4.0],
        ["f/%all-reduce.2 all-reduce f32[8,8]", 2.0]]
    assert tr.parse_op(FUSION) == {"short": "%fusion.1", "opcode": "fusion",
                                   "kind": "kOutput", "shape": "f32[8,8]"}
    assert tr.module_short("jit__lm_decode_paged_jit(1103575)") == \
        "lm_decode_paged"
    spans = [("product", 0.0, 0.1), ("block_until_ready", 0.1, 7.0),
             ("product", 7.0, 7.1)]
    assert tr.named_gaps(d, spans, 0, 10) == [["block_until_ready", 2.0]]


# ---- the recorded trace: matmul.square-mesh4 (n 36864, 2x2 v5e), a 3 s
# window of 7 products, taken on the chip by PR 27 (74 KB)

RECORDED = os.path.join(HERE, "data", "mesh4_3s.xplane.pb")


@pytest.fixture(scope="module")
def recorded():
    return tr.load(RECORDED)


def test_recorded_trace_loads_four_chips_and_the_window(recorded):
    assert [d.name for d in recorded.devices] == [
        f"/device:TPU:{i}" for i in range(4)]
    lo, hi = tr.window_of(recorded)
    assert hi - lo == pytest.approx(3.429494, abs=1e-5)
    for d in recorded.devices:
        assert len(d.ops) == 35 and len(d.modules) == 7
        # the device's clock and the host's agree to about a millisecond
        assert all(lo - 1e-3 <= e.start and e.end <= hi + 1e-3 for e in d.ops)


def test_recorded_busy_idle_collectives_and_kernel_time(recorded):
    lo, hi = tr.window_of(recorded)
    busy = [tr.busy_seconds(d, lo, hi) for d in recorded.devices]
    assert busy[0] == pytest.approx(3.416122, abs=1e-5)
    assert max(tr.idle_share(d, lo, hi) for d in recorded.devices) == \
        pytest.approx(0.0039146, abs=1e-6)
    # collective-permute (start, done) and the fusion that calls
    # %all-reduce-scatter; nothing computes meanwhile
    d1 = recorded.devices[1]
    moving = {tr.parse_op(e.name)["short"] for e in d1.ops
              if tr.is_collective(e)}
    assert moving == {"%collective-permute-start", "%collective-permute-done",
                      "%fusion"}
    assert tr.exposed_collective_seconds(d1, lo, hi) == \
        pytest.approx(0.665500, abs=1e-5)
    by_name = tr.seconds_by_name(d1.ops, lo, hi)
    dot = next(v for k, v in by_name.items() if k.startswith("%fusion.1 "))
    assert dot == pytest.approx(2.750608, abs=1e-5)
    assert tr.top_ops(recorded, lo, hi, n=1)[0][0] == \
        "f/%fusion.1 fusion[kOutput] f32[18432,36864]"


def test_recorded_layer_metrics(recorded):
    from benchmarks import costs
    from benchmarks.run import load_module

    lo, hi = tr.window_of(recorded)
    ctx = {"trace": recorded, "window": (lo, hi), "counters": {"products": 7},
           "facts": {"n": 36864, "itemsize": 4, "precision": "high",
                     "chips": 4},
           "peaks": costs.load_peaks("TPU v5 lite")}
    read = lambda name: load_module("layer_metrics", name).read(ctx)  # noqa: E731
    # least time 3 x 2 x 36864^3 / 4 / 197e12 = 0.381445 s a product
    assert read("gemm_roofline_pct") == pytest.approx(
        100 * 7 * 0.381445 / 2.750595, rel=1e-4)
    assert read("collective_exposed_pct") == pytest.approx(19.4058, abs=1e-3)
    assert read("device_idle_pct.matmul") == pytest.approx(0.39146, abs=1e-4)
    assert read("decode_step_ms") is None and read("rows_per_step") is None
