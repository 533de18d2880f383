"""The ordinal join of dispatch spans and executions
(``benchmarks/launches.py``) on synthetic events: a worker that is ahead of
the chip, the way the pipelined loop runs. Iteration ``i`` dispatches its
chunks and then decode call ``i`` while call ``i - 1`` runs, and lands call
``i - 1``; the device runs what it is given back to back. No trace and no
chip: the join works on plain spans and events."""

import pytest

from benchmarks import launches
from benchmarks.engine_spans import Span
from benchmarks.trace_reduce import Event

DECODE_S, CHUNK_S = 0.020, 0.010
MODULE = {"prefill": "jit__lm_prefill_paged_jit(1)",
          "decode": "jit__lm_decode_paged_spec_jit(2)"}


def _pipeline(chunks_by_iter):
    """Worker spans and device events of a run whose iteration ``i``
    dispatches ``chunks_by_iter[i]`` chunks and one decode call. The chip is
    never idle: a program starts when the one before it ends (the first at
    0.001), and a dispatch takes a millisecond of the worker's time."""
    spans, runs = [], []
    seq, t_host, t_dev = 0, 0.0, 0.001
    pending = None  # (seq, end on the device) of the decode call in flight
    for chunks in chunks_by_iter:
        lo = t_host
        inner = []
        for kind in ["prefill"] * chunks + ["decode"]:
            seq += 1
            fields = {"seq": seq, "rows": 4} if kind == "decode" else {
                "seq": seq, "tokens": 48, "width": 64, "final": 0}
            inner.append(Span(f"serve.{kind}.dispatch", t_host, t_host + 1e-3,
                              fields, 1))
            t_host += 1e-3
            start = max(t_dev, t_host)
            t_dev = start + (DECODE_S if kind == "decode" else CHUNK_S)
            runs.append(Event(MODULE[kind], start, t_dev))
        if pending is not None:  # land the call dispatched one iteration ago
            landed = max(t_host, pending[1]) + 1e-4
            inner.append(Span("serve.decode.sync", t_host, landed,
                              {"seq": pending[0]}, 1))
            t_host = landed
        pending = (seq, t_dev)
        spans += [Span("serve.iter", lo, t_host + 1e-4, {}, 1)] + inner
        t_host += 2e-4
    return spans, runs


def _kinds(join):
    return [(x.seq, x.kind, None if x.run is None else x.run.name)
            for x in join.launches]


def test_a_clean_window_joins_every_dispatch_to_its_execution():
    spans, runs = _pipeline([0, 1, 0, 2, 0, 0])
    got = launches.join_events(spans, runs)
    assert got.ok and got.counts() == {
        "matched": 9, "head_runs": 0, "head_spans": 0, "tail_spans": 0,
        "tail_runs": 0, "edge": launches.EDGE + 4, "why": None}
    assert [x.seq for x in got.launches] == list(range(1, 10))
    assert all(x.run.name == MODULE[x.kind] for x in got.launches)
    assert [x.run for x in got.launches] == runs
    # each launch knows its iteration, and a decode call its landing
    iters = [s for s in spans if s.name == "serve.iter"]
    assert [iters.index(x.iter) for x in got.launches] == [
        0, 1, 1, 2, 3, 3, 3, 4, 5]
    first = got.launches[0]
    assert first.landed == pytest.approx(first.run.end + 1e-4)
    # the worker is ahead: the second call sat queued behind the first
    assert got.launches[2].queued_s == pytest.approx(
        got.launches[2].run.start - got.launches[2].span.end)
    assert got.launches[2].queued_s > 0.005


#: what the capture lacks -> (head_runs, head_spans, tail_spans) the join
#: must count
EDGES = {"an execution at the head whose span closed before the capture": (
             1, 0, 0),
         "a span at the tail whose program was still queued": (0, 0, 1),
         "a span at the head whose program ran before the device's side": (
             0, 1, 0),
         "two at the head and two at the tail": (2, 0, 2),
         "a chunk and the calls around it still queued at the tail": (0, 0, 3),
         "two spans at the head and three at the tail": (0, 2, 3)}
WHOLE = [0, 1, 0, 2, 0, 0, 1, 0, 0, 1, 0]


@pytest.mark.parametrize("case", sorted(EDGES))
def test_unmatched_at_an_edge_are_counted_and_the_rest_joined(case):
    head_runs, head_spans, tail_spans = EDGES[case]
    spans, runs = _pipeline(WHOLE)
    dispatches = launches.numbered(spans)
    gone = {id(s) for s in dispatches[:head_runs]}
    spans = [s for s in spans if id(s) not in gone]
    runs = runs[head_spans:len(runs) - tail_spans]
    got = launches.join_events(spans, runs)
    assert got.ok, got.why
    assert (got.head_runs, got.head_spans, got.tail_spans, got.tail_runs) == (
        head_runs, head_spans, tail_spans, 0)
    matched = [x for x in got.launches if x.run is not None]
    assert len(matched) == (len(dispatches) - head_runs - head_spans
                            - tail_spans)
    assert all(x.run.name == MODULE[x.kind] for x in matched)
    # the same pairs as the whole capture gives
    whole = {x.seq: x.run for x in launches.join_events(
        *_pipeline(WHOLE)).launches}
    assert all(x.run == whole[x.seq] for x in matched)
    # the unmatched spans stay in the list, in seq order, without a run
    assert [x.run is None for x in got.launches] == (
        [True] * head_spans + [False] * len(matched) + [True] * tail_spans)


def test_the_edge_is_what_two_iterations_dispatch_and_two_more():
    """The worker lands call t after dispatching call t + 1 and waits for no
    chunk: what a capture's edge can cut off is read from the capture."""
    spans, _ = _pipeline([0, 0, 0])
    worker = [s for s in spans if s.name != "serve.iter"]
    iters = [s for s in spans if s.name == "serve.iter"]
    assert launches.in_flight(iters, launches.numbered(worker)) == (
        launches.EDGE + 2)
    spans, _ = _pipeline([0, 3, 1, 0])
    iters = [s for s in spans if s.name == "serve.iter"]
    assert launches.in_flight(iters, launches.numbered(spans)) == (
        launches.EDGE + 4 + 2)
    assert launches.in_flight([], []) == launches.EDGE


def _swap_kinds(spans, runs):
    """An execution of the other kind in the middle."""
    runs[5] = Event(MODULE["decode" if "prefill" in runs[5].name
                           else "prefill"], runs[5].start, runs[5].end)


def _drop_a_span(spans, runs):
    spans.remove(launches.numbered(spans)[4])


def _seven_at_the_head(spans, runs):
    """The first four iterations' seven dispatch spans: the rest of the
    capture shows a pipeline that holds three."""
    for s in launches.numbered(spans)[:7]:
        spans.remove(s)


def _lose_an_execution(spans, runs):
    del runs[4]


BROKEN = {"a kind out of order in the middle": _swap_kinds,
          "a dispatch span missing in the middle": _drop_a_span,
          "more executions without a span at the head than the pipeline "
          "holds and two": _seven_at_the_head,
          "an execution missing in the middle": _lose_an_execution}


@pytest.mark.parametrize("case", sorted(BROKEN))
def test_no_join_where_the_middle_does_not_agree(case):
    spans, runs = _pipeline([0, 1, 0, 2, 0, 0, 1, 0, 0])
    BROKEN[case](spans, runs)
    got = launches.join_events(spans, runs)
    assert not got.ok and got.why and got.launches == []
    assert got.counts()["matched"] == 0


def test_one_execution_too_few_left_out_is_refused_by_the_clock():
    """Decode calls only, so the kinds agree at every offset: an execution
    that started before a span opened was not launched by it."""
    spans, runs = _pipeline([0] * 8)
    first = launches.numbered(spans)[0]
    spans.remove(first)
    got = launches.join_events(spans, runs)
    assert got.ok and got.head_runs == 1
    assert [x.run for x in got.launches] == runs[1:]


def test_spans_without_seq_give_nothing_to_join():
    spans, runs = _pipeline([0, 1, 0])
    for s in spans:
        s.fields.pop("seq", None)
    assert launches.numbered(spans) == []
    assert not launches.join_events(spans, runs).ok
    ctx = {"trace": None, "window": (0.0, 1.0)}
    assert launches.join(ctx) is None and launches.iterations(ctx) is None
    assert launches.in_window(ctx) is None
    assert launches.dispatch_spans(ctx, "serve.decode.dispatch") is None
