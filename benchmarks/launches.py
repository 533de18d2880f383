#!/usr/bin/env python3
"""Which program did this span launch, and what did that program then cost.

Since the engine's loop became a pipeline one call deep, a
``serve.*.dispatch`` span closes while the PREVIOUS program still runs:
nothing that cuts the device trace by the spans' extents says which
execution a span launched. The engine numbers every prefill chunk and decode
call it dispatches (``seq`` on ``serve.prefill.dispatch`` /
``serve.decode.dispatch``, and on the ``.sync`` span in which the result
lands), in the device's order. The join here gives one :class:`Launch` a
dispatch span: the span, its enclosing ``serve.iter``, and the ``XLA
Modules`` event (``lm_prefill_paged*`` / ``lm_decode_paged*``) it launched.

- :func:`align` is the ordinal join: the device runs the worker's programs in
  dispatch order, so the executions of the two program kinds, in start
  order, are the dispatch spans in ``seq`` order, but for a few executions
  at the head whose span closed before the capture began and a few spans at
  the tail whose program was still queued at its end. It works on what a
  traced run has loaded already.
- :func:`by_flow` is the exact join, the check on the ordinal one: it reads
  the profiler's file again and follows the runtime's own flow ids from each
  ``XLA Modules`` event back to the calling thread (``DoEnqueueProgram`` ->
  ``PJRT_LoadedExecutable_Execute`` -> the ``linkage`` event inside the
  span).

    python3 benchmarks/launches.py <xplane.pb>

reads any capture (an operator's ``POST /debug/profile`` too) both ways,
prints one line a dispatch and says whether the two joins agree. On a trace
of a program whose spans carry no ``seq`` everything here finds nothing and
returns ``None``.
"""

from __future__ import annotations

import dataclasses
import json
import os
import statistics
import sys

if __name__ == "__main__":  # run as a script: the checkout is the package root
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from benchmarks import engine_spans, stats, trace_reduce  # noqa: E402

#: dispatch span -> the kind of program it launches
DISPATCH = {"serve.prefill.dispatch": "prefill",
            "serve.decode.dispatch": "decode"}
#: the spans in which a numbered program's result lands
SYNC = ("serve.prefill.sync", "serve.decode.sync")
#: what a program's module name holds, by kind (dense or ``_spec``)
PROGRAM = {"prefill": "lm_prefill_paged", "decode": "lm_decode_paged"}
#: executions without a span, or spans without an execution, tolerated at
#: each edge of a capture beyond what the worker's pipeline can hold
#: (:func:`in_flight`). More than that at an edge, or any in the middle, and
#: there is no join
EDGE = 2


def kind_of(module_name: str):
    for kind, part in PROGRAM.items():
        if part in module_name:
            return kind
    return None


@dataclasses.dataclass
class Launch:
    seq: int
    kind: str             # "prefill" | "decode"
    span: object          # the dispatch span (engine_spans.Span)
    iter: object = None   # its enclosing serve.iter span, where captured
    run: object = None    # the XLA Modules event (trace_reduce.Event)
    landed: float | None = None  # end of the .sync span that names seq

    @property
    def queued_s(self) -> float:
        """From the end of the dispatch span to the start of the execution:
        how long the program sat behind the one running. At 0 (the program
        ran before its span had closed) the chip waited for the host."""
        return max(0.0, self.run.start - self.span.end)

    def line(self) -> dict:
        """The CLI's line: what an operator reads of one dispatch."""
        f = self.span.fields
        out = {"seq": self.seq, "kind": self.kind,
               "dispatch_ms": 1e3 * (self.span.end - self.span.start)}
        if self.kind == "decode":
            out["rows"] = f.get("rows")
        else:
            out["tokens"], out["width"] = f.get("tokens"), f.get("width")
        if self.run is not None:
            out["queued_ms"] = 1e3 * self.queued_s
            out["device_ms"] = 1e3 * self.run.seconds
            if self.landed is not None:
                out["landed_after_ms"] = 1e3 * (self.landed - self.run.end)
        return out


@dataclasses.dataclass
class Join:
    launches: list        # Launch, in seq order; ``run`` None if unmatched
    head_runs: int = 0    # executions whose span the capture does not hold
    head_spans: int = 0   # spans whose program ran before the device's side
    tail_spans: int = 0   # spans whose program had not run at its end
    tail_runs: int = 0    # executions after the last span
    edge: int = EDGE      # how many of each the join would have taken
    why: str | None = None  # why there is no join (then ``launches`` is [])

    @property
    def ok(self) -> bool:
        return self.why is None

    def counts(self) -> dict:
        return {"matched": sum(1 for x in self.launches if x.run is not None),
                "head_runs": self.head_runs, "head_spans": self.head_spans,
                "tail_spans": self.tail_spans,
                "tail_runs": self.tail_runs, "edge": self.edge,
                "why": self.why}


def numbered(spans) -> list:
    """The dispatch spans that launched a numbered program, in start order
    (a sweep that found no live row, or a call that failed, has no
    ``seq``)."""
    return [s for s in spans if s.name in DISPATCH and "seq" in s.fields]


def align(spans, runs, landed=None, edge: int = EDGE) -> Join:
    """The ordinal join of ``spans`` (:func:`numbered`, in start order) and
    ``runs`` (the executions of both program kinds, in start order): leave
    out a few executions at the head (their spans closed before the capture
    began) or a few spans (the device's side of the capture began later than
    the host's), and pair the rest in order. The offset taken is the one at
    which the two sequences of KINDS agree throughout, no execution starts
    before its span does and none ends after its landing does (``landed``:
    ``seq`` -> end of the ``.sync`` span that names it). One off either way
    and an execution would have started before the span that launched it
    opened, or ended after the worker had its result, so at most one offset
    holds; the nearest to 0 is tried first. At most ``edge`` executions or
    spans go unmatched at either end."""
    landed = landed or {}
    seqs = [int(s.fields["seq"]) for s in spans]
    if not spans:
        return Join([], edge=edge, why="no dispatch span carries seq")
    for a, b in zip(seqs, seqs[1:]):
        if b != a + 1:
            return Join([], edge=edge, why=f"seq {a} is followed by {b}: a "
                        f"dispatch span is missing in the middle")
    for skip in sorted(range(-edge, edge + 1), key=lambda d: (abs(d), -d)):
        head_runs, head_spans = max(skip, 0), max(-skip, 0)
        given = runs[head_runs:head_runs + len(spans) - head_spans]
        paired = list(zip(seqs[head_spans:], spans[head_spans:], given))
        tail_spans = len(spans) - head_spans - len(paired)
        tail_runs = len(runs) - head_runs - len(paired)
        if not paired or tail_spans > edge or tail_runs > edge:
            continue
        if all(DISPATCH[s.name] == kind_of(r.name) and r.start >= s.start
               and r.end <= landed.get(q, r.end) for q, s, r in paired):
            runs_of = dict((q, r) for q, _, r in paired)
            return Join([Launch(q, DISPATCH[s.name], s, run=runs_of.get(q),
                                landed=landed.get(q))
                         for q, s in zip(seqs, spans)],
                        head_runs, head_spans, tail_spans, tail_runs, edge)
    return Join([], edge=edge,
                why=f"{len(spans)} dispatch spans and {len(runs)} "
                    f"executions agree at no offset of -{edge}..{edge}")


def in_flight(iters, dispatches) -> int:
    """How many executions or spans a capture's edge may cut off from their
    other half: what the device can hold, and :data:`EDGE` more for the two
    sides of a capture not starting and stopping together (the device's side
    has been seen to end a decode call before the host's). The worker lands
    call ``t`` only after it has dispatched call ``t + 1`` and waits for no
    chunk, so what has not run yet is at most what two consecutive
    iterations dispatched: read from the capture itself, as the most
    numbered dispatches two consecutive ``serve.iter`` spans hold. One
    1024-wide chunk between two decode calls is three already."""
    held, i = [0] * len(iters), 0
    for s in dispatches:
        while i < len(iters) and iters[i].end < s.start:
            i += 1
        if i < len(iters) and iters[i].start <= s.start:
            held[i] += 1
    return EDGE + max([0] + [a + b for a, b in zip(held, held[1:])])


def join_events(spans, modules) -> Join:
    """:func:`align` for all spans of one capture (``engine_spans.load``,
    sorted by start) and one chip's program events (sorted by start), with
    each launch's ``serve.iter``."""
    worker = engine_spans.worker_spans(spans)
    landed = {int(s.fields["seq"]): s.end for s in worker
              if s.name in SYNC and "seq" in s.fields}
    iters = [s for s in worker if s.name == engine_spans.ITER]
    dispatches = numbered(worker)
    got = align(dispatches, [m for m in modules if kind_of(m.name)], landed,
                in_flight(iters, dispatches))
    i = 0
    for launch in got.launches:
        while i < len(iters) and iters[i].end < launch.span.start:
            i += 1
        if i < len(iters) and iters[i].start <= launch.span.start:
            launch.iter = iters[i]
    return got


def join(ctx: dict):
    """The :class:`Join` of a traced run, worked out once a capture (kept in
    the capture's ``memo``, as ``engine_spans.idle_seconds`` keeps its own):
    from the spans the run's readers have loaded already and the first
    chip's program events. ``None`` without a trace, without the program's
    spans, or where no dispatch span carries ``seq`` (the parent)."""
    got = engine_spans.capture_for(ctx)
    if (got is None or engine_spans.worker_line(got["spans"]) is None
            or not ctx["trace"].devices):
        return None
    if "launches" not in got["memo"]:
        got["memo"]["launches"] = (
            join_events(got["spans"], ctx["trace"].devices[0].modules)
            if numbered(got["spans"]) else None)
    return got["memo"]["launches"]


def in_window(ctx: dict, kind: str | None = None):
    """The launches of ``ctx``'s window (dispatch span begun inside it) that
    have their execution, of one ``kind`` or of both; ``None`` where there
    is no join to go by."""
    got = join(ctx)
    if got is None or not got.ok:
        return None
    lo, hi = ctx["window"]
    return [x for x in got.launches if x.run is not None
            and lo <= x.span.start <= hi and kind in (None, x.kind)]


def iterations(ctx: dict):
    """One entry a ``serve.iter`` of the window that dispatched a program,
    every one of them executed inside the capture: ``{"chunks", "device_s",
    "slack_s"}``: the prefill chunks it carried, the device time of all it
    dispatched (its decode call or calls plus its chunks), and the LEAST
    queue wait among them. ``None`` without a join."""
    got = join(ctx)
    if got is None or not got.ok:
        return None
    lo, hi = ctx["window"]
    by_iter = {}
    for x in got.launches:
        if x.iter is not None and lo <= x.iter.start <= hi:
            by_iter.setdefault(id(x.iter), []).append(x)
    return [{"chunks": sum(x.kind == "prefill" for x in group),
             "device_s": sum(x.run.seconds for x in group),
             "slack_s": min(x.queued_s for x in group)}
            for group in by_iter.values()
            if all(x.run is not None for x in group)]


def dispatch_spans(ctx: dict, name: str):
    """The window's ``name`` spans of a program that numbers its dispatches;
    ``None`` on any other (the span-only readers print nothing on the
    parent either)."""
    if join(ctx) is None:
        return None
    return [s for s in engine_spans.in_window(engine_spans.for_ctx(ctx), name,
                                              *ctx["window"])
            if "seq" in s.fields]


def _p50_p95(values) -> dict:
    return {"n": len(values), "p50": stats.percentile(values, 50),
            "p95": stats.percentile(values, 95)}


def note(ctx: dict) -> None:
    """Print the ``launch_join`` note of a traced run: matched and unmatched
    counts, device milliseconds an iteration split by the chunks it carried,
    the least queue wait beside the worker's own wait in
    ``serve.decode.sync``, and the device time of the programs outside the
    numbering (the feed's writes, copy-on-write copies)."""
    got = join(ctx)
    if got is None:
        return
    out = {"note": "launch_join", **got.counts()}
    its = iterations(ctx)
    if its:
        lo, hi = ctx["window"]
        by_chunks = {}
        for it in its:
            by_chunks.setdefault(it["chunks"], []).append(1e3 * it["device_s"])
        syncs = [1e3 * (s.end - s.start) for s in engine_spans.in_window(
            engine_spans.for_ctx(ctx), "serve.decode.sync", lo, hi)]
        other = {}
        for m in ctx["trace"].devices[0].modules:
            if kind_of(m.name) is None and lo <= m.start <= hi:
                name = trace_reduce.module_short(m.name)
                other[name] = other.get(name, 0.0) + 1e3 * m.seconds
        out.update(
            iterations=len(its),
            iter_device_ms={str(k): _p50_p95(v)
                            for k, v in sorted(by_chunks.items())},
            launch_slack_ms_p50=1e3 * statistics.median(
                it["slack_s"] for it in its),
            decode_sync_ms_p50=statistics.median(syncs) if syncs else None,
            other_programs_ms=other,
            other_programs_pct=100.0 * sum(other.values()) / (1e3 * (hi - lo)))
    print(json.dumps(out), flush=True)


# ------------------------------------------------------------ the exact join


def _host_lines(data) -> list:
    """Every host thread's events as ``[start_ns, end_ns, name, stats]``,
    sorted so that an event follows the events around it, with the index of
    its parent (the innermost event around it on its thread) appended."""
    lines = []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            evs = sorted(([ev.start_ns, ev.start_ns + ev.duration_ns, ev.name,
                           dict(ev.stats)] for ev in line.events),
                         key=lambda e: (e[0], -e[1]))
            stack = []
            for i, ev in enumerate(evs):
                while stack and evs[stack[-1]][1] < ev[1]:
                    stack.pop()
                ev.append(stack[-1] if stack else None)
                stack.append(i)
            lines.append(evs)
    return lines


def by_flow(path: str, device: str = "/device:TPU:0") -> dict:
    """``{start of an XLA Modules event in ns: seq}`` for the executions of
    ``device`` whose launch the capture holds, by the runtime's own record:
    the module event is the consumer (``_c``, of type ``_ct``) of the flow
    that the host's ``DoEnqueueProgram`` event of the same ``run_id``
    produced (``_p`` / ``_pt``); an event around that one on its thread
    consumes the flow of ``CommonPjRtLoadedExecutable::Execute``, and so on
    back to the ``PJRT_LoadedExecutable_Execute linkage`` event on the
    calling thread, which lies inside the dispatch span. Reads the file
    anew: the slow way, for a check and for an operator's capture."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    lines = _host_lines(data)
    producers = {}
    for n, evs in enumerate(lines):
        for i, ev in enumerate(evs):
            if "_p" in ev[3]:
                producers[ev[3].get("_pt"), ev[3]["_p"]] = (n, i)

    def launcher(stat: dict):
        """The ``seq`` of the dispatch span around the producer end of the
        chain of flows that ends in ``stat``'s consumer id."""
        at = producers.get((stat.get("_ct"), stat.get("_c")))
        for _ in range(8):  # the chain is three flows long
            if at is None:
                return None
            n, i = at
            at, evs = None, lines[n]
            while i is not None:
                _, _, name, st, parent = evs[i]
                if (name.startswith(engine_spans.PREFIX)
                        and name[len(engine_spans.PREFIX):] in DISPATCH):
                    return st.get("seq")
                if at is None and "_c" in st:
                    at = producers.get((st.get("_ct"), st["_c"]))
                i = parent
        return None

    out = {}
    for plane in data.planes:
        if plane.name != device:
            continue
        for line in plane.lines:
            if line.name != trace_reduce.MODULES_LINE:
                continue
            for ev in line.events:
                seq = (launcher(dict(ev.stats)) if kind_of(ev.name)
                       else None)
                if seq is not None:
                    out[int(round(ev.start_ns))] = int(seq)
    return out


def describe(path: str) -> dict:
    """Both joins of one capture, pair by pair: ``launches`` (one
    :meth:`Launch.line` a dispatch), the ordinal join's counts, and whether
    the exact join names the same span for every execution both know."""
    spans = engine_spans.load(path)["spans"]
    if not numbered(spans):
        return {"why": "no dispatch span in the trace carries seq"}
    trace = trace_reduce.load(path)
    if not trace.devices:
        return {"why": "the trace holds no device plane"}
    got = join_events(spans, trace.devices[0].modules)
    exact = by_flow(path, trace.devices[0].name)
    ordinal = {int(round(x.run.start * 1e9)): x.seq
               for x in got.launches if x.run is not None}
    both = sorted(set(ordinal) & set(exact))
    differ = [[t, ordinal[t], exact[t]] for t in both
              if ordinal[t] != exact[t]]
    return {**got.counts(), "flow_pairs": len(exact), "compared": len(both),
            "agree": got.ok and bool(both) and not differ,
            "differ": differ, "launches": [x.line() for x in got.launches]}


if __name__ == "__main__":
    found = describe(sys.argv[1])
    for one in found.pop("launches", ()):
        print(json.dumps(one))
    print(json.dumps(found))
