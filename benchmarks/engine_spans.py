#!/usr/bin/env python3
"""The program's own spans, read back from a profiler trace.

``marlin_tpu.utils.tracing.annotate`` writes ``marlin:<name>`` events with
their fields into the host plane of the ``.xplane.pb`` that ``jax.profiler``
takes, on the device trace's clock. ``ServeEngine``'s paged loop marks every
boundary of a worker iteration that way (``docs/observability.md``, "Engine
phases in a profile"). This module reads them: :func:`load` one file,
:func:`for_ctx` the file behind a ``--trace 1`` run's ``ctx``, and the
arithmetic the ``layer_metrics`` readers share. On a trace of a program
without such spans everything here finds nothing and returns ``None``.

    python3 benchmarks/engine_spans.py <xplane.pb>

prints the phase table (:func:`phases`) of any capture, an operator's
``POST /debug/profile`` included.
"""

from __future__ import annotations

import dataclasses
import functools
import glob
import json
import os
import re
import sys
import time

if __name__ == "__main__":  # run as a script: the checkout is the package root
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from benchmarks import costs, trace_reduce  # noqa: E402

PREFIX = "marlin:"
ITER = "serve.iter"
#: where ``benchmarks/run.py`` keeps a traced run's files until it has
#: reduced them
TRACE_ROOT = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), ".bench_trace")
#: the worker's spans by phase; a later ``serve.<new>`` span falls to
#: ``schedule`` until it is listed, and a phase without any span to
#: ``unattributed``
PHASES = ("schedule", "prefill", "decode")
_KERNEL = re.compile(r"paged_decode_attention")


@dataclasses.dataclass
class Span:
    name: str        # without the ``marlin:`` prefix
    start: float     # seconds on the profiler's clock
    end: float
    fields: dict
    line: int        # which host thread: one index per thread

    @property
    def label(self) -> str:
        """The phase table's row: prefill spans split by ``final``, since
        only a prompt's final chunk has a token the host must wait for."""
        if "final" in self.fields:
            return f"{self.name} final={self.fields['final']}"
        return self.name


def phase_of(name: str) -> str:
    """The phase a worker span's (``serve.*``) own time belongs to."""
    for phase in PHASES[1:]:
        if name == f"serve.{phase}" or name.startswith(f"serve.{phase}."):
            return phase
    return "schedule"


@functools.lru_cache(maxsize=4)
def load(path: str) -> dict:
    """``{"window": (lo, hi) or None, "spans": [Span, ...], "load_s", "memo"}``
    of one ``.xplane.pb``: every host event named ``marlin:*`` with its
    fields and thread line, sorted by start; ``window`` is the benchmark's own
    ``bench:window`` annotation where the capture has one. Cached per path,
    so the readers of one run load the file once; ``memo`` holds what they
    derive from it in common (:func:`idle_seconds`)."""
    from jax.profiler import ProfileData

    t0 = time.perf_counter()
    data = ProfileData.from_file(path)
    spans, window, line_no = [], None, 0
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            line_no += 1
            for ev in line.events:
                if ev.name == "bench:window":
                    start = ev.start_ns * 1e-9
                    window = (start, start + ev.duration_ns * 1e-9)
                elif ev.name.startswith(PREFIX):
                    start = ev.start_ns * 1e-9
                    spans.append(Span(ev.name[len(PREFIX):], start,
                                      start + ev.duration_ns * 1e-9,
                                      dict(ev.stats), line_no))
    spans.sort(key=lambda s: (s.start, -s.end))
    return {"window": window, "spans": spans,
            "load_s": time.perf_counter() - t0, "memo": {}}


def capture_for(ctx: dict, trace_root: str | None = None):
    """What :func:`load` gives for the trace that ``ctx["trace"]`` was read
    from: the one ``.xplane.pb`` under ``.bench_trace/`` whose
    ``bench:window`` is ``ctx["window"]``. ``None`` without a trace (the CPU
    rehearsal) and where no file has that window: a stale capture is
    refused."""
    if ctx.get("trace") is None:
        return None
    lo, hi = ctx["window"]
    for cell_dir in sorted(glob.glob(os.path.join(trace_root or TRACE_ROOT,
                                                  "*"))):
        try:
            got = load(trace_reduce.find_xplane(cell_dir))
        except FileNotFoundError:
            continue
        w = got["window"]
        if w is not None and abs(w[0] - lo) <= 1e-6 and abs(w[1] - hi) <= 1e-6:
            return got
    return None


def for_ctx(ctx: dict, trace_root: str | None = None):
    """The spans of ``ctx``'s trace, or ``None`` where :func:`capture_for`
    finds none or the program recorded no ``serve.iter``."""
    got = capture_for(ctx, trace_root)
    if got is None or worker_line(got["spans"]) is None:
        return None
    return got["spans"]


def worker_line(spans) -> int | None:
    """The engine worker's thread: the line that holds ``serve.iter``."""
    for s in spans:
        if s.name == ITER:
            return s.line
    return None


def worker_spans(spans) -> list:
    line = worker_line(spans)
    return [s for s in spans if s.line == line and s.name.startswith("serve.")]


def in_window(spans, name: str, lo: float, hi: float) -> list:
    """Spans called ``name`` that began inside ``[lo, hi]``: a count belongs
    to the window in which its boundary was crossed."""
    return [s for s in spans if s.name == name and lo <= s.start <= hi]


def self_segments(spans, by: str = "name") -> list:
    """One thread's nested spans cut into disjoint ``(start, end, name)``
    pieces, each named by the innermost span that covers it (its ``name``,
    or its ``label``): a span's self time is its pieces. ``spans`` sorted by
    ``(start, -end)``."""
    out, stack = [], []   # stack: [span, start of its next own piece]

    def close(until: float) -> None:
        while stack and stack[-1][0].end <= until:
            span, cur = stack.pop()
            if span.end > cur:
                out.append((cur, span.end, getattr(span, by)))
            if stack:
                stack[-1][1] = max(stack[-1][1], span.end)

    for s in spans:
        close(s.start)
        if stack:
            parent, cur = stack[-1]
            if s.start > cur:
                out.append((cur, s.start, getattr(parent, by)))
            stack[-1][1] = max(cur, s.start)
        stack.append([s, s.start])
    close(float("inf"))
    out.sort()
    return out


def idle_by_phase(idle, spans, lo: float, hi: float) -> dict:
    """Seconds of the chip's ``idle`` intervals (disjoint, sorted, inside
    ``[lo, hi]``) by what the worker was doing: one entry per phase and
    ``unattributed`` for idle time inside none of the worker's spans. The
    entries add up to the idle time."""
    by_phase = {p: [] for p in PHASES}
    for start, end, name in self_segments(worker_spans(spans)):
        by_phase[phase_of(name)].append((start, end))
    out = {p: trace_reduce.total(trace_reduce.intersect(
        idle, trace_reduce.clip(trace_reduce.union(iv), lo, hi)))
        for p, iv in by_phase.items()}
    out["unattributed"] = max(
        0.0, trace_reduce.total(idle) - sum(out.values()))  # rounding
    return out


def idlest_device(trace, lo: float, hi: float):
    return max(trace.devices, key=lambda d: trace_reduce.idle_share(d, lo, hi))


def idle_intervals(trace, lo: float, hi: float) -> list:
    """The idle intervals of the chip that idles most, as
    ``device_idle_pct.*`` reads it."""
    dev = idlest_device(trace, lo, hi)
    return trace_reduce.gaps(trace_reduce.clip(dev.busy, lo, hi), lo, hi)


def idle_seconds(ctx: dict):
    """``(idle intervals, idle_by_phase)`` of ``ctx``'s run, worked out once
    per capture for the four ``idle_pct.*`` readers and the note; ``None``
    without spans or without a device in the trace."""
    got = capture_for(ctx)
    if (got is None or worker_line(got["spans"]) is None
            or not ctx["trace"].devices):
        return None
    lo, hi = ctx["window"]
    if "idle" not in got["memo"]:
        idle = idle_intervals(ctx["trace"], lo, hi)
        got["memo"]["idle"] = (idle, idle_by_phase(idle, got["spans"],
                                                   lo, hi))
    return got["memo"]["idle"]


def idle_pct(ctx: dict, phase: str):
    """The ``idle_pct.*`` readers: the share of the traced window in which
    no device operation ran and the worker was in ``phase``."""
    got = idle_seconds(ctx)
    if got is None:
        return None
    lo, hi = ctx["window"]
    return 100.0 * got[1][phase] / (hi - lo)


def mean_field(spans, field: str):
    values = [s.fields[field] for s in spans if field in s.fields]
    return sum(values) / len(values) if values else None


def iter_mean_pct(ctx: dict, over: str, under) -> float | None:
    """The ``kv_*`` readers: mean over the window's ``serve.iter`` spans of
    field ``over`` against ``under(fields)``, in percent; iterations where
    the base is 0 (nothing resident) are left out."""
    spans = for_ctx(ctx)
    if spans is None:
        return None
    shares = [s.fields[over] / under(s.fields)
              for s in in_window(spans, ITER, *ctx["window"])
              if under(s.fields)]
    return 100.0 * sum(shares) / len(shares) if shares else None


def dispatches(spans, lo: float, hi: float) -> list:
    """The decode dispatches of the window: ``serve.decode.dispatch`` spans
    that handed the chip a call (a sweep over a bucket without live rows
    leaves ``rows`` 0 and no ``table_width``)."""
    return [s for s in in_window(spans, "serve.decode.dispatch", lo, hi)
            if s.fields.get("rows") and "table_width" in s.fields]


def attention_least_seconds(calls, config: dict, peaks: dict) -> dict:
    """The least seconds the chip could take for the attention work the
    decode ``calls`` (dispatch spans) were given: per call and layer,
    ``costs.paged_attention_cost`` over the padded rows and the whole table
    width, bytes over the memory peak against flops over the bf16 peak."""
    import jax.numpy as jnp

    heads = int(config["n_heads"])
    dh = int(config["d_model"]) // heads
    page_len = int(config["engine"]["page_len"])
    itemsize = jnp.dtype(config["compute_dtype"]).itemsize
    memory_s = compute_s = 0.0
    for s in calls:
        c = costs.paged_attention_cost(
            int(s.fields["padded_rows"]), int(s.fields["table_width"]),
            page_len, heads, 1, dh, itemsize)
        memory_s += c["bytes"] / peaks["hbm_bytes_per_s"]
        compute_s += c["flops"] / peaks["bf16_flops_per_s"]
    layers = int(config["n_layers"])
    return {"seconds": layers * max(memory_s, compute_s),
            "bound": "memory" if memory_s >= compute_s else "compute",
            "memory_s": layers * memory_s, "compute_s": layers * compute_s}


def kernel_seconds(trace, lo: float, hi: float) -> float:
    """Traced seconds of the paged decode-attention kernel in the window
    (the operation's own name, not a fusion that consumes it)."""
    dev = trace.devices[0]
    return trace_reduce.total(trace_reduce.clip(trace_reduce.op_intervals(
        dev, lambda e: bool(_KERNEL.search(
            trace_reduce.parse_op(e.name)["short"]))), lo, hi))


def request_parts(spans, lo: float, hi: float) -> dict:
    """For the requests admitted in the window, the two parts of TTFT after
    the queue: ``serve.admit`` to the first ``serve.prefill.dispatch``, and
    from there to the final ``serve.prefill.sync``. Milliseconds, means and
    sample counts."""
    first, last = {}, {}
    for s in spans:
        rid = s.fields.get("rid")
        if s.name == "serve.prefill.dispatch" and rid not in first:
            first[rid] = s.start
        elif s.name == "serve.prefill.sync" and s.fields.get("final"):
            last[rid] = s.end
    to_dispatch, to_first = [], []
    for s in in_window(spans, "serve.admit", lo, hi):
        rid = s.fields.get("rid")
        if rid in first:
            to_dispatch.append(1e3 * (first[rid] - s.start))
            if rid in last:
                to_first.append(1e3 * (last[rid] - first[rid]))
    mean = lambda v: sum(v) / len(v) if v else None  # noqa: E731
    return {"admit_to_prefill_ms": mean(to_dispatch),
            "prefill_to_first_token_ms": mean(to_first),
            "samples": [len(to_dispatch), len(to_first)]}


#: fields that name a span (and join it to a request) instead of counting
_NAMING = ("rid", "final")


def phases(spans, idle, lo: float, hi: float, by_phase=None) -> dict:
    """The phase table: for every span label its count, seconds, self seconds
    and (with ``idle`` intervals of a chip) idle seconds inside its self
    time, clipped to ``[lo, hi]``, and the mean of each count it carries
    (``fields``: the queue, rows and pages an iteration found, the tokens a
    chunk held, ...); the admissions' queue waits; the parts of TTFT; the
    decode dispatches and their rows. ``by_phase`` is
    ``idle_by_phase(idle, spans, lo, hi)`` where the caller has it."""
    table, sums = {}, {}
    for s in spans:
        start, end = max(s.start, lo), min(s.end, hi)
        if end <= start:
            continue
        row = table.setdefault(s.label, {
            "count": 0, "seconds": 0.0, "self_s": 0.0,
            "idle_s": None if idle is None else 0.0, "fields": {}})
        row["count"] += 1
        row["seconds"] += end - start
        for k, v in s.fields.items():
            if k not in _NAMING and isinstance(v, (int, float)):
                n, total = sums.get((s.label, k), (0, 0.0))
                sums[s.label, k] = (n + 1, total + v)
    for (label, k), (n, total) in sums.items():
        table[label]["fields"][k] = total / n
    for start, end, name in self_segments(worker_spans(spans), by="label"):
        piece = trace_reduce.clip([(start, end)], lo, hi)
        if not piece or name not in table:
            continue
        table[name]["self_s"] += trace_reduce.total(piece)
        if idle is not None:
            table[name]["idle_s"] += trace_reduce.total(
                trace_reduce.intersect(idle, piece))
    on_worker = {s.label for s in worker_spans(spans)}
    for name, row in table.items():
        if name not in on_worker:  # a caller's thread: nothing nests there
            row["self_s"], row["idle_s"] = row["seconds"], None
    waits = [s.fields["queue_wait_ms"]
             for s in in_window(spans, "serve.admit", lo, hi)
             if "queue_wait_ms" in s.fields]
    calls = dispatches(spans, lo, hi)
    return {"spans": table,
            "idle_by_phase_s": (
                None if idle is None
                else by_phase or idle_by_phase(idle, spans, lo, hi)),
            "queue_wait_ms": {"count": len(waits), "samples": waits},
            "request_parts": request_parts(spans, lo, hi),
            "decode_dispatches": len(calls),
            "decode_rows": sum(int(s.fields["rows"]) for s in calls)}


def note(ctx: dict) -> None:
    """Print the ``engine_phases`` note of a traced run: the phase table,
    the span-side decode counts beside the sink's, and what reading the
    ``.xplane.pb`` a second time cost."""
    got = capture_for(ctx)
    if got is None or worker_line(got["spans"]) is None:
        return
    idle, by_phase = idle_seconds(ctx) or (None, None)
    print(json.dumps({
        "note": "engine_phases",
        **phases(got["spans"], idle, *ctx["window"], by_phase),
        "sink_decode_steps": ctx["counters"].get("decode_steps"),
        "sink_decode_rows": ctx["counters"].get("decode_rows"),
        "xplane_second_read_s": got["load_s"]}), flush=True)


def describe(path: str) -> dict:
    """The phase table of any capture: the window is the benchmark's where
    it marked one, else the extent of the worker's spans."""
    got = load(path)
    spans = got["spans"]
    if worker_line(spans) is None:
        return {"spans": {}, "why": "no marlin:serve.iter span in the trace"}
    lo, hi = got["window"] or (min(s.start for s in worker_spans(spans)),
                               max(s.end for s in worker_spans(spans)))
    trace = trace_reduce.load(path)
    idle = idle_intervals(trace, lo, hi) if trace.devices else None
    return {"window_s": hi - lo, **phases(spans, idle, lo, hi)}


if __name__ == "__main__":
    print(json.dumps(describe(sys.argv[1]), indent=1))
