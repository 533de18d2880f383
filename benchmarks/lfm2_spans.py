"""What the ``serve_lfm2`` cells' three per-layer readers share: the device
time of the operations traced under the ``short_conv`` scope
(``models/hybrid.py``: the gated short convolution's input projection,
gates, taps, tail read and write, output projection), found through
``laguna_spans.op_scopes``. On a trace of a program without the scope (the
parent commit) it finds nothing and returns ``None``."""

from __future__ import annotations

from benchmarks import laguna_spans, trace_reduce

SCOPE = "short_conv"
DECODE = laguna_spans.DECODE
PREFILL = r"lm_prefill_paged"


def scoped_seconds(ctx: dict, module: str | None = None):
    """Traced seconds, in the window, of the first chip's operations under
    :data:`SCOPE`; with ``module``, only inside the events of the programs
    whose name matches it. ``None`` on a trace without the scope."""
    got = laguna_spans.scoped_intervals(ctx, SCOPE)
    if got is None:
        return None
    lo, hi = ctx["window"]
    got = trace_reduce.clip(trace_reduce.union(got), lo, hi)
    if module is not None:
        got = trace_reduce.intersect(got, trace_reduce.union(
            (e.start, e.end) for e in trace_reduce.module_events(
                ctx["trace"].devices[0], module, lo, hi)))
    return trace_reduce.total(got) or None
