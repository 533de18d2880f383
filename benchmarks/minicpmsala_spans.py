"""What the ``serve_minicpmsala`` cells' eight per-layer readers share: the
device time of the operations traced under this family's scopes
(``models/hybrid.py``: ``attn_sparse`` with ``sparse_select`` and
``sparse_attend`` inside it, ``lightning_attn`` with ``lightning_scan`` and
``lightning_update`` inside it) and of its two decode kernels by name, and
the counters the engine sets on ``serve.decode.sync`` where a decode call
lands (``sparse_blocks_attended``, ``sparse_blocks_held``, ``sparse_rows``).
The capture's scope table (``laguna_spans.op_scopes``) is read once for all
of them. On a trace of a program without these (the parent commit) every
function here finds nothing and returns ``None``."""

from __future__ import annotations

import functools
import re

from benchmarks import engine_spans, laguna_spans, trace_reduce

DECODE = laguna_spans.DECODE
PREFILL = r"lm_prefill_paged"
BLOCKS_KERNEL = re.compile(r"paged_decode_attention_blocks_call")
BLOCKS_HINT = ("paged_decode_attention_blocks",)
UPDATE_KERNEL = re.compile(r"lightning_decode_update_call")
UPDATE_HINT = ("lightning_decode_update",)


@functools.lru_cache(maxsize=2)
def _scopes(path: str) -> dict:
    return laguna_spans.op_scopes(path)


def scoped(ctx: dict, scope: str) -> list | None:
    """``(start, end)`` of the first chip's operations traced under
    ``scope``; ``None`` where the capture is not found or names none so."""
    path = laguna_spans.xplane_path(ctx)
    if path is None or not ctx["trace"].devices:
        return None
    marked = {name for name, where in _scopes(path).items()
              if f"/{scope}/" in where or where.endswith("/" + scope)}
    got = [(e.start, e.end) for e in ctx["trace"].devices[0].ops
           if e.name in marked]
    return got or None


def seconds(ctx: dict, scope: str | None = None, kernel=None, hint=(),
            module: str | None = None):
    """Traced seconds, in the window, of the union of the operations under
    ``scope`` and of the operations named ``kernel``; with ``module``, only
    inside the programs whose name matches it. ``None`` where neither is in
    the trace."""
    if ctx.get("trace") is None or not ctx["trace"].devices:
        return None
    dev = ctx["trace"].devices[0]
    got = (scoped(ctx, scope) or []) if scope else []
    if kernel is not None:
        got = got + laguna_spans.named_intervals(dev, kernel, hint)
    lo, hi = ctx["window"]
    got = trace_reduce.clip(trace_reduce.union(got), lo, hi)
    if module is not None:
        got = trace_reduce.intersect(got, trace_reduce.union(
            (e.start, e.end)
            for e in trace_reduce.module_events(dev, module, lo, hi)))
    return trace_reduce.total(got) or None


def share_of_busy(ctx: dict, scope: str, kernel, hint):
    """:func:`seconds` over the first chip's busy time in the window, %."""
    spent = seconds(ctx, scope, kernel, hint)
    if spent is None:
        return None
    busy = trace_reduce.busy_seconds(ctx["trace"].devices[0], *ctx["window"])
    return 100.0 * spent / busy if busy > 0 else None


def landed(ctx: dict):
    """The window's ``serve.decode.sync`` spans that carry the sparse
    layers' counters."""
    spans = engine_spans.for_ctx(ctx)
    if spans is None:
        return None
    got = [s for s in engine_spans.in_window(spans, "serve.decode.sync",
                                             *ctx["window"])
           if "sparse_blocks_held" in s.fields]
    return got or None


def chunks(ctx: dict):
    """The window's prefill dispatches of this family (they carry
    ``lightning_tokens``)."""
    spans = engine_spans.for_ctx(ctx)
    if spans is None:
        return None
    got = [s for s in engine_spans.in_window(
        spans, "serve.prefill.dispatch", *ctx["window"])
        if "lightning_tokens" in s.fields]
    return got or None
