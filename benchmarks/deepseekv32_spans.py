"""What the ``serve_deepseekv32`` cells' seven per-layer readers share: the
device time of the operations traced under the selection's three scopes
inside ``attn_latent`` (``models/hybrid.py``: ``dsa_index``, ``dsa_select``,
``dsa_attend``) and of its two index-score kernels by name, and the counters
the engine sets where a decode call lands (``serve.decode.sync``:
``dsa_tokens_attended``, ``dsa_tokens_held``, ``dsa_rows``) and where a
prefill chunk lands (``serve.prefill.sync``: ``dsa_queries``,
``dsa_pairs_scored``). Everything that reads a trace is
``minicpmsala_spans``' and ``laguna_spans``', by import. On a trace of a
program without these (the parent commit) every function here finds nothing
and returns ``None``."""

from __future__ import annotations

import re

from benchmarks import engine_spans, laguna_spans, trace_reduce
from benchmarks import minicpmsala_spans as sala

DECODE = laguna_spans.DECODE
PREFILL = sala.PREFILL
SCOPES = ("dsa_index", "dsa_select", "dsa_attend")
CHUNK_KERNEL = re.compile(r"dsa_index_chunk_call")
PAGED_KERNEL = re.compile(r"dsa_index_paged_call")
KERNEL = re.compile(r"dsa_index_(chunk|paged)_call")
KERNEL_HINT = ("dsa_index_",)


def seconds(ctx: dict, scopes=(), kernel=None, module: str | None = None):
    """Traced seconds, in the window, of the union of the operations under
    any of ``scopes`` and of the operations named ``kernel``; with
    ``module``, only inside the programs whose name matches it."""
    if ctx.get("trace") is None or not ctx["trace"].devices:
        return None
    dev = ctx["trace"].devices[0]
    got = [iv for scope in scopes for iv in (sala.scoped(ctx, scope) or [])]
    if kernel is not None:
        got = got + laguna_spans.named_intervals(dev, kernel, KERNEL_HINT)
    lo, hi = ctx["window"]
    got = trace_reduce.clip(trace_reduce.union(got), lo, hi)
    if module is not None:
        got = trace_reduce.intersect(got, trace_reduce.union(
            (e.start, e.end)
            for e in trace_reduce.module_events(dev, module, lo, hi)))
    return trace_reduce.total(got) or None


def landed(ctx: dict, name: str, field: str):
    """The window's ``name`` spans that carry ``field``."""
    spans = engine_spans.for_ctx(ctx)
    if spans is None:
        return None
    got = [s for s in engine_spans.in_window(spans, name, *ctx["window"])
           if field in s.fields]
    return got or None


def selecting_chunks(ctx: dict):
    """``(start, tokens)`` of the window's prefill dispatches that select
    (``costs_deepseekv32.selects``), where the program has an indexer (some
    chunk landed with ``dsa_queries``)."""
    from benchmarks import costs_deepseekv32 as costs

    if not landed(ctx, "serve.prefill.sync", "dsa_queries"):
        return None
    spans = engine_spans.for_ctx(ctx)
    got = [(int(s.fields["start"]), int(s.fields["tokens"]))
           for s in engine_spans.in_window(spans, "serve.prefill.dispatch",
                                           *ctx["window"])
           if "start" in s.fields and "tokens" in s.fields
           and costs.selects(int(s.fields["start"]), ctx["config"])]
    return got or None
