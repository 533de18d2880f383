"""layer: kernels (``ops/paged_attention.py``). The least seconds the chip
could take for the attention work the window's decode dispatches were given
(``engine_spans.attention_least_seconds``: per ``serve.decode.dispatch`` span
and layer, ``costs.paged_attention_cost`` of the padded rows over the whole
table width, bytes over the memory peak against flops over the bf16 peak,
the larger) over the traced seconds of the ``paged_decode_attention``
operations in the window. Source: device trace + the program's spans."""

from benchmarks import engine_spans


def read(ctx):
    spans = engine_spans.for_ctx(ctx)
    if spans is None or not ctx["trace"].devices:
        return None
    lo, hi = ctx["window"]
    spent = engine_spans.kernel_seconds(ctx["trace"], lo, hi)
    calls = engine_spans.dispatches(spans, lo, hi)
    if spent <= 0 or not calls:
        return None
    least = engine_spans.attention_least_seconds(calls, ctx["config"],
                                                 ctx["peaks"])
    return 100.0 * least["seconds"] / spent
