"""layer: kernels (``ops/delta_rule.py:delta_chunk_scan`` with a decay a
channel inside the prefill programs, the operations traced under the
``kda_scan`` scope). The least seconds for the window's prefill chunks
(``costs_solaropen2.kda_prefill_least_seconds``: each
``serve.prefill.dispatch`` span's ``kda_tokens`` x the KDA layers x the
chunked form's flops at the block the program uses over the bf16 peak
against its bytes over the memory peak, the row's state read and written
once a chunk among them, the larger) over the traced seconds of those
operations. Priced by VALID tokens: a chunk's padding is the program's cost,
not the algorithm's. Source: device trace + spans."""

from benchmarks import costs_solaropen2, engine_spans, laguna_spans, \
    trace_reduce

SCOPE = "kda_scan"


def read(ctx):
    spans = engine_spans.for_ctx(ctx)
    if spans is None or not ctx["trace"].devices:
        return None
    chunks = [s for s in engine_spans.in_window(
        spans, "serve.prefill.dispatch", *ctx["window"])
        if "kda_tokens" in s.fields]
    got = laguna_spans.scoped_intervals(ctx, SCOPE) if chunks else None
    if not got:
        return None
    spent = trace_reduce.total(trace_reduce.clip(
        trace_reduce.union(got), *ctx["window"]))
    if spent <= 0:
        return None
    return 100.0 * costs_solaropen2.kda_prefill_least_seconds(
        sum(s.fields["kda_tokens"] for s in chunks), len(chunks),
        ctx["config"], ctx["peaks"])["seconds"] / spent
