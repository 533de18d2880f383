"""layer: scheduler (``serving/engine.py`` ``_step_paged``). Live rows per
device call of the decode program: mean ``rows`` of the window's
``serve.decode.dispatch`` spans that handed the chip a call. Beside it
stands the engine's ``max_batch``, to which every call is padded:
``rows_per_step`` counts rows a bucket's ``step`` record, this the rows that
shared one read of the weights. Source: program counter."""

from benchmarks import engine_spans


def read(ctx):
    spans = engine_spans.for_ctx(ctx)
    if spans is None:
        return None
    return engine_spans.mean_field(
        engine_spans.dispatches(spans, *ctx["window"]), "rows")
