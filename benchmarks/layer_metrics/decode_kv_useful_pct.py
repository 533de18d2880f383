"""layer: kernels (``ops/paged_attention.py``). Useful outcomes over attempts
of the decode attention call: the positions the live rows attend
(``kv_tokens``) over the positions every call covers (``padded_rows`` x
``table_width`` x ``page_len``), summed over the window's
``serve.decode.dispatch`` spans. Source: program counter."""

from benchmarks import engine_spans


def read(ctx):
    spans = engine_spans.for_ctx(ctx)
    if spans is None:
        return None
    calls = engine_spans.dispatches(spans, *ctx["window"])
    page_len = int(ctx["config"]["engine"]["page_len"])
    given = sum(s.fields["padded_rows"] * s.fields["table_width"] * page_len
                for s in calls)
    if not given:
        return None
    return 100.0 * sum(s.fields["kv_tokens"] for s in calls) / given
