"""layer: kernels (``models/hybrid.py:_short_conv`` inside the prefill
programs: XLA's fusions today). The least seconds for the window's prefill
chunks (``costs_lfm2.conv_prefill_least_seconds``: each
``serve.prefill.dispatch`` span's ``conv_tokens`` x the conv layers x a
token's flops through the mixer, over the bf16 peak) over the traced seconds
of the operations under the ``short_conv`` scope inside prefill programs.
Priced by VALID tokens: a chunk's padding is the program's cost. Source:
device trace + spans."""

from benchmarks import costs_lfm2, engine_spans, lfm2_spans


def read(ctx):
    spans = engine_spans.for_ctx(ctx)
    if spans is None or not ctx["trace"].devices \
            or "conv_L_cache" not in ctx["config"]:
        return None
    chunks = [s for s in engine_spans.in_window(
        spans, "serve.prefill.dispatch", *ctx["window"])
        if "conv_tokens" in s.fields]
    spent = lfm2_spans.scoped_seconds(ctx, lfm2_spans.PREFILL) \
        if chunks else None
    if spent is None:
        return None
    return 100.0 * costs_lfm2.conv_prefill_least_seconds(
        sum(s.fields["conv_tokens"] for s in chunks), ctx["config"],
        ctx["peaks"]) / spent
