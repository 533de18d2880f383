"""layer: kernels (``models/hybrid.py:_short_conv`` inside the decode
program: XLA's fusions today). The least seconds for the window's decode
calls (``costs_lfm2.conv_decode_least_seconds``: each
``serve.decode.dispatch`` span x the conv layers x the mixer's two
projections read once and its ``state_rows`` live rows' tails read and
written once, over the memory peak) over the traced seconds of the
operations under the ``short_conv`` scope inside decode programs. Priced by
the mixer's WORK and by LIVE rows. Source: device trace + spans."""

from benchmarks import costs_lfm2, laguna_spans, lfm2_spans


def read(ctx):
    calls = laguna_spans.decode_dispatches(ctx, "state_rows")
    if calls is None or not ctx["trace"].devices \
            or "conv_L_cache" not in ctx["config"]:
        return None
    spent = lfm2_spans.scoped_seconds(ctx, lfm2_spans.DECODE)
    if spent is None:
        return None
    return 100.0 * costs_lfm2.conv_decode_least_seconds(
        len(calls), sum(s.fields["state_rows"] for s in calls),
        ctx["config"], ctx["peaks"]) / spent
