"""layer: programs (``lm_prefill_paged``). Device seconds of the executions
joined to the window's ``serve.prefill.dispatch`` spans over the sum of those
spans' ``tokens`` (valid prompt tokens; a chunk's padding is not counted), in
microseconds: what batching prefill or narrowing a chunk lowers, whatever the
traffic. Source: device trace + spans, joined by ``seq``
(``benchmarks/launches.py``)."""

from benchmarks import launches


def read(ctx):
    chunks = launches.in_window(ctx, "prefill")
    tokens = sum(x.span.fields.get("tokens", 0) for x in chunks or ())
    if not tokens:
        return None
    return 1e6 * sum(x.run.seconds for x in chunks) / tokens
