"""layer: scheduler (``serving/engine.py`` ``_prefill_one_chunk``). How full
the prefill chunks were: sum of ``tokens`` (valid prompt tokens) over sum of
``width`` (the width the chunk was compiled at) of the window's
``serve.prefill.dispatch`` spans. Source: program counter."""

from benchmarks import launches


def read(ctx):
    chunks = launches.dispatch_spans(ctx, "serve.prefill.dispatch")
    width = sum(s.fields.get("width", 0) for s in chunks or ())
    if not width:
        return None
    return 100.0 * sum(s.fields["tokens"] for s in chunks) / width
