"""layer: scheduler. Share of the traced window in which no device operation
ran and the engine's worker (``serving/engine.py`` ``_run_paged``) was
inside ``serve.prefill``: chunk build and dispatch, the ``int(first)`` sync,
the chunk's records.
The four ``idle_pct.*`` add up to ``device_idle_pct.serve``.
Source: device trace, cut by the program's spans."""

from benchmarks import engine_spans


def read(ctx):
    return engine_spans.idle_pct(ctx, "prefill")
