"""layer: scheduler. Share of the traced window in which no device operation
ran and the engine's worker (``serving/engine.py`` ``_run_paged``) was
in ``serve.wait``, ``serve.claim``, ``serve.admit`` or the self time of
``serve.iter`` (migrations, program rows, page gauges). Also prints the
``engine_phases`` note of the run.
The four ``idle_pct.*`` add up to ``device_idle_pct.serve``.
Source: device trace, cut by the program's spans."""

from benchmarks import engine_spans


def read(ctx):
    engine_spans.note(ctx)
    return engine_spans.idle_pct(ctx, "schedule")
