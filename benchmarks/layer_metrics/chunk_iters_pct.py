"""layer: scheduler (``serving/engine.py`` ``_run_paged``). Share of the
window's ``serve.iter`` spans that hold at least one
``serve.prefill.dispatch``: over 5 % and the 95th-percentile gap between
tokens IS an iteration that also ran a chunk. Source: program counter."""

from benchmarks import engine_spans, launches


def read(ctx):
    chunks = launches.dispatch_spans(ctx, "serve.prefill.dispatch")
    if chunks is None:
        return None
    iters = engine_spans.in_window(engine_spans.for_ctx(ctx),
                                   engine_spans.ITER, *ctx["window"])
    if not iters:
        return None
    starts = sorted(s.start for s in chunks)
    held = sum(1 for it in iters
               if any(it.start <= t <= it.end for t in starts))
    return 100.0 * held / len(iters)
