"""layer: scheduler (``_admit_paged``). Mean wait between ``submit`` and the
admission to a row, over the ``serve.admit`` spans of the window (their
``queue_wait_ms`` field: the engine's own ``queue_s``). In a closed loop a
caller sends its next request only after this wait. Source: program counter."""

from benchmarks import engine_spans


def read(ctx):
    spans = engine_spans.for_ctx(ctx)
    if spans is None:
        return None
    return engine_spans.mean_field(
        engine_spans.in_window(spans, "serve.admit", *ctx["window"]),
        "queue_wait_ms")
