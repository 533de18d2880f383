"""layer: kvpool (``serving/kvpool.py``, the window class). Pages resident
rows hold in the window class (``window_pages``: a ring each) over what the
same rows would pin there if a sliding layer kept every position
(``global_pages``: what they hold in the global class), mean over the
window's ``serve.iter`` spans. Source: program counter."""

from benchmarks import engine_spans


def read(ctx):
    return engine_spans.iter_mean_pct(ctx, "window_pages",
                                      lambda f: f.get("global_pages"))
