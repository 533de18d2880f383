"""layer: kvpool (``serving/kvpool.py``). Pages held by resident rows
(``row_pages``, reserved up front for prompt + steps) over the pool's pages
(``pages_total``), mean over the window's ``serve.iter`` spans.
Source: program counter."""

from benchmarks import engine_spans


def read(ctx):
    return engine_spans.iter_mean_pct(ctx, "row_pages",
                                      lambda f: f.get("pages_total"))
