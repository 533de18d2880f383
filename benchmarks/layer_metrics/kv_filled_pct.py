"""layer: kvpool (``serving/kvpool.py``). Positions written (``kv_tokens``)
over the positions the resident rows' pages hold (``row_pages`` x
``page_len``), mean over the window's ``serve.iter`` spans: what up-front
reservation leaves empty, which is what limits the batch.
Source: program counter."""

from benchmarks import engine_spans


def read(ctx):
    page_len = int(ctx["config"]["engine"]["page_len"])
    return engine_spans.iter_mean_pct(
        ctx, "kv_tokens", lambda f: f.get("row_pages", 0) * page_len)
