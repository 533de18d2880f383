"""layer: kernels (``ops/paged_attention.py``, a configuration-built model's
decode kernel over every position: the full layers', or the latent one). Grid
steps that hold attended positions over the grid steps of the call: sum of
``global_kv_pages`` (``latent_kv_pages``) over sum of ``padded_rows`` x
``global_table_width`` (``latent_table_width``) of the window's decode
dispatches. The rest are dead steps, skipped at a fixed cost each. The dense
cell has ``decode_kv_useful_pct``. Source: program counter."""

from benchmarks import launches

KINDS = (("global_kv_pages", "global_table_width"),
         ("latent_kv_pages", "latent_table_width"))


def read(ctx):
    calls = launches.dispatch_spans(ctx, "serve.decode.dispatch")
    for pages, width in KINDS:
        held = [s.fields for s in calls or ()
                if pages in s.fields and width in s.fields]
        steps = sum(f["padded_rows"] * f[width] for f in held)
        if steps:
            return 100.0 * sum(f[pages] for f in held) / steps
    return None
