"""layer: kernels (``ops/delta_rule.py:delta_chunk_scan`` with a decay a
channel inside the prefill programs). The blocks of the chunk scan a KDA
layer RAN (``kda_blocks`` of each ``serve.prefill.dispatch`` span: those
that hold a token where the scan's kernel skips the blocks past the row's
length, every block where XLA's form runs) over the blocks the window's
chunks hold (``width`` / the configuration's ``kda_chunk_size``). 100: the
scan computes every position whatever the row holds. Nothing where the
program's spans carry no ``kda_blocks``. Source: program counter."""

from benchmarks import engine_spans


def read(ctx):
    spans = engine_spans.for_ctx(ctx)
    if spans is None:
        return None
    chunks = [s for s in engine_spans.in_window(
        spans, "serve.prefill.dispatch", *ctx["window"])
        if "kda_blocks" in s.fields and "width" in s.fields]
    block = int(ctx["config"].get("kda_chunk_size", 64))
    held = sum(s.fields["width"] // min(block, s.fields["width"])
               for s in chunks)
    if not held:
        return None
    return 100.0 * sum(s.fields["kda_blocks"] for s in chunks) / held
