"""layer: kernels (``ops/lightning.py:lightning_chunk_scan`` inside the
prefill programs, the operations traced under the ``lightning_scan``
scope). The least seconds for the window's prefill chunks
(``costs_minicpmsala.lightning_prefill_least_seconds``: each
``serve.prefill.dispatch`` span's ``lightning_tokens`` x the lightning
layers x the block form's flops at the block the program uses over the bf16
peak against its bytes over the memory peak, the row's state read and
written once a chunk among them, the larger) over the traced seconds of
those operations. Priced by VALID tokens: a chunk's padding is the
program's cost, not the algorithm's. Source: device trace + spans."""

from benchmarks import costs_minicpmsala, minicpmsala_spans as sala


def read(ctx):
    chunks = sala.chunks(ctx)
    spent = sala.seconds(ctx, "lightning_scan") if chunks else None
    if spent is None:
        return None
    return 100.0 * costs_minicpmsala.lightning_prefill_least_seconds(
        sum(s.fields["lightning_tokens"] for s in chunks), len(chunks),
        ctx["config"], ctx["peaks"])["seconds"] / spent
