"""layer: kernels (``ops/paged_attention.py``, the block-walk decode kernel
``_paged_decode_attention_blocks_call``). The least seconds for the blocks
the live rows attended (``costs_minicpmsala.sparse_decode_least_seconds``:
``sparse_blocks_attended`` of each ``serve.decode.sync`` span, which is
summed over rows, KV heads and sparse layers, x one block's K and V bytes of
ONE head's 128 lanes, plus the queries and outputs of each ``serve.decode.dispatch`` span's
``rows``, over the memory peak) over the traced seconds of the kernel. Priced by the blocks the
selection names, never by the rows' contexts. Source: device trace +
program counter."""

from benchmarks import costs_minicpmsala, laguna_spans, \
    minicpmsala_spans as sala


def read(ctx):
    landed = sala.landed(ctx)
    calls = laguna_spans.decode_dispatches(ctx, "rows")
    spent = sala.seconds(ctx, kernel=sala.BLOCKS_KERNEL,
                         hint=sala.BLOCKS_HINT) if landed and calls else None
    if spent is None:
        return None
    return 100.0 * costs_minicpmsala.sparse_decode_least_seconds(
        sum(s.fields["sparse_blocks_attended"] for s in landed),
        sum(s.fields["rows"] for s in calls), ctx["config"],
        ctx["peaks"]) / spent
