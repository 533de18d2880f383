"""layer: kernels (``ops/dsa.py``, the index-score kernel
``_dsa_index_chunk_call`` inside the prefill program). The least seconds for
the index scores of the window's chunks
(``costs_deepseekv32.index_prefill_least_seconds``: ``dsa_pairs_scored`` of
each ``serve.prefill.sync`` span, the causal (query, key) pairs over all
layers, x 16,384 operations over the bf16 peak, or the queries' and keys'
bytes over the memory peak where larger) over the traced seconds of the
kernel. Source: device trace + program counter."""

from benchmarks import costs_deepseekv32 as costs, deepseekv32_spans as dsa


def read(ctx):
    landed = dsa.landed(ctx, "serve.prefill.sync", "dsa_pairs_scored")
    chunks = dsa.selecting_chunks(ctx) if landed else None
    spent = dsa.seconds(ctx, kernel=dsa.CHUNK_KERNEL) if chunks else None
    if spent is None:
        return None
    return 100.0 * costs.index_prefill_least_seconds(
        sum(s.fields["dsa_pairs_scored"] for s in landed),
        sum(t for _, t in chunks), sum(s + t for s, t in chunks),
        ctx["config"], ctx["peaks"])["seconds"] / spent
