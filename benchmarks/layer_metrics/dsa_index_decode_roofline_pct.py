"""layer: kernels (``ops/dsa.py``, the index-score page walk
``_dsa_index_paged_call`` inside the decode program). The least seconds for
the live rows' index scores (``costs_deepseekv32.index_decode_least_seconds``:
``dsa_tokens_held`` of each ``serve.decode.sync`` span, summed over rows and
layers, x one index key's 256 B over the memory peak, or the pairs' 16,384
operations over the bf16 peak where larger) over the traced seconds of the
kernel. Source: device trace + program counter."""

from benchmarks import costs_deepseekv32 as costs, deepseekv32_spans as dsa


def read(ctx):
    calls = dsa.landed(ctx, "serve.decode.sync", "dsa_tokens_held")
    spent = dsa.seconds(ctx, kernel=dsa.PAGED_KERNEL) if calls else None
    if spent is None:
        return None
    return 100.0 * costs.index_decode_least_seconds(
        sum(s.fields["dsa_tokens_held"] for s in calls), ctx["config"],
        ctx["peaks"])["seconds"] / spent
