"""layer: kernels (``ops/paged_attention.py``, the full layers' decode
kernel). The least seconds for the pages that hold the positions the live
rows attend (``global_kv_pages`` of each ``serve.decode.dispatch`` span x
the full layers x one page's K and V bytes, over the memory peak) over the
traced seconds of the kernel. Priced by the pages that must be read, not by
``padded_rows`` x ``global_table_width``: the kernel's pipeline does not
fetch the dummy page again while the block index stays on it, so the padded
price counts bytes that never move. Source: device trace + spans."""

from benchmarks import costs_laguna, laguna_spans


def read(ctx):
    calls = laguna_spans.decode_dispatches(ctx, "global_kv_pages")
    if calls is None or not ctx["trace"].devices:
        return None
    spent = laguna_spans.op_seconds(ctx, laguna_spans.GLOBAL_KERNEL,
                                    laguna_spans.KERNEL_HINT)
    if spent <= 0:
        return None
    pages = sum(s.fields["global_kv_pages"] for s in calls)
    return 100.0 * costs_laguna.attention_least_seconds(
        pages, "full_attention", ctx["config"], ctx["peaks"]) / spent
