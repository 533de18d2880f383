"""layer: kernels (``ops/dsa.py:attend_list`` and the gather of its entries
inside the prefill program, the operations traced under the ``dsa_attend``
scope). The least seconds for the entries the window's selecting chunks'
tokens attended (``costs_deepseekv32.selected_entries``: ``min(index_topk,
position + 1)`` a valid token, from each ``serve.prefill.dispatch`` span's
``start`` and ``tokens``, x the layers x 1280 B over the memory peak, or the
absorbed form's operations over the bf16 peak where larger) over the traced
seconds of those operations. Source: device trace + spans."""

from benchmarks import costs_deepseekv32 as costs, deepseekv32_spans as dsa


def read(ctx):
    chunks = dsa.selecting_chunks(ctx)
    spent = dsa.seconds(ctx, ("dsa_attend",), module=dsa.PREFILL) \
        if chunks else None
    if spent is None:
        return None
    cfg = ctx["config"]
    entries = costs.layers(cfg) * sum(
        costs.selected_entries(s, t, cfg) for s, t in chunks)
    return 100.0 * costs.attend_least_seconds(
        entries, cfg, ctx["peaks"])["seconds"] / spent
