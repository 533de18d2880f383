"""layer: programs (``models/hybrid.py``, the token selection inside a latent
layer of the paged programs). Device time of everything traced under the
``dsa_index``, ``dsa_select`` and ``dsa_attend`` scopes (the indexer's
projections, norm and rotary embedding, the index scores, the k-th score, the
mask and the lists, the gather of the selected entries and the attention over
them) plus the two index-score kernels by name, over the device's busy time
in the traced window, prefill and decode together. Source: device trace."""

from benchmarks import deepseekv32_spans as dsa, trace_reduce


def read(ctx):
    spent = dsa.seconds(ctx, dsa.SCOPES, dsa.KERNEL)
    if spent is None:
        return None
    busy = trace_reduce.busy_seconds(ctx["trace"].devices[0], *ctx["window"])
    return 100.0 * spent / busy if busy > 0 else None
