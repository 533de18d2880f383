"""layer: programs (``models/hybrid.py``, the sparse-attention mixer inside
the paged programs). Device time of everything traced under the
``attn_sparse`` scope (projections, QK-norm, the compressed keys, the
selection, prefill's masked attention, gate, output projection) plus the
decode block-walk kernel by name, over the device's busy time in the traced
window, prefill and decode together. Source: device trace."""

from benchmarks import minicpmsala_spans as sala


def read(ctx):
    return sala.share_of_busy(ctx, "attn_sparse", sala.BLOCKS_KERNEL,
                              sala.BLOCKS_HINT)
