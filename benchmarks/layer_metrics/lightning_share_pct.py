"""layer: programs (``models/hybrid.py``, the lightning mixer inside the
paged programs). Device time of everything traced under the
``lightning_attn`` scope (projections, QK-norm, the rotary embedding, the
block form or the decode update, the output norm and gate, the output
projection) plus the decode state update's kernel by name, over the
device's busy time in the traced window, prefill and decode together.
Source: device trace."""

from benchmarks import minicpmsala_spans as sala


def read(ctx):
    return sala.share_of_busy(ctx, "lightning_attn", sala.UPDATE_KERNEL,
                              sala.UPDATE_HINT)
