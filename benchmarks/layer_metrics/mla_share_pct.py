"""layer: programs (``models/hybrid.py``, the attention half of a latent
layer inside the paged programs). Device time of everything traced under the
``attn_latent`` scope (the projections, norms and rotary embedding, prefill's
blocked attention, decode's absorption) plus the latent decode kernel by
name, over the device's busy time in the traced window, prefill and decode
together. Source: device trace."""

import re

from benchmarks import laguna_spans, trace_reduce

SCOPE = "attn_latent"
KERNEL = re.compile(r"paged_decode_attention_latent_call")
KERNEL_HINT = ("paged_decode_attention_latent",)


def read(ctx):
    got = laguna_spans.scoped_intervals(ctx, SCOPE)
    if got is None:
        return None
    lo, hi = ctx["window"]
    dev = ctx["trace"].devices[0]
    got = got + laguna_spans.named_intervals(dev, KERNEL, KERNEL_HINT)
    inside = trace_reduce.clip(trace_reduce.union(got), lo, hi)
    busy = trace_reduce.busy_seconds(dev, lo, hi)
    if not inside or busy <= 0:
        return None
    return 100.0 * trace_reduce.total(inside) / busy
