"""layer: programs (``models/hybrid.py``, the KDA mixer inside the paged
programs). Device time of everything traced under the ``attn_kda`` scope
(the projections, the low-rank decay and gate, the convolution, the chunked
form or the decode update, the output norm, the output projection) plus the
decode state update's kernel by name, over the device's busy time in the
traced window, prefill and decode together. Source: device trace."""

import re

from benchmarks import laguna_spans, trace_reduce

SCOPE = "attn_kda"
KERNEL = re.compile(r"delta_decode_update_call")
KERNEL_HINT = ("delta_decode_update",)


def read(ctx):
    if "kda_use_full_proj" not in ctx["config"]:
        return None
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
