"""layer: programs (``models/hybrid.py``, the state-space mixer inside the
paged programs). Device time of everything traced under the ``ssm_mixer``
scope (the input projection, the convolution, the chunked scan, the gate and
norm, the output projection) plus the decode state update's kernel by name,
over the device's busy time in the traced window, prefill and decode
together. Source: device trace."""

import re

from benchmarks import laguna_spans, trace_reduce

SCOPE = "ssm_mixer"
KERNEL = re.compile(r"ssm_decode_update_call")
KERNEL_HINT = ("ssm_decode_update",)


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
