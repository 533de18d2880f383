"""layer: programs (``models/hybrid.py``, the gated short convolution inside
the paged programs). Device time of everything traced under the
``short_conv`` scope (the input projection, the gates, the taps, the tail's
read and write, the output projection; a kernel of its own would be added
here by name), over the device's busy time in the traced window, prefill and
decode together. Source: device trace."""

from benchmarks import lfm2_spans, trace_reduce


def read(ctx):
    spent = lfm2_spans.scoped_seconds(ctx)
    if spent is None:
        return None
    busy = trace_reduce.busy_seconds(ctx["trace"].devices[0], *ctx["window"])
    return 100.0 * spent / busy if busy > 0 else None
