"""layer: programs (``lm_prefill_paged``). Device time inside prefill programs
over the device's busy time, in the traced window. Source: device trace."""

from benchmarks import trace_reduce

PROGRAM = r"lm_prefill_paged"


def read(ctx):
    trace = ctx["trace"]
    if trace is None or not trace.devices:
        return None
    lo, hi = ctx["window"]
    dev = trace.devices[0]
    spans = [(e.start, e.end)
             for e in trace_reduce.module_events(dev, PROGRAM, lo, hi)]
    busy = trace_reduce.busy_seconds(dev, lo, hi)
    if not spans or busy <= 0:
        return None
    return 100.0 * trace_reduce.busy_inside(dev, spans, lo, hi) / busy
