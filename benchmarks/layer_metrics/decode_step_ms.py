"""layer: programs (``lm_decode_paged``). Median device time of one
decode-step program in the traced window. Source: device trace."""

import statistics

from benchmarks import trace_reduce

PROGRAM = r"lm_decode_paged"


def read(ctx):
    trace = ctx["trace"]
    if trace is None or not trace.devices:
        return None
    lo, hi = ctx["window"]
    steps = [e.seconds for d in trace.devices
             for e in trace_reduce.module_events(d, PROGRAM, lo, hi)]
    if not steps:
        return None
    return 1e3 * statistics.median(steps)
