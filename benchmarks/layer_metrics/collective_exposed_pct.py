"""layer: strategy (``parallel/matmul.py``, ``parallel/carma.py``). Share of
the traced window in which a collective ran on a chip and no compute operation
did, for the chip where that share is largest. Source: device trace."""

from benchmarks import trace_reduce


def read(ctx):
    trace = ctx["trace"]
    if trace is None or len(trace.devices) < 2:
        return None
    lo, hi = ctx["window"]
    return 100.0 * max(trace_reduce.exposed_collective_seconds(d, lo, hi)
                       for d in trace.devices) / (hi - lo)
