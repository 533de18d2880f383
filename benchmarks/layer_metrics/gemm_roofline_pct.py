"""layer: kernels (``ops/local.py``, XLA's dot). The least time the chips
could take for the window's products (``costs.matmul_least_seconds``: bf16
passes x 2n^3 / chips / the published bf16 peak, or bytes over bandwidth
where that is longer) over the device time of the products' matrix-multiply
operations, summed per chip and averaged over the chips. Source: device trace."""

import re

from benchmarks import costs, trace_reduce

_KOUTPUT = re.compile(r"kind=kOutput")


def is_gemm(ev) -> bool:
    """The XLA operations that do the multiplying: a ``dot`` or
    ``convolution``, alone or as the root of a fusion (which the TPU compiler
    marks ``kind=kOutput``; elementwise fusions are ``kLoop``)."""
    if ev.category in ("dot", "convolution"):
        return True
    return ev.category == "fusion" and bool(_KOUTPUT.search(ev.name))


def read(ctx):
    trace, facts = ctx["trace"], ctx["facts"]
    products = ctx["counters"].get("products")
    if trace is None or not trace.devices or not products:
        return None
    lo, hi = ctx["window"]
    n = facts["n"]
    least = costs.matmul_least_seconds(n, n, n, facts["itemsize"],
                                       facts["precision"], facts["chips"],
                                       ctx["peaks"])
    spent = [trace_reduce.total(trace_reduce.clip(
        trace_reduce.union(trace_reduce.op_intervals(d, is_gemm)), lo, hi))
        for d in trace.devices]
    spent = sum(spent) / len(spent)
    if spent <= 0:
        return None
    return 100.0 * products * least["seconds"] / spent
