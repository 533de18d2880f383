"""layer: programs (``models/moe.py:moe_experts_ffn`` inside the paged
programs). Device time of the expert layers over the device's busy time, in
the traced window: router, sort, combine and shared expert are the
operations traced under the ``moe_experts`` scope
(``laguna_spans.op_scopes``); the grouped matmuls are found by their own
name as well, because XLA's ``ragged-dot`` kernels do not keep the scope
they were traced under. Source: device trace."""

from benchmarks import laguna_spans, trace_reduce


def read(ctx):
    got = laguna_spans.scoped_intervals(ctx, laguna_spans.MOE_SCOPE)
    if got is None:
        return None
    lo, hi = ctx["window"]
    got = got + laguna_spans.named_intervals(
        ctx["trace"].devices[0], laguna_spans.GROUPED_MATMUL,
        laguna_spans.GROUPED_MATMUL_HINT)
    inside = trace_reduce.clip(trace_reduce.union(got), lo, hi)
    busy = trace_reduce.busy_seconds(ctx["trace"].devices[0], lo, hi)
    if not inside or busy <= 0:
        return None
    return 100.0 * trace_reduce.total(inside) / busy
