"""layer: kernels (the grouped expert matmuls, ``moe._grouped_matmul``). For
the window's decode dispatches: the least seconds for the held experts they
touched (``costs_laguna.moe_least_seconds``: ``moe_experts_touched`` x one
expert's bytes over the memory peak against ``moe_local_assignments`` x 6 x
hidden x width flops over the bf16 peak, the larger) over the traced seconds
of the ``gmm`` (or ``ragged-dot``) operations inside the decode programs.
Source: device trace + the program's counters."""

from benchmarks import costs_laguna, laguna_spans


def read(ctx):
    landed = laguna_spans.landings(ctx)
    if landed is None or not ctx["trace"].devices:
        return None
    spent = laguna_spans.op_seconds(ctx, laguna_spans.GROUPED_MATMUL,
                                    laguna_spans.GROUPED_MATMUL_HINT,
                                    module=laguna_spans.DECODE)
    if spent <= 0:
        return None
    least = costs_laguna.moe_least_seconds(
        sum(s.fields["moe_experts_touched"] for s in landed),
        sum(s.fields["moe_local_assignments"] for s in landed),
        ctx["config"], ctx["peaks"])
    return 100.0 * least["seconds"] / spent
