"""layer: kernels (``ops/dsa.py:attend_list`` and the gather of its entries
inside the decode program, the operations traced under the ``dsa_attend``
scope). The least seconds for the entries the live rows attended
(``costs_deepseekv32.attend_least_seconds``: ``dsa_tokens_attended`` of each
``serve.decode.sync`` span, ``min(index_topk, length)`` a row and layer, x
1280 B over the memory peak, or the absorbed form's operations over the bf16
peak where larger) over the traced seconds of those operations. Source:
device trace + program counter."""

from benchmarks import costs_deepseekv32 as costs, deepseekv32_spans as dsa


def read(ctx):
    calls = dsa.landed(ctx, "serve.decode.sync", "dsa_tokens_attended")
    spent = dsa.seconds(ctx, ("dsa_attend",), module=dsa.DECODE) \
        if calls else None
    if spent is None:
        return None
    return 100.0 * costs.attend_least_seconds(
        sum(s.fields["dsa_tokens_attended"] for s in calls), ctx["config"],
        ctx["peaks"])["seconds"] / spent
