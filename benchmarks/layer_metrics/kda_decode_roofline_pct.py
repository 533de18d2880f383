"""layer: kernels (``ops/delta_rule.py``, the decode state update with a
decay a channel). The least seconds for the live rows' state slots
(``costs_solaropen2.kda_decode_least_seconds``: ``state_rows`` of each
``serve.decode.dispatch`` span x the KDA layers x one read and one write of
a row's state AS STORED and of its convolution tail, over the memory peak)
over the traced seconds of the update inside the decode program: the
operations traced under the ``kda_update`` scope (the convolution's step,
the tails' gather and scatter, the operands of the recurrence) and the
kernel ``_delta_decode_update_call`` by name. Priced by LIVE rows, never by
the padded call: the rows no live row fills share the dummy slot. Source:
device trace + spans."""

import re

from benchmarks import costs_solaropen2, laguna_spans, trace_reduce

SCOPE = "kda_update"
KERNEL = re.compile(r"delta_decode_update_call")
KERNEL_HINT = ("delta_decode_update",)


def read(ctx):
    calls = laguna_spans.decode_dispatches(ctx, "state_rows")
    if calls is None or not ctx["trace"].devices \
            or "kda_use_full_proj" not in ctx["config"]:
        return None
    scoped = laguna_spans.scoped_intervals(ctx, SCOPE)
    if scoped is None:
        return None
    got = scoped + laguna_spans.named_intervals(ctx["trace"].devices[0],
                                                KERNEL, KERNEL_HINT)
    spent = trace_reduce.total(trace_reduce.clip(
        trace_reduce.union(got), *ctx["window"]))
    if spent <= 0:
        return None
    return 100.0 * costs_solaropen2.kda_decode_least_seconds(
        sum(s.fields["state_rows"] for s in calls), ctx["config"],
        ctx["peaks"]) / spent
