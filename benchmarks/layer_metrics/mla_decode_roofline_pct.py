"""layer: kernels (``ops/paged_attention.py``, the latent decode kernel).
The least seconds for the pages that hold the positions the live rows attend
(``costs_mistral4.mla_decode_least_seconds``: ``latent_kv_pages`` of each
``serve.decode.dispatch`` span x the held layers x one page's bytes as
stored, over the memory peak, against ``kv_tokens`` x the layers x 2 x heads
x (entry + value) flops over the bf16 peak, the larger) over the traced
seconds of ``_paged_decode_attention_latent_call``. Priced by the pages that
hold attended positions, never by the padded table (``PERF.md`` section 7).
Source: device trace + spans."""

import re

from benchmarks import costs_mistral4, laguna_spans

KERNEL = re.compile(r"paged_decode_attention_latent_call")
KERNEL_HINT = ("paged_decode_attention_latent",)


def read(ctx):
    calls = laguna_spans.decode_dispatches(ctx, "latent_kv_pages")
    if calls is None or not ctx["trace"].devices:
        return None
    spent = laguna_spans.op_seconds(ctx, KERNEL, KERNEL_HINT)
    if spent <= 0:
        return None
    least = costs_mistral4.mla_decode_least_seconds(
        sum(s.fields["latent_kv_pages"] for s in calls),
        sum(s.fields["kv_tokens"] for s in calls),
        ctx["config"], ctx["peaks"])
    return 100.0 * least["seconds"] / spent
