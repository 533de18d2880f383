"""layer: kernels (``ops/flash_attention.py`` as prefill's latent attention
drives it: ``hybrid._latent_prefill_flash_call``, whose kernel operation is
named after ``_latent_prefill_flash_head``). The least seconds for the
window's prefill chunks (``costs_mistral4.mla_prefill_least_seconds``: each
``serve.prefill.dispatch`` span's ``start`` and the configured chunk width
give its (query, visible key) pairs; x the held layers x 2 x heads x (qk +
v) flops over the bf16 peak: compute-bound) over the traced seconds of the
kernel inside the prefill programs. Source: device trace + spans."""

import re

from benchmarks import costs_mistral4, engine_spans, laguna_spans

KERNEL = re.compile(r"latent_prefill_flash")
KERNEL_HINT = ("latent_prefill_flash",)


def read(ctx):
    spans = engine_spans.for_ctx(ctx)
    if spans is None or not ctx["trace"].devices:
        return None
    chunks = [s for s in engine_spans.in_window(
        spans, "serve.prefill.dispatch", *ctx["window"])
        if "start" in s.fields]
    spent = laguna_spans.op_seconds(ctx, KERNEL, KERNEL_HINT)
    if not chunks or spent <= 0:
        return None
    width = int(ctx["config"]["engine"]["prefill_chunk"])
    pairs = sum(costs_mistral4.chunk_pairs(s.fields["start"], width)
                for s in chunks)
    return 100.0 * costs_mistral4.mla_prefill_least_seconds(
        pairs, ctx["config"], ctx["peaks"]) / spent
