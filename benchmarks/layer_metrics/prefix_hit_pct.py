"""layer: kvpool (``serving/kvpool.py`` ``match_prefix``). Prompt tokens
that were found in the prefix cache (``shared_tokens``) over the prompt
tokens admitted (``prompt_tokens``), summed over the window's ``serve.admit``
spans: the share of the prompts that was not prefilled again.
Source: program counter."""

from benchmarks import engine_spans


def read(ctx):
    spans = engine_spans.for_ctx(ctx)
    if spans is None:
        return None
    admits = [s for s in engine_spans.in_window(spans, "serve.admit",
                                                *ctx["window"])
              if "prompt_tokens" in s.fields]
    prompt = sum(s.fields["prompt_tokens"] for s in admits)
    if not prompt:
        return None
    return 100.0 * sum(s.fields["shared_tokens"] for s in admits) / prompt
