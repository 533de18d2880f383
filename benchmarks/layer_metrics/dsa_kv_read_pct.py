"""layer: kvpool (``serving/engine.py``'s counters of a decode call of a
model with a lightning indexer). The cache entries the live rows attended
over the entries their contexts hold, both summed over rows and layers on the
``serve.decode.sync`` spans of the window (``dsa_tokens_attended`` /
``dsa_tokens_held``): the share of a row's latent cache a decode step reads
(``index_topk`` 2048 of ~66 k: ~3.1; 100 below ``index_topk``). Lower is the
mechanism working. Source: program counter."""

from benchmarks import deepseekv32_spans as dsa


def read(ctx):
    calls = dsa.landed(ctx, "serve.decode.sync", "dsa_tokens_held")
    held = sum(s.fields["dsa_tokens_held"] for s in calls) if calls else 0
    if not held:
        return None
    return 100.0 * sum(s.fields["dsa_tokens_attended"] for s in calls) / held
