"""layer: kvpool (``serving/engine.py``'s counters of the sparse layers'
decode steps). The KV blocks the live rows' selections attended over the
blocks their contexts hold, both summed over rows, KV heads and sparse
layers on the ``serve.decode.sync`` spans of the window
(``sparse_blocks_attended`` / ``sparse_blocks_held``): the share of a
row's cache a decode step reads in the sparse layers (``topk`` blocks of
~540 at 34 k of context; 100 below ``dense_len``). Lower is the mechanism
working. Source: program counter."""

from benchmarks import minicpmsala_spans as sala


def read(ctx):
    calls = sala.landed(ctx)
    held = sum(s.fields["sparse_blocks_held"] for s in calls) if calls else 0
    if not held:
        return None
    return 100.0 * sum(s.fields["sparse_blocks_attended"]
                       for s in calls) / held
