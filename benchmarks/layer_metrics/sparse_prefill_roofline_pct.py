"""layer: kernels (``ops/sparse_attention.py:attend_selected`` inside the
prefill programs, the operations traced under the ``sparse_attend`` scope:
XLA's fusions today). The least seconds for the window's prefill chunks
(``costs_minicpmsala.sparse_prefill_least_seconds``: the (query, key) pairs
the SELECTION leaves each ``serve.prefill.dispatch`` span's valid tokens,
from its ``start`` and ``tokens``, x the sparse layers x every query head x
the score's and the value's flops, over the bf16 peak) over the traced
seconds of those operations. A masked-dense form spends the dense FLOPs and
reads low (at 33 k of context at most ~12 %); a kernel that skips unselected
blocks can raise it and never pass 100: the same work whatever computes it.
Source: device trace + spans."""

from benchmarks import costs_minicpmsala, minicpmsala_spans as sala


def read(ctx):
    chunks = sala.chunks(ctx)
    spent = sala.seconds(ctx, "sparse_attend", module=sala.PREFILL) \
        if chunks else None
    if spent is None:
        return None
    pairs = sum(costs_minicpmsala.selected_pairs(
        s.fields["start"], s.fields["tokens"], ctx["config"]) for s in chunks)
    return 100.0 * costs_minicpmsala.sparse_prefill_least_seconds(
        pairs, ctx["config"], ctx["peaks"]) / spent
