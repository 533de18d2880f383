"""layer: programs (``models/hybrid.py``, the selection stage of a sparse
layer). Device time traced under the ``sparse_select`` scope (decode: the
fetch of the rows' compressed keys, the scores over them, the pooling to
blocks, the top-k; prefill: the same for a chunk's queries and the mask
built from it) over the device time of the whole sparse mixer (the
``attn_sparse`` scope plus the block-walk kernel): what choosing costs
beside attending. Source: device trace."""

from benchmarks import minicpmsala_spans as sala


def read(ctx):
    whole = sala.seconds(ctx, "attn_sparse", sala.BLOCKS_KERNEL,
                         sala.BLOCKS_HINT)
    select = sala.seconds(ctx, "sparse_select")
    if whole is None or select is None:
        return None
    return 100.0 * select / whole
