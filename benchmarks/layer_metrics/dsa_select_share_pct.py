"""layer: programs (``models/hybrid.py``, the selection stage of a latent
layer with an indexer). Device time traced under the ``dsa_select`` scope
(the k-th largest score by a radix search over the scores' bit patterns, the
ties, the mask, and each tile of queries' list from it) over the device time
of the three stages together (``dsa_index`` + ``dsa_select`` + ``dsa_attend``
and the index-score kernels): what choosing costs beside scoring and
attending. Source: device trace."""

from benchmarks import deepseekv32_spans as dsa


def read(ctx):
    whole = dsa.seconds(ctx, dsa.SCOPES, dsa.KERNEL)
    select = dsa.seconds(ctx, ("dsa_select",))
    if whole is None or select is None:
        return None
    return 100.0 * select / whole
