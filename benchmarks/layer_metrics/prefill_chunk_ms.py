"""layer: programs (``lm_prefill_paged``). Median device time of the
executions joined to the window's ``serve.prefill.dispatch`` spans:
``decode_step_ms``'s counterpart for a chunk. Source: device trace, joined to
the program's spans by ``seq`` (``benchmarks/launches.py``)."""

import statistics

from benchmarks import launches


def read(ctx):
    chunks = launches.in_window(ctx, "prefill")
    if not chunks:
        return None
    return 1e3 * statistics.median(x.run.seconds for x in chunks)
