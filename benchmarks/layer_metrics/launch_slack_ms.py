"""layer: scheduler (``serving/engine.py`` ``_run_paged``). How far ahead of
the chip the worker is. A program's queue wait is the start of its execution
on the device less the end of its dispatch span: how long it sat behind the
one running. The metric is the LEAST queue wait among the programs one
``serve.iter`` dispatched, median over the window's iterations; at 0 the chip
waited for the host. The ``launch_join`` note prints the median length of
``serve.decode.sync`` beside it. Source: device trace, joined to the
program's spans by ``seq`` (``benchmarks/launches.py``)."""

import statistics

from benchmarks import launches


def read(ctx):
    its = launches.iterations(ctx)
    if not its:
        return None
    return 1e3 * statistics.median(it["slack_s"] for it in its)
