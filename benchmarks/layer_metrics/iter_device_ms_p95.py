"""layer: scheduler (``serving/engine.py`` ``_run_paged``). Device time of the
programs ONE ``serve.iter`` dispatched (its decode call or calls plus its
prefill chunks), 95th percentile over the window's iterations: in a full
pipeline the gap between a request's tokens is landing to landing, which is
the device time one iteration carries. Each dispatch span is joined to the
execution it launched by its ``seq`` (``benchmarks/launches.py``). Also
prints the ``launch_join`` note of the run.
Source: device trace, joined to the program's spans."""

from benchmarks import launches, stats


def read(ctx):
    launches.note(ctx)
    its = launches.iterations(ctx)
    if not its:
        return None
    return 1e3 * stats.percentile([it["device_s"] for it in its], 95)
