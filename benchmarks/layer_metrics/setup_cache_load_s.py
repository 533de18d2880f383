"""layer: programs (``obs/collectors.py`` ``startup_report``). Seconds of
the backend compile events inside a start-up span that were cache HITS: the
event wraps the persistent cache's read and the executable's load (a row's
``retrieval_s`` is the read alone). Seconds of the loading threads: they may
pass ``setup_engine_s``, which is wall time, where programs load side by
side. Needs no trace. Source: program counter."""


def read(ctx):
    try:
        from marlin_tpu.obs.collectors import startup_report
    except (ImportError, AttributeError):  # no record: the parent commit
        return None
    return startup_report()["totals"]["cache_load_s"]
