"""layer: programs (``obs/collectors.py`` ``startup_report``). Seconds of
the backend compile events inside a start-up span that were NOT loads from
the persistent cache (``miss``, or ``off`` where JAX would not cache the
program): 0 on a warm machine. Seconds of compiling threads: programs
compiled side by side count each its own, so the sum may pass
``setup_engine_s``, which is wall time. Needs no trace.
Source: program counter."""


def read(ctx):
    try:
        from marlin_tpu.obs.collectors import startup_report
    except (ImportError, AttributeError):  # no record: the parent commit
        return None
    return startup_report()["totals"]["compile_s"]
