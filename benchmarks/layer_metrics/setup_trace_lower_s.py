"""layer: programs (``obs/collectors.py`` ``startup_report``). Seconds of
tracing (``jaxpr_trace_duration``) and lowering
(``jaxpr_to_mlir_module_duration``) of the programs whose backend event fell
inside a start-up span: host Python that a warm compile cache does not
remove. Seconds of the threads that did it: they may pass ``setup_engine_s``,
which is wall time. Needs no trace. Source: program counter."""


def read(ctx):
    try:
        from marlin_tpu.obs.collectors import startup_report
    except (ImportError, AttributeError):  # no record: the parent commit
        return None
    return startup_report()["totals"]["trace_lower_s"]
