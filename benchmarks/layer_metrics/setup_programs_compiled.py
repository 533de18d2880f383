"""layer: programs (``obs/collectors.py`` ``startup_report``). How many
programs were compiled inside a start-up span: the rows ``setup_compile_s``
sums (``miss`` or ``off``, not loads from the persistent cache). 0 on a warm
machine; the ``startup`` note names them. Needs no trace.
Source: program counter."""


def read(ctx):
    try:
        from marlin_tpu.obs.collectors import startup_report
    except (ImportError, AttributeError):  # no record: the parent commit
        return None
    return startup_report()["totals"]["programs_compiled"]
