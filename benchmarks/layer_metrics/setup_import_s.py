"""layer: programs (``marlin_tpu/__init__.py``). Seconds between the first
and the last line of ``import marlin_tpu``: the ``startup.import`` span of the
program's start-up record (``obs/collectors.py`` ``startup_report``). With
``jax`` not yet imported (the span's ``jax_preloaded`` field) that import is
in it; the TPU runtime's start inside the harness's ``jax.devices()`` is not.
Needs no trace. Source: program counter."""


def read(ctx):
    try:
        from marlin_tpu.obs.collectors import startup_report
    except (ImportError, AttributeError):  # no record: the parent commit
        return None
    for span in startup_report()["spans"]:
        if span["name"] == "startup.import":
            return span["t1"] - span["t0"]
    return None
