"""layer: scheduler (``serving/engine.py`` ``_run_paged``). Live rows per
decode dispatch over the window: the rows of every ``step`` record the engine
announced, over the number of such records. Beside it stands the engine's
``max_batch``, to which every dispatch is padded. Source: program counter."""


def read(ctx):
    c = ctx["counters"]
    if not c.get("decode_steps"):
        return None
    return c["decode_rows"] / c["decode_steps"]
