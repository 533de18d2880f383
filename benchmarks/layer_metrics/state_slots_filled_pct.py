"""layer: kvpool (``serving/kvpool.py``, the recurrent-state slots beside
the pages). Slots held by resident rows (``state_rows``) over the slots the
pool has (``state_slots``), mean over the window's ``serve.iter`` spans:
what of the state slab the traffic really uses. Source: program counter."""

from benchmarks import engine_spans


def read(ctx):
    return engine_spans.iter_mean_pct(
        ctx, "state_rows", lambda f: f.get("state_slots", 0))
