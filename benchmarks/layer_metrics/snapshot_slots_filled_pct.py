"""layer: kvpool (``serving/kvpool.py``, the state snapshots beside the
prefix cache's pages). Snapshots the cache's entries own
(``snapshots_held``) over the snapshot slots the pool has
(``snapshot_slots``), mean over the window's ``serve.iter`` spans: what of
the snapshot slots the traffic's shared prefixes really fill.
Source: program counter."""

from benchmarks import engine_spans


def read(ctx):
    return engine_spans.iter_mean_pct(
        ctx, "snapshots_held", lambda f: f.get("snapshot_slots", 0))
