"""layer: kernels (``ops/lightning.py``, the decode state update). The least
seconds for the live rows' state slots (``costs_minicpmsala
.lightning_decode_least_seconds``: ``state_rows`` of each
``serve.decode.dispatch`` span x the lightning layers x one read and one
write of a row's state as stored, over the memory peak) over the traced
seconds of the update inside the decode program: the operations traced
under the ``lightning_update`` scope and the kernel
``_lightning_decode_update_call`` by name. Priced by LIVE rows, never by the
padded call: the rows no live row fills share the dummy slot. Source:
device trace + spans."""

from benchmarks import costs_minicpmsala, laguna_spans, \
    minicpmsala_spans as sala


def read(ctx):
    calls = laguna_spans.decode_dispatches(ctx, "state_rows")
    if calls is None or "lightning_nh" not in ctx["config"]:
        return None
    spent = sala.seconds(ctx, "lightning_update", sala.UPDATE_KERNEL,
                         sala.UPDATE_HINT)
    if spent is None:
        return None
    return 100.0 * costs_minicpmsala.lightning_decode_least_seconds(
        sum(s.fields["state_rows"] for s in calls), ctx["config"],
        ctx["peaks"]) / spent
