"""layer: scheduler (how ``_run_paged`` splits rows over bucket dispatches).
``moe_experts_touched`` over (experts held x expert layers) a decode
dispatch, mean over the window's ``serve.decode.sync`` spans: how much of
the held experts a step reads for the rows it carries.
Source: program counter."""

from benchmarks import laguna_spans


def read(ctx):
    landed = laguna_spans.landings(ctx)
    if landed is None:
        return None
    cfg = ctx["config"]
    n = cfg["num_hidden_layers"]
    slots = cfg["num_experts"] * sum(
        1 for kind in cfg["mlp_layer_types"][:n] if kind != "dense")
    return (100.0 * sum(s.fields["moe_experts_touched"] for s in landed)
            / (slots * len(landed)))
