"""layer: strategy (which experts this chip holds). ``moe_local_assignments``
over ``moe_assignments``, summed over the window's decode and prefill
landings: the share of the routing that falls on the held experts (held /
total if routing is even). Source: program counter."""

from benchmarks import laguna_spans


def read(ctx):
    landed = ((laguna_spans.landings(ctx) or [])
              + (laguna_spans.landings(ctx, "serve.prefill.sync") or []))
    total = sum(s.fields["moe_assignments"] for s in landed)
    if not total:
        return None
    return (100.0 * sum(s.fields["moe_local_assignments"] for s in landed)
            / total)
