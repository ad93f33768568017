"""Distinct routed experts a decode step reads per MoE layer, mean over
the decode steps dispatched in the window: each step's
``experts_touched`` counter (the engine's ``step_counters`` instant,
joined to its ``decode_dispatch`` span by step id: distinct (MoE layer,
expert) pairs with at least one real token) over the MoE layers.
Nothing is read for a model without routed experts, or where the
engine stamps no counters."""
from bench import counts, stats

UNIT = "experts"
BETTER = "lower"
SOURCE = "program_counter"
LAYER = "routed experts"
MOVES = "output_tok_per_s"


def read(ctx):
    _, routed = counts.split_routed(ctx.linears)
    if not routed:
        return None
    touched = [s.counters["experts_touched"]
               for s in ctx.steps(*ctx.window)
               if s.kind == "decode" and "experts_touched" in s.counters]
    if not touched:
        return None
    return counts.touched_per_layer(routed, stats.mean(touched))
