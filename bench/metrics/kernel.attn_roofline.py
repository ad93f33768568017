"""Share of their roofline the paged-attention kernels reach.

Bound time per traced step: decode -- every live row's context read
once through the block table (live blocks x block x KV bytes per token
and layer), plus q in and the output; prefill -- the chunk's causal
attention over its blocks.  Scaled to the program executions the
device trace holds, over the summed durations of the attention
kernels' events."""
from bench import counts, stats
from bench.peaks import bound_s

UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
LAYER = "paged attention"
MOVES = "output_tok_per_s"


def _bound(step, ctx):
    if step.kind == "decode":
        w = counts.decode_attention(step.ctx, ctx.shape)
    else:
        w = counts.prefill_attention(step.ctx[0], step.ctx[1], ctx.shape)
    # one kernel call per layer, each bounded on its own
    per = bound_s(w.flops / ctx.shape.layers, w.bytes / ctx.shape.layers,
                  ctx.peaks)
    return per * ctx.shape.layers


def read(ctx):
    ops = ctx.kernel_ops("attn")
    if not ops:
        return None
    steps = ctx.traced_steps()
    bound = 0.0
    for kind in ("decode", "prefill"):
        host = [s for s in steps if s.kind == kind]
        runs = len(ctx.program_runs(kind))
        if host and runs:
            bound += stats.mean(_bound(s, ctx) for s in host) * runs
    return 100.0 * bound / sum(e.dur for e in ops)
