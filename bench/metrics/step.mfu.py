"""Model FLOP/s utilization of the whole window: the useful operations of
every step dispatched in it (every linear on each live decode row and
each real prefill token, attention over each token's context, the LM
head where logits are produced) over the window times peak FLOP/s.
Nothing is read for a model with routed experts whose steps carry no
``experts_touched``."""
from bench import counts

UNIT = "%"
BETTER = "higher"
SOURCE = "program_span"
LAYER = "model step"
MOVES = "output_tok_per_s"


def read(ctx):
    steps = ctx.steps(*ctx.window)
    if not steps:
        return None
    work = counts.total_work(steps, ctx.linears, ctx.shape)
    if work is None:
        return None
    secs = ctx.window[1] - ctx.window[0]
    return 100.0 * work.flops / (secs * ctx.peaks.bf16_flops)
