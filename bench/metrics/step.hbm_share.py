"""Share of peak HBM bandwidth the steps' least bytes would use: per step
dispatched in the window, the stored weights, the LM head and the live
KV read, summed, over the window times peak bytes/s.  Nothing is read
for a model with routed experts whose steps carry no
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
    return 100.0 * work.bytes / (secs * ctx.peaks.hbm_bytes_per_s)
