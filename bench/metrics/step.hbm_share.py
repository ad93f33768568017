"""Share of peak HBM bandwidth the steps' least bytes would use: per step
dispatched in the window, the stored weights, the LM head and the live
KV read, summed, over the window times peak bytes/s."""
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
    nbytes = sum(counts.step_model_work(s, ctx.linears, ctx.shape).bytes
                 for s in steps)
    secs = ctx.window[1] - ctx.window[0]
    return 100.0 * nbytes / (secs * ctx.peaks.hbm_bytes_per_s)
