"""Share of their roofline the quantized-linear kernels reach.

Bound time: every linear call of every traced step, each bounded on its
own by the larger of 2*M*N*K over peak FLOP/s and (stored bundle bytes
+ activations in and out) over peak bytes/s, with M the rows the
program runs (the decode batch, or the prefill chunk's bucket).  The
steps are those the host dispatched in the traced window, scaled to the
number of program executions the device trace holds.  Device time: the
summed durations of the GEMM kernels' events there.  A linear that runs
outside these kernels adds bound time and no kernel time, and shows as
a share over 100%."""
from bench import stats
from bench.layers import gemm_bound_s

UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
LAYER = "linear kernels"
MOVES = "output_tok_per_s"


def read(ctx):
    ops = ctx.kernel_ops("gemm")
    if not ops:
        return None
    steps = ctx.traced_steps()
    bound = 0.0
    for kind, m_of in (("decode", lambda s: ctx.engine["max_batch"]),
                       ("prefill", lambda s: s.bucket)):
        host = [s for s in steps if s.kind == kind]
        runs = len(ctx.program_runs(kind))
        if host and runs:
            bound += stats.mean(gemm_bound_s(m_of(s), ctx)
                                for s in host) * runs
    return 100.0 * bound / sum(e.dur for e in ops)
