"""Share of their roofline the quantized-linear kernels reach.

Bound time: every linear call of every traced step, each bounded on its
own by the larger of 2*M*N*K over peak FLOP/s and (stored bundle bytes
+ activations in and out) over peak bytes/s, with M the rows the
program runs (the decode batch, or the prefill chunk's bucket); a head
stored as a bundle on the rows that produce logits (the decode batch,
one row of a prefill chunk); a routed expert role over the experts the
step touched.  The steps are those the host dispatched in the traced
window, scaled to the number of program executions the device trace
holds.  Device time: the summed durations of the GEMM kernels' events
there.  A linear that runs outside these kernels adds bound time and no
kernel time, and shows as a share over 100%.  Nothing is read for a
model with routed experts whose steps carry no ``experts_touched``."""
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
    b = ctx.engine["max_batch"]
    bound = 0.0
    for kind, m_of, head_rows in (("decode", lambda s: b, b),
                                  ("prefill", lambda s: s.bucket, 1)):
        host = [s for s in steps if s.kind == kind]
        runs = len(ctx.program_runs(kind))
        if host and runs:
            each = [gemm_bound_s(m_of(s), ctx, head_rows,
                                 s.counters.get("experts_touched"))
                    for s in host]
            if None in each:
                return None
            bound += stats.mean(each) * runs
    return 100.0 * bound / sum(e.dur for e in ops)
