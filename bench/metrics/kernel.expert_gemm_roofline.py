"""Share of its roofline the grouped expert kernel reaches.

Bound time: every routed expert role's call in every traced step, each
bounded on its own by the larger of its operations over peak FLOP/s and
its bytes over peak bytes/s (``counts.routed_gemm``): the step's real
rows (a decode step's live rows, a prefill chunk's real tokens) times
``experts_per_token`` assignments, and the matrices of the experts the
step touched (its ``experts_touched`` counter, spread evenly over the
MoE layers).  The steps are those the host dispatched in the traced
window, scaled to the number of program executions the device trace
holds.  Device time: the summed durations of the grouped kernel's
events there, matched by its name (``*grouped_matmul*_tiled``).
Nothing is read where the trace holds no such kernel or the steps carry
no ``experts_touched``."""
import re

from bench import counts, stats
from bench.peaks import bound_s

UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
LAYER = "routed experts"
MOVES = "output_tok_per_s"

KERNEL = re.compile(r"\w*grouped_matmul\w*_tiled\b")


def _bound(step, rows, routed, ctx):
    per = counts.touched_per_layer(routed, step.counters["experts_touched"])
    return sum(lin.layers * bound_s(*_work(rows, lin, per, ctx), ctx.peaks)
               for lin in routed)


def _work(rows, lin, per, ctx):
    w = counts.routed_gemm(rows, lin, ctx.shape.experts_per_token, per)
    return w.flops, w.bytes


def read(ctx):
    _, routed = counts.split_routed(ctx.linears)
    ops = [e for e in ctx.in_window(ctx.device.ops()) if KERNEL.match(e.op)]
    if not routed or not ops:
        return None
    steps = ctx.traced_steps()
    bound = 0.0
    for kind, rows_of in (("decode", lambda s: len(s.ctx)),
                          ("prefill", lambda s: s.ctx[1])):
        host = [s for s in steps if s.kind == kind]
        runs = len(ctx.program_runs(kind))
        if host and runs:
            if any("experts_touched" not in s.counters for s in host):
                return None
            bound += stats.mean(_bound(s, rows_of(s), routed, ctx)
                                for s in host) * runs
    return 100.0 * bound / sum(e.dur for e in ops)
