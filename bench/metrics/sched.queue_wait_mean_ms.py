"""Mean queue wait: a request's first admission by the scheduler minus
the time it was due, over every request due in the window (one never
admitted waits until the drain gave up)."""
from bench import stats

UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
LAYER = "scheduler"
MOVES = "ttft_p50_ms"


def read(ctx):
    first = {}
    for e in ctx.obs("admit"):
        first.setdefault(e["args"]["uid"], ctx.obs_time(e))
    waits = [first.get(r.req.uid if r.req is not None else None,
                       ctx.drain_end) - r.due for r in ctx.recs]
    return stats.mean(waits) * 1e3 if waits else None
