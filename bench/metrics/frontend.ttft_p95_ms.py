"""95th percentile of time to first token, due to first token, over
every request due in the window (one that failed or never answered
waited until the drain gave up).  Recorded, not judged: a window holds
some fourteen requests, so this is their slowest."""
from bench import stats

UNIT = "ms"
BETTER = "lower"
SOURCE = "host_clock"
LAYER = "front end"
MOVES = "ttft_p50_ms"


def read(ctx):
    ttft = [r.times[0] - r.due if r.ok and r.times else ctx.drain_end - r.due
            for r in ctx.recs]
    return stats.percentile(ttft, 95) * 1e3 if ttft else None
