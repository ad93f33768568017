"""Mean submit lag: when the engine took a request in (the start of its
``queue`` span) minus the time it was due, over the window's answered
requests (``bench.phases``).  The frontend runs each tick inline on the
event loop, so a request due while a tick runs is sent when it ends."""
from bench import phases, stats

UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
LAYER = "front end"
MOVES = "ttft_p50_ms"


def read(ctx):
    rows = phases.answered(ctx)
    if not rows:
        return None
    return stats.mean(ctx.obs_time(spans["queue"]) - r.due
                      for r, spans in rows) * 1e3
