"""Device time of one decode step: mean duration of the decode program's
executions in the traced window."""
from bench import stats

UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "engine step"
MOVES = "itl_p99_ms"


def read(ctx):
    runs = ctx.program_runs("decode")
    return stats.mean(e.dur for e in runs) * 1e3 if runs else None
