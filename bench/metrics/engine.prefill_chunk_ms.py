"""Device time of one prefill chunk: mean duration of the chunked-prefill
program's executions in the traced window (all buckets together)."""
from bench import stats

UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "engine step"
MOVES = "ttft_p50_ms"


def read(ctx):
    runs = ctx.program_runs("prefill")
    return stats.mean(e.dur for e in runs) * 1e3 if runs else None
