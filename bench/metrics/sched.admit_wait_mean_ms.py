"""Mean admission wait: the engine's ``queue`` span of a request, from
its submit to its first admission by the scheduler, over the window's
answered requests (``bench.phases``)."""
from bench import phases

UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
LAYER = "scheduler"
MOVES = "ttft_p50_ms"


def read(ctx):
    return phases.mean_ms(ctx, "queue")
