"""Mean prefill wait: the engine's ``prefill_wait`` span of a request,
from its first admission to the dispatch of its first prefill chunk (a
tick runs one chunk, first come first served), over the window's
answered requests (``bench.phases``)."""
from bench import phases

UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
LAYER = "scheduler"
MOVES = "ttft_p50_ms"


def read(ctx):
    return phases.mean_ms(ctx, "prefill_wait")
