"""Mean prefill-to-token time: the engine's ``prefill`` span of a
request, from the dispatch of its first prefill chunk to the emission of
its first token (its chunks, the decode steps between them and the async
tick's one-tick emission lag), over the window's answered requests
(``bench.phases``)."""
from bench import phases

UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
LAYER = "engine step"
MOVES = "ttft_p50_ms"


def read(ctx):
    return phases.mean_ms(ctx, "prefill")
