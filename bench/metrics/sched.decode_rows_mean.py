"""Mean live rows per decode step dispatched in the window (the engine's
``decode_dispatch`` spans carry their row count)."""
from bench import stats

UNIT = "rows"
BETTER = "higher"
SOURCE = "program_span"
LAYER = "scheduler"
MOVES = "output_tok_per_s"


def read(ctx):
    rows = [e["args"]["rows"] for e in ctx.obs("decode_dispatch", *ctx.window)
            if e["ph"] == "X"]
    return stats.mean(rows) if rows else None
