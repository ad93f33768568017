"""Seconds of set-up spent building programs: the engine's ``compile``
spans (each program compiled, or loaded from the persistent compilation
cache: drawing the weights, warm-up) that ended before the first request
the tracer saw submitted, summed.  A program that traces no builds gives
``None``."""
UNIT = "s"
BETTER = "lower"
SOURCE = "program_span"
LAYER = "set-up"
MOVES = "setup_s"


def read(ctx):
    builds = ctx.obs("compile")
    submits = ctx.obs("submit")
    if not builds or not submits:
        return None
    first = submits[0]["ts"]
    return sum(e["dur"] for e in builds
               if e["ts"] + e["dur"] <= first) * 1e-6
