"""Programs built (compiled, or loaded from the persistent compilation
cache) that finished inside the measured window: the engine's
``compile`` spans on ``engine/compile`` whose end lies in it.  Every
shape is warmed up before the window, so any build here is a recompile
the traffic forced.  A program that traces no builds gives ``None``."""
UNIT = "programs"
BETTER = "lower"
SOURCE = "program_span"
LAYER = "engine step"
MOVES = "itl_p99_ms"


def read(ctx):
    builds = ctx.obs("compile")
    if not builds:
        return None
    t0, t1 = ctx.window
    return sum(1 for e in builds
               if t0 <= ctx.obs_time(e) + e["dur"] * 1e-6 <= t1)
