"""Share of the device's busy time spent in the quantized-linear
kernels, over the traced window."""
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "linear kernels"
MOVES = "output_tok_per_s"


def read(ctx):
    ops = ctx.kernel_ops("gemm")
    busy = ctx.busy_s()
    if not ops or busy <= 0:
        return None
    return 100.0 * sum(e.dur for e in ops) / busy
