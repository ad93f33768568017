"""Share of the traced window in which no operation ran on the device."""
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "device"
MOVES = "output_tok_per_s"


def read(ctx):
    if ctx.trace_seconds <= 0 or not ctx.device.devices:
        return None
    return 100.0 * (1.0 - ctx.busy_s() / ctx.trace_seconds)
