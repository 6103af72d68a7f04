"""The share of the traced window in which no program ran on the device."""


def read(ctx):
    if ctx.trace is None or not ctx.trace.devices:
        return None
    return 1.0 - ctx.trace.busy_s / ctx.trace.window_s
