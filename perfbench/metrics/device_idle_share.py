"""device: share of the traced window in which no operation ran on the
device, in percent: 1 - (union of device-busy intervals) / window."""


def read(ctx):
    if ctx.trace is None or ctx.trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - ctx.devtrace.busy_s(ctx.trace)
                    / ctx.trace.window_s)
