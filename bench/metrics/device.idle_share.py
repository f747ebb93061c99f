"""Share of the traced window in which no operation ran on the device:
1 - (union of the device's operation intervals) / window, in percent."""


def read(ctx):
    w = ctx.trace.window_s
    return 100.0 * (1.0 - ctx.trace.busy_s / w) if w > 0 else None
