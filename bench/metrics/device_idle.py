"""device_idle: share of the traced window in which no operation ran on the
device (1 - union of the device's operation intervals / window), in %."""


def read(ctx):
    if not ctx.trace.device_ops:
        return None
    return 100.0 * (1.0 - ctx.busy_s / ctx.window_s)
