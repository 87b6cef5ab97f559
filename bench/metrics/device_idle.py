"""device_idle: share of the traced window (%) in which no operation ran
on the device: 1 - union of device-op intervals / window."""


def read(ctx):
    if ctx.busy_s is None or not ctx.trace_window_s:
        return None
    return 100.0 * (1.0 - ctx.busy_s / ctx.trace_window_s)
