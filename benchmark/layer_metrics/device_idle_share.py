"""device_idle_share: 1 - union of device-op intervals over the traced
window, in %; the mean over the chips the cell uses."""


def read(ctx):
    trace = ctx["trace"]
    return 100.0 * (1.0 - trace.busy_s / trace.window_s)
