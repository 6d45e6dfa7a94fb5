"""Device: share of the traced window in which no operation ran on the chip
(1 - union of the device-op intervals over the window)."""


def read(ctx):
    tr = ctx["trace"]
    return 100.0 * (1.0 - tr.busy_s / tr.window_s)
