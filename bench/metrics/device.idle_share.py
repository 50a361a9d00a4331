"""Share of the traced window, in percent, in which no operation ran on the
device: 1 - (union of device-op intervals / window)."""


def read(ctx):
    if ctx["trace"] is None:
        return None
    window = ctx["trace"].window_s
    if window <= 0:
        return None
    return (1.0 - ctx["busy_s"] / window) * 100.0
