"""Share of the traced window in which the device ran no operation: one
minus the union of its operations' intervals over the window, averaged over
the devices used."""


def read(ctx):
    lo, hi = ctx.trace_window
    busy = ctx.trace.busy_s(lo, hi)
    if busy <= 0.0:
        return None
    return 100.0 * (1.0 - busy / ((hi - lo) / 1e9))
