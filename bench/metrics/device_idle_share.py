"""Device: the share of the traced window in which no operation ran, one
minus the union of each device's op intervals over the window, averaged
over the cell's devices."""


def read(d):
    if d.trace is None:
        return None
    share = d.trace.idle_share()
    return None if share is None else 100.0 * share
