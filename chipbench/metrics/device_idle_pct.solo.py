"""Share of the window in which no operation ran on the device, in %
(device trace)."""


def read(r):
    if r.trace is None:
        return None
    return 100.0 * (1.0 - r.trace.busy_s / r.window_s)
