"""Share of the traced window in which no operation ran on the device, in %:
1 - the union of device operation intervals over the window."""


def read(window):
    return None if window.trace is None else 100.0 * window.trace.idle_share
