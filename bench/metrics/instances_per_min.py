"""Census instances recorded in the window, over the window's whole time."""


def read(window):
    return 60.0 * window.instances / window.window_s if window.instances else None
