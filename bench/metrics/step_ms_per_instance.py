"""Procedure-4 engine steps (measurement and mean-rank analysis) per
instance: the campaign loop's ``step_s`` over the window's instances."""


def read(window):
    t = window.seen.timings
    return 1e3 * t["step_s"] / window.instances if window.instances and "step_s" in t else None
