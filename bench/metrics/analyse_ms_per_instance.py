"""Procedure 2-3 analysis per instance (quantile table and the sorts of the
mean ranks): the program's ``session.analyse`` spans (``analyse_s``) over the
window's instances. A part of ``step_ms_per_instance``."""


def read(window):
    t = window.seen.timings
    if not window.instances or "analyse_s" not in t:
        return None
    return 1e3 * t["analyse_s"] / window.instances
