"""The timer's samples per instance: the program's ``session.sample`` spans
(``sample_s``) over the window's instances. A part of
``step_ms_per_instance``."""


def read(window):
    t = window.seen.timings
    if not window.instances or "sample_s" not in t:
        return None
    return 1e3 * t["sample_s"] / window.instances
