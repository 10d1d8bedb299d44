"""Record building and store appends per instance: the campaign loop's
``record_s`` plus ``append_s`` over the window's instances."""


def read(window):
    t = window.seen.timings
    if not window.instances or "append_s" not in t:
        return None
    return 1e3 * (t.get("record_s", 0.0) + t["append_s"]) / window.instances
