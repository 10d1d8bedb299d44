"""The MoE site's host routing per instance: the program's ``moe.route``
spans (``route_s``: sigmoid scores, biased top-k and weights over T tokens,
once per instance, inside the session build) over the window's instances.
A part of ``build_ms_per_instance``."""


def read(window):
    t = window.seen.timings
    if not window.instances or "route_s" not in t:
        return None
    return 1e3 * t["route_s"] / window.instances
