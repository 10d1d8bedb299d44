"""Workload build per instance: inputs, jit and every warm-up call of the
timer's workloads, the program's ``session.warmup`` spans (``warmup_s``)
over the window's instances. A part of ``build_ms_per_instance``."""


def read(window):
    t = window.seen.timings
    if not window.instances or "warmup_s" not in t:
        return None
    return 1e3 * t["warmup_s"] / window.instances
