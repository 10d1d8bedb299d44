"""Session build (workload build, warm run, candidate filter) per instance:
the campaign loop's ``build_s`` over the window's instances."""


def read(window):
    t = window.seen.timings
    return 1e3 * t["build_s"] / window.instances if window.instances and "build_s" in t else None
