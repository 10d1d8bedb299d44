"""Share of the flash kernel's grid steps that do work, in %: the program's
``flash_live_steps`` over its ``flash_grid_steps`` (counted with the
kernel's own block predicate as each flash variant is built), over the
window. A dead step does no arithmetic but still fetches its k/v blocks."""


def read(window):
    grid = window.seen.timings.get("flash_grid_steps", 0.0)
    if grid <= 0:
        return None
    return 100.0 * window.seen.timings.get("flash_live_steps", 0.0) / grid
