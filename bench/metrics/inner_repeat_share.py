"""Share of measured algorithms whose timer calibrated an inner-repeat loop
(``inner_repeats`` above 1: one call was under the timer's floor), in %."""


def read(window):
    counts = [r for rec in window.seen.records.values()
              for r in rec.get("inner_repeats", {}).values()]
    return 100.0 * sum(1 for r in counts if r > 1) / len(counts) if counts else None
