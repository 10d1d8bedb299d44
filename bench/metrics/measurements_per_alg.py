"""Mean of the records' ``measurements_per_alg``: how many samples
Procedure 4 took per algorithm before it converged or hit its maximum."""


def read(window):
    recs = list(window.seen.records.values())
    return sum(r["measurements_per_alg"] for r in recs) / len(recs) if recs else None
