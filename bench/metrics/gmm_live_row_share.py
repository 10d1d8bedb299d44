"""Share of the grouped matmul's executed rows that are assigned rows, in %:
the program's ``gmm_assigned_rows`` (T k per call) over its
``gmm_executed_rows`` (the m tiles the kernel visits times its m tile,
counted as megablox's ``make_group_metadata`` counts them), summed over the
gmm variants built in the window. A padded row costs a full row of FLOPs."""


def read(window):
    executed = window.seen.timings.get("gmm_executed_rows", 0.0)
    if executed <= 0:
        return None
    return 100.0 * window.seen.timings.get("gmm_assigned_rows", 0.0) / executed
