"""The Pallas grouped matmul against its roofline, in %: for each
``gmm_<tm>x<tk>x<tn>`` event of the trace, the chip's least time for a call
of that m tile and result width, max(executed FLOPs / peak, least bytes /
HBM bandwidth), summed over the events and divided by their summed device
time. The trace does not say which instance an event belongs to, so each
event's least time is the mean over the window's instances of that call's
least time on each instance's own routing. None where the trace has no such
event."""

from bench.families.moe_variants import KERNELS, gmm_least_seconds


def read(window):
    if window.trace is None or not window.seen.rows:
        return None
    pattern, peaks = KERNELS["gmm"], window.peaks
    rows = list(window.seen.rows.values())
    mean = {}
    least = seconds = 0.0
    for name, duration in window.trace.op_events:
        m = pattern.search(name)
        if m is None:
            continue
        key = (int(m.group(1)), int(m.group(5)))
        if key not in mean:
            mean[key] = sum(gmm_least_seconds(p, *key, peaks.flops, peaks.hbm_bw)
                            for p in rows) / len(rows)
        least += mean[key]
        seconds += duration
    return 100.0 * least / seconds if seconds > 0 else None
