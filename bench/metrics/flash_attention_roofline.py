"""The repo's Pallas flash kernel against its roofline, in %: for each
``flash_<mask>_<bq>x<bk>`` event of the trace, the chip's least time for that
mask and tiling at the cell's shape, max(executed FLOPs / peak, least bytes
/ HBM bandwidth), summed over the events and divided by their summed device
time. None where the trace has no such event."""

from bench.families.attention_variants import KERNELS, flash_least_seconds


def read(window):
    if window.trace is None or not window.seen.rows:
        return None
    params = next(iter(window.seen.rows.values()))
    pattern, peaks = KERNELS["flash"], window.peaks
    least = seconds = 0.0
    for name, duration in window.trace.op_events:
        m = pattern.search(name)
        if m is None:
            continue
        swa, bq, bk = m.groups()
        least += flash_least_seconds(params, None if swa is None else int(swa), int(bq),
                                     int(bk), peaks.flops, peaks.hbm_bw)
        seconds += duration
    return 100.0 * least / seconds if seconds > 0 else None
