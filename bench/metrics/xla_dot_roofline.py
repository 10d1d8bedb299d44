"""XLA's GEMMs against their roofline, in %: the chip's least time for the
cell's GEMM, max(FLOPs / peak, bytes / HBM bandwidth), times XLA's dot
events in the trace, over their summed device time."""


def read(window):
    return window.kernel_roofline("xla_dot")
