"""The repo's Pallas GEMM against its roofline, in %: the chip's least time
for the cell's GEMM, max(FLOPs / peak, bytes / HBM bandwidth), times the
kernel's events in the trace, over their summed device time."""


def read(window):
    return window.kernel_roofline("pallas_matmul")
