"""sddmm_s.gat: device seconds a forward of the operations named `sddmm*` (the SDDMM Pallas kernel), union in the window, mean over devices."""

from benchmark.span_metrics import kernel_s


def read(ctx):
    return kernel_s(ctx, "sddmm")
