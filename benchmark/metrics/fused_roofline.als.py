"""fused_roofline.als: % of its roofline of the fused SDDMM->SpMM kernel: the least time of the window's ops tagged `fused` in the app's `work()` (FLOPs over bf16 peak or least bytes over HBM bandwidth, the larger) over the device time of the operations named `fused*` (`fused_tiles.<n>`), union in the window, mean over devices."""

from benchmark.layer_metrics import kernel_roofline


def read(ctx):
    return kernel_roofline(ctx, "fused")
