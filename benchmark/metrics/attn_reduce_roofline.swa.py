"""attn_reduce_roofline.swa: % of its roofline of the attn_reduce kernel in the sliding-window sublayer: the least time of the window's ops tagged `attn_reduce` in the app's `work()` (FLOPs over bf16 peak or least bytes over HBM bandwidth, the larger) over the device time of the operations named `attn_reduce*` (`attn_reduce_tiles.<n>`), union in the window, mean over devices."""

from benchmark.layer_metrics import kernel_roofline


def read(ctx):
    return kernel_roofline(ctx, "attn_reduce")
