"""attn_norm_roofline.swa: % of its roofline of the attn_norm kernel in the sliding-window sublayer: the least time of the window's ops tagged `attn_norm` in the app's `work()` (FLOPs over bf16 peak or least bytes over HBM bandwidth, the larger) over the device time of the operations named `attn_norm*` (`attn_norm_tiles.<n>`), union in the window, mean over devices."""

from benchmark.layer_metrics import kernel_roofline


def read(ctx):
    return kernel_roofline(ctx, "attn_norm")
