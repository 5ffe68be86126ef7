"""spmm_roofline.swa: % of its roofline of the spmm kernel in the sliding-window sublayer: the least time of the window's ops tagged `spmm` in the app's `work()` (FLOPs over bf16 peak or least bytes over HBM bandwidth, the larger) over the device time of the operations named `spmm*` (`spmm_tiles.<n>`), union in the window, mean over devices. Nothing where no attention epilogue (`attn*`) ran in the window: the spmm is then not the attention's."""

from benchmark.layer_metrics import kernel_roofline
from benchmark.span_metrics import kernel_s


def read(ctx):
    if kernel_s(ctx, "attn") is None:
        return None
    return kernel_roofline(ctx, "spmm")
