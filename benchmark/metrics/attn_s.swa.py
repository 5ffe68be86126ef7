"""attn_s.swa: device seconds a forward of the attention epilogue kernels, the operations named `attn*` (`attn_reduce_tiles.<n>`, `attn_norm_tiles.<n>`), union in the window, mean over devices."""

from benchmark.span_metrics import kernel_s


def read(ctx):
    return kernel_s(ctx, "attn")
