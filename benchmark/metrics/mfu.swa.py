"""mfu.swa: % of the chips' bf16 peak: the window's model FLOPs (the sliding-window sublayer's four projections and its attention's SDDMM and SpMM) over window, chips and peak."""

from benchmark.layer_metrics import mfu as read  # noqa: F401
