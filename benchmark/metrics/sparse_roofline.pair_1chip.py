"""sparse_roofline.pair_1chip (the one-chip pair, whose end-to-end rate has a bound of its own): % of its roofline: the least time of the window's SDDMM and SpMM work (FLOPs over bf16 peak or least bytes over HBM bandwidth, the larger) over the device time of the Mosaic custom calls."""

from benchmark.layer_metrics import sparse_roofline as read  # noqa: F401
