"""mfu.pair_1chip (the one-chip pair, whose end-to-end rate has a bound of its own): % of the chips' bf16 peak: the window's model FLOPs over window, chips and peak."""

from benchmark.layer_metrics import mfu as read  # noqa: F401
