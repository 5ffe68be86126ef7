"""idle_share.swa: % of the traced window in which no operation runs on the device, mean over the cell's devices."""

from benchmark.layer_metrics import idle_share as read  # noqa: F401
