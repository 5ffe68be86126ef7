"""pair_gflops_1chip: as pair_gflops, for the one-chip pair; a metric of
its own because its bound is wider (a one-chip host stalls its fetches).
The reference's rate, ``2 nnz * 2 R`` a fused pair
(`benchmark_dist.cpp:147-149`, the pair's ``model_flops``), over the
window and the chips, in GFLOP/s."""

from benchmark.window_metrics import model_gflops_per_chip as read  # noqa: F401,E501
