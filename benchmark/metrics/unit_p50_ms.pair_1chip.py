"""unit_p50_ms.pair_1chip: the median latency of the window's units (in
the closed ``back_to_back`` mix, a unit's time), in ms, on the host's
clock. A steadier reading of the one-chip pair's speed than
``pair_gflops_1chip``, which a one-chip host's occasional stalls of a
tenth of a second and more move by a few percent; it leaves them out,
so it is no end-to-end metric."""

import statistics


def read(ctx):
    lat = ctx["window"]["latency_s"]
    return 1e3 * statistics.median(lat) if lat else None
