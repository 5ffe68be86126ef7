"""Every per-layer reader of ``BENCHMARK.json`` on the two traces recorded
on a v5e (``data/tiny.xplane.pb``, ``data/spans.xplane.pb``), with each
cell's work at its configuration's size: the readers that came before
the per-kernel tags read the same whether ``work()`` tags its sparse ops
with kernels or not, and the per-kernel rooflines read by hand."""

import json
import os

import pytest

from benchmark import harness
from benchmark import trace as tr
from benchmark.tests.conftest import ROOT

DATA = os.path.join(os.path.dirname(__file__), "data")
#: Units of work each trace holds.
TRACES = {"tiny.xplane.pb": 3, "spans.xplane.pb": 1}
PER_KERNEL = {"fused_roofline.als": "fused", "spmm_roofline.als": "spmm",
              "sddmm_roofline.gat": "sddmm", "spmm_roofline.gat": "spmm"}
#: What the readers that take the work read on ``spans.xplane.pb`` before
#: the apps tagged their sparse ops (work at 32 nonzeros a row).
BEFORE = {
    "sparse_roofline.als": 10099.314474139317,
    "sparse_roofline.gat": 14743.918991657933,
    "sparse_roofline.pair": 1728.224936751113,
    "sparse_roofline.pair_1chip": 432.0562341877783,
    "mfu.als": 1.615507600634367,
    "mfu.gat": 24.679111085067717,
    "mfu.pair": 0.25978011668492335,
    "mfu.pair_1chip": 0.06494502917123084,
}

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)
with open(os.path.join(ROOT, "benchmark", "peaks.json")) as f:
    PEAK = json.load(f)["TPU v5 lite"]


def _work(cell: str) -> dict:
    """One unit's work of ``cell`` at its configuration's rows, 32
    nonzeros a row."""
    c = harness.Cell(cell)
    M = 1 << c.cfg["matrix"]["log_m"]
    return c.app.work(c.cfg, 32 * M, M, M)


def _reader(name):
    return harness.load_module(
        os.path.join(ROOT, "benchmark", "metrics", name + ".py"),
        "benchmark.metrics." + name).read


@pytest.fixture(scope="module", params=sorted(TRACES))
def trace(request):
    t = tr.load(os.path.join(DATA, request.param))
    lo, hi = t.window("bench:window")
    units = TRACES[request.param]
    return request.param, {
        "trace": t, "lo": lo, "hi": hi, "devices": t.devices,
        "units": units, "chips": 1, "peak": PEAK,
        "window": {"latency_s": [0.1, 0.2, 0.3][:units],
                   "ends_s": [0.1, 0.3, 0.6][:units], "window_s": 0.6}}


def test_readers_read_the_same_through_either_work_shape(trace):
    name, ctx = trace
    read = 0
    for m in BENCH["per_layer"]:
        new = _work(m["workloads"][0])
        old = {k: new[k] for k in ("model_flops", "sparse_ops")}
        got = _reader(m["name"])(dict(ctx, work=new))
        before = _reader(m["name"])(dict(ctx, work=old))
        if m["name"] in PER_KERNEL:
            assert before is None, m["name"]
            continue
        assert got == before, m["name"]
        if name == "spans.xplane.pb" and m["name"] in BEFORE:
            assert got == BEFORE[m["name"]], m["name"]
        read += got is not None
    assert read >= 15


def test_per_kernel_rooflines_by_hand(trace):
    """Least time of the ops a kernel runs over that kernel's device time
    (``test_span_metrics``: 89,592 ns fused, 12,434 sddmm, 49,695 spmm on
    ``spans.xplane.pb``). ``tiny.xplane.pb`` names no kernel: nothing."""
    name, ctx = trace
    kernel_ns = {"fused": 89592, "sddmm": 12434, "spmm": 49695}
    bw = PEAK["hbm_bytes_per_s"]
    M = 1 << 18
    nnz, R, f = 32 * M, 128, 256
    least = {  # bytes a unit; bandwidth binds every op
        "fused_roofline.als": 22 * (12 * nnz + 3 * 4 * M * R + 4 * nnz),
        "spmm_roofline.als": 2 * (12 * nnz + 2 * 4 * M * R),
        "sddmm_roofline.gat": 14 * (12 * nnz + 2 * 4 * M * f + 4 * nnz),
        "spmm_roofline.gat": 14 * (12 * nnz + 2 * 4 * M * f),
    }
    for metric, kind in PER_KERNEL.items():
        got = _reader(metric)(dict(ctx, work=_work(
            next(m for m in BENCH["per_layer"]
                 if m["name"] == metric)["workloads"][0])))
        if name == "tiny.xplane.pb":
            assert got is None, metric
        else:
            want = 100 * least[metric] / bw / (kernel_ns[kind] * 1e-9)
            assert got == pytest.approx(want, rel=1e-9), metric
