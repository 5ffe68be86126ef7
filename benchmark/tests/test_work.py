"""Each configuration's work function against hand counts."""

import json
import os

import pytest

from benchmark.harness import load_module
from benchmark.tests.conftest import ROOT


def _cfg(name):
    with open(os.path.join(ROOT, "benchmark", "configs", name + ".json")) as f:
        return json.load(f)


def _app(name):
    return load_module(os.path.join(ROOT, "benchmark", "apps", name + ".py"),
                       "benchmark.apps." + name)


def test_pair_work_by_hand():
    cfg = _cfg("pair-rmat20-r128")
    nnz, M = 33_554_432, 1 << 20
    w = _app("pair").work(cfg, nnz, M, M)
    assert w["model_flops"] == 4 * nnz * 128
    # S: rows, cols, values (12 B a nonzero); A, B read; out and mid
    # written; 4 bytes an element.
    assert w["sparse_ops"] == [(4 * nnz * 128,
                                12 * nnz + 3 * 4 * M * 128 + 4 * nnz)]
    # The reference's rate: 2 nnz * 2 R per pair, per chip.
    rate = load_module(os.path.join(ROOT, "benchmark", "metrics",
                                    "pair_gflops.py"), "pair_gflops").read
    ctx = {"window": {"ends_s": [0.2] * 10, "window_s": 2.0}, "chips": 4,
           "work": w}
    assert rate(ctx) == pytest.approx(4 * nnz * 128 * 10 / 2.0 / 4 / 1e9)


def test_als_work_by_hand():
    cfg = _cfg("als-rmat18-r128")
    nnz, M = 8_388_064, 1 << 18
    w = _app("als").work(cfg, nnz, M, M)
    R, it = 128, 10
    # Per half-step: the right-hand side's SpMM, one fused pair for the
    # initial residual and one per CG iteration.
    assert len(w["sparse_ops"]) == 2 * (1 + it + 1)
    spmm = (2 * nnz * R, 12 * nnz + 2 * 4 * M * R)
    fused = (4 * nnz * R, 12 * nnz + 3 * 4 * M * R + 4 * nnz)
    assert w["sparse_ops"].count(spmm) == 2
    assert w["sparse_ops"].count(fused) == 2 * (it + 1)
    vector = 2 * it * 12 * M * R
    assert w["model_flops"] == 2 * spmm[0] + 2 * (it + 1) * fused[0] + vector
    assert w["model_flops"] / 1e9 == pytest.approx(106.83, abs=0.01)


def test_gat_work_by_hand():
    cfg = _cfg("gat-ref-rmat18")
    nnz, M = 8_388_064, 1 << 18
    w = _app("gat").work(cfg, nnz, M, M)
    # Projections: 256 -> 4x256, 1024 -> 4x256, 1024 -> 6x256.
    proj = 2 * M * (256 * 1024 + 1024 * 1024 + 1024 * 1536)
    sparse = 14 * 2 * (2 * nnz * 256)
    assert w["model_flops"] == proj + sparse
    assert proj / 1e12 == pytest.approx(1.512, abs=1e-3)
    assert len(w["sparse_ops"]) == 28
    sddmm = (2 * nnz * 256, 12 * nnz + 2 * 4 * M * 256 + 4 * nnz)
    spmm = (2 * nnz * 256, 12 * nnz + 2 * 4 * M * 256)
    assert w["sparse_ops"] == [sddmm, spmm] * 14


def test_one_chip_pair_readers_by_hand():
    """The one-chip pair's rate is the pair's rate; its median unit time
    leaves a stalled unit out."""
    def reader(name):
        return load_module(os.path.join(ROOT, "benchmark", "metrics",
                                        name + ".py"), name).read

    w = _app("pair").work(_cfg("pair-rmat18-r128"), 8_388_064, 1 << 18,
                          1 << 18)
    ctx = {"window": {"ends_s": [0.08, 0.16, 0.36], "window_s": 0.36,
                      "latency_s": [0.08, 0.08, 0.2]},
           "chips": 1, "work": w}
    assert reader("pair_gflops_1chip")(ctx) == reader("pair_gflops")(ctx)
    assert reader("unit_p50_ms.pair_1chip")(ctx) == pytest.approx(80.0)
