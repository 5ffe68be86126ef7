"""Each configuration's work function against hand counts."""

import json
import os

import pytest

from benchmark import layer_metrics as lm
from benchmark.harness import app_module, load_module
from benchmark.tests.conftest import ROOT


def _cfg(name):
    with open(os.path.join(ROOT, "benchmark", "configs", name + ".json")) as f:
        return json.load(f)


def test_pair_work_by_hand():
    cfg = _cfg("pair-rmat20-r128")
    nnz, M = 33_554_432, 1 << 20
    w = app_module("pair").work(cfg, nnz, M, M)
    assert w["model_flops"] == 4 * nnz * 128
    # S: rows, cols, values (12 B a nonzero); A, B read; out and mid
    # written; 4 bytes an element.
    assert w["sparse_ops"] == [(4 * nnz * 128,
                                12 * nnz + 3 * 4 * M * 128 + 4 * nnz)]
    assert w["sparse_kernels"] == ["fused"]
    # The reference's rate: 2 nnz * 2 R per pair, per chip.
    rate = load_module(os.path.join(ROOT, "benchmark", "metrics",
                                    "pair_gflops.py"), "pair_gflops").read
    ctx = {"window": {"ends_s": [0.2] * 10, "window_s": 2.0}, "chips": 4,
           "work": w}
    assert rate(ctx) == pytest.approx(4 * nnz * 128 * 10 / 2.0 / 4 / 1e9)


def test_als_work_by_hand():
    cfg = _cfg("als-rmat18-r128")
    nnz, M = 8_388_064, 1 << 18
    w = app_module("als").work(cfg, nnz, M, M)
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
    # Each half-step: the SpMM kernel for the right-hand side, the fused
    # kernel for the residual and each CG iteration.
    assert w["sparse_kernels"] == (["spmm"] + ["fused"] * (it + 1)) * 2
    assert w["sparse_kernels"].count("fused") == 22
    assert w["sparse_kernels"].count("spmm") == 2


def test_gat_work_by_hand():
    cfg = _cfg("gat-ref-rmat18")
    nnz, M = 8_388_064, 1 << 18
    w = app_module("gat").work(cfg, nnz, M, M)
    # Projections: 256 -> 4x256, 1024 -> 4x256, 1024 -> 6x256.
    proj = 2 * M * (256 * 1024 + 1024 * 1024 + 1024 * 1536)
    sparse = 14 * 2 * (2 * nnz * 256)
    assert w["model_flops"] == proj + sparse
    assert proj / 1e12 == pytest.approx(1.512, abs=1e-3)
    assert len(w["sparse_ops"]) == 28
    sddmm = (2 * nnz * 256, 12 * nnz + 2 * 4 * M * 256 + 4 * nnz)
    spmm = (2 * nnz * 256, 12 * nnz + 2 * 4 * M * 256)
    assert w["sparse_ops"] == [sddmm, spmm] * 14
    assert w["sparse_kernels"] == ["sddmm", "spmm"] * 14


PEAK = {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.mark.parametrize("app,config,nnz,M,kinds", [
    ("als", "als-rmat18-r128", 8_388_064, 1 << 18, ("fused", "spmm")),
    ("gat", "gat-ref-rmat18", 8_388_064, 1 << 18, ("sddmm", "spmm")),
    ("pair", "pair-rmat18-r128", 8_388_064, 1 << 18, ("fused",)),
    ("pair", "pair-rmat20-r128", 33_554_432, 1 << 20, ("fused",)),
])
def test_kernel_shares_add_up_to_the_sparse_roofline(app, config, nnz, M,
                                                     kinds):
    """Every sparse op names its kernel, and the kernels' least times add
    up to the untagged total, which the tags leave as it was."""
    w = app_module(app).work(_cfg(config), nnz, M, M)
    assert len(w["sparse_kernels"]) == len(w["sparse_ops"])
    assert set(w["sparse_kernels"]) == set(kinds)
    ctx = {"peak": PEAK, "chips": 1, "units": 3, "work": w}
    old = dict(ctx, work={k: w[k] for k in ("model_flops", "sparse_ops")})
    assert lm.roofline_s(ctx) == lm.roofline_s(old)
    assert sum(lm.roofline_s(ctx, k) for k in kinds) == pytest.approx(
        lm.roofline_s(ctx), rel=1e-12)
    assert lm.roofline_s(ctx, "attn_norm") == 0
    # Bandwidth binds every op: the least time is the least bytes.
    assert lm.roofline_s(ctx) == pytest.approx(
        3 * sum(b for _, b in w["sparse_ops"]) / PEAK["hbm_bytes_per_s"],
        rel=1e-12)


def test_least_time_of_one_spmm_call_by_hand():
    """ALS's right-hand-side SpMM at logM 18, R 128: S's 12 bytes a
    nonzero, B read and the output written, 4 bytes an element."""
    w = app_module("als").work(_cfg("als-rmat18-r128"), 8_388_064, 1 << 18,
                         1 << 18)
    ctx = {"peak": PEAK, "chips": 1, "units": 1, "work": w}
    one = (12 * 8_388_064 + 2 * 4 * (1 << 18) * 128) / 819e9
    assert lm.roofline_s(ctx, "spmm") == pytest.approx(2 * one, rel=1e-12)
    assert one * 1e3 == pytest.approx(0.4507, abs=1e-4)


def test_one_chip_pair_readers_by_hand():
    """The one-chip pair's rate is the pair's rate; its median unit time
    leaves a stalled unit out."""
    def reader(name):
        return load_module(os.path.join(ROOT, "benchmark", "metrics",
                                        name + ".py"), name).read

    w = app_module("pair").work(_cfg("pair-rmat18-r128"), 8_388_064, 1 << 18,
                          1 << 18)
    ctx = {"window": {"ends_s": [0.08, 0.16, 0.36], "window_s": 0.36,
                      "latency_s": [0.08, 0.08, 0.2]},
           "chips": 1, "work": w}
    assert reader("pair_gflops_1chip")(ctx) == reader("pair_gflops")(ctx)
    assert reader("unit_p50_ms.pair_1chip")(ctx) == pytest.approx(80.0)
