"""The sliding-window attention cell's work function against hand
counts at its configuration's size, and its kernel tags."""

import json
import os

import pytest

from benchmark import layer_metrics as lm
from benchmark.harness import app_module
from benchmark.tests.conftest import ROOT

PEAK = {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}


def _cfg():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "mimo-v2-flash-swa-32k.json")) as f:
        return json.load(f)


def test_swa_work_by_hand():
    cfg = _cfg()
    T, w = 1 << cfg["matrix"]["log_m"], cfg["matrix"]["window"]
    nnz = T * w - w * (w - 1) // 2
    assert (T, nnz) == (32768, 4_186_176)
    work = app_module("swa").work(cfg, nnz, T, T)
    H, hid, dq, dv = 8, 4096, 192, 128
    proj = 2 * T * hid * (H * dq + dq + dv) + 2 * T * H * dv * hid
    sparse = 2 * H * nnz * (dq + dv)
    assert work["model_flops"] == proj + sparse
    assert proj / 1e9 == pytest.approx(773.1, abs=0.05)
    assert sparse / 1e9 == pytest.approx(21.43, abs=0.01)
    assert work["sparse_kernels"] == ["sddmm", "attn_reduce", "attn_norm",
                                      "spmm"]
    n, rows = H * nnz, H * T
    assert work["sparse_ops"] == [
        (2 * n * dq, 12 * n + 4 * rows * dq + 4 * T * dq + 4 * n),
        (3 * n, 16 * n + 8 * rows),
        (3 * n, 20 * n + 8 * rows),
        (2 * n * dv, 12 * n + 4 * T * dv + 4 * rows * dv),
    ]
    # Bandwidth binds every op: the least time is the least bytes, and
    # the kernels' shares add up to the whole.
    ctx = {"peak": PEAK, "chips": 1, "units": 2, "work": work}
    kinds = ("sddmm", "attn_reduce", "attn_norm", "spmm")
    assert sum(lm.roofline_s(ctx, k) for k in kinds) == pytest.approx(
        lm.roofline_s(ctx), rel=1e-12)
    assert lm.roofline_s(ctx) == pytest.approx(
        2 * sum(b for _, b in work["sparse_ops"]) / PEAK["hbm_bytes_per_s"],
        rel=1e-12)
