"""A configuration, a traffic mix, an end-to-end metric and a per-layer
metric added as new files, with entries in BENCHMARK.json, are found by
name: no existing file of the harness is edited. The mix is data only;
the generator drives it."""

import json
import os

import pytest

from benchmark import harness
from benchmark.tests.helpers import run_cell

MIXES = {
    "closed2": {"arrivals": "closed", "clients": 2, "keep_one_of_first": 2},
    "open_poisson": {"arrivals": "open", "rate_per_s": 40,
                     "interval": "poisson", "burst": 2,
                     "keep_one_of_first": 1},
}


@pytest.mark.parametrize("mix", sorted(MIXES))
def test_new_parts_are_found_by_name(tiny, capsys, mix):
    bdir = os.path.join(tiny, "benchmark")
    with open(os.path.join(bdir, "configs", "pair-rmat20-r128.json")) as f:
        cfg = json.load(f)
    cfg.update(name="throwaway-cfg", R=8)
    with open(os.path.join(bdir, "configs", "throwaway-cfg.json"), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(bdir, "traffic", "throwaway_mix.json"), "w") as f:
        json.dump(MIXES[mix], f)
    with open(os.path.join(bdir, "metrics", "throwaway_metric.py"), "w") as f:
        f.write("def read(ctx):\n    return 42.0 + ctx['units'] * 0\n")
    with open(os.path.join(bdir, "metrics", "throwaway_p95_ms.py"), "w") as f:
        f.write("def read(ctx):\n"
                "    lat = sorted(ctx['window']['latency_s'])\n"
                "    return 1e3 * lat[int(0.95 * (len(lat) - 1))]\n")
    with open(os.path.join(tiny, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "throwaway-cfg", "source": "test",
                             "file": "benchmark/configs/throwaway-cfg.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "throwaway-cell",
                               "config": "throwaway-cfg",
                               "traffic": "throwaway_mix", "chips": 4,
                               "why": "test"})
    bench["end_to_end"].append({"name": "throwaway_p95_ms", "unit": "ms",
                                "better": "lower", "bound": 0.1,
                                "source": "host_clock",
                                "workloads": ["throwaway-cell"]})
    bench["per_layer"].append({"name": "throwaway_metric", "unit": "count",
                               "better": "lower", "source": "device_trace",
                               "layer": "device",
                               "moves": "throwaway_p95_ms",
                               "workloads": ["throwaway-cell"]})
    with open(os.path.join(tiny, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)

    res = run_cell(tiny, capsys, "throwaway-cell")
    assert res["correct"]
    assert set(res["metrics"]) == {"throwaway_p95_ms", "setup_s"}
    assert res["metrics"]["throwaway_p95_ms"]["value"] > 0
    res = run_cell(tiny, capsys, "throwaway-cell", trace=1)
    assert res["metrics"]["throwaway_metric"] == {"value": 42.0,
                                                  "unit": "count"}


BANDED = '''"""A band of ``width`` diagonals from the main one, wrapping."""
import numpy as np


def make(spec, rng):
    n, w = spec["n"], spec["width"]
    rows = np.repeat(np.arange(n), w)
    cols = (rows + np.tile(np.arange(w), n)) % n
    return {"rows": rows, "cols": cols,
            "vals": rng.uniform(0.5, 1.5, rows.size), "M": n, "N": n}


def tiny(spec):
    return dict(spec, n=128, width=4)
'''

THROWAWAY_APP = '''"""The fused pair under a name of its own, with its own tiny size."""
from benchmark.apps.pair import Run, work  # noqa: F401


def tiny(cfg):
    return dict(cfg, R=8)
'''


def test_new_generator_and_app_are_found_by_name(tmp_path, capsys):
    """A configuration whose matrix is not an R-mat and whose app is new
    comes in as new files: its generator and its app cut it to a tiny
    size, and a run finds both by name."""
    from benchmark.tests.conftest import copy_root, cut_configs

    root = copy_root(str(tmp_path))
    bdir = os.path.join(root, "benchmark")
    with open(os.path.join(bdir, "matrices", "banded.py"), "w") as f:
        f.write(BANDED)
    with open(os.path.join(bdir, "apps", "throwaway_app.py"), "w") as f:
        f.write(THROWAWAY_APP)
    with open(os.path.join(bdir, "configs", "pair-rmat18-r128.json")) as f:
        cfg = json.load(f)
    cfg.update(name="band-cfg", app="throwaway_app",
               matrix={"generator": "banded", "n": 1 << 18, "width": 32})
    with open(os.path.join(bdir, "configs", "band-cfg.json"), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "band-cfg", "source": "test",
                             "file": "benchmark/configs/band-cfg.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "band-cell", "config": "band-cfg",
                               "traffic": "back_to_back", "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"]:
        if m["name"] == "pair_gflops":
            m["workloads"].append("band-cell")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)

    cut_configs(root)
    with open(os.path.join(bdir, "configs", "band-cfg.json")) as f:
        cut = json.load(f)
    assert cut["matrix"] == {"generator": "banded", "n": 128, "width": 4}
    assert cut["R"] == 8
    res = run_cell(root, capsys, "band-cell")
    assert res["correct"], res
    assert set(res["metrics"]) == {"pair_gflops", "setup_s"}
    assert res["metrics"]["pair_gflops"]["value"] > 0


STANDIN_APP = '''"""The fused pair under a name of its own, with faults of its own: its
``altered`` moves an answer of the SDDMM's output (``mid``), not of the
SpMM's, and the reference can plant ``values_ignored`` (every nonzero
read as 1)."""
import numpy as np

from benchmark import faults
from benchmark.apps import pair
from benchmark.apps.pair import work  # noqa: F401


def tiny(cfg):
    return dict(cfg, R=8)


def _altered():
    from distributed_sddmm_tpu.parallel.dense_shift_15d import DenseShift15D

    fused = DenseShift15D.fused_spmm

    def altered(self, *a, **k):
        out, mid = fused(self, *a, **k)
        return out, faults.alter(mid)
    return faults.patched(DenseShift15D, "fused_spmm", altered)


FAULTS = {"half": None, "altered": _altered}
REFERENCE_FAULTS = ("values_ignored",)


class Run(pair.Run):
    def reference(self, quant, keep=None, fault=None):
        if fault is None:
            return super().reference(quant, keep)
        if fault != "values_ignored":
            raise ValueError(fault)
        mat = self.mat
        self.mat = dict(mat, vals=np.ones_like(mat["vals"]))
        try:
            return super().reference(quant, keep)
        finally:
            self.mat = mat
'''

STANDIN_READER = '''"""fused_roofline.standin: the fused kernel's share of its roofline."""
from benchmark.layer_metrics import kernel_roofline


def read(ctx):
    return kernel_roofline(ctx, "fused")
'''


def _digests(root: str) -> dict:
    import hashlib

    out = {}
    for d, _, files in os.walk(os.path.join(root, "benchmark")):
        for name in files:
            path = os.path.join(d, name)
            with open(path, "rb") as f:
                out[os.path.relpath(path, root)] = hashlib.sha256(
                    f.read()).hexdigest()
    return out


def test_new_app_with_its_own_faults_needs_no_edit(tmp_path, capsys):
    """An app with its own faults, its configuration, its cell and a
    per-kernel reader come in as new files and entries: a run, the fault
    cases as ``test_faults`` builds them, ``calibrate.py --faults
    --control`` and the tests' list of cells take them up, and no file
    that was there before changes."""
    from benchmark import trace as tr
    from benchmark.tests.conftest import cut_configs, tiny_root
    from benchmark.tests.helpers import (apps_and_chips, assert_fault_caught,
                                         calibrate_readings, fault_cases)

    root = tiny_root(str(tmp_path))
    before = _digests(root)
    bdir = os.path.join(root, "benchmark")
    with open(os.path.join(bdir, "apps", "standin.py"), "w") as f:
        f.write(STANDIN_APP)
    with open(os.path.join(bdir, "metrics", "fused_roofline.standin.py"),
              "w") as f:
        f.write(STANDIN_READER)
    with open(os.path.join(bdir, "configs", "pair-rmat18-r128.json")) as f:
        cfg = json.load(f)
    cfg.update(name="standin-cfg", app="standin")
    with open(os.path.join(bdir, "configs", "standin-cfg.json"), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "standin-cfg", "source": "test",
                             "file": "benchmark/configs/standin-cfg.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "standin-cell", "config": "standin-cfg",
                               "traffic": "back_to_back", "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"]:
        if m["name"] == "pair_gflops_1chip":
            m["workloads"].append("standin-cell")
    bench["per_layer"].append({"name": "fused_roofline.standin", "unit": "%",
                               "better": "higher", "source": "device_trace",
                               "layer": "kernels",
                               "moves": "pair_gflops_1chip",
                               "workloads": ["standin-cell"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    cut_configs(root)

    assert apps_and_chips(root)["standin-cell"] == ("standin", 1)
    res = run_cell(root, capsys, "standin-cell")
    assert res["correct"], res
    assert set(res["metrics"]) == {"pair_gflops_1chip", "setup_s"}
    res = run_cell(root, capsys, "standin-cell", trace=1)
    assert res["correct"], res
    assert set(res["metrics"]) <= {"fused_roofline.standin"}

    cases = [c for c in fault_cases(root) if c[0] == "standin-cell"]
    assert cases == [("standin-cell", "half"), ("standin-cell", "altered")]
    for cell, fault in cases:
        res = assert_fault_caught(root, capsys, cell, fault)
    # The app's own ``altered`` ran: ``mid`` is off, ``out`` is not.
    checks = res["checks"]
    assert checks["mid.max"]["value"] > checks["mid.max"]["limit"]
    assert checks["out"]["value"] < checks["out"]["limit"]

    lines = calibrate_readings(root, capsys, "standin-cell")
    assert all("fault.values_ignored" in x for x in lines)

    # The reader finds the fused kernel by name in a trace.
    c = harness.Cell("standin-cell", root)
    M = 1 << 18
    dev = tr.Device("/device:TPU:0", [tr.Op("fused_tiles.1", 0, 10**6,
                                            "custom_call")], [])
    ctx = {"trace": tr.Trace([dev], []), "devices": [dev], "lo": 0,
           "hi": 10**6, "units": 2, "chips": 1,
           "peak": {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9},
           "work": c.app.work(dict(c.cfg, R=128), 32 * M, M, M)}
    least = 2 * (12 * 32 * M + 3 * 4 * M * 128 + 4 * 32 * M) / 819e9
    assert c.readers["fused_roofline.standin"].read(ctx) == pytest.approx(
        100 * least / 1e-3)

    after = _digests(root)
    assert {k: after.get(k) for k in before} == before
