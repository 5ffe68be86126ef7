"""A configuration, a traffic mix, an end-to-end metric and a per-layer
metric added as new files, with entries in BENCHMARK.json, are found by
name: no existing file of the harness is edited. The mix is data only;
the generator drives it."""

import json
import os

import pytest

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
