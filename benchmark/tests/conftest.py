"""The benchmark's tests run on a virtual 4-device CPU mesh, at tiny
sizes, with JAX's persistent cache off (XLA:CPU executables read back
from it can fail to run here)."""

import json
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
os.environ["JAX_PLATFORMS"] = "cpu"
flags = [f for f in os.environ.get("XLA_FLAGS", "").split()
         if not f.startswith("--xla_force_host_platform_device_count")]
os.environ["XLA_FLAGS"] = " ".join(
    flags + ["--xla_force_host_platform_device_count=4"])

import pytest  # noqa: E402


def copy_root(dst: str) -> str:
    """A checkout with the benchmark's files, at full size, and a CPU
    stand-in in the table of peaks."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), dst)
    shutil.copytree(os.path.join(ROOT, "benchmark"),
                    os.path.join(dst, "benchmark"),
                    ignore=shutil.ignore_patterns("_work", "tests",
                                                  "__pycache__"))
    peaks = os.path.join(dst, "benchmark", "peaks.json")
    with open(peaks) as f:
        table = json.load(f)
    table["cpu"] = dict(table["TPU v5 lite"], source="test stand-in")
    with open(peaks, "w") as f:
        json.dump(table, f)
    return dst


def cut_configs(root: str) -> None:
    """Every configuration under ``root`` cut to its tiny stand-in: its
    matrix by its generator's ``tiny``, the rest by its app's."""
    from benchmark import harness

    cdir = os.path.join(root, "benchmark", "configs")
    for name in os.listdir(cdir):
        path = os.path.join(cdir, name)
        with open(path) as f:
            cfg = json.load(f)
        gen = harness.generator_module(cfg["matrix"]["generator"], root)
        app = harness.app_module(cfg["app"], root)
        cfg = app.tiny(dict(cfg, matrix=gen.tiny(cfg["matrix"])))
        with open(path, "w") as f:
            json.dump(cfg, f)


def tiny_root(dst: str) -> str:
    """A checkout with the benchmark's files and every configuration cut
    to a tiny size: what a run needs, runnable on the CPU."""
    copy_root(dst)
    cut_configs(dst)
    return dst


@pytest.fixture
def tiny(tmp_path):
    return tiny_root(str(tmp_path))
