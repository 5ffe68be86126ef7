"""Auxiliary components: serial ALS oracle, charts, baseline, overlap,
kernel-sweep CLI (SURVEY.md components #17, #19, #23, #24, #29)."""

import json

import numpy as np
import pytest

from distributed_sddmm_tpu.bench.baseline import run_baseline
from distributed_sddmm_tpu.bench.overlap import run_overlap_experiment
from distributed_sddmm_tpu.models.serial_als import SerialALS
from distributed_sddmm_tpu.tools import charts
from distributed_sddmm_tpu.utils.coo import HostCOO


class TestSerialALS:
    def test_residual_decreases_toward_zero(self):
        S = HostCOO.erdos_renyi(120, 90, 5, seed=0)
        als = SerialALS(S, R=8, seed=1)
        r0 = als.compute_residual()
        als.run_cg(3, cg_iters=10)
        r1 = als.compute_residual()
        assert r1 < 0.1 * r0, (r0, r1)

    def test_matches_distributed_als_trajectory(self):
        # Same artificial-groundtruth protocol as DistributedALS: both must
        # drive their residuals down on the same matrix.
        from distributed_sddmm_tpu.models.als import DistributedALS
        from distributed_sddmm_tpu.parallel.dense_shift_15d import DenseShift15D

        S = HostCOO.erdos_renyi(96, 80, 4, seed=2)
        serial = SerialALS(S, R=8, seed=0)
        serial.run_cg(2)
        dist = DistributedALS(DenseShift15D(S, R=8, c=1))
        dist.run_cg(2)
        assert serial.compute_residual() < 0.5
        assert dist.compute_residual() < 0.5

    def test_explicit_ground_truth(self):
        S = HostCOO.erdos_renyi(60, 60, 4, seed=3)
        obs = np.random.default_rng(0).standard_normal(S.nnz) * 0.01
        als = SerialALS(S, R=6, artificial_groundtruth=False, ground_truth_vals=obs)
        r0 = als.compute_residual()
        als.run_cg(2)
        assert als.compute_residual() < r0


class TestBaseline:
    def test_schema_and_positive_throughput(self, tmp_path):
        S = HostCOO.erdos_renyi(256, 256, 8, seed=0)
        out = str(tmp_path / "base.jsonl")
        rec = run_baseline(S, R=32, iters=3, output_file=out)
        assert rec["overall_throughput"] > 0
        assert rec["nnz"] == S.nnz and rec["r"] == 32
        on_disk = json.loads(open(out).read().strip())
        assert on_disk == pytest.approx(rec, rel=1e-9) or on_disk == rec


class TestOverlap:
    def test_runs_on_mesh(self, tmp_path):
        rec = run_overlap_experiment(block=64, steps_work=2, trials=2,
                                     output_file=str(tmp_path / "o.jsonl"))
        assert rec["p"] >= 1
        assert rec["interleaved_ms"] > 0 and rec["serialized_ms"] > 0


class TestCharts:
    def test_end_to_end(self, tmp_path):
        pytest.importorskip("matplotlib")
        records = [
            {
                "algorithm": "15d_fusion2", "fused": True, "R": 64,
                "overall_throughput": 10.0, "c": 1,
                "perf_stats": {"fusedSpMM": 1.2},
                "alg_info": {"c": 1},
            },
            {
                "algorithm": "15d_sparse", "fused": False, "R": 64,
                "overall_throughput": 12.0, "c": 1,
                "perf_stats": {"sddmmA": 0.5, "spmmA": 0.9},
                "alg_info": {"c": 1},
            },
        ]
        src = tmp_path / "r.jsonl"
        with open(src, "w") as f:
            for r in records:
                f.write(json.dumps(r) + "\n")
        rc = charts.main([str(src), "-o", str(tmp_path / "charts")])
        assert rc == 0
        assert (tmp_path / "charts" / "benchmark.png").exists()
        winners = json.loads((tmp_path / "charts" / "winners.json").read_text())
        assert winners == {"R=64,c=1": "15d_sparse"}

    def test_empty_input(self, tmp_path):
        src = tmp_path / "empty.jsonl"
        src.write_text("")
        assert charts.main([str(src)]) == 1

    def test_kernels_mode(self, tmp_path):
        records = [
            {"kernel": "xla", "logM": 14, "npr": 32, "R": 128,
             "fused_pair_gflops": 16.0},
            {"kernel": "pallas-bf16", "logM": 14, "npr": 32, "R": 128,
             "bm": 512, "bn": 512, "group": 4, "fused_pair_gflops": 80.0},
            # second record for the same (point, kernel): best one wins
            {"kernel": "pallas-bf16", "logM": 14, "npr": 32, "R": 128,
             "bm": 256, "bn": 512, "group": 1, "fused_pair_gflops": 40.0},
            {"kernel": "pallas-bf16", "logM": 16, "npr": 32, "R": 128,
             "bm": 512, "bn": 512, "group": 4, "fused_pair_gflops": 70.0},
        ]
        src = tmp_path / "k.jsonl"
        with open(src, "w") as f:
            for r in records:
                f.write(json.dumps(r) + "\n")
        rc = charts.main([str(src), "--kernels", "-o", str(tmp_path / "out")])
        assert rc == 0
        assert (tmp_path / "out" / "kernels.png").exists()
        # A harness-records file in --kernels mode has nothing to plot.
        src2 = tmp_path / "h.jsonl"
        src2.write_text(json.dumps({"algorithm": "15d_sparse",
                                    "overall_throughput": 1.0}) + "\n")
        assert charts.main([str(src2), "--kernels",
                            "-o", str(tmp_path / "out2")]) == 1


class TestKernelSweepCLI:
    def test_tiny_sweep_smoke(self, capsys):
        from distributed_sddmm_tpu.bench.kernels import run_kernel_benchmark

        recs = run_kernel_benchmark(
            log_m_values=[8], nnz_per_row_values=[4], r_values=[8],
            kernels=("xla",), trials=1,
        )
        assert len(recs) == 1
        assert recs[0]["sddmm_gflops"] > 0 and recs[0]["spmm_gflops"] > 0
        assert "GFLOP" in capsys.readouterr().out


def test_run_pod_dry_run(capsys):
    """The pod runner's wiring is validated without a pod: forwarded bench
    args must parse and the resolved initialize() kwargs print
    (`/root/reference/jobscript.sh` analog, SURVEY component #28)."""
    import importlib.util
    import pathlib

    spec = importlib.util.spec_from_file_location(
        "run_pod",
        pathlib.Path(__file__).resolve().parents[1] / "scripts" / "run_pod.py",
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)

    assert mod.main(["--dry-run", "er", "16", "32", "15d_fusion2", "128", "2"]) == 0
    out = capsys.readouterr().out
    assert "dry-run ok" in out

    import pytest

    with pytest.raises(SystemExit):
        mod.main(["--dry-run", "er", "not-an-int"])


class TestCompileCache:
    """utils.platform.enable_compile_cache: on the TPU, the directory
    JAX_COMPILATION_CACHE_DIR names (and no other), else
    <checkout>/artifacts/xla_cache; on any other backend, nothing."""

    @staticmethod
    def _enable_as_tpu(monkeypatch):
        import jax

        from distributed_sddmm_tpu.utils import platform

        saved = {k: jax.config.values[k] for k in (
            "jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")}
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        try:
            return platform.enable_compile_cache(), \
                jax.config.values["jax_compilation_cache_dir"]
        finally:
            for k, v in saved.items():
                jax.config.update(k, v)

    def test_env_dir_wins_on_tpu(self, monkeypatch, tmp_path):
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert self._enable_as_tpu(monkeypatch) == (str(tmp_path),) * 2

    def test_default_is_the_checkout_on_tpu(self, monkeypatch):
        import pathlib

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = str(pathlib.Path(__file__).resolve().parents[1]
                   / "artifacts" / "xla_cache")
        assert self._enable_as_tpu(monkeypatch) == (want, want)

    def test_off_the_tpu_nothing_is_set(self):
        import jax

        from distributed_sddmm_tpu.utils import platform

        before = jax.config.values["jax_compilation_cache_dir"]
        assert platform.enable_compile_cache() is None
        assert jax.config.values["jax_compilation_cache_dir"] == before


def _barrier_tree(kind: str):
    """An output pytree of a shape the timed paths wait on: a tuple of
    leaves, a dict beside a non-array leaf, or a leaf sharded over every
    device. Freshly dispatched, so the barrier has work to wait for."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    square = jax.jit(lambda x: (x @ x, x.sum(0).astype(jnp.int32)))
    x = jnp.full((256, 256), 0.5)
    if kind == "tuple":
        return square(x)
    if kind == "dict":
        return {"out": square(x)[0], "mid": x * 2.0, "label": "pair"}
    mesh = Mesh(jax.devices(), ("d",))
    y = jax.device_put(jnp.arange(8 * len(jax.devices()), dtype=jnp.float32),
                       NamedSharding(mesh, PartitionSpec("d")))
    return (jax.jit(lambda y: y * 3.0)(y),)


class TestForceFetch:
    """utils.platform.force_fetch — the execution barrier every timed
    region relies on: one wait over every array leaf."""

    @pytest.mark.parametrize("tree", ["tuple", "dict", "sharded"])
    def test_every_leaf_ready(self, tree):
        import jax

        from distributed_sddmm_tpu.utils.platform import force_fetch

        out = _barrier_tree(tree)
        assert force_fetch(out) is None
        leaves = [x for x in jax.tree.leaves(out) if isinstance(x, jax.Array)]
        assert leaves and all(x.is_ready() for x in leaves)

    @pytest.mark.parametrize("guard", ["transfer_guard_device_to_host",
                                       "transfer_guard"])
    @pytest.mark.parametrize("tree", ["tuple", "dict", "sharded"])
    def test_no_transfer(self, tree, guard):
        """No copy to the host and no transfer at all: the barrier only
        waits. (On the CPU a device-to-host copy aliases host memory and
        escapes its own guard; a per-leaf fetch still trips the guard on
        every direction, for the index it sends to the device.)"""
        import jax

        from distributed_sddmm_tpu.utils.platform import force_fetch

        out = _barrier_tree(tree)
        with getattr(jax, guard)("disallow"):
            force_fetch(out)

    def test_empty_and_non_array_leaves(self):
        import jax
        import jax.numpy as jnp

        from distributed_sddmm_tpu.utils.platform import force_fetch

        tree = (jnp.zeros((0, 5)), "label", None, 7)
        with jax.transfer_guard("disallow"):
            assert force_fetch(tree) is None

    def test_tracer_safe(self):
        import jax
        import jax.numpy as jnp

        from distributed_sddmm_tpu.utils.platform import force_fetch

        def f(x):
            force_fetch(x)  # must be a no-op under trace, not a crash
            return (x * 2).sum()

        assert float(jax.grad(f)(jnp.ones((3,)))[0]) == 2.0
