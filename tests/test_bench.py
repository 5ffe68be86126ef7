"""Benchmark harness + CLI tests (reference `benchmark_dist.cpp`,
`bench_erdos_renyi.cpp`, `bench_heatmap.cpp`, `scratch.cpp`)."""

import json

import pytest

from distributed_sddmm_tpu.bench.harness import (
    ALGORITHM_FACTORIES,
    benchmark_algorithm,
    make_algorithm,
)
from distributed_sddmm_tpu.utils.coo import HostCOO
from distributed_sddmm_tpu.utils.verify import verify_algorithms


@pytest.fixture(scope="module")
def small_s():
    return HostCOO.rmat(log_m=7, edge_factor=4, seed=3)


def test_factory_has_all_five_reference_configs():
    assert set(ALGORITHM_FACTORIES) == {
        "15d_fusion1",
        "15d_fusion2",
        "15d_sparse",
        "25d_dense_replicate",
        "25d_sparse_replicate",
    }


def test_factory_unknown_name(small_s):
    with pytest.raises(ValueError, match="unknown algorithm"):
        make_algorithm("nope", small_s, R=16, c=1)


@pytest.mark.parametrize("alg,c", [("15d_fusion2", 2), ("15d_sparse", 2),
                                   ("25d_dense_replicate", 2)])
def test_vanilla_record_schema(small_s, tmp_path, alg, c):
    out = tmp_path / "results.json"
    rec = benchmark_algorithm(
        small_s, alg, str(out), fused=True, R=16, c=c, trials=2, warmup=1
    )
    assert rec["overall_throughput"] > 0
    assert rec["elapsed"] > 0
    assert rec["alg_info"]["nnz"] == small_s.nnz
    assert rec["alg_info"]["p"] == 8
    assert rec["alg_info"]["c"] == c
    assert sum(rec["alg_info"]["nnz_procs"]) == small_s.nnz
    # strategies with a native fused program log "fusedSpMM"; those using
    # the base chained implementation log the two constituent ops.
    assert set(rec["perf_stats"]) & {"fusedSpMM", "sddmmA"}
    # one JSON line appended
    lines = out.read_text().strip().splitlines()
    assert len(lines) == 1 and json.loads(lines[0])["algorithm"] == alg


def test_vanilla_unfused(small_s):
    rec = benchmark_algorithm(
        small_s, "15d_fusion1", None, fused=False, R=16, c=1, trials=1
    )
    assert "sddmmA" in rec["perf_stats"] and "spmmA" in rec["perf_stats"]


def test_als_app(small_s):
    rec = benchmark_algorithm(
        small_s, "15d_fusion2", None, fused=True, R=16, c=1,
        app="als", trials=1, warmup=0,
    )
    assert rec["als_residual"] >= 0


def test_gat_app(small_s):
    rec = benchmark_algorithm(
        small_s, "15d_fusion2", None, fused=True, R=8, c=1,
        app="gat", trials=1, warmup=0,
    )
    assert rec["gat_heads"] == [4, 4, 6]


def test_bad_app(small_s):
    with pytest.raises(ValueError, match="unknown app"):
        benchmark_algorithm(small_s, "15d_fusion2", None, True, 16, 1, app="wat")


def test_verify_driver_all_algorithms():
    # c=2, R=16: every algorithm is constructible on p=8 (p/c=4 | R etc.)
    assert verify_algorithms(log_m=6, edge_factor=4, R=16, c=2, verbose=False)


def test_cli_er_and_heatmap(tmp_path, capsys):
    from distributed_sddmm_tpu.bench.cli import main

    out = tmp_path / "er.json"
    assert main(["er", "6", "4", "15d_fusion2", "16", "1",
                 "--trials", "1", "--kernel", "xla", "-o", str(out)]) == 0
    assert json.loads(out.read_text().splitlines()[0])["overall_throughput"] > 0

    assert main(["heatmap", "6", "4", "1", "--alg", "15d_fusion2",
                 "--r-values", "8", "16", "--trials", "1", "--kernel", "xla"]) == 0
    printed = [json.loads(l) for l in capsys.readouterr().out.strip().splitlines()]
    assert {p["R"] for p in printed if "R" in p} == {8, 16}


def test_cli_permute_roundtrip(tmp_path):
    from distributed_sddmm_tpu.bench.cli import main

    S = HostCOO.rmat(log_m=5, edge_factor=4, seed=1)
    src = tmp_path / "m.mtx"
    S.save_mtx(str(src))
    assert main(["permute", str(src), "--seed", "7"]) == 0
    P = HostCOO.load_mtx(str(tmp_path / "m-permuted.mtx"))
    assert P.nnz == S.nnz and P.M == S.M
    # permutation preserves the value multiset
    import numpy as np

    assert np.allclose(sorted(P.vals), sorted(S.vals))


def test_cli_verify(capsys):
    from distributed_sddmm_tpu.bench.cli import main

    assert main(["verify", "--log-m", "6", "--edge-factor", "4",
                 "--R", "16", "--c", "2"]) == 0
    assert "OK" in capsys.readouterr().out


class TestHeadlineOrchestrator:
    """bench.py measures the chip or nothing, and its parent process
    leaves the chip to the worker."""

    ROOT = __import__("pathlib").Path(__file__).resolve().parents[1]

    def test_exits_nonzero_without_tpu(self):
        import subprocess
        import sys

        proc = subprocess.run(
            [sys.executable, str(self.ROOT / "bench.py")],
            capture_output=True, text=True, timeout=300,
            env={**__import__("os").environ, "JAX_PLATFORMS": "cpu"},
        )
        assert proc.returncode != 0
        assert proc.stdout.strip() == ""  # no record, live or banked
        assert "no attempt produced a TPU record" in proc.stderr

    def test_orchestrator_never_imports_jax(self):
        import subprocess
        import sys

        code = (
            "import importlib.util, sys\n"
            f"spec = importlib.util.spec_from_file_location('b', "
            f"{str(self.ROOT / 'bench.py')!r})\n"
            "m = importlib.util.module_from_spec(spec)\n"
            "spec.loader.exec_module(m)\n"
            "m._knob_env_defaults(); m._best_measured_env()\n"
            "assert 'jax' not in sys.modules, 'orchestrator imported jax'\n"
        )
        subprocess.run([sys.executable, "-c", code], check=True, timeout=60)


class TestBestMeasuredEnv:
    """bench.py steers the headline measurement from KERNELS_TPU.jsonl; the
    selection must pick the fastest matching Pallas record and tolerate
    junk/missing files."""

    def _bench(self):
        import importlib.util
        import pathlib

        root = pathlib.Path(__file__).resolve().parents[1]
        spec = importlib.util.spec_from_file_location("bench_mod", root / "bench.py")
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def test_picks_fastest_matching_record(self, tmp_path, monkeypatch):
        bench = self._bench()
        recs = [
            {"kernel": "xla", "logM": 16, "npr": 32, "R": 128,
             "fused_pair_gflops": 999.0},  # wrong kernel — ignored
            {"kernel": "pallas-bf16", "logM": 14, "npr": 32, "R": 128,
             "bm": 512, "bn": 512, "group": 8,
             "fused_pair_gflops": 500.0},  # wrong grid point — ignored
            {"kernel": "pallas-bf16", "logM": 16, "npr": 32, "R": 128,
             "bm": 512, "bn": 512, "group": 1,
             "fused_pair_gflops": 60.0},
            {"kernel": "pallas-bf16", "logM": 16, "npr": 32, "R": 128,
             "bm": 256, "bn": 512, "group": 4, "scatter_form": "nt",
             "chunk": 256, "fused_pair_gflops": 90.0},
            "not json at all",
        ]
        p = tmp_path / "KERNELS_TPU.jsonl"
        p.write_text(
            "\n".join(r if isinstance(r, str) else json.dumps(r) for r in recs)
        )
        # _best_measured_env resolves the JSONL next to bench.__file__ at
        # call time; repoint only the module, never the shared os.path.
        monkeypatch.setattr(bench, "__file__", str(tmp_path / "bench.py"))
        for var in ("BENCH_LOG_M", "BENCH_NNZ_PER_ROW", "BENCH_R"):
            monkeypatch.delenv(var, raising=False)
        env = bench._best_measured_env()
        assert env == {
            "DSDDMM_BLOCK_ROWS": "256",
            "DSDDMM_BLOCK_COLS": "512",
            "DSDDMM_CHUNK_GROUP": "4",
            "DSDDMM_SCATTER_FORM": "nt",
            "DSDDMM_CHUNK": "256",
            "DSDDMM_BATCH_STEP": "0",
        }

    def test_missing_file_and_no_match(self, tmp_path, monkeypatch):
        bench = self._bench()
        monkeypatch.setattr(bench, "__file__", str(tmp_path / "bench.py"))
        for var in ("BENCH_LOG_M", "BENCH_NNZ_PER_ROW", "BENCH_R"):
            monkeypatch.delenv(var, raising=False)
        assert bench._best_measured_env() is None  # no file
        (tmp_path / "KERNELS_TPU.jsonl").write_text(
            json.dumps({"kernel": "pallas-bf16", "logM": 11, "npr": 2,
                        "R": 8, "bm": 512, "bn": 512,
                        "fused_pair_gflops": 5.0}) + "\n"
        )
        assert bench._best_measured_env() is None  # no matching grid point
