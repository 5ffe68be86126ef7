"""Auto plan selection: validity, oracle correctness, degradation.

The acceptance bar: on the 8-device CPU mesh, ``algorithm="auto"`` must
return a *valid* plan across each of the five algorithm configs' home
turf (the paper heatmap's regimes), the planned strategy's output must
still match the scipy oracle, and a backend whose measurements time out
must degrade to cost-model ranking — never hang, never raise.
"""

import numpy as np
import pytest

from distributed_sddmm_tpu.autotune import Problem, get_plan
from distributed_sddmm_tpu.autotune.cache import PlanCache
from distributed_sddmm_tpu.autotune.candidates import (
    Candidate, enumerate_candidates, hbm_guard, legal_c_values,
    rank_candidates,
)
from distributed_sddmm_tpu.autotune.measure import MeasureTimeout, measure_candidates
from distributed_sddmm_tpu.bench.harness import ALGORITHM_FACTORIES
from distributed_sddmm_tpu.utils.coo import HostCOO
from distributed_sddmm_tpu.utils.verify import (
    fingerprint_algorithm, oracle_fingerprints,
)

# One problem per algorithm config's home turf (paper heatmap regimes,
# scaled to test size): dense-shift fusions at moderate density/R,
# sparse-shift where R is large relative to density, 2.5D where the
# square grid's divisibility holds and replication pays.
HOME_TURF = [
    ("15d_fusion2", dict(log_m=7, edge_factor=8, R=16)),
    ("15d_fusion1", dict(log_m=7, edge_factor=16, R=8)),
    ("15d_sparse", dict(log_m=7, edge_factor=4, R=64)),
    ("25d_dense_replicate", dict(log_m=6, edge_factor=8, R=32)),
    ("25d_sparse_replicate", dict(log_m=6, edge_factor=32, R=32)),
]


@pytest.mark.parametrize("turf,cfg", HOME_TURF, ids=[t for t, _ in HOME_TURF])
def test_auto_plan_valid_and_oracle_correct(turf, cfg, tmp_path):
    S = HostCOO.rmat(log_m=cfg["log_m"], edge_factor=cfg["edge_factor"], seed=0)
    prob = Problem.from_coo(S, cfg["R"])
    plan = get_plan(prob, mode="model", cache=PlanCache(tmp_path))

    # Valid: a real algorithm name with a legal replication factor.
    assert plan.algorithm in ALGORITHM_FACTORIES
    assert plan.c in legal_c_values(plan.algorithm, 8, cfg["R"])

    # Constructible AND correct: every op fingerprint matches the oracle.
    alg = plan.instantiate(S, R=cfg["R"])
    got = fingerprint_algorithm(alg, S)
    want = oracle_fingerprints(S, cfg["R"])
    for op, v in want.items():
        assert np.isclose(got[op], v, rtol=1e-4), (turf, op, got[op], v)


def test_all_five_configs_enumerable_on_8dev_mesh():
    """Every algorithm config appears among the candidates of a problem
    whose R satisfies all divisibility constraints (R=32: 8|32 for
    sparse-shift at c=1, sqrt(p/c)=2 | 32, 2*2 | 32)."""
    prob = Problem(M=256, N=256, nnz=2048, R=32)
    algs = {cand.algorithm for cand in enumerate_candidates(prob, p=8)}
    assert algs == set(ALGORITHM_FACTORIES)


def test_legal_c_mirrors_constructor_constraints():
    assert legal_c_values("15d_fusion2", 8, 32) == [1, 2, 4, 8]
    assert legal_c_values("15d_sparse", 8, 32) == [1, 2, 4, 8]
    assert legal_c_values("15d_sparse", 8, 12) == [2, 4, 8]  # needs (p/c)|R
    assert legal_c_values("25d_dense_replicate", 8, 32) == [2, 8]
    assert legal_c_values("25d_sparse_replicate", 8, 32) == [2, 8]
    assert legal_c_values("25d_sparse_replicate", 8, 8) == [2, 8]
    assert legal_c_values("25d_sparse_replicate", 8, 4) == [2]
    assert legal_c_values("25d_sparse_replicate", 8, 2) == []


def test_hbm_guard_routes_heavy_corner_to_chunked_kernel():
    """The reference grid's OOM corner (logM=16, nnz/row=128, R=512,
    single device): un-chunked XLA would gather ~17 GB; the guard must
    rewrite to a chunked candidate, not emit the OOM and not prune."""
    M = 1 << 16
    prob = Problem(M=M, N=M, nnz=M * 128, R=512)
    cand = hbm_guard(prob, Candidate("15d_fusion2", c=1), p=1)
    assert cand is not None
    assert cand.gather_budget is not None
    assert cand.gather_budget * 4 < 12 * (1 << 30)
    # A small problem on the same path stays un-chunked.
    small = Problem(M=256, N=256, nnz=2048, R=16)
    assert hbm_guard(small, Candidate("15d_fusion2", c=1), p=1).gather_budget is None


def test_enumeration_never_emits_oom_xla_candidate():
    M = 1 << 16
    prob = Problem(M=M, N=M, nnz=M * 128, R=512)
    for cand in enumerate_candidates(prob, p=1):
        if cand.kernel == "xla":
            assert cand.gather_budget is not None, cand


def test_rank_prefers_cheaper_communication():
    """At c=1 on 8 devices the fused single-pass dense shift must not
    rank below the two-pass variant of itself (same volume + extra
    pass)."""
    prob = Problem(M=4096, N=4096, nnz=4096 * 32, R=128)
    cands = [Candidate("15d_fusion2", 1), Candidate("15d_fusion1", 1)]
    ranked = rank_candidates(prob, cands, p=8)
    assert ranked[0][0].algorithm == "15d_fusion2"


def test_measure_timeout_degrades_to_model_ranking(tmp_path):
    """Failing-backend simulation: every trial times out; selection falls
    back to the cost model instead of raising or hanging, and the backoff
    path was exercised."""
    S = HostCOO.rmat(log_m=6, edge_factor=4, seed=0)
    prob = Problem.from_coo(S, 16)
    attempts = []

    def timing_out(S_, problem, cand, trials, warmup):
        attempts.append(cand)
        raise MeasureTimeout("simulated 600s backend hang")

    plan = get_plan(
        prob, S=S, mode="measure", cache=PlanCache(tmp_path),
        trial_fn=timing_out, top_k=2, retries=1, backoff_s=0.0,
    )
    assert plan.source in ("model", "seed")
    assert plan.algorithm in ALGORITHM_FACTORIES
    # Each shortlisted candidate got its retry before the fallback.
    assert len(attempts) == 2 * 2


def test_measured_winner_beats_model_ranking(tmp_path):
    """When trials succeed, the measured-fastest candidate takes the plan
    even if the model ranked it lower."""
    S = HostCOO.rmat(log_m=6, edge_factor=4, seed=0)
    prob = Problem.from_coo(S, 16)

    def rigged(S_, problem, cand, trials, warmup):
        g = 100.0 if cand.algorithm == "15d_sparse" else 1.0
        return {"overall_throughput": g}

    plan = get_plan(
        prob, S=S, mode="measure", cache=PlanCache(tmp_path),
        trial_fn=rigged, top_k=64, backoff_s=0.0,
    )
    assert plan.source == "measured"
    assert plan.algorithm == "15d_sparse"
    assert plan.measured_gflops == 100.0


def test_block_knobs_rebind_module_defaults():
    """Pallas block configs apply by rebinding ops.blocked's module
    attributes — the env vars were snapshotted at import, so env mutation
    would be a silent no-op (the geometry would never vary)."""
    from distributed_sddmm_tpu.autotune.measure import block_knobs
    from distributed_sddmm_tpu.ops import blocked

    before = (blocked.DEFAULT_BLOCK_ROWS, blocked.DEFAULT_BLOCK_COLS)
    with block_knobs(Candidate("15d_fusion2", 1, kernel="pallas", block=(256, 128))):
        assert (blocked.DEFAULT_BLOCK_ROWS, blocked.DEFAULT_BLOCK_COLS) == (256, 128)
    assert (blocked.DEFAULT_BLOCK_ROWS, blocked.DEFAULT_BLOCK_COLS) == before
    # Non-pallas candidates touch nothing.
    with block_knobs(Candidate("15d_fusion2", 1)):
        assert (blocked.DEFAULT_BLOCK_ROWS, blocked.DEFAULT_BLOCK_COLS) == before


def test_kernel_only_seed_does_not_fabricate_algorithm():
    """A KERNELS_TPU.jsonl kernel-family match without a winner-record
    match must NOT seed a candidate (it would override the cost model's
    algorithm/c with invented defaults)."""
    from distributed_sddmm_tpu.autotune.plan import _seed_candidate
    from distributed_sddmm_tpu.autotune.cache import seed_kernel_family

    # The headline grid point exists in KERNELS_TPU.jsonl...
    prob = Problem(M=1 << 16, N=1 << 16, nnz=(1 << 16) * 32, R=128)
    assert seed_kernel_family(prob, "tpu") == "pallas"
    # ...but with no cpu_mesh winner record for this shape, no seed.
    assert _seed_candidate(prob, p=8, backend="tpu",
                           kernels=("pallas", "xla")) is None


def test_measure_candidates_retry_backoff_sequence():
    """Backoff doubles per attempt and stops at success; jitter=0 keeps
    the schedule exact."""
    sleeps = []
    calls = {"n": 0}

    def flaky(S_, problem, cand, trials, warmup):
        calls["n"] += 1
        if calls["n"] < 3:
            raise MeasureTimeout("flaky")
        return {"overall_throughput": 5.0}

    out = measure_candidates(
        None, Problem(M=64, N=64, nnz=256, R=8),
        [Candidate("15d_fusion2", 1)],
        retries=2, backoff_s=1.5, jitter=0.0, trial_fn=flaky,
        sleep=sleeps.append,
    )
    assert len(out) == 1
    assert sleeps == [1.5, 3.0]


def test_measure_candidates_backoff_jitter_and_elapsed_cap():
    """Default backoff carries jitter (desynchronizes workers that timed
    out together: sleeps land in (base, base*(1+j)], never exactly base);
    the max-elapsed cap stops retrying a dead backend early."""
    import itertools
    import random

    sleeps = []

    def always_out(S_, problem, cand, trials, warmup):
        raise MeasureTimeout("dead backend")

    measure_candidates(
        None, Problem(M=64, N=64, nnz=256, R=8),
        [Candidate("15d_fusion2", 1)],
        retries=3, backoff_s=2.0, jitter=0.5, rng=random.Random(11),
        trial_fn=always_out, sleep=sleeps.append,
    )
    assert len(sleeps) == 3
    for i, s in enumerate(sleeps):
        base = 2.0 * 2 ** i
        assert base < s <= base * 1.5, (i, s)

    # Elapsed cap: a fake clock advancing 100s per attempt blows a 150s
    # budget after the first retry — the rest of the schedule is skipped.
    sleeps2 = []
    clock = itertools.count(0, 100)
    measure_candidates(
        None, Problem(M=64, N=64, nnz=256, R=8),
        [Candidate("15d_fusion2", 1)],
        retries=5, backoff_s=1.0, jitter=0.0, max_elapsed_s=150.0,
        trial_fn=always_out, sleep=sleeps2.append,
        monotonic=lambda: float(next(clock)),
    )
    assert len(sleeps2) < 5


def test_cli_auto_runs_end_to_end(tmp_path, monkeypatch, capsys):
    """`bench ... --algorithm auto` resolves a plan and produces a record
    on the 8-device CPU mesh."""
    import json

    from distributed_sddmm_tpu.bench import cli

    monkeypatch.setenv("DSDDMM_PLAN_CACHE", str(tmp_path))
    out = tmp_path / "records.jsonl"
    rc = cli.main(
        ["er", "6", "4", "auto", "16", "1", "--trials", "1",
         "--kernel", "xla", "--plan-mode", "model", "-o", str(out)]
    )
    assert rc == 0
    line = capsys.readouterr().out.strip().splitlines()[-1]
    rec = json.loads(line)
    assert rec["algorithm"] in ALGORITHM_FACTORIES
    # The full (unrounded) record, not the stdout line: its GFLOPs field
    # rounds to 3 decimals, and one timed ~30ms trial of this toy problem
    # rounds to 0.0 whenever the 1-core CI box is busy — a scheduler
    # coin flip, not a signal about the auto path.
    full = json.loads(out.read_text().splitlines()[-1])
    assert full["overall_throughput"] > 0
    assert full["plan"]["algorithm"] == rec["algorithm"]


def test_als_through_plan_routes_onto_program_path():
    """The round-5 gap: apps never took the jit-chained fused_program
    path. Invoked through a plan that selects the dense-shift fusion, the
    CG loop must dispatch ONE compiled program per CG step (cgStep
    counters), not one fusedSpMM per inner call."""
    from distributed_sddmm_tpu.autotune.plan import Plan
    from distributed_sddmm_tpu.models.als import DistributedALS

    S = HostCOO.rmat(log_m=6, edge_factor=8, seed=0)
    plan = Plan(algorithm="15d_fusion2", c=2, kernel="xla")
    als = DistributedALS.from_plan(S, R=16, plan=plan)
    assert als._use_programs  # the plan route landed on the program path
    als.initialize_embeddings()
    als.run_cg(1, cg_iters=4)
    counts = als.d_ops.call_count
    assert counts["cgStep"] == 2 * 4  # both half-steps, 4 iters each
    # The inner loop must NOT have gone through per-call dispatch: the
    # only fusedSpMM calls are the per-half-step initial Gram products.
    assert counts["fusedSpMM"] <= 2
    assert als.compute_residual() < 1.0


def test_als_auto_plan_still_correct(tmp_path, monkeypatch):
    """Fully-auto plan request (no pinned plan): whatever the model picks
    must drive ALS to a small residual."""
    from distributed_sddmm_tpu.models.als import DistributedALS

    monkeypatch.setenv("DSDDMM_PLAN_CACHE", str(tmp_path))
    S = HostCOO.rmat(log_m=6, edge_factor=8, seed=0)
    als = DistributedALS.from_plan(S, R=16)
    assert als.plan.algorithm in ALGORITHM_FACTORIES
    als.initialize_embeddings()
    als.run_cg(2, cg_iters=5)
    assert als.compute_residual() < 0.5


def test_gat_through_plan_routes_onto_program_path():
    from distributed_sddmm_tpu.autotune.plan import Plan
    from distributed_sddmm_tpu.models.gat import GAT, GATLayer

    S = HostCOO.rmat(log_m=6, edge_factor=8, seed=0)
    layers = [GATLayer(16, 16, 2), GATLayer(32, 16, 2)]
    plan = Plan(algorithm="15d_fusion2", c=2, kernel="xla")
    gat = GAT.from_plan(S, layers, plan=plan)
    assert gat._use_programs
    gat.forward()
    counts = gat.d_ops.call_count
    assert counts["gatLayer"] == len(layers)  # ONE program per layer
    assert counts.get("sddmmA", 0) == 0 and counts.get("spmmA", 0) == 0
