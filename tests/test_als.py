import numpy as np
import pytest

from distributed_sddmm_tpu.common import MatMode
from distributed_sddmm_tpu.models.als import DistributedALS
from distributed_sddmm_tpu.parallel.cannon_dense_25d import CannonDense25D
from distributed_sddmm_tpu.parallel.cannon_sparse_25d import CannonSparse25D
from distributed_sddmm_tpu.parallel.dense_shift_15d import DenseShift15D
from distributed_sddmm_tpu.parallel.sparse_shift_15d import SparseShift15D
from distributed_sddmm_tpu.utils.coo import HostCOO


def _problem(M=48, N=32, seed=0):
    return HostCOO.erdos_renyi(M, N, 5, seed=seed)


STRATEGIES = [
    ("15d_dense_f2_c2", lambda S: DenseShift15D(S, R=8, c=2, fusion_approach=2)),
    ("15d_dense_f1_c1", lambda S: DenseShift15D(S, R=8, c=1, fusion_approach=1)),
    ("15d_sparse_c2", lambda S: SparseShift15D(S, R=8, c=2)),
    ("25d_dense_c2", lambda S: CannonDense25D(S, R=8, c=2)),
    ("25d_sparse_c2", lambda S: CannonSparse25D(S, R=8, c=2)),
]


@pytest.mark.parametrize("name,mk", STRATEGIES)
def test_als_residual_decreases(name, mk):
    """End-to-end numeric sanity (reference protocol: ground truth comes
    from an SDDMM of known factors, so CG must drive the residual down;
    `als_conjugate_gradients.cpp:157-184,207-219`)."""
    S = _problem()
    als = DistributedALS(mk(S), seed=0)
    als.initialize_embeddings()
    r0 = als.compute_residual()
    als.run_cg(1, cg_iters=5)
    r1 = als.compute_residual()
    als.run_cg(1, cg_iters=5)
    r2 = als.compute_residual()
    assert r1 < r0 * 0.5, (r0, r1, r2)
    assert r2 < r1 * 1.01, (r0, r1, r2)


def test_als_converges_close_to_zero():
    S = _problem()
    als = DistributedALS(DenseShift15D(S, R=8, c=2), seed=1)
    als.initialize_embeddings()
    als.run_cg(4, cg_iters=10)
    r = als.compute_residual()
    assert r < 1e-3 * als.d_ops.S_tiles.nnz ** 0.5 or r < 1e-2


def test_als_real_ground_truth_values():
    """artificial_groundtruth=False path with user-provided observations."""
    S = _problem()
    rng = np.random.default_rng(2)
    obs = rng.standard_normal(S.nnz) * 0.01
    d_ops = DenseShift15D(S, R=8, c=1)
    als = DistributedALS(
        d_ops,
        artificial_groundtruth=False,
        ground_truth_vals=obs,
        ground_truth_vals_transpose=S.with_values(obs).transpose().vals,
    )
    als.initialize_embeddings()
    r0 = als.compute_residual()
    als.run_cg(1, cg_iters=8)
    assert als.compute_residual() < r0


def test_als_requires_ground_truth_vals():
    S = _problem()
    with pytest.raises(ValueError):
        DistributedALS(DenseShift15D(S, R=8, c=1), artificial_groundtruth=False)
    # missing transpose values -> clear error at the B half-step
    rng = np.random.default_rng(3)
    als = DistributedALS(
        DenseShift15D(S, R=8, c=1),
        artificial_groundtruth=False,
        ground_truth_vals=rng.standard_normal(S.nnz),
    )
    als.initialize_embeddings()
    with pytest.raises(ValueError, match="transposed ground-truth"):
        als.cg_optimizer(MatMode.B, 1)


def _executions(fn, logdir) -> int:
    """Device programs that ``fn()`` runs on the CPU backend: the
    runtime's ``PjRtCpuExecutable::Execute`` events in a profiler
    capture (host tracer at its most detailed, Python tracer off)."""
    import jax
    from jax.profiler import ProfileData

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 3
    jax.profiler.start_trace(str(logdir), profiler_options=opts)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    (path,) = logdir.glob("**/*.xplane.pb")
    return sum(
        ev.name == "PjRtCpuExecutable::Execute"
        for plane in ProfileData.from_file(str(path)).planes
        for line in plane.lines
        for ev in line.events
    )


def test_als_step_runs_only_its_own_programs(tmp_path, monkeypatch):
    """One alternating step at the benchmark ALS app's tiny size (R-mat
    logM 8, 8 a row, R 16, 3 CG iterations, one device) runs the same
    device programs whether the barrier after each dispatch waits or
    not: the barrier adds no program of its own, however many output
    leaves it waits on. It is still one barrier a dispatch: an SpMM, the
    initial fused pair and one ``cgStep`` an iteration, each half-step."""
    import jax

    from distributed_sddmm_tpu.bench.harness import make_algorithm
    from distributed_sddmm_tpu.ops.kernels import get_kernel
    from distributed_sddmm_tpu.utils import platform

    cg_iters = 3
    S = HostCOO.rmat(log_m=8, edge_factor=8, seed=0)
    alg = make_algorithm("15d_fusion2", S, 16, 1, kernel=get_kernel("auto"),
                         devices=jax.devices()[:1])
    als = DistributedALS(alg, seed=0)
    als.initialize_embeddings()

    def step():
        als.run_cg(1, cg_iters=cg_iters)
        jax.block_until_ready((als.A, als.B))

    step()  # compiles every program the step runs
    barrier, waits = platform.force_fetch, []

    def counted(out):
        waits.append(out)
        barrier(out)

    monkeypatch.setattr(platform, "force_fetch", counted)
    with_barrier = _executions(step, tmp_path / "barrier")
    monkeypatch.setattr(platform, "force_fetch", lambda out: None)
    without = _executions(step, tmp_path / "none")
    assert len(waits) == 2 * (2 + cg_iters)
    assert with_barrier == without > 0
