"""AOT compile/serialize/load helpers (bench/aot.py) and the tune_blocks
setup functions they share with the offline compiler.

The real payoff path (serialize for a v5e topology, load onto the chip)
can only run on hardware — scripts/aot_load_probe.py owns that
answer. These tests pin everything testable off-chip: the round-trip
through serialize/deserialize on the CPU backend, the timing protocol's
shape, and that the step functions the offline compiler imports are the
same objects tune_blocks measures.
"""

import importlib.util
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_sddmm_tpu.bench import aot

ROOT = pathlib.Path(__file__).resolve().parents[1]


def _tune():
    spec = importlib.util.spec_from_file_location(
        "tune_blocks", ROOT / "scripts" / "tune_blocks.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def toy_step(state):
    x, w = state
    return (jnp.tanh(x @ w), w)


def test_compile_load_roundtrip_cpu(tmp_path):
    """serialize -> deserialize_and_load on the same backend reproduces the
    jitted chain exactly, for both trip counts."""
    dev = jax.devices("cpu")[0]
    rng = np.random.default_rng(0)
    state = (jnp.asarray(rng.standard_normal((64, 32)), jnp.float32),
             jnp.asarray(rng.standard_normal((32, 32)), jnp.float32))
    trials = 3
    times = aot.compile_chain_pair(toy_step, state, trials, dev,
                                   tmp_path, "toy")
    assert set(times) == {1, 1 + trials}
    loaded = aot.load_chain_pair(tmp_path, "toy", trials, dev)
    for n in aot.trip_counts(trials):
        out = loaded[n](state)
        ref = state
        for _ in range(n):
            ref = toy_step(ref)
        np.testing.assert_allclose(np.asarray(out[0]), np.asarray(ref[0]),
                                   rtol=1e-6)
    dt = aot.chain_time_loaded(loaded, state, trials)
    assert dt > 0


def test_load_missing_pair_raises(tmp_path):
    with pytest.raises(FileNotFoundError):
        aot.load_chain_pair(tmp_path, "absent", 3, jax.devices("cpu")[0])


def test_tune_blocks_setup_shapes():
    """build_inputs/build_blk/pallas_steps — the pieces the offline AOT
    compiler imports — agree on shapes, and the clamp path returns None."""
    tune = _tune()
    S, A, B, flops = tune.build_inputs(8, 4, 16)
    assert A.shape == (S.M, 16) and B.shape == (S.N, 16)
    assert flops == 2.0 * S.nnz * 16

    meta, blk, cvals = tune.build_blk(S, 128, 128, 1)
    assert blk is not None
    assert cvals.shape == (meta.n_chunks * tune.CHUNK,)

    meta2, blk2, cvals2 = tune.build_blk(S, 4096, 4096, 1)
    assert blk2 is None and cvals2 is None
    assert (meta2.bm, meta2.bn) != (4096, 4096)

    from distributed_sddmm_tpu.ops.pallas_kernels import PallasKernel

    kernp = PallasKernel(precision="f32", interpret=True)
    steps = tune.pallas_steps(kernp, blk, cvals, S, A)
    assert set(steps) == {"fused", "sddmm", "spmm"}
    out = steps["fused"]((B, cvals))
    assert out[0].shape == B.shape


def test_inject_program_roundtrip(tmp_path):
    """A strategy program serialized offline and injected back produces
    the same numerics as the jitted path, and shape-mismatched calls fall
    back to the jit instead of failing (the GAT case)."""
    import jax

    from distributed_sddmm_tpu.common import MatMode
    from distributed_sddmm_tpu.ops.kernels import XlaKernel
    from distributed_sddmm_tpu.parallel.dense_shift_15d import DenseShift15D
    from distributed_sddmm_tpu.utils.coo import HostCOO

    dev = jax.devices("cpu")[0]
    S = HostCOO.erdos_renyi(96, 80, 4, seed=5, values="normal")
    alg = DenseShift15D(S, R=16, c=1, fusion_approach=2, kernel=XlaKernel(),
                        devices=[dev])
    A = alg.dummy_initialize(MatMode.A)
    B = alg.dummy_initialize(MatMode.B)
    ones = alg.like_s_values(1.0)
    ref = np.asarray(alg.sddmm_a(A, B, ones))

    prog = alg._program("sddmm", use_st=False)
    args = (A, B, *alg._tile_args(alg.S_tiles, ones))

    def sds_like(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding)

    compiled = prog.lower(*(sds_like(x) for x in args)).compile()
    aot.save_executable(compiled, tmp_path, "sddmm_a", 0)
    loaded = aot.load_executable(tmp_path, "sddmm_a", 0, dev)

    alg2 = DenseShift15D(S, R=16, c=1, fusion_approach=2, kernel=XlaKernel(),
                        devices=[dev])
    alg2.inject_program("sddmm", False, loaded)
    got = np.asarray(alg2.sddmm_a(A, B, ones))
    np.testing.assert_allclose(got, ref, rtol=1e-6)

    # Shape mismatch (different R) must fall back to the jit, not raise.
    alg2.set_r_value(8)
    A8 = alg2.dummy_initialize(MatMode.A)
    B8 = alg2.dummy_initialize(MatMode.B)
    out8 = alg2.sddmm_a(A8, B8, ones)
    assert np.asarray(out8).shape == np.asarray(ones).shape


def test_chain_matches_chain_time_protocol(tmp_path):
    """aot._chain must mirror bench.kernels._chain_time's jitted fori_loop
    shape — a drift would make AOT timings incomparable to on-device ones."""
    from distributed_sddmm_tpu.bench.kernels import _chain_time

    dev = jax.devices("cpu")[0]
    rng = np.random.default_rng(1)
    state = (jnp.asarray(rng.standard_normal((32, 16)), jnp.float32),
             jnp.asarray(rng.standard_normal((16, 16)), jnp.float32))
    t_jit = _chain_time(toy_step, state, 2)
    aot.compile_chain_pair(toy_step, state, 2, dev, tmp_path, "toy")
    loaded = aot.load_chain_pair(tmp_path, "toy", 2, dev)
    t_aot = aot.chain_time_loaded(loaded, state, 2)
    # Same machine, same program: both must be positive; equality of the
    # computed VALUES is asserted via the roundtrip test above.
    assert t_jit > 0 and t_aot > 0
