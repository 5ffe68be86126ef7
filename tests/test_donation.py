"""Buffer donation on the chained programs (PR 6 satellite): the CG-step
and GAT-layer programs must donate their carry buffers — pinned by
compiled-program inspection (``input_output_alias``), by bit-identical
results with donation on vs off, and by the automatic stand-down under
the resilience ladder's retry rung (a retry re-invokes the program with
buffers a donating first attempt already consumed)."""

import re

import jax.numpy as jnp
import numpy as np
import pytest

from distributed_sddmm_tpu.common import MatMode
from distributed_sddmm_tpu.models.als import DistributedALS, donation_enabled
from distributed_sddmm_tpu.models.gat import GAT, GATLayer
from distributed_sddmm_tpu.parallel.dense_shift_15d import DenseShift15D
from distributed_sddmm_tpu.utils.coo import HostCOO


@pytest.fixture(autouse=True)
def _donation_on(monkeypatch):
    monkeypatch.delenv("DSDDMM_DONATE", raising=False)
    monkeypatch.delenv("DSDDMM_FAULTS", raising=False)
    monkeypatch.delenv("DSDDMM_GUARDS", raising=False)


def _aliased_params(hlo: str) -> list[int]:
    """Parameter indices aliased to outputs in the compiled module
    header: ``input_output_alias={ {0}: (0, {}, may-alias), ... }``."""
    line = next(l for l in hlo.splitlines() if "input_output_alias" in l)
    blob = line.split("input_output_alias=", 1)[1]
    return sorted(int(m) for m in re.findall(r"\((\d+), \{\}", blob))


def test_cg_step_donates_all_four_carries():
    S = HostCOO.erdos_renyi(64, 48, 5, seed=2, values="normal")
    alg = DenseShift15D(S, R=8, c=1, fusion_approach=2)
    m = DistributedALS(alg, S_host=S)
    m.initialize_embeddings()
    assert donation_enabled()
    prog, ops = m._cg_iter_program(MatMode.A, m.ridge_lambda)
    X = m.A
    rsold = jnp.zeros(X.shape[:-1], jnp.float32)
    hlo = prog.lower(X, m.B, X, X, rsold, *ops).compile().as_text()
    # X (0), r (2), p (3), rsold (4) donate; `other` (1) and the tile
    # operands must NOT.
    assert _aliased_params(hlo) == [0, 2, 3, 4]


def test_gat_square_layer_donates_activation_carry():
    S = HostCOO.erdos_renyi(64, 64, 5, seed=2, values="normal")
    layers = [GATLayer(input_features=8, features_per_head=4, num_heads=2)]
    gat = GAT(layers, DenseShift15D(S, R=8, c=1, fusion_approach=2))
    prog, ops = gat._layer_program(0)
    X = gat.d_ops.dummy_initialize(MatMode.A)
    hlo = prog.lower(X, layers[0].weights, *ops).compile().as_text()
    assert _aliased_params(hlo) == [0]


def test_gat_nonsquare_layer_skips_donation():
    """Donation is shape-gated: a layer whose output width differs from
    its input's could never reuse the buffer — requesting donation would
    only earn an unusable-donation warning."""
    S = HostCOO.erdos_renyi(64, 64, 5, seed=2, values="normal")
    layers = [GATLayer(input_features=8, features_per_head=8, num_heads=2)]
    gat = GAT(layers, DenseShift15D(S, R=8, c=1, fusion_approach=2))
    prog, ops = gat._layer_program(0)
    gat.d_ops.set_r_value(layers[0].input_features)
    X = gat.d_ops.dummy_initialize(MatMode.A)
    hlo = prog.lower(X, layers[0].weights, *ops).compile().as_text()
    assert not any("input_output_alias" in l for l in hlo.splitlines()[:1]) \
        or _aliased_params(hlo) == []


def test_run_cg_bit_identical_with_donation_on_and_off(monkeypatch):
    S = HostCOO.erdos_renyi(64, 48, 5, seed=2, values="normal")

    def run():
        alg = DenseShift15D(S, R=8, c=1, fusion_approach=2)
        m = DistributedALS(alg, S_host=S)
        m.run_cg(3, cg_iters=5)
        return np.asarray(m.A), np.asarray(m.B)

    monkeypatch.setenv("DSDDMM_DONATE", "1")
    A1, B1 = run()
    monkeypatch.setenv("DSDDMM_DONATE", "0")
    A0, B0 = run()
    assert np.array_equal(A1, A0)
    assert np.array_equal(B1, B0)


def test_donated_half_step_preserves_live_factors():
    """The half-step's entry X aliases the committed factor attribute;
    donation must never consume THAT buffer (the damped-restart ladder
    re-reads it). Pinned by using self.A after a donating half-step."""
    S = HostCOO.erdos_renyi(64, 48, 5, seed=2, values="normal")
    alg = DenseShift15D(S, R=8, c=1, fusion_approach=2)
    m = DistributedALS(alg, S_host=S)
    m.initialize_embeddings()
    A_before = np.asarray(m.A)  # host copy for comparison
    X = m._cg_run(MatMode.A, cg_max_iter=3, lam=m.ridge_lambda)
    # self.A's buffer must still be alive and unchanged (the half-step
    # did NOT commit).
    assert np.array_equal(np.asarray(m.A), A_before)
    assert np.asarray(X).shape == A_before.shape


def test_donation_stands_down_under_fault_plans():
    from distributed_sddmm_tpu.resilience import (
        FaultPlan, FaultSpec, fault_plan,
    )

    assert donation_enabled()
    with fault_plan(FaultPlan(
        [FaultSpec(site="output:cgStep", kind="nan", at=(2,))]
    )):
        assert not donation_enabled()
        # And the retry rung actually works: the injected NaN heals
        # without a donated-buffer RuntimeError.
        S = HostCOO.erdos_renyi(48, 32, 5, seed=2, values="normal")
        alg = DenseShift15D(S, R=8, c=1, fusion_approach=2)
        m = DistributedALS(alg, S_host=S)
        m.run_cg(2, cg_iters=3)
        assert np.isfinite(np.asarray(m.A)).all()
    assert donation_enabled()


def test_donation_kill_switch(monkeypatch):
    monkeypatch.setenv("DSDDMM_DONATE", "0")
    assert not donation_enabled()
    S = HostCOO.erdos_renyi(48, 32, 5, seed=2, values="normal")
    alg = DenseShift15D(S, R=8, c=1, fusion_approach=2)
    m = DistributedALS(alg, S_host=S)
    m.initialize_embeddings()
    prog, ops = m._cg_iter_program(MatMode.A, m.ridge_lambda)
    X = m.A
    rsold = jnp.zeros(X.shape[:-1], jnp.float32)
    hlo = prog.lower(X, m.B, X, X, rsold, *ops).compile().as_text()
    header = hlo.splitlines()[0]
    assert "input_output_alias" not in header
