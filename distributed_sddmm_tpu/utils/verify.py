"""Fingerprint verification driver.

TPU-native counterpart of the reference's manual correctness driver
``scratch.cpp`` (`/root/reference/scratch.cpp:26-76` ``verify_operation``):
fill A/B deterministically with ``dummyInitialize`` semantics, run
sddmmA / spmmA / spmmB / fusedSpMM on every algorithm, and compare the
squared-norm fingerprints. Where the reference could only compare variants
against each other, we also compare against the scipy/numpy oracle — the
single source of truth the reference never had (SURVEY.md section 4).
"""

from __future__ import annotations

import numpy as np

from distributed_sddmm_tpu.common import KernelMode, MatMode
from distributed_sddmm_tpu.obs import log
from distributed_sddmm_tpu.utils import oracle
from distributed_sddmm_tpu.utils.coo import HostCOO


def fingerprint_algorithm(alg, S: HostCOO) -> dict[str, float]:
    """Run the verify protocol on one constructed algorithm; return the
    op -> fingerprint map (values in S's canonical nonzero order, dense
    outputs in global row order with padding stripped)."""
    A = alg.dummy_initialize(MatMode.A)
    B = alg.dummy_initialize(MatMode.B)
    s_ones = alg.like_s_values(1.0)
    st_ones = alg.like_st_values(1.0)

    out: dict[str, float] = {}

    A_s, B_s = alg.initial_shift(A, B, KernelMode.SDDMM_A)
    mid = alg.sddmm_a(A_s, B_s, s_ones)
    out["sddmmA"] = oracle.fingerprint(alg.gather_s_values(mid))

    # spmm accumulates into the passed output-role buffer, so the verify
    # protocol seeds it with zeros (the ALS computeRHS pattern,
    # `als_conjugate_gradients.cpp:192-205`).
    zero_a, B_s = alg.initial_shift(alg.like_a_matrix(0.0), B, KernelMode.SPMM_A)
    y = alg.spmm_a(zero_a, B_s, s_ones)
    y, _ = alg.de_shift(y, None, KernelMode.SPMM_A)
    out["spmmA"] = oracle.fingerprint(alg.host_a(y))

    A_s, zero_b = alg.initial_shift(A, alg.like_b_matrix(0.0), KernelMode.SPMM_B)
    yb = alg.spmm_b(A_s, zero_b, st_ones)
    _, yb = alg.de_shift(None, yb, KernelMode.SPMM_B)
    out["spmmB"] = oracle.fingerprint(alg.host_b(yb))

    out.update(fused_fingerprints(alg))
    return out


def fused_fingerprints(alg) -> dict[str, float]:
    """The fused SDDMM->SpMM pair's two fingerprints (``fusedSpMM`` and
    its SDDMM half ``fusedSpMM_mid``) on dummy-initialized operands —
    the part of the verify protocol the headline program runs."""
    A = alg.dummy_initialize(MatMode.A)
    B = alg.dummy_initialize(MatMode.B)
    A_s, B_s = alg.initial_shift(A, B, KernelMode.SDDMM_A)
    fz, fmid = alg.fused_spmm(A_s, B_s, alg.like_s_values(1.0), MatMode.A)
    fz, _ = alg.de_shift(fz, None, KernelMode.SPMM_A)
    return {
        "fusedSpMM": oracle.fingerprint(alg.host_a(fz)),
        "fusedSpMM_mid": oracle.fingerprint(alg.gather_s_values(fmid)),
    }


def oracle_fused_fingerprints(S: HostCOO, R: int) -> dict[str, float]:
    """:func:`fused_fingerprints` computed by the host oracle."""
    A = oracle.dummy_dense(S.M, R)
    B = oracle.dummy_dense(S.N, R)
    S1 = S.with_values(np.ones_like(S.vals))
    mid = oracle.sddmm(S1, A, B)
    return {
        "fusedSpMM": oracle.fingerprint(
            oracle.spmm_a(S1.with_values(mid), B)),
        "fusedSpMM_mid": oracle.fingerprint(mid),
    }


def oracle_fingerprints(S: HostCOO, R: int) -> dict[str, float]:
    """The same op set computed by the host oracle on dummy-initialized
    operands."""
    A = oracle.dummy_dense(S.M, R)
    B = oracle.dummy_dense(S.N, R)
    S1 = S.with_values(np.ones_like(S.vals))
    fused = oracle_fused_fingerprints(S, R)
    return {
        "sddmmA": fused["fusedSpMM_mid"],
        "spmmA": oracle.fingerprint(oracle.spmm_a(S1, B)),
        "spmmB": oracle.fingerprint(oracle.spmm_b(S1, A)),
        **fused,
    }


def verify_algorithms(
    log_m: int = 8,
    edge_factor: int = 8,
    R: int = 16,
    c: int = 1,
    alg_names=None,
    kernel=None,
    rtol: float = 1e-4,
    verbose: bool = False,
    S: HostCOO | None = None,
) -> bool:
    """Cross-check every named algorithm's fingerprints against the oracle.

    Returns True iff all constructible algorithms match within ``rtol``
    (dummyInitialize values grow as M*R, so float32 squared norms carry a
    relative, not absolute, tolerance). Algorithms whose divisibility
    constraints reject the configuration are skipped with a note, mirroring
    the reference where incompatible configs exit early.

    Pass ``S`` to verify against an explicit matrix instead of the default
    R-mat — the route the edge-case tests use (empty tile blocks,
    adversarially skewed patterns, sanitized ingests).
    """
    from distributed_sddmm_tpu.bench.harness import ALGORITHM_FACTORIES, make_algorithm

    if S is None:
        S = HostCOO.rmat(log_m=log_m, edge_factor=edge_factor, seed=0)
    want = oracle_fingerprints(S, R)
    names = alg_names or sorted(ALGORITHM_FACTORIES)

    all_ok = True
    for name in names:
        try:
            alg = make_algorithm(name, S, R, c, kernel=kernel)
        except ValueError as e:
            # Diagnostic, not table output — goes to the structured log.
            log.info("verify", f"skip {name}", reason=str(e))
            continue
        got = fingerprint_algorithm(alg, S)
        for op, v in want.items():
            ok = np.isclose(got[op], v, rtol=rtol)
            all_ok &= bool(ok)
            if verbose:
                flag = "OK " if ok else "FAIL"
                print(f"{flag} {name:22s} {op:14s} got={got[op]:.6e} want={v:.6e}")  # cli-output
    return all_ok
