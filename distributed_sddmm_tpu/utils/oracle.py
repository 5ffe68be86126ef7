"""Numpy/scipy reference implementations used as test oracles.

The reference has no oracle — correctness was established by comparing
"fingerprints" (allreduced squared norms) across algorithm variants
(`/root/reference/scratch.cpp:26-76`). We keep that protocol (see
``fingerprint``) but additionally check full results against these
single-process dense/scipy references, which the reference never had
(SURVEY.md section 4).
"""

from __future__ import annotations

import numpy as np

from distributed_sddmm_tpu.utils.coo import HostCOO


#: Nonzeros per slice in the oracles below. Slicing bounds the
#: ``[slice, R]`` row gathers (a whole-matrix gather of an 8 M-nonzero
#: matrix at R=128 is 8.6 GB in float64) and keeps every element's
#: arithmetic and accumulation order unchanged.
_SLICE = 1 << 18


def _slices(nnz: int):
    return (slice(lo, lo + _SLICE) for lo in range(0, nnz, _SLICE))


def sddmm(S: HostCOO, A: np.ndarray, B: np.ndarray) -> np.ndarray:
    """``out_vals[k] = S.vals[k] * <A[S.rows[k], :], B[S.cols[k], :]>``.

    Matches the reference semantics: dot products accumulate into the CSR
    values, then are multiplied elementwise by the input values
    (`sparse_kernels.cpp:44-55`, `15D_dense_shift.hpp:364-368`).
    """
    dots = np.empty(S.nnz, dtype=np.result_type(A, B))
    for sl in _slices(S.nnz):
        dots[sl] = np.einsum("kr,kr->k", A[S.rows[sl]], B[S.cols[sl]])
    return S.vals * dots


def spmm_a(S: HostCOO, B: np.ndarray, A_in: np.ndarray | None = None) -> np.ndarray:
    """``A += S @ B`` (accumulate semantics, beta=1; `sparse_kernels.cpp:94-121`)."""
    out = np.zeros((S.M, B.shape[1])) if A_in is None else A_in.copy()
    for sl in _slices(S.nnz):
        np.add.at(out, S.rows[sl], S.vals[sl, None] * B[S.cols[sl]])
    return out


def spmm_b(S: HostCOO, A: np.ndarray, B_in: np.ndarray | None = None) -> np.ndarray:
    """``B += S^T @ A``."""
    out = np.zeros((S.N, A.shape[1])) if B_in is None else B_in.copy()
    for sl in _slices(S.nnz):
        np.add.at(out, S.cols[sl], S.vals[sl, None] * A[S.rows[sl]])
    return out


def fused_spmm_a(S: HostCOO, A: np.ndarray, B: np.ndarray) -> np.ndarray:
    """SDDMM -> SpMM-A fusion: ``A_new = (S_vals * (A B^T)|_S) @ B``.

    Reference ``Distributed_Sparse::fusedSpMM`` with mode=Amat
    (`distributed_sparse.h:296-312`).
    """
    mid = sddmm(S, A, B)
    return spmm_a(S.with_values(mid), B)


def fused_spmm_b(S: HostCOO, A: np.ndarray, B: np.ndarray) -> np.ndarray:
    """SDDMM-B -> SpMM-B fusion: ``B_new = (S_vals * (A B^T)|_S)^T @ A``."""
    mid = sddmm(S, A, B)
    return spmm_b(S.with_values(mid), A)


def masked_softmax(
    S: HostCOO, logits: np.ndarray, sink: np.ndarray | None = None
) -> np.ndarray:
    """Row-wise masked softmax over the sparse logit values (float64).

    Entries with ``S.vals == 0`` are masked out (the same ``gate != 0``
    indicator the device kernels use); a row whose entries are all
    masked — or that has no entries at all — gets exactly-zero weights,
    never NaN. The max subtraction matches the device kernels' stable
    formulation so f32 comparisons are apples-to-apples. ``sink``
    (``[M]``): a per-row logit that joins the row's max and denominator
    and carries no value.
    """
    from distributed_sddmm_tpu.ops.kernels import ATTN_NEG

    z = np.asarray(logits, dtype=np.float64)
    gate = S.vals != 0
    m = np.full(S.M, ATTN_NEG)
    np.maximum.at(m, S.rows[gate], z[gate])
    if sink is not None:
        m = np.maximum(m, sink)
    e = np.zeros_like(z)
    e[gate] = np.exp(z[gate] - m[S.rows[gate]])
    d = np.zeros(S.M)
    np.add.at(d, S.rows, e)
    if sink is not None:
        d += np.exp(sink - m)
    out = np.zeros_like(z)
    ok = gate & (d[S.rows] > 0)
    out[ok] = e[ok] / d[S.rows[ok]]
    return out


def fused_attention_a(
    S: HostCOO, A: np.ndarray, B: np.ndarray, V: np.ndarray | None = None,
    sink: np.ndarray | None = None,
) -> tuple[np.ndarray, np.ndarray]:
    """Block-sparse attention reference: SDDMM logits → row-wise masked
    softmax (with ``sink``, see :func:`masked_softmax`) → SpMM
    aggregation of ``V`` (``B`` by default), all float64. Returns
    ``(out [M, R_v], probs [nnz])`` in S's nonzero order."""
    probs = masked_softmax(S, sddmm(S, A, B), sink)
    return spmm_a(S.with_values(probs), B if V is None else V), probs


def dummy_dense(n_rows: int, R: int, dtype=np.float64) -> np.ndarray:
    """Deterministic fill ``value = row * R + col``.

    The reference's ``dummyInitialize`` (`distributed_sparse.h:322-346`):
    layout-independent, so every distribution must produce identical global
    results from it.
    """
    return (
        np.arange(n_rows, dtype=dtype)[:, None] * R + np.arange(R, dtype=dtype)[None, :]
    )


def fingerprint(x: np.ndarray) -> float:
    """Squared-norm fingerprint (`scratch.cpp:45-75`)."""
    x = np.asarray(x, dtype=np.float64)
    return float(np.sum(x * x))
