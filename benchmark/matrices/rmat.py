"""The benchmark's own R-mat generator, seeded by ``--seed``.

A configuration's ``matrix`` with ``"generator": "rmat"`` gives
``log_m``, ``edge_factor``, ``initiator`` and ``values`` (``"ones"`` or
``"uniform"``, the latter in [0.5, 1.5) from the same stream after the
pattern).

A copy of the program's vectorised numpy R-mat path
(``distributed_sddmm_tpu/native.py::rmat_edges`` fallback, with
``HostCOO.rmat``'s dedup and Graph500 vertex permutation), kept here so
that the matrix a cell runs on cannot change when the program changes.
The reference draws its matrices with CombBLAS ``GenGraph500Data``
(`SpmatLocal.hpp:500-507`).

For the uniform initiator (0.25 each, the reference's) every level's row
and column bits are fair coins, so the recursion is a uniform draw of
each endpoint: that case draws the endpoints directly, with the same
distribution and a tenth of the time.
"""

from __future__ import annotations

import numpy as np


def edges(log_m: int, n_edges: int, initiator, rng) -> tuple:
    """``n_edges`` R-mat endpoints ``(rows, cols)`` over ``2**log_m``."""
    a, b, c, d = (float(x) for x in initiator)
    if not np.isclose(a + b + c + d, 1.0):
        raise ValueError(f"initiator {initiator} does not sum to 1")
    M = 1 << log_m
    if np.allclose((a, b, c, d), 0.25):
        return (rng.integers(0, M, n_edges, dtype=np.int64),
                rng.integers(0, M, n_edges, dtype=np.int64))
    rows = np.zeros(n_edges, dtype=np.int64)
    cols = np.zeros(n_edges, dtype=np.int64)
    top = b / (a + b) if a + b > 0 else 0.0
    bot = d / (c + d) if c + d > 0 else 0.0
    for _ in range(log_m):
        rbit = (rng.random(n_edges) >= a + b).astype(np.int64)
        cbit = (rng.random(n_edges) < np.where(rbit == 0, top, bot))
        rows = (rows << 1) | rbit
        cols = (cols << 1) | cbit.astype(np.int64)
    return rows, cols


def first_occurrences(rows: np.ndarray, cols: np.ndarray, n_cols: int):
    """Indices of the first occurrence of every (row, col), in order."""
    keys = rows * n_cols + cols
    srt = np.sort(keys)
    dup_keys = np.unique(srt[1:][srt[1:] == srt[:-1]])
    keep = np.ones(keys.size, dtype=bool)
    if dup_keys.size:
        at = np.flatnonzero(np.isin(keys, dup_keys))
        _, first = np.unique(keys[at], return_index=True)
        keep[at] = False
        keep[at[first]] = True
    return np.flatnonzero(keep)


def rmat(log_m: int, edge_factor: int, initiator, rng) -> dict:
    """A Graph500-style R-mat matrix as COO arrays: duplicates dropped
    (first kept) and vertex names permuted, one permutation for rows
    and columns
    (Graph500 ``PermEdges`` + ``RenameVertices``,
    `SpmatLocal.hpp:505-506`). Values are ones."""
    M = 1 << log_m
    rows, cols = edges(log_m, M * edge_factor, initiator, rng)
    keep = first_occurrences(rows, cols, M)
    perm = rng.permutation(M)
    rows, cols = perm[rows[keep]], perm[cols[keep]]
    return {"rows": rows, "cols": cols, "vals": np.ones(rows.size),
            "M": M, "N": M}


def make(spec: dict, rng) -> dict:
    """The matrix ``spec`` describes, drawn from ``rng``."""
    mat = rmat(spec["log_m"], spec["edge_factor"], spec["initiator"], rng)
    if spec.get("values", "ones") == "uniform":
        mat["vals"] = rng.uniform(0.5, 1.5, mat["rows"].size)
    return mat


def tiny(spec: dict) -> dict:
    """``spec`` cut to a size the CPU tests run: 256 rows, 8 a row."""
    return dict(spec, log_m=8, edge_factor=8)
