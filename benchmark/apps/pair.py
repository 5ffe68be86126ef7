"""The fused SDDMM->SpMM pair (`distributed_sparse.h:296-312`) through
the program's public ``fused_spmm(A, B, s_vals, MatMode.A)``.

A unit of work is one call: ``mid = s * (A B^T)|_S`` and
``out = S(mid) @ B``. The benchmark makes A, B and the matrix's values
from the seed and hands them to the program.

Correctness: both outputs of one call of the window, drawn from the seed
among its first ones, against the plain reference: ``out`` as one
relative Frobenius gap, ``mid`` as another, read in the matrix's COO
order through the program's ``gather_s_values``.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from benchmark import faults, program, sparse_ref


def tiny(cfg: dict) -> dict:
    return dict(cfg, R=16)


def work(cfg: dict, nnz: int, M: int, N: int) -> dict:
    R = cfg["R"]
    fused = (4 * nnz * R, 12 * nnz + 4 * (M + N) * R + 4 * M * R + 4 * nnz)
    return {"model_flops": fused[0], "sparse_ops": [fused],
            "sparse_kernels": ["fused"]}


def _unchanged():
    """Each call returns its inputs: ``A`` as ``out``, ``s`` as ``mid``."""
    from distributed_sddmm_tpu.parallel.dense_shift_15d import DenseShift15D

    return faults.patched(DenseShift15D, "fused_spmm",
                          lambda self, A, B, s, mode=None: (A, s))


def _altered():
    """Each call's ``out`` has one element moved."""
    from distributed_sddmm_tpu.parallel.dense_shift_15d import DenseShift15D

    fused = DenseShift15D.fused_spmm

    def altered(self, *a, **k):
        out, mid = fused(self, *a, **k)
        return faults.alter(out), mid
    return faults.patched(DenseShift15D, "fused_spmm", altered)


#: The faults of ``faults.py`` that this app can have, with its patches.
FAULTS = {"unchanged": _unchanged, "half": None, "no_exchange": None,
          "altered": _altered}


def _dense(key, shape_a, shape_b, sh_a, sh_b):
    def make(key):
        ka, kb = jax.random.split(key)
        return (jax.random.uniform(ka, shape_a, jnp.float32, -1.0, 1.0),
                jax.random.uniform(kb, shape_b, jnp.float32, -1.0, 1.0))
    return jax.jit(make, out_shardings=(sh_a, sh_b))(key)


class Run:
    def __init__(self, cfg: dict, mat: dict, key, devices, check: dict):
        from distributed_sddmm_tpu.common import MatMode

        self.cfg, self.mat = cfg, mat
        self.mode = MatMode.A
        alg = program.build_strategy(cfg, mat, cfg["R"], devices)
        self.kernel = program.kernel_line(alg)
        self.A, self.B = _dense(key, alg.dense_shape(MatMode.A),
                                alg.dense_shape(MatMode.B),
                                alg.a_sharding(), alg.b_sharding())
        self.s_vals = alg.scatter_s_values(mat["vals"].astype(np.float32))
        self.alg = alg
        self.out = None
        for _ in range(2):
            self.unit()

    def unit(self) -> None:
        self.out = None
        self.out = self.alg.fused_spmm(self.A, self.B, self.s_vals, self.mode)
        jax.block_until_ready(self.out)

    def keep(self) -> None:
        self.kept = self.out

    def release(self) -> None:
        out, mid = self.kept
        M = self.mat["M"]
        self.kept = (np.asarray(out)[:M],
                     self.alg.gather_s_values(mid).astype(np.float32))
        self.A, self.B = (np.asarray(self.A)[:M],
                          np.asarray(self.B)[: self.mat["N"]])
        self.alg = self.s_vals = self.out = None

    def outputs(self) -> dict:
        out, mid = self.kept
        return {"out": (out,), "mid": (mid,)}

    def reference(self, quant, keep=None) -> dict:
        dev = jax.devices()[0]
        ell = sparse_ref.Ell(self.mat["rows"], self.mat["cols"],
                             self.mat["M"], device=dev)
        A, B = jax.device_put(self.A, dev), jax.device_put(self.B, dev)
        vals = jax.device_put(self.mat["vals"].astype(np.float32), dev)
        if keep is not None:
            vals = vals * jax.device_put(keep, dev)
        mid = ell.sddmm(ell.to_ell(vals), A, B, quant)
        return {"out": (ell.spmm(mid, B, quant),), "mid": (ell.to_coo(mid),)}
