"""ALS-CG training (`als_conjugate_gradients.cpp`): the program's
``DistributedALS`` driven one alternating step at a time.

A unit of work is ``run_cg(1, cg_iters)``: an A half-step and a B
half-step, each one right-hand-side SpMM, one fused pair for the initial
residual and one per CG iteration. The benchmark makes the initial
factors and the artificial ground truth (an SDDMM of two random factors
at the matrix's pattern, `als_conjugate_gradients.cpp:157-184`) on the
device from the seed and hands them to the program.

Correctness follows the training recipe: set-up drives the model through
its first ``check.steps`` steps through the window's own call, keeps the
factors after each, and after the window the plain reference
(``als_ref.py``) follows the same steps from the same start. Each step's
number is the worse of the two factors' relative Frobenius gaps.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark import faults, program, sparse_ref
from benchmark.apps import als_ref


def tiny(cfg: dict) -> dict:
    return dict(cfg, R=16, cg_iters=3)


def work(cfg: dict, nnz: int, M: int, N: int) -> dict:
    """Per alternating step: the model FLOPs and, per sparse op, its FLOPs
    and least bytes (S's rows, cols and values, each dense operand read
    once, each output written once; 4 bytes an element) and the kernel
    that runs it."""
    R, it = cfg["R"], cfg["cg_iters"]
    ops, kernels, flops = [], [], 0
    for rows, cols in ((M, N), (N, M)):  # the A, then the B half-step
        spmm = (2 * nnz * R, 12 * nnz + 4 * (cols + rows) * R)
        fused = (4 * nnz * R,
                 12 * nnz + 4 * (rows + cols) * R + 4 * rows * R + 4 * nnz)
        ops += [spmm] + [fused] * (it + 1)
        kernels += ["spmm"] + ["fused"] * (it + 1)
        # A CG iteration's vector algebra: the ridge term, two row dots
        # and three row axpys, 2 FLOPs an element each.
        flops += it * 12 * rows * R
    return {"model_flops": flops + sum(f for f, _ in ops),
            "sparse_ops": ops, "sparse_kernels": kernels}


def _unchanged():
    """Each step leaves the factors as they were."""
    from distributed_sddmm_tpu.models.als import DistributedALS

    return faults.patched(DistributedALS, "run_cg",
                          lambda self, *a, **k: None)


def _altered():
    """Each half-step's solve moves one element of ``A``."""
    from distributed_sddmm_tpu.models.als import DistributedALS

    solve = DistributedALS.cg_optimizer

    def altered(self, mode, *a, **k):
        solve(self, mode, *a, **k)
        self.A = faults.alter(self.A)
    return faults.patched(DistributedALS, "cg_optimizer", altered)


#: The faults of ``faults.py`` that this app can have, with its patches.
FAULTS = {"unchanged": _unchanged, "half": None, "altered": _altered}


def _factors(key, R, shape_a, shape_b, sh_a, sh_b):
    """A0, B0 (`initializeEmbeddings`, cpp:221-233) and the ground truth's
    factors, in one jitted call on the device."""
    def make(key):
        ka, kb, kga, kgb = jax.random.split(key, 4)
        u = lambda k, s: jax.random.uniform(k, s, jnp.float32, -1.0, 1.0)
        return (u(ka, shape_a) / R * 1.4, u(kb, shape_b) / R / 1.3,
                u(kga, shape_a) / R, u(kgb, shape_b) / R)
    return jax.jit(make, out_shardings=(sh_a, sh_b, sh_a, sh_b))(key)


class Run:
    def __init__(self, cfg: dict, mat: dict, key, devices, check: dict):
        from distributed_sddmm_tpu.common import MatMode
        from distributed_sddmm_tpu.models.als import DistributedALS

        self.cfg, self.mat = cfg, mat
        R = cfg["R"]
        alg = program.build_strategy(cfg, mat, R, devices)
        self.kernel = program.kernel_line(alg)
        A0, B0, Agt, Bgt = _factors(
            key, R, alg.dense_shape(MatMode.A), alg.dense_shape(MatMode.B),
            alg.a_sharding(), alg.b_sharding())
        M, N = mat["M"], mat["N"]
        self.A0, self.B0 = A0[:M], B0[:N]
        gt_host = sparse_ref.coo_dots(mat["rows"], mat["cols"], Agt[:M],
                                      Bgt[:N])
        self.gt = jax.device_put(gt_host, list(self.A0.devices())[0])
        del Agt, Bgt
        self.als = DistributedALS(
            alg, ridge_lambda=cfg["ridge_lambda"],
            artificial_groundtruth=False, ground_truth_vals=gt_host,
            ground_truth_vals_transpose=gt_host)
        self.als.A, self.als.B = A0, B0
        self.kept = []
        for _ in range(check["steps"]):
            self.unit()
            self.kept.append((self.als.A[:M], self.als.B[:N]))

    def unit(self) -> None:
        self.als.run_cg(1, cg_iters=self.cfg["cg_iters"])
        jax.block_until_ready((self.als.A, self.als.B))

    def keep(self) -> None:
        """The check compares the set-up steps; the window keeps nothing."""

    def release(self) -> None:
        self.als = None

    def outputs(self) -> dict:
        return {f"factors.step{k}": AB
                for k, AB in enumerate(self.kept, 1)}

    def reference(self, quant, keep=None) -> dict:
        """``keep`` (0/1 per nonzero) leaves nonzeros out: how a fault is
        planted in the reference."""
        ell, ell_t = als_ref.layouts(self.mat)
        steps = als_ref.steps(ell, ell_t, self.gt, self.A0, self.B0,
                              self.cfg, len(self.kept), quant, keep)
        return {f"factors.step{k}": AB for k, AB in enumerate(steps, 1)}
