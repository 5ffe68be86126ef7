"""Single-device local-kernel microbenchmark.

The per-chip analog of the reference's ``local_kernel_benchmark``
(`/root/reference/local_kernel_benchmark.cpp:109-305`): sweep matrix size,
nnz/row and R over the local SDDMM / SpMM / fused kernels and print a
GFLOP/s table (`local_kernel_benchmark.cpp:264-267`). Where the reference
swept a hand COO loop vs an MKL CSR path, we sweep the XLA gather/segment-sum
kernel vs the Pallas one-hot MXU kernel.

Timing chains iterations data-dependently inside one jitted ``fori_loop``
ending in a host fetch, so per-call dispatch is paid once per chain and
the fetch cannot return before the chain has run (as in bench.py).
"""

from __future__ import annotations

import json
import time
from functools import partial

import numpy as np

import jax
import jax.numpy as jnp

from distributed_sddmm_tpu.ops.blocked import CHUNK, DEFAULT_GROUP, build_blocked
from distributed_sddmm_tpu.ops.kernels import XlaKernel
from distributed_sddmm_tpu.utils.coo import HostCOO

# Reference sweep: logM 13-16, nnz/row 8-128, R 8-4096
# (`local_kernel_benchmark.cpp:276-280`). Default to a tractable subset.
DEFAULT_LOG_M = [13, 14, 15, 16]
DEFAULT_NNZ_PER_ROW = [8, 32, 128]
DEFAULT_R = [32, 128, 512]


def _chain_time(step_fn, state, trials: int) -> float:
    """Time ``trials`` data-dependent applications of ``step_fn``."""

    @partial(jax.jit, static_argnums=1)
    def chain(state, n):
        return jax.lax.fori_loop(0, n, lambda _, s: step_fn(s), state)

    def run(n):
        out = chain(state, n)
        # Host fetch: returns only once the chain has run.
        float(jnp.asarray(out[0]).sum())

    run(1)
    run(1 + trials)  # compile both trip counts
    t0 = time.perf_counter()
    run(1)
    t_one = time.perf_counter() - t0
    t0 = time.perf_counter()
    run(1 + trials)
    # Clamp: at tiny sizes the dispatch-noise difference can go negative.
    return max((time.perf_counter() - t0 - t_one) / trials, 1e-9)


def _bench_one(S: HostCOO, R: int, kernel_name: str, trials: int) -> dict:
    rng = np.random.default_rng(0)
    A = jnp.array(rng.standard_normal((S.M, R)), jnp.float32)
    B = jnp.array(rng.standard_normal((S.N, R)), jnp.float32)

    if kernel_name == "xla":
        kern = XlaKernel()
        rows = jnp.array(S.rows.astype(np.int32))
        cols = jnp.array(S.cols.astype(np.int32))
        vals = jnp.array(S.vals.astype(np.float32))

        # Each step must feed its output back into a DENSE operand — chaining
        # only the sparse values would leave the gather/dot loop-invariant
        # and XLA hoists it out of the timing loop.
        def sddmm_step(state):
            B, v = state
            out = kern.sddmm(rows, cols, v, A, B)
            return (B + out.sum() * 1e-30, v)

        def spmm_step(state):
            B, _ = state
            return (B + kern.spmm(rows, cols, vals, B, S.M)[: S.N] * 1e-12, _)

        t_sddmm = _chain_time(sddmm_step, (B, vals), trials)
        t_spmm = _chain_time(spmm_step, (B, vals), trials)
        t_fused = t_sddmm + t_spmm  # no fused XLA program
    else:
        from distributed_sddmm_tpu.ops.pallas_kernels import BlockedTile, PallasKernel

        precision = "bf16" if kernel_name == "pallas" else "f32"
        kern = PallasKernel(precision=precision)
        meta = build_blocked(
            1, np.zeros(S.nnz, np.int64), S.rows, S.cols, S.M, S.N,
            group=DEFAULT_GROUP, R=R,
        )
        blk = BlockedTile(
            lr=jnp.array(meta.lr[0]), lc=jnp.array(meta.lc[0]),
            meta=jnp.array(meta.meta[0]), bm=meta.bm, bn=meta.bn,
            gr_blocks=meta.gr_blocks, gc_blocks=meta.gc_blocks,
            group=meta.group,
        )
        vals_np = np.zeros(meta.n_chunks * CHUNK, np.float32)
        vals_np[meta.host_to_chunk] = S.vals
        vals = jnp.array(vals_np)

        def sddmm_step(state):
            B, v = state
            out = kern.sddmm_tile(blk, v, A, B)
            return (B + out.sum() * 1e-30, v)

        def spmm_step(state):
            B, _ = state
            return (B + kern.spmm_tile(blk, vals, B, S.M)[: S.N] * 1e-12, _)

        def fused_step(state):
            B, _ = state
            o, _mid = kern.fused_tile(blk, vals, A, B)
            return (B + o[: S.N] * 1e-12, _)

        t_sddmm = _chain_time(sddmm_step, (B, vals), trials)
        t_spmm = _chain_time(spmm_step, (B, vals), trials)
        t_fused = _chain_time(fused_step, (B, vals), trials)

    flops = 2.0 * S.nnz * R
    rec = {
        "M": S.M, "N": S.N, "nnz": S.nnz, "R": R, "kernel": kernel_name,
        "sddmm_ms": t_sddmm * 1e3, "spmm_ms": t_spmm * 1e3,
        "fused_pair_ms": t_fused * 1e3,
        "sddmm_gflops": flops / t_sddmm / 1e9,
        "spmm_gflops": flops / t_spmm / 1e9,
        "fused_pair_gflops": 2 * flops / t_fused / 1e9,
    }
    if kernel_name != "xla":
        # Record the active tuning knobs so the table is reproducible.
        rec.update(
            bm=meta.bm, bn=meta.bn, group=meta.group, chunk=CHUNK,
            scatter_form=kern.scatter_form, batch_step=kern.batch_step,
        )
    return rec


def run_kernel_benchmark(
    log_m_values=None,
    nnz_per_row_values=None,
    r_values=None,
    kernels=("xla", "pallas"),
    trials: int = 5,
    output_file: str | None = None,
) -> list:
    """Sweep and print the per-chip kernel table; returns all records."""
    log_m_values = log_m_values or DEFAULT_LOG_M
    nnz_per_row_values = nnz_per_row_values or DEFAULT_NNZ_PER_ROW
    r_values = r_values or DEFAULT_R

    header = (
        f"{'M':>9} {'nnz':>10} {'R':>5} {'kernel':>12} "
        f"{'SDDMM':>9} {'SpMM':>9} {'fused':>9}   (GFLOP/s)"
    )
    print(header)
    print("-" * len(header))
    records = []
    for log_m in log_m_values:
        for npr in nnz_per_row_values:
            S = HostCOO.rmat(log_m=log_m, edge_factor=npr, seed=0)
            S = S.with_values(
                np.random.default_rng(1).standard_normal(S.nnz)
            )
            for R in r_values:
                for kname in kernels:
                    rec = _bench_one(S, R, kname, trials)
                    records.append(rec)
                    print(
                        f"{rec['M']:>9} {rec['nnz']:>10} {rec['R']:>5} "
                        f"{rec['kernel']:>12} {rec['sddmm_gflops']:>9.2f} "
                        f"{rec['spmm_gflops']:>9.2f} "
                        f"{rec['fused_pair_gflops']:>9.2f}"
                    )
                    if output_file:
                        # non-atomic-ok: append-only record stream.
                        with open(output_file, "a") as f:
                            f.write(json.dumps(rec) + "\n")
    return records
