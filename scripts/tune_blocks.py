"""Block-size tuning probe for the Pallas chunk kernels (runs on real TPU).

For one (logM, nnz/row, R) config, times the fused/sddmm/spmm tile kernels
across (block_rows, block_cols) candidates plus the XLA gather kernel, and
prints one JSON line per measurement. Model for interpreting results:

    t_chunk ~ max(mxu: 2*R*CHUNK*(2*bm+bn)/PEAK, dma: bt block, fixed overhead)
    total   ~ n_chunks * t_chunk

When ``TUNE_LOAD_DIR`` is set, the chained-trial programs are NOT compiled
on-device: pre-serialized AOT executables (built offline by
`scripts/aot_compile_kernels.py`, validated by `scripts/aot_load_probe.py`)
are loaded onto the chip instead — same programs, same timing protocol
(`bench.aot.chain_time_loaded`), without compiling on the chip's clock.
Any load failure falls back to the on-device path.

Usage: python scripts/tune_blocks.py [logM npr R trials]
"""

from __future__ import annotations

import json
import sys

import numpy as np
import jax
import jax.numpy as jnp

from distributed_sddmm_tpu.ops.blocked import CHUNK, build_blocked
from distributed_sddmm_tpu.ops.kernels import XlaKernel
from distributed_sddmm_tpu.ops.pallas_kernels import BlockedTile, PallasKernel
from distributed_sddmm_tpu.utils.coo import HostCOO
from distributed_sddmm_tpu.bench.kernels import _chain_time

import os

BLOCKS = [(512, 512), (256, 512), (512, 1024), (256, 1024), (1024, 512),
          (1024, 1024), (256, 256), (128, 512)]
if os.environ.get("TUNE_BLOCKS"):
    BLOCKS = [tuple(int(x) for x in pair.split("x"))
              for pair in os.environ["TUNE_BLOCKS"].split(",")]
FUSED_ONLY = bool(os.environ.get("TUNE_FUSED_ONLY"))
SKIP_XLA = bool(os.environ.get("TUNE_SKIP_XLA"))
SCATTER_FORM = os.environ.get("TUNE_SCATTER", "bt")
BATCH_STEP = os.environ.get("TUNE_BATCH", "0") not in ("", "0")
LOAD_DIR = os.environ.get("TUNE_LOAD_DIR", "")


def build_inputs(log_m: int, npr: int, R: int):
    """Deterministic benchmark operands (shared with the offline AOT
    compiler, which only needs the shapes/dtypes to match)."""
    S = HostCOO.rmat(log_m=log_m, edge_factor=npr, seed=0)
    S = S.with_values(np.random.default_rng(1).standard_normal(S.nnz))
    rng = np.random.default_rng(0)
    A = jnp.array(rng.standard_normal((S.M, R)), jnp.float32)
    B = jnp.array(rng.standard_normal((S.N, R)), jnp.float32)
    return S, A, B, 2.0 * S.nnz * R


def build_blk(S, bm_pref: int, bn_pref: int, group: int):
    """Chunk-list metadata + device tile for one block preference.
    Returns (meta, blk, cvals); blk/cvals are None when pick_block clamped
    the preference (caller emits a tombstone)."""
    meta = build_blocked(1, np.zeros(S.nnz, np.int64), S.rows, S.cols,
                         S.M, S.N, block_rows=bm_pref, block_cols=bn_pref,
                         group=group)
    if (meta.bm, meta.bn) != (bm_pref, bn_pref):
        return meta, None, None
    blk = BlockedTile(
        lr=jnp.array(meta.lr[0]), lc=jnp.array(meta.lc[0]),
        meta=jnp.array(meta.meta[0]), bm=meta.bm, bn=meta.bn,
        gr_blocks=meta.gr_blocks, gc_blocks=meta.gc_blocks,
        group=meta.group,
    )
    vals_np = np.zeros(meta.n_chunks * CHUNK, np.float32)
    vals_np[meta.host_to_chunk] = S.vals
    return meta, blk, jnp.array(vals_np)


def xla_operands(S):
    """Device COO operands for the XLA kernel branch (single home for the
    dtype/shape conversion the offline AOT compiler must replicate)."""
    return (jnp.array(S.rows.astype(np.int32)),
            jnp.array(S.cols.astype(np.int32)),
            jnp.array(S.vals.astype(np.float32)))


def xla_steps(kern, S) -> dict:
    """The XLA-kernel chained-trial step functions (shared with the
    offline AOT compiler). Every array rides the chained state
    (:func:`xla_state`), so it enters the timed program as an argument:
    a closed-over array would be baked into each compile as a constant.
    The moving operand B is the state's first leaf."""

    def sddmm_step(state):
        Bs, v, A, rows, cols = state
        out = kern.sddmm(rows, cols, v, A, Bs)
        return (Bs + out.sum() * 1e-30, v, A, rows, cols)

    def spmm_step(state):
        Bs, v, A, rows, cols = state
        Bs = Bs + kern.spmm(rows, cols, v, Bs, S.M)[: S.N] * 1e-12
        return (Bs, v, A, rows, cols)

    return {"xla_sddmm": sddmm_step, "xla_spmm": spmm_step}


def xla_state(rows, cols, vals, A, B) -> tuple:
    """The XLA chains' initial state: ``(B, vals, A, rows, cols)``."""
    return (B, vals, A, rows, cols)


def pallas_steps(kernp, S) -> dict:
    """The three chained-trial step functions (shared with the offline AOT
    compiler so the serialized programs are byte-identical in structure).
    The state (:func:`pallas_state`) carries every array, the tile's chunk
    arrays included, so each enters the timed program as an argument (a
    closed-over array would be baked in as a constant: about 300 MB a
    compile at logM 18). The moving operand B is the state's first leaf."""

    def fused_step(state):
        Bs, v, A, blk = state
        o, _mid = kernp.fused_tile(blk, v, A, Bs)
        return (Bs + o[: S.N] * 1e-12, v, A, blk)

    def sddmm_step(state):
        Bs, v, A, blk = state
        out = kernp.sddmm_tile(blk, v, A, Bs)
        return (Bs + out.sum() * 1e-30, v, A, blk)

    def spmm_step(state):
        Bs, v, A, blk = state
        return (Bs + kernp.spmm_tile(blk, v, Bs, S.M)[: S.N] * 1e-12, v, A,
                blk)

    return {"fused": fused_step, "sddmm": sddmm_step, "spmm": spmm_step}


def pallas_state(blk, cvals, A, B) -> tuple:
    """The Pallas chains' initial state: ``(B, cvals, A, blk)`` (``blk``
    is a pytree whose chunk arrays are leaves and whose geometry is
    static)."""
    return (B, cvals, A, blk)


def clamp_tombstone(log_m: int, npr: int, R: int, meta,
                    bm_pref: int, bn_pref: int) -> dict:
    """Timing-free record for a block preference pick_block clamped away.

    Carries the REQUESTED blocks (``blocks_req``) so kernel_sweep's resume
    key matches the plan config — without it the config re-runs (and
    "fails": zero output lines) on every queue cycle. Consumers drop it via
    the ``skipped`` field / the absent ``fused_pair_gflops``.
    """
    return {
        "kernel": "pallas-bf16", "logM": log_m, "npr": npr, "R": R,
        "blocks_req": f"{bm_pref}x{bn_pref}",
        "bm": meta.bm, "bn": meta.bn, "group": meta.group,
        "scatter_form": SCATTER_FORM, "chunk": CHUNK,
        "batch_step": BATCH_STEP, "skipped": "clamped",
    }


def _timed_op(op: str, step, state, trials: int) -> tuple[float, bool]:
    """Seconds per trial for one op, preferring the AOT-loaded executables
    when TUNE_LOAD_DIR holds this op's pair. ANY failure along the AOT
    path — load OR execution — falls back to the on-device jit; returns
    (seconds, used_aot)."""
    if LOAD_DIR:
        from distributed_sddmm_tpu.bench import aot

        try:
            loaded = aot.load_chain_pair(LOAD_DIR, op, trials,
                                         jax.devices()[0])
            return aot.chain_time_loaded(loaded, state, trials), True
        except Exception as e:  # noqa: BLE001 — any AOT failure -> jit path
            print(f"[tune] AOT path failed for {op} ({type(e).__name__}: "
                  f"{e}); falling back to on-device compile", file=sys.stderr)
    return _chain_time(step, state, trials), False


def main():
    log_m = int(sys.argv[1]) if len(sys.argv) > 1 else 16
    npr = int(sys.argv[2]) if len(sys.argv) > 2 else 32
    R = int(sys.argv[3]) if len(sys.argv) > 3 else 128
    trials = int(sys.argv[4]) if len(sys.argv) > 4 else 5

    S, A, B, flops = build_inputs(log_m, npr, R)

    if not SKIP_XLA:
        kern = XlaKernel()
        steps = xla_steps(kern, S)
        state = xla_state(*xla_operands(S), A, B)

        t_sddmm, aot_s = _timed_op("xla_sddmm", steps["xla_sddmm"],
                                   state, trials)
        t_spmm, aot_m = _timed_op("xla_spmm", steps["xla_spmm"],
                                  state, trials)
        rec = {"kernel": "xla", "logM": log_m, "npr": npr, "R": R,
               "aot": aot_s and aot_m,
               "sddmm_ms": t_sddmm * 1e3, "spmm_ms": t_spmm * 1e3,
               "sddmm_gflops": flops / t_sddmm / 1e9,
               "spmm_gflops": flops / t_spmm / 1e9,
               "fused_pair_gflops": 2 * flops / (t_sddmm + t_spmm) / 1e9}
        print(json.dumps(rec), flush=True)

    kernp = PallasKernel(scatter_form=SCATTER_FORM, batch_step=BATCH_STEP)
    for bm_pref, bn_pref in BLOCKS:
        group = int(os.environ.get("TUNE_GROUP", "1"))
        meta, blk, cvals = build_blk(S, bm_pref, bn_pref, group)
        if blk is None:
            print(json.dumps(
                clamp_tombstone(log_m, npr, R, meta, bm_pref, bn_pref)
            ), flush=True)
            continue
        steps = pallas_steps(kernp, S)
        state = pallas_state(blk, cvals, A, B)

        t_f, used_aot = _timed_op("fused", steps["fused"], state, trials)
        t_s = t_m = None
        if not FUSED_ONLY:
            t_s, aot_s = _timed_op("sddmm", steps["sddmm"], state, trials)
            t_m, aot_m = _timed_op("spmm", steps["spmm"], state, trials)
            used_aot = used_aot and aot_s and aot_m
        occ = float((~meta.pad_lane).mean())
        rec = {"kernel": "pallas-bf16", "logM": log_m, "npr": npr, "R": R,
               "blocks_req": f"{bm_pref}x{bn_pref}",
               "bm": meta.bm, "bn": meta.bn, "n_chunks": meta.n_chunks,
               "group": meta.group, "scatter_form": SCATTER_FORM,
               "chunk": CHUNK, "batch_step": BATCH_STEP,
               "occupancy": round(occ, 3),
               "aot": used_aot,
               "fused_pair_ms": t_f * 1e3,
               "sddmm_ms": t_s and t_s * 1e3, "spmm_ms": t_m and t_m * 1e3,
               "fused_ns_per_chunk": t_f / meta.n_chunks * 1e9,
               "sddmm_ns_per_chunk": t_s and t_s / meta.n_chunks * 1e9,
               "spmm_ns_per_chunk": t_m and t_m / meta.n_chunks * 1e9,
               "fused_pair_gflops": 2 * flops / t_f / 1e9,
               "sddmm_gflops": t_s and flops / t_s / 1e9,
               "spmm_gflops": t_m and flops / t_m / 1e9}
        print(json.dumps(rec), flush=True)


if __name__ == "__main__":
    main()
