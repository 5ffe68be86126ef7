"""2.5D Cannon's algorithm, dense-replicating variant.

TPU-native redesign of the reference's ``Sparse25D_Cannon_Dense``
(`/root/reference/25D_cannon_dense.hpp:48-315`):

* Grid ``sqrt(p/c) x sqrt(p/c) x c`` -> mesh axes ``rows x cols x layers``
  (adjacency 3, the reference's recommended order).
* Sparse tiles live at their **Cannon-skewed** home from ingest
  (:class:`~distributed_sddmm_tpu.parallel.layouts.BlockCyclic25D` bakes the
  skew in, replacing the reference's setup ``shiftCSR`` round,
  `25D_cannon_dense.hpp:137-145`).
* Dense matrices are R-split over the ``cols`` axis (``localAcols =
  R / sqrtpc``, `25D_cannon_dense.hpp:150-159`) and row-distributed over
  ``(rows, layers)`` — sharding ``P(("rows", "layers"), "cols")``.
* The stationary dense operand is replicated over the ``layers`` fiber with
  ``lax.all_gather`` (reference ``MPI_Allgather``,
  `25D_cannon_dense.hpp:261-269`).
* Per Cannon step BOTH the moving dense operand (``rows`` axis) and the
  sparse tile + its values (``cols`` axis) rotate, via ``lax.ppermute``
  (`25D_cannon_dense.hpp:271-305`). SDDMM partial dots (this device's
  R-slice) travel with the tile, summing to the full dot over one ring trip;
  SpMM needs no reduction at all because outputs are R-split.
* ``initial_shift`` / ``de_shift`` pre/un-skew the MOVING dense operand with
  a multi-axis ``ppermute`` over ``("rows", "cols")`` — the per-column shift
  distance of the Cannon dense skew (`25D_cannon_dense.hpp:169-211`) cannot
  be a single-axis rotation. Ops expect the moving operand pre-skewed,
  matching the reference's API contract ("the user is responsible for any
  initial and final shifts", `distributed_sparse.h:292-295`).

**Transposed-values quirk (preserved from the reference,
`25D_cannon_dense.hpp:214-220`)**: A-ops run over the S^T tiles, so
``sddmm_a``/``spmm_a`` take and return values in S^T's canonical order, and
``like_s_values``/``scatter_s_values``/``gather_s_values`` address the S^T
tile structure (B-ops and the ``*_st_*`` helpers the reverse).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P
from distributed_sddmm_tpu.compat import shard_map

from distributed_sddmm_tpu.common import KernelMode, MatMode, divide_round_up
from distributed_sddmm_tpu.parallel.base import DistributedSparse
from distributed_sddmm_tpu.parallel.loops import (
    abl_all_gather, abl_ppermute, ring_loop, ring_perm, vary,
)
from distributed_sddmm_tpu.parallel.layouts import BlockCyclic25D
from distributed_sddmm_tpu.parallel.mesh import make_grid
from distributed_sddmm_tpu.parallel.sharding import build_tiles
from distributed_sddmm_tpu.utils.coo import HostCOO

_DENSE_SPEC = P(("rows", "layers"), "cols")
_TILE_SPEC = P("rows", "cols", "layers", None, None)

_A_MODES = (KernelMode.SDDMM_A, KernelMode.SPMM_A)


class CannonDense25D(DistributedSparse):
    algorithm_name = "2.5D Cannon's Algorithm Replicating Dense Matrices"
    cost_model_name = "25d_dense"
    proc_grid_names = ("# Rows", "# Cols", "# Layers")

    def __init__(
        self,
        S: HostCOO,
        R: int,
        c: int = 1,
        kernel=None,
        adjacency: int = 3,
        devices=None,
        dtype=jnp.float32,
        unroll: bool = True,
        wire=None,
    ):
        if devices is None:
            devices = jax.devices()
        p = len(devices)
        sqrtpc = int(math.isqrt(p // c))
        if sqrtpc * sqrtpc * c != p:
            raise ValueError(
                f"2.5D algorithm requires p/c to be a perfect square "
                f"(p={p}, c={c}; reference check at 25D_cannon_dense.hpp:59-67)"
            )
        if R % sqrtpc != 0:
            raise ValueError(
                f"2.5D dense-replicating requires sqrt(p/c) | R "
                f"(R={R}, sqrt(p/c)={sqrtpc})"
            )
        grid = make_grid(sqrtpc, sqrtpc, c, adjacency=adjacency, devices=devices)
        super().__init__(grid, S.M, S.N, R, c, kernel=kernel, dtype=dtype,
                         wire=wire)
        self.sqrtpc = sqrtpc
        self.r_split = True
        self.r_split_axis = "cols"  # reference A_R_split_world = row_world
        self.unroll = unroll

        self.localArows = divide_round_up(S.M, sqrtpc * c)
        self.localBrows = divide_round_up(S.N, sqrtpc * c)
        self.M_pad = self.localArows * sqrtpc * c
        self.N_pad = self.localBrows * sqrtpc * c
        self.a_spec = _DENSE_SPEC
        self.b_spec = _DENSE_SPEC

        # Blocked (Pallas) encoding in SWAPPED orientation: Cannon-dense SpMM
        # scatters into the tile's COLUMN dimension (the rotating output,
        # `25D_cannon_dense.hpp:271-305`), so chunks must group by col block.
        block = getattr(self.kernel, "is_blocked", False)
        variant = getattr(self.kernel, "variant", None)
        self.S_tiles = build_tiles(
            S, grid, BlockCyclic25D(self.M_pad, self.N_pad, sqrtpc, c),
            tile_rows=self.localArows * c, tile_cols=self.localBrows, dtype=dtype,
            block=block, block_swap=True, variant=variant, R=R,
        )
        self.ST_tiles = build_tiles(
            S.transpose(), grid, BlockCyclic25D(self.N_pad, self.M_pad, sqrtpc, c),
            tile_rows=self.localBrows * c, tile_cols=self.localArows, dtype=dtype,
            block=block, block_swap=True, variant=variant, R=R,
        )
        self._note_tile_metrics()

    def set_r_value(self, R: int) -> None:
        if R % self.sqrtpc != 0:
            raise ValueError(f"sqrt(p/c) | R required (R={R}, sqrt={self.sqrtpc})")
        self.R = R

    # -- transposed-values quirk (see module docstring) ------------------ #

    def like_s_values(self, value: float):
        return self.ST_tiles.like_values(value)

    def like_st_values(self, value: float):
        return self.S_tiles.like_values(value)

    def scatter_s_values(self, host_vals):
        """Values for A-ops: host order follows S.transpose() nonzeros."""
        return self.ST_tiles.scatter_values(host_vals)

    def gather_s_values(self, dev_vals):
        return self.ST_tiles.gather_values(dev_vals)

    def scatter_st_values(self, host_vals):
        """Values for B-ops: host order follows S's nonzeros."""
        return self.S_tiles.scatter_values(host_vals)

    def gather_st_values(self, dev_vals):
        return self.S_tiles.gather_values(dev_vals)

    # ------------------------------------------------------------------ #
    # Cannon skew of the moving dense operand
    # ------------------------------------------------------------------ #

    def _skew_program(self, sign: int):
        key = ("skew", sign)
        if key in self._programs:
            return self._programs[key]
        n = self.sqrtpc

        def flat(i, j):
            return i * n + j

        # sign=+1: device (i,j) block moves to (i-j, j) => afterwards (i,j)
        # holds the block of (i+j, j) — Cannon's initial skew. sign=-1 undoes.
        perm = [
            (flat(i, j), flat((i - sign * j) % n, j))
            for i in range(n)
            for j in range(n)
        ]

        def prog(x):
            if n == 1:
                return x
            # raw-collective-ok: one-time layout skew outside the ring
            # loops — a multi-axis permute the wire policy does not
            # price (it moves the operand once at op entry, not per
            # pair), so it stays on the raw f32 path deliberately.
            return lax.ppermute(x, ("rows", "cols"), perm)

        fn = jax.jit(
            shard_map(prog, mesh=self.grid.mesh, in_specs=_DENSE_SPEC,
                      out_specs=_DENSE_SPEC)
        )
        self._programs[key] = fn
        return fn

    def initial_shift(self, A, B, mode: KernelMode):
        """Pre-skew the moving operand (A for A-modes, B for B-modes)."""
        skew = self._skew_program(+1)
        if mode in _A_MODES:
            return (skew(A) if A is not None else None), B
        return A, (skew(B) if B is not None else None)

    def de_shift(self, A, B, mode: KernelMode):
        unskew = self._skew_program(-1)
        if mode in _A_MODES:
            return (unskew(A) if A is not None else None), B
        return A, (unskew(B) if B is not None else None)

    # ------------------------------------------------------------------ #
    # Cannon main loop
    # ------------------------------------------------------------------ #

    def _program(self, op: str, use_st: bool):
        """Build (and cache) the jitted shard_map program for one op
        (``op`` in {"sddmm", "spmm"}). The tile and its traveling values
        rotate around the ``cols`` ring, the moving dense operand around
        the ``rows`` ring. The accumulating output rides the tile's
        column frame, so the tile seam reads the tile swapped (the
        blocked encoding is built swapped for the same reason)."""
        key = self._program_cache_key(op, use_st)
        if key in self._programs:
            return self._programs[key]

        tiles = self.ST_tiles if use_st else self.S_tiles
        form = self._tile_ops(tiles, swap=True)
        n, c = self.sqrtpc, self.c
        max_nnz = tiles.max_nnz
        unroll = self.unroll
        perm = ring_perm(n)
        # Wire roles: read-only ring payloads (the SDDMM moving input,
        # tile mask/values; int indices never cast) vs the two in-flight
        # accumulators — the traveling SDDMM dots and SpMM's rotating
        # OUTPUT — which hop at ring_accum (f32 under the default bf16
        # policy).
        w_ring = self.wire.dtype_for("ring")
        w_ring_accum = self.wire.dtype_for("ring_accum")
        w_gather = self.wire.dtype_for("gather")

        def shift_dense(x, wire=w_ring):
            if n == 1:
                return x
            return abl_ppermute(x, "rows", perm, wire=wire)

        def shift_sparse(tree, wire=w_ring):
            if n == 1:
                return tree
            return jax.tree.map(
                lambda t: abl_ppermute(t, "cols", perm, wire=wire), tree
            )

        def replicate(stat):
            # (localXrows, r_loc) -> (localXrows * c, r_loc), k-major order
            # matching the tile row frame (fiber allgather,
            # 25D_cannon_dense.hpp:261-269).
            if c == 1:
                return stat
            return abl_all_gather(stat, "layers", axis=0, tiled=True, size=c,
                                  wire=w_gather)

        def dvary(x):
            return vary(x, ("rows", "cols", "layers"))

        def squeeze(t):
            return t.reshape(max_nnz)

        if op == "sddmm":
            # Partial R-slice dots travel with the tile around the cols ring
            # while the moving dense rotates around the rows ring. The
            # traveling accumulator must complete its round trip home.

            def prog(stat, mov, *targs):
                *fields, mask, vals = targs
                x_c = form.cols(replicate(stat))
                init = (
                    form.squeeze(fields), squeeze(mask),
                    dvary(jnp.zeros((max_nnz,), mask.dtype)),
                    mov,
                )

                def body(s, state):
                    fields, mask, acc, mov = state
                    acc = acc + form.sddmm(
                        form.tile(*fields), mask, form.rows(mov), x_c
                    )
                    return (fields, mask, acc, mov)

                def shift_all(state):
                    fields, mask, acc, mov = state
                    fields, mask = shift_sparse((fields, mask))
                    acc = shift_sparse(acc, wire=w_ring_accum)
                    return (fields, mask, acc, shift_dense(mov))

                def shift_acc_home(state):
                    fields, mask, acc, mov = state
                    return (fields, mask,
                            shift_sparse(acc, wire=w_ring_accum), mov)

                state = ring_loop(
                    n, body, init, shift_all, shift_final=shift_acc_home,
                    unroll=unroll,
                )
                acc = state[2]
                return (squeeze(vals) * acc).reshape(1, 1, 1, 1, max_nnz)

            in_specs = (
                _DENSE_SPEC, _DENSE_SPEC, *form.specs(_TILE_SPEC),
                _TILE_SPEC, _TILE_SPEC,
            )
            out_specs = _TILE_SPEC

        elif op == "spmm":
            # out[tile.cols] += vals * stat[tile.rows]; the output IS the
            # moving operand, accumulating as it rotates (the reference's
            # rotating bBuf output, 25D_cannon_dense.hpp:271-305).

            def prog(stat, mov, *targs):
                *fields, vals = targs
                x_c = form.cols(replicate(stat))
                init = (form.squeeze(fields), squeeze(vals), mov)

                def body(s, state):
                    fields, vals, mov = state
                    partial = form.spmm(form.tile(*fields), vals, x_c)
                    return (fields, vals, mov + form.finish(partial, mov))

                def shift_all(state):
                    fields, vals, mov = state
                    fields, vals = shift_sparse((fields, vals))
                    # mov IS the accumulating output (rotating bBuf):
                    # ring_accum, not ring.
                    return (fields, vals,
                            shift_dense(mov, wire=w_ring_accum))

                def shift_out_home(state):
                    fields, vals, mov = state
                    return fields, vals, shift_dense(mov, wire=w_ring_accum)

                # The rotating OUTPUT must complete the ring back to its
                # skewed home; the spent tile needn't.
                state = ring_loop(
                    n, body, init, shift_all, shift_final=shift_out_home,
                    unroll=unroll,
                )
                return state[2]

            in_specs = (
                _DENSE_SPEC, _DENSE_SPEC, *form.specs(_TILE_SPEC), _TILE_SPEC,
            )
            out_specs = _DENSE_SPEC

        else:
            raise ValueError(op)

        return self._shard_program(key, form, prog, in_specs, out_specs)

    # ------------------------------------------------------------------ #
    # Public ops (moving operand must be pre-skewed via initial_shift)
    # ------------------------------------------------------------------ #

    def sddmm_a(self, A, B, s_vals):
        t = self.ST_tiles
        prog = self._program("sddmm", use_st=True)
        return self._timed(
            "sddmmA", prog, B, A, *self._tile_args(t, s_vals, mask=True)
        )

    def sddmm_b(self, A, B, st_vals):
        t = self.S_tiles
        prog = self._program("sddmm", use_st=False)
        return self._timed(
            "sddmmB", prog, A, B, *self._tile_args(t, st_vals, mask=True)
        )

    def spmm_a(self, A, B, s_vals):
        """A = S @ B; A must be pre-skewed zeros (or accumulate base)."""
        t = self.ST_tiles
        prog = self._program("spmm", use_st=True)
        return self._timed("spmmA", prog, B, A, *self._tile_args(t, s_vals))

    def spmm_b(self, A, B, st_vals):
        t = self.S_tiles
        prog = self._program("spmm", use_st=False)
        return self._timed("spmmB", prog, A, B, *self._tile_args(t, st_vals))

    def fused_spmm(self, A, B, s_vals, mode: MatMode = MatMode.A):
        """SDDMM -> SpMM with the moving operand pre-skewed once for both."""
        if mode == MatMode.A:
            mid = self.sddmm_a(A, B, s_vals)
            zero = self.like_a_matrix(0.0)
            return self.spmm_a(zero, B, mid), mid
        mid = self.sddmm_b(A, B, s_vals)
        zero = self.like_b_matrix(0.0)
        return self.spmm_b(A, zero, mid), mid
