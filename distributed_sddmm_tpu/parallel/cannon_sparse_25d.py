"""2.5D Cannon's algorithm, sparse-replicating variant.

TPU-native redesign of the reference's ``Sparse25D_Cannon_Sparse``
(`/root/reference/25D_cannon_sparse.hpp:42-314`):

* Grid ``sqrt(p/c) x sqrt(p/c) x c``. The sparse matrix is 2-D blocked on
  the grid floor and **replicated up the fiber** — here simply a sharding
  spec that omits the ``layers`` axis (the reference's explicit
  ``MPI_Bcast`` of coordinates, `25D_cannon_sparse.hpp:47-54`, is a no-op
  under SPMD). Each layer owns a contiguous 1/c slice of every tile's
  VALUES (``shard_across_layers``, `SpmatLocal.hpp:338-356`).
* Dense matrices are R-split ``sqrt(p/c) * c`` ways. The resident layout is
  Cannon-skewed in the R dimension: device ``(i, j, k)`` holds row-block
  ``i`` and R-slice ``((i + j) mod sqrtpc) * c + k``
  (`25D_cannon_sparse.hpp:147-154`). Storage is a plain ``(M_pad, R)`` array
  sharded ``P("rows", ("cols", "layers"))``; the skew lives in the
  host<->device converters and the dummy-init formula, so it costs zero
  communication — exactly like the reference, whose ``aSubmatrices`` simply
  *define* the skewed layout as home.
* ``initial_shift``/``de_shift`` move the moving operand to the transposed
  grid position (self-inverse, `25D_cannon_sparse.hpp:157-186`) — a
  multi-axis ``ppermute`` over ``("rows", "cols")``.
* Main loop: the sparse stays put; BOTH dense operands rotate (A-role along
  ``cols``, B-role along ``rows``, `25D_cannon_sparse.hpp:257-280`). For
  SpMM, values are all-gathered up the fiber first
  (`25D_cannon_sparse.hpp:221-242`); the rotating A-role output accumulates
  complete results (no dense reduction). For SDDMM, every device
  accumulates dots over its R-slices; a fiber ``psum_scatter`` sums the c
  layers and hands each layer its owned value slice
  (`25D_cannon_sparse.hpp:287-306`).
* ``r_split`` reduction world = the ``("cols", "layers")`` axis pair
  (reference ``colfiber_slice``, `25D_cannon_sparse.hpp:80-81`).
"""

from __future__ import annotations

import math

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P
from distributed_sddmm_tpu.compat import shard_map

from distributed_sddmm_tpu.common import KernelMode, MatMode, divide_round_up
from distributed_sddmm_tpu.parallel.base import DistributedSparse
from distributed_sddmm_tpu.parallel.loops import (
    abl_all_gather, abl_ppermute, abl_psum_scatter, ring_loop,
    ring_perm, vary,
)
from distributed_sddmm_tpu.parallel.layouts import Floor2D
from distributed_sddmm_tpu.parallel.mesh import make_grid
from distributed_sddmm_tpu.parallel.sharding import build_replicated_tiles
from distributed_sddmm_tpu.utils.coo import HostCOO

_DENSE_SPEC = P("rows", ("cols", "layers"))
_STRUCT_SPEC = P("rows", "cols", None)
_VALUES_SPEC = P("rows", "cols", "layers", None)

_A_MODES = (KernelMode.SDDMM_A, KernelMode.SPMM_A)


class CannonSparse25D(DistributedSparse):
    algorithm_name = "2.5D Cannon's Algorithm Replicating Sparse Matrix"
    cost_model_name = "25d_sparse"
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
                f"2.5D algorithm requires p/c to be a perfect square (p={p}, c={c})"
            )
        if R % (sqrtpc * c) != 0:
            raise ValueError(
                f"2.5D sparse-replicating requires sqrt(p/c)*c | R "
                f"(R={R}, sqrt(p/c)*c={sqrtpc * c}; reference check at "
                "25D_cannon_sparse.hpp:142-145)"
            )
        grid = make_grid(sqrtpc, sqrtpc, c, adjacency=adjacency, devices=devices)
        super().__init__(grid, S.M, S.N, R, c, kernel=kernel, dtype=dtype,
                         wire=wire)
        self.sqrtpc = sqrtpc
        self.r_split = True
        self.r_split_axis = ("cols", "layers")
        self.unroll = unroll

        self.localArows = divide_round_up(S.M, sqrtpc)
        self.localBrows = divide_round_up(S.N, sqrtpc)
        self.M_pad = self.localArows * sqrtpc
        self.N_pad = self.localBrows * sqrtpc
        self.a_spec = _DENSE_SPEC
        self.b_spec = _DENSE_SPEC

        block = getattr(self.kernel, "is_blocked", False)
        variant = getattr(self.kernel, "variant", None)
        self.S_tiles = build_replicated_tiles(
            S, grid, Floor2D(self.M_pad, self.N_pad, sqrtpc),
            tile_rows=self.localArows, tile_cols=self.localBrows, dtype=dtype,
            block=block, variant=variant, R=R,
        )
        self.ST_tiles = build_replicated_tiles(
            S.transpose(), grid, Floor2D(self.N_pad, self.M_pad, sqrtpc),
            tile_rows=self.localBrows, tile_cols=self.localArows, dtype=dtype,
            block=block, variant=variant, R=R,
        )
        self._note_tile_metrics()

    def set_r_value(self, R: int) -> None:
        if R % (self.sqrtpc * self.c) != 0:
            raise ValueError(f"sqrt(p/c)*c | R required (R={R})")
        self.R = R

    # ------------------------------------------------------------------ #
    # Skewed resident R layout: host/device converters + dummy init.
    #
    # Stored column position scp on row-block i maps to global column
    #   q_st = scp // la; j = q_st // c; k = q_st % c
    #   q_gl = ((i + j) mod n) * c + k;  g_col = q_gl * la + scp % la
    # ------------------------------------------------------------------ #

    def _la(self) -> int:
        return self.R // (self.sqrtpc * self.c)

    def _col_permutation(self) -> np.ndarray:
        """stored-position -> global-column map, per row-block.

        Returns an int array (n, R): entry [i, scp] = global column of
        stored position scp on row-block i.
        """
        n, c, la = self.sqrtpc, self.c, self._la()
        scp = np.arange(self.R)
        q_st = scp // la
        j, k = q_st // c, q_st % c
        i = np.arange(n)[:, None]
        q_gl = ((i + j[None, :]) % n) * c + k[None, :]
        return q_gl * la + (scp % la)[None, :]

    def put_a(self, host: np.ndarray) -> jax.Array:
        return self._put(host, self.M_pad, self.localArows, self.a_sharding())

    def put_b(self, host: np.ndarray) -> jax.Array:
        return self._put(host, self.N_pad, self.localBrows, self.b_sharding())

    def _put(self, host, n_rows_pad, block, sharding):
        buf = np.zeros((n_rows_pad, self.R), dtype=self.dtype)
        buf[: host.shape[0]] = host
        perm = self._col_permutation()
        out = np.empty_like(buf)
        for i in range(self.sqrtpc):
            rows = slice(i * block, (i + 1) * block)
            out[rows] = buf[rows][:, perm[i]]  # stored[:, scp] = global[:, perm]
        return jax.device_put(out, sharding)

    def host_a(self, A: jax.Array) -> np.ndarray:
        return self._host(A, self.localArows)[: self.M]

    def host_b(self, B: jax.Array) -> np.ndarray:
        return self._host(B, self.localBrows)[: self.N]

    def _host(self, X, block):
        stored = np.asarray(X)
        perm = self._col_permutation()
        out = np.empty_like(stored)
        for i in range(self.sqrtpc):
            rows = slice(i * block, (i + 1) * block)
            blockvals = np.empty_like(stored[rows])
            blockvals[:, perm[i]] = stored[rows]
            out[rows] = blockvals
        return out

    def dummy_initialize(self, mode: MatMode) -> jax.Array:
        shape = self.dense_shape(mode)
        sharding = self.a_sharding() if mode == MatMode.A else self.b_sharding()
        key = ("dummy", shape, sharding)
        if key not in self._programs:

            def make():
                # Global-order fill, then the one device-side skew impl.
                rows = jnp.arange(shape[0], dtype=self.dtype)[:, None]
                col = jnp.arange(self.R, dtype=self.dtype)
                return self._skew_cols(rows * self.R + col, mode)

            self._programs[key] = jax.jit(make, out_shardings=sharding)
        return self._programs[key]()

    def _row_blocks(self, X, mode: MatMode):
        block = self.localArows if mode == MatMode.A else self.localBrows
        return jnp.arange(X.shape[0], dtype=jnp.int32)[:, None] // block

    def _skew_cols(self, X, mode: MatMode):
        """global col order -> resident skewed layout: stored[scp] =
        global[g_col(i_blk, scp)] — device-side iota gather, any width
        divisible by sqrtpc*c."""
        n, c = self.sqrtpc, self.c
        w = X.shape[-1]
        if w % (n * c) != 0:
            raise ValueError(
                f"feature width {w} must be divisible by sqrt(p/c)*c = {n * c}"
            )
        la = w // (n * c)
        i_blk = self._row_blocks(X, mode)
        scp = jnp.arange(w, dtype=jnp.int32)[None, :]
        q_st = scp // la
        j, k = q_st // c, q_st % c
        g = (jnp.mod(i_blk + j, n) * c + k) * la + scp % la
        return jnp.take_along_axis(X, jnp.broadcast_to(g, X.shape), axis=-1)

    def _unskew_cols(self, X, mode: MatMode):
        """resident skewed layout -> global col order: global[t] =
        stored[scp(i_blk, t)]."""
        n, c = self.sqrtpc, self.c
        w = X.shape[-1]
        if w % (n * c) != 0:
            raise ValueError(
                f"feature width {w} must be divisible by sqrt(p/c)*c = {n * c}"
            )
        la = w // (n * c)
        i_blk = self._row_blocks(X, mode)
        t = jnp.arange(w, dtype=jnp.int32)[None, :]
        q_gl = t // la
        k, q = q_gl % c, q_gl // c
        j = jnp.mod(q - i_blk, n)
        scp = (j * c + k) * la + t % la
        return jnp.take_along_axis(X, jnp.broadcast_to(scp, X.shape), axis=-1)

    # ------------------------------------------------------------------ #
    # Transpose shift (initial_shift == de_shift, self-inverse)
    # ------------------------------------------------------------------ #

    def _transpose_program(self):
        key = ("transpose_shift",)
        if key in self._programs:
            return self._programs[key]
        n = self.sqrtpc

        def prog(x):
            if n == 1:
                return x
            perm = [(i * n + j, j * n + i) for i in range(n) for j in range(n)]
            # raw-collective-ok: one-time transpose skew outside the
            # ring loops (multi-axis permute, not a per-pair payload
            # the wire policy prices) — deliberately on the raw path.
            return lax.ppermute(x, ("rows", "cols"), perm)

        fn = jax.jit(
            shard_map(prog, mesh=self.grid.mesh, in_specs=_DENSE_SPEC,
                      out_specs=_DENSE_SPEC)
        )
        self._programs[key] = fn
        return fn

    def initial_shift(self, A, B, mode: KernelMode):
        """Move the moving operand (B for A-modes, A for B-modes) to the
        transposed grid position."""
        t = self._transpose_program()
        if mode in _A_MODES:
            return A, (t(B) if B is not None else None)
        return (t(A) if A is not None else None), B

    def de_shift(self, A, B, mode: KernelMode):
        return self.initial_shift(A, B, mode)

    # ------------------------------------------------------------------ #
    # Cannon main loop (sparse stationary, both dense operands rotate)
    # ------------------------------------------------------------------ #

    def _program(self, op: str, use_st: bool):
        """Build (and cache) the jitted shard_map program for one op
        (``op`` in {"sddmm", "spmm"}). The tile stays put (its structure
        is replicated up the fiber); both dense operands rotate and enter
        the tile seam each step. The fiber value collectives
        (`25D_cannon_sparse.hpp:221-242,287-306`) work on the flat value
        layout, which a chunk list shares (its length is padded to split
        evenly into owned slices)."""
        key = self._program_cache_key(op, use_st)
        if key in self._programs:
            return self._programs[key]

        tiles = self.ST_tiles if use_st else self.S_tiles
        form = self._tile_ops(tiles)
        n, c = self.sqrtpc, self.c
        max_nnz, owned_len = tiles.max_nnz, tiles.owned_len
        unroll = self.unroll
        perm = ring_perm(n)
        # Wire roles: both rotating dense operands are read-only in
        # SDDMM (ring); in SpMM the A-role is the accumulating OUTPUT
        # (ring_accum). The fiber value gather is input data (gather);
        # the fiber psum_scatter of SDDMM dots is a reduction (reduce —
        # f32 under the default bf16 policy).
        w_ring = self.wire.dtype_for("ring")
        w_ring_accum = self.wire.dtype_for("ring_accum")
        w_gather = self.wire.dtype_for("gather")
        w_reduce = self.wire.dtype_for("reduce")

        def shift_a(x, wire=w_ring):  # A-role rotates along cols (row_world)
            return x if n == 1 else abl_ppermute(x, "cols", perm, wire=wire)

        def shift_b(x):  # B-role rotates along the rows axis (col_world)
            return x if n == 1 else abl_ppermute(x, "rows", perm, wire=w_ring)

        def dvary(x):
            return vary(x, ("rows", "cols", "layers"))

        struct_specs = form.specs(_STRUCT_SPEC)

        if op == "sddmm":

            def prog(a_role, b_role, *targs):
                *fields, mask, vals_owned = targs
                tile = form.tile(*form.squeeze(fields))
                mask = mask.reshape(max_nnz)
                init = (
                    dvary(jnp.zeros((max_nnz,), mask.dtype)),
                    a_role, b_role,
                )

                def body(s, state):
                    acc, a, b = state
                    acc = acc + form.sddmm(
                        tile, mask, form.rows(a), form.cols(b)
                    )
                    return (acc, a, b)

                def shift_ab(state):
                    acc, a, b = state
                    return (acc, shift_a(a), shift_b(b))

                # acc is stationary (the sparse stays put); the spent dense
                # operands need no trailing rotation.
                state = ring_loop(n, body, init, shift_ab, unroll=unroll)
                acc = state[0]
                if c > 1:
                    owned = abl_psum_scatter(
                        acc, "layers", scatter_dimension=0, tiled=True,
                        size=c, wire=w_reduce,
                    )
                else:
                    owned = acc
                return (vals_owned.reshape(owned_len) * owned).reshape(
                    1, 1, 1, owned_len
                )

            in_specs = (
                _DENSE_SPEC, _DENSE_SPEC, *struct_specs,
                _STRUCT_SPEC, _VALUES_SPEC,
            )
            out_specs = _VALUES_SPEC

        elif op == "spmm":
            # A-role is the rotating OUTPUT accumulating complete results;
            # values gathered up the fiber first.

            def prog(a_role, b_role, *targs):
                *fields, vals_owned = targs
                tile = form.tile(*form.squeeze(fields))
                v = vals_owned.reshape(owned_len)
                if c > 1:
                    vals = abl_all_gather(v, "layers", axis=0, tiled=True,
                                          size=c, wire=w_gather)
                else:
                    vals = v
                init = (a_role, b_role)

                def body(s, state):
                    a, b = state
                    partial = form.spmm(tile, vals, form.cols(b))
                    return (a + form.finish(partial, a), b)

                def shift_ab(state):
                    # The A-role is the accumulating output: ring_accum.
                    a, b = state
                    return (shift_a(a, wire=w_ring_accum), shift_b(b))

                def shift_out_home(state):
                    a, b = state
                    return (shift_a(a, wire=w_ring_accum), b)

                # The rotating A-role OUTPUT completes its ring trip home;
                # the spent B-role needn't.
                state = ring_loop(
                    n, body, init, shift_ab, shift_final=shift_out_home,
                    unroll=unroll,
                )
                return state[0]

            in_specs = (_DENSE_SPEC, _DENSE_SPEC, *struct_specs, _VALUES_SPEC)
            out_specs = _DENSE_SPEC

        else:
            raise ValueError(op)

        return self._shard_program(key, form, prog, in_specs, out_specs)

    # ------------------------------------------------------------------ #
    # Public ops (the moving operand must be transpose-shifted first)
    # ------------------------------------------------------------------ #

    def sddmm_a(self, A, B, s_vals):
        t = self.S_tiles
        prog = self._program("sddmm", use_st=False)
        return self._timed(
            "sddmmA", prog, A, B, *self._tile_args(t, s_vals, mask=True)
        )

    def sddmm_b(self, A, B, st_vals):
        t = self.ST_tiles
        prog = self._program("sddmm", use_st=True)
        return self._timed(
            "sddmmB", prog, B, A, *self._tile_args(t, st_vals, mask=True)
        )

    def spmm_a(self, A, B, s_vals):
        t = self.S_tiles
        prog = self._program("spmm", use_st=False)
        return self._timed("spmmA", prog, A, B, *self._tile_args(t, s_vals))

    def spmm_b(self, A, B, st_vals):
        t = self.ST_tiles
        prog = self._program("spmm", use_st=True)
        return self._timed("spmmB", prog, B, A, *self._tile_args(t, st_vals))

    def fused_spmm(self, A, B, s_vals, mode: MatMode = MatMode.A):
        if mode == MatMode.A:
            mid = self.sddmm_a(A, B, s_vals)
            return self.spmm_a(self.like_a_matrix(0.0), B, mid), mid
        mid = self.sddmm_b(A, B, s_vals)
        return self.spmm_b(A, self.like_b_matrix(0.0), mid), mid
