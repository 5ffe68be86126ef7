"""1.5D sparse-shift algorithm: rotating sparse, stationary R-split dense.

TPU-native redesign of the reference's ``Sparse15D_Sparse_Shift``
(`/root/reference/15D_sparse_shift.hpp:48-277`):

* Grid ``(p/c) x c``; sparse matrix block-row distributed
  (:class:`~distributed_sddmm_tpu.parallel.layouts.ShardedBlockRow`), one
  monolithic tile per device with GLOBAL column indices.
* Dense matrices are **stationary and R-split**: each device holds
  ``R * c / p`` feature columns of every row it sees — the reference's
  ``r_split=true`` feature-dimension sharding (`15D_sparse_shift.hpp:139-157`),
  the framework's analog of Ulysses-style head/feature parallelism. The
  canonical dense representation is 4-D ``(p/c stripes, c, block_rows, R)``
  sharded ``P(None, "cols", None, "rows")`` — a pure reshape of the global
  ``(M_pad, R)`` row-major matrix (stripe/layer leading dims encode the
  block-cyclic row order that a flat PartitionSpec cannot express).
* The stationary operand is replicated over the ``cols`` axis per stripe
  (reference per-stripe ``MPI_Allgather``, `15D_sparse_shift.hpp:203-215`),
  yielding all N_pad rows of this device's R-slice.
* The SPARSE tile ring-shifts around the ``rows`` axis: ``lax.ppermute`` of
  the padded ``(rows, cols, mask, vals)`` struct-of-arrays — the XLA-native
  form of the reference's 4-array ``shiftCSR`` with max_nnz-sized buffers
  (`SpmatLocal.hpp:200-259`, `15D_sparse_shift.hpp:252-268`). For SDDMM the
  partial R-slice dot products travel WITH the tile, accumulating the full
  dot over one ring trip; for SpMM each device writes the output stripe
  matching the tile it currently holds (`15D_sparse_shift.hpp:228-249`).
* CG-style consumers must ``psum`` dot products over the ``rows`` axis
  (``r_split`` reduction world, `15D_sparse_shift.hpp:80-81`).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P
from distributed_sddmm_tpu.compat import shard_map

from distributed_sddmm_tpu.common import MatMode, divide_round_up
from distributed_sddmm_tpu.parallel.base import DistributedSparse
from distributed_sddmm_tpu.parallel.loops import (
    abl_all_gather, abl_ppermute, ring_loop, ring_loop_overlap,
    ring_perm, vary,
)
from distributed_sddmm_tpu.parallel.layouts import ShardedBlockRow
from distributed_sddmm_tpu.parallel.mesh import make_grid
from distributed_sddmm_tpu.parallel.sharding import build_tiles
from distributed_sddmm_tpu.utils.coo import HostCOO

_DENSE_SPEC = P(None, "cols", None, "rows")
_TILE_SPEC = P("rows", "cols", None, None, None)


class SparseShift15D(DistributedSparse):
    algorithm_name = "1.5D Sparse Shifting Dense Replicating Algorithm"
    cost_model_name = "15d_sparse"
    proc_grid_names = ("# Rows", "# Layers")

    def __init__(
        self,
        S: HostCOO,
        R: int,
        c: int = 1,
        kernel=None,
        adjacency: int = 1,
        devices=None,
        dtype=jnp.float32,
        unroll: bool = True,
        overlap: bool = False,
        wire=None,
    ):
        if devices is None:
            devices = jax.devices()
        p = len(devices)
        if p % c != 0:
            raise ValueError(f"1.5D algorithm requires c | p (p={p}, c={c})")
        nr = p // c
        if R % nr != 0:
            raise ValueError(
                f"sparse-shift requires (p/c) | R (R={R}, p/c={nr}): the R "
                "dimension is split across the shift axis "
                "(reference check at 15D_sparse_shift.hpp:145-147)"
            )
        grid = make_grid(nr, c, 1, adjacency=adjacency, devices=devices)
        super().__init__(grid, S.M, S.N, R, c, kernel=kernel, dtype=dtype,
                         wire=wire)
        #: Double-buffered ring programs (``--fusion overlap``): the
        #: traveling tile's body-independent arrays (indices, mask/vals)
        #: hop BEFORE the local kernel consumes the resident copy; the
        #: SDDMM pass's accumulating dots — which depend on the body —
        #: still hop after it (``ring_loop_overlap``'s ``shift_carry``).
        self.overlap = bool(overlap)
        self.r_split = True
        self.r_split_axis = "rows"  # psum axis for CG dot products
        self.unroll = unroll
        self.nr = nr

        self.blockAwidth = divide_round_up(S.M, p)
        self.blockBwidth = divide_round_up(S.N, p)
        self.M_pad = self.blockAwidth * p
        self.N_pad = self.blockBwidth * p
        self.a_spec = _DENSE_SPEC
        self.b_spec = _DENSE_SPEC

        block = getattr(self.kernel, "is_blocked", False)
        variant = getattr(self.kernel, "variant", None)
        self.S_tiles = build_tiles(
            S, grid, ShardedBlockRow(self.M_pad, self.N_pad, p, c),
            tile_rows=self.blockAwidth, tile_cols=self.N_pad, dtype=dtype,
            block=block, variant=variant, R=R,
        )
        self.ST_tiles = build_tiles(
            S.transpose(), grid, ShardedBlockRow(self.N_pad, self.M_pad, p, c),
            tile_rows=self.blockBwidth, tile_cols=self.M_pad, dtype=dtype,
            block=block, variant=variant, R=R,
        )
        self._note_tile_metrics()

    # Canonical dense representation: (stripes, c, block, R), see module doc.
    def dense_shape(self, mode: MatMode) -> tuple:
        bw = self.blockAwidth if mode == MatMode.A else self.blockBwidth
        return (self.nr, self.c, bw, self.R)

    def _dense_global_rows(self, mode: MatMode) -> jax.Array:
        bw = self.blockAwidth if mode == MatMode.A else self.blockBwidth
        s = jnp.arange(self.nr, dtype=self.dtype)[:, None, None]
        j = jnp.arange(self.c, dtype=self.dtype)[None, :, None]
        r = jnp.arange(bw, dtype=self.dtype)[None, None, :]
        return (s * self.c + j) * bw + r

    def set_r_value(self, R: int) -> None:
        if R % self.nr != 0:
            raise ValueError(f"(p/c) | R required (R={R}, p/c={self.nr})")
        self.R = R

    # ------------------------------------------------------------------ #
    # shard_map programs
    # ------------------------------------------------------------------ #

    def _build_blocked_program(self, op: str, use_st: bool):
        """Blocked (Pallas) variants: the chunk-list tile metadata ring-shifts
        WITH the tile (`shiftCSR` analog — the blocked encoding is just more
        arrays in the traveling struct-of-arrays), local compute runs through
        the feature-major tile kernels."""
        from distributed_sddmm_tpu.ops.blocked import CHUNK

        tiles = self.ST_tiles if use_st else self.S_tiles
        nr, c = self.nr, self.c
        max_nnz = tiles.max_nnz
        out_bw = tiles.tile_rows
        kern = self.kernel
        perm = ring_perm(nr)
        unroll = self.unroll
        overlap = self.overlap
        bm, bn, grb, gcb, grp = tiles.blk_geom
        rows_pad, cols_pad = grb * bm, gcb * bn
        C = max_nnz // CHUNK
        # Wire roles: the tile's index/mask/value arrays are read-only
        # ring payloads (indices are int — the boundary cast skips
        # them); the SDDMM dots accumulate IN FLIGHT, so they hop at
        # the ring_accum dtype (f32 under the default bf16 policy).
        w_ring = self.wire.dtype_for("ring")
        w_ring_accum = self.wire.dtype_for("ring_accum")
        w_gather = self.wire.dtype_for("gather")

        def shift(tree, wire=w_ring):
            if nr == 1:
                return tree
            return jax.tree.map(
                lambda x: abl_ppermute(x, "rows", perm, wire=wire), tree
            )

        def shift_accum(tree):
            return shift(tree, wire=w_ring_accum)

        def replicate_stationary(blk):
            if c > 1:
                blk = abl_all_gather(blk, "cols", axis=1, tiled=True, size=c,
                                     wire=w_gather)
            return blk.reshape(blk.shape[0] * blk.shape[1] * blk.shape[2], blk.shape[3])

        def dvary(x):
            return vary(x, ("rows", "cols"))

        def my_stripe(step):
            i_idx = lax.axis_index("rows")
            return jax.numpy.mod(i_idx - step, nr)

        def squeeze_blk(blr, blc, bmeta):
            return (
                blr.reshape(C, CHUNK),
                blc.reshape(C, CHUNK),
                bmeta.reshape(C),
            )

        make_tile = self._blk_tile_factory(tiles)

        def blk_of(fields):
            blr, blc, bmeta = fields
            return make_tile(blr, blc, bmeta)

        BLK6 = P("rows", "cols", None, None, None, None)
        mesh = self.grid.mesh

        if op == "sddmm":

            def prog(a_role, b_role, blr, blc, bmeta, t_mask, t_vals):
                bt = kern.prep(replicate_stationary(b_role), cols_pad)
                mov0 = (squeeze_blk(blr, blc, bmeta), t_mask.reshape(max_nnz))
                acc0 = dvary(jnp.zeros((max_nnz,), t_mask.dtype))

                def local(s, fields, mask, acc):
                    stripe = lax.dynamic_index_in_dim(
                        a_role, my_stripe(s), axis=0, keepdims=False
                    ).reshape(out_bw, a_role.shape[-1])
                    at = kern.prep(stripe, rows_pad)
                    return acc + kern.sddmm_tile_t(
                        blk_of(fields), mask, at, bt, mask.dtype
                    )

                if overlap:
                    def body(s, acc, mov):
                        fields, mask = mov
                        return local(s, fields, mask, acc)

                    acc, _ = ring_loop_overlap(
                        nr, body, acc0, mov0, shift,
                        shift_carry=shift_accum,
                        final_shift=True, unroll=unroll,
                    )
                else:
                    def body(s, state):
                        (fields, mask), acc = state
                        return ((fields, mask), local(s, fields, mask, acc))

                    def shift_state(state):
                        mov, acc = state
                        return (shift(mov), shift_accum(acc))

                    state = ring_loop(
                        nr, body, (mov0, acc0), shift_state,
                        shift_final=shift_state, unroll=unroll,
                    )
                    acc = state[1]
                return (t_vals.reshape(max_nnz) * acc).reshape(1, 1, 1, 1, max_nnz)

            in_specs = (
                _DENSE_SPEC, _DENSE_SPEC, BLK6, BLK6,
                _TILE_SPEC, _TILE_SPEC, _TILE_SPEC,
            )
            out_specs = _TILE_SPEC

        elif op == "spmm":

            def prog(stat, blr, blc, bmeta, t_vals):
                bt = kern.prep(replicate_stationary(stat), cols_pad)
                mov0 = (squeeze_blk(blr, blc, bmeta), t_vals.reshape(max_nnz))
                out0 = dvary(
                    jnp.zeros((nr, 1, out_bw, stat.shape[-1]), stat.dtype)
                )

                def local(s, fields, vals, out):
                    partial = kern.spmm_tile_t(blk_of(fields), vals, bt)
                    stripe = partial.T[:out_bw].astype(out.dtype)
                    return lax.dynamic_update_index_in_dim(
                        out, stripe[None, :, :], my_stripe(s), axis=0
                    )

                if overlap:
                    def body(s, out, mov):
                        fields, vals = mov
                        return local(s, fields, vals, out)

                    out, _ = ring_loop_overlap(
                        nr, body, out0, mov0, shift, unroll=unroll
                    )
                    return out

                def body(s, state):
                    (fields, vals), out = state
                    return ((fields, vals), local(s, fields, vals, out))

                def shift_tile_only(state):
                    mov, out = state
                    return (shift(mov), out)

                state = ring_loop(
                    nr, body, (mov0, out0), shift_tile_only, unroll=unroll
                )
                return state[1]

            in_specs = (_DENSE_SPEC, BLK6, BLK6, _TILE_SPEC, _TILE_SPEC)
            out_specs = _DENSE_SPEC

        else:
            raise ValueError(op)

        return jax.jit(
            shard_map(
                prog, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                check_vma=False,
            )
        )

    def _program_cache_key(self, op: str, use_st: bool) -> tuple:
        """Base key + the fusion build (see DenseShift15D)."""
        return (
            *super()._program_cache_key(op, use_st),
            "overlap" if self.overlap else "seq",
        )

    def _program(self, op: str, use_st: bool):
        key = self._program_cache_key(op, use_st)
        if key in self._programs:
            return self._programs[key]
        if self._use_blocked(self.ST_tiles if use_st else self.S_tiles):
            fn = self._finalize_program(
                key, self._build_blocked_program(op, use_st)
            )
            self._programs[key] = fn
            return fn

        tiles = self.ST_tiles if use_st else self.S_tiles
        nr, c = self.nr, self.c
        max_nnz = tiles.max_nnz
        out_bw = tiles.tile_rows  # output stripe height (A-role block width)
        kern = self.kernel
        perm = ring_perm(nr)
        unroll = self.unroll
        overlap = self.overlap
        # Wire roles (see the blocked builder): read-only tile arrays
        # ride at the ring dtype, the in-flight SDDMM dot accumulator
        # at ring_accum (f32 under the default bf16 policy).
        w_ring = self.wire.dtype_for("ring")
        w_ring_accum = self.wire.dtype_for("ring_accum")
        w_gather = self.wire.dtype_for("gather")

        def shift(tree, wire=w_ring):
            if nr == 1:
                return tree
            return jax.tree.map(
                lambda x: abl_ppermute(x, "rows", perm, wire=wire), tree
            )

        def shift_accum(tree):
            return shift(tree, wire=w_ring_accum)

        def replicate_stationary(blk):
            # blk: (nr, 1, bw, r_loc) -> all-gather layers -> (N_pad, r_loc)
            if c > 1:
                blk = abl_all_gather(blk, "cols", axis=1, tiled=True, size=c,
                                     wire=w_gather)
            return blk.reshape(blk.shape[0] * blk.shape[1] * blk.shape[2], blk.shape[3])

        def dvary(x):
            return vary(x, ("rows", "cols"))

        def my_stripe(step):
            i_idx = lax.axis_index("rows")
            return jax.numpy.mod(i_idx - step, nr)

        def squeeze_tile(t):
            return t.reshape(max_nnz)

        mesh = self.grid.mesh

        if op == "sddmm":
            # Partial dots accumulate onto the traveling tile; one full ring
            # trip returns them to the owner with the complete R sum.

            def prog(a_role, b_role, t_rows, t_cols, t_mask, t_vals):
                # a_role supplies the per-step output-side stripe; b_role is
                # replicated across layers (reference Arole/Brole split,
                # `15D_sparse_shift.hpp:176-199`).
                b_rep = replicate_stationary(b_role)  # (rows_pad, r_loc)
                fields = (
                    squeeze_tile(t_rows),
                    squeeze_tile(t_cols),
                    squeeze_tile(t_mask),
                )
                acc0 = dvary(jnp.zeros((max_nnz,), t_mask.dtype))

                def stripe_at(s):
                    return lax.dynamic_index_in_dim(
                        a_role, my_stripe(s), axis=0, keepdims=False
                    ).reshape(out_bw, a_role.shape[-1])

                if overlap:
                    # Index/mask arrays are body-independent: they
                    # double-buffer. The accumulating dots depend on the
                    # body, so they hop after it (shift_carry) — the one
                    # leg of this traveling tile that cannot overlap —
                    # and at the ring_accum wire dtype (a changing
                    # partial sum must not be re-rounded per hop).
                    def body(s, acc, fields):
                        rows, cols, mask = fields
                        return acc + kern.sddmm(
                            rows, cols, mask, stripe_at(s), b_rep
                        )

                    acc, _ = ring_loop_overlap(
                        nr, body, acc0, fields, shift,
                        shift_carry=shift_accum,
                        final_shift=True, unroll=unroll,
                    )
                else:
                    def body(s, state):
                        rows, cols, mask, acc = state
                        acc = acc + kern.sddmm(
                            rows, cols, mask, stripe_at(s), b_rep
                        )
                        return (rows, cols, mask, acc)

                    def shift_state(state):
                        rows, cols, mask, acc = state
                        rows, cols, mask = shift((rows, cols, mask))
                        return (rows, cols, mask, shift_accum(acc))

                    # The accumulating dots travel WITH the tile; the
                    # final shift completes their round trip home.
                    state = ring_loop(
                        nr, body, (*fields, acc0), shift_state,
                        shift_final=shift_state, unroll=unroll,
                    )
                    acc = state[3]
                return (squeeze_tile(t_vals) * acc).reshape(1, 1, 1, 1, max_nnz)

            in_specs = (
                _DENSE_SPEC, _DENSE_SPEC,
                _TILE_SPEC, _TILE_SPEC, _TILE_SPEC, _TILE_SPEC,
            )
            out_specs = _TILE_SPEC

        elif op == "spmm":
            # The tile (with its values) rotates; each step computes the
            # output stripe matching the tile currently held.

            def prog(stat, t_rows, t_cols, t_vals):
                stat_rep = replicate_stationary(stat)
                fields = (
                    squeeze_tile(t_rows),
                    squeeze_tile(t_cols),
                    squeeze_tile(t_vals),
                )
                out0 = dvary(
                    jnp.zeros((nr, 1, out_bw, stat.shape[-1]), stat.dtype)
                )

                if overlap:
                    # The whole traveling tile is body-independent here
                    # (the output stays put): every hop double-buffers.
                    def body(s, out, fields):
                        rows, cols, vals = fields
                        stripe = kern.spmm(rows, cols, vals, stat_rep, out_bw)
                        return lax.dynamic_update_index_in_dim(
                            out, stripe[None, :, :].astype(out.dtype),
                            my_stripe(s), axis=0,
                        )

                    out, _ = ring_loop_overlap(
                        nr, body, out0, fields, shift, unroll=unroll
                    )
                    return out

                def body(s, state):
                    rows, cols, vals, out = state
                    stripe = kern.spmm(rows, cols, vals, stat_rep, out_bw)
                    out = lax.dynamic_update_index_in_dim(
                        out, stripe[None, :, :].astype(out.dtype), my_stripe(s), axis=0
                    )
                    return (rows, cols, vals, out)

                def shift_tile_only(state):
                    rows, cols, vals, out = state
                    rows, cols, vals = shift((rows, cols, vals))
                    return (rows, cols, vals, out)

                state = ring_loop(
                    nr, body, (*fields, out0), shift_tile_only, unroll=unroll
                )
                return state[3]

            in_specs = (_DENSE_SPEC, _TILE_SPEC, _TILE_SPEC, _TILE_SPEC)
            out_specs = _DENSE_SPEC

        else:
            raise ValueError(op)

        fn = self._finalize_program(
            key,
            jax.jit(shard_map(prog, mesh=mesh, in_specs=in_specs,
                              out_specs=out_specs)),
        )
        self._programs[key] = fn
        return fn

    # ------------------------------------------------------------------ #
    # Public ops
    # ------------------------------------------------------------------ #

    def sddmm_a(self, A, B, s_vals):
        t = self.S_tiles
        prog = self._program("sddmm", use_st=False)
        return self._timed("sddmmA", prog, A, B, *self._sddmm_args(t, s_vals))

    def sddmm_b(self, A, B, st_vals):
        t = self.ST_tiles
        prog = self._program("sddmm", use_st=True)
        return self._timed("sddmmB", prog, B, A, *self._sddmm_args(t, st_vals))

    def spmm_a(self, A, B, s_vals):
        t = self.S_tiles
        prog = self._program("spmm", use_st=False)
        return self._timed("spmmA", prog, B, *self._spmm_args(t, s_vals))

    def spmm_b(self, A, B, st_vals):
        t = self.ST_tiles
        prog = self._program("spmm", use_st=True)
        return self._timed("spmmB", prog, A, *self._spmm_args(t, st_vals))
