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

    def _program_cache_key(self, op: str, use_st: bool) -> tuple:
        """Base key + the fusion build (see DenseShift15D)."""
        return (
            *super()._program_cache_key(op, use_st),
            "overlap" if self.overlap else "seq",
        )

    def _program(self, op: str, use_st: bool):
        """Build (and cache) the jitted shard_map program for one op
        (``op`` in {"sddmm", "spmm"}). The tile's structure arrays —
        flat indices or chunk lists, whichever the tile seam
        (``_tile_ops``) takes — ring-shift WITH its values (the
        `shiftCSR` analog)."""
        key = self._program_cache_key(op, use_st)
        if key in self._programs:
            return self._programs[key]

        tiles = self.ST_tiles if use_st else self.S_tiles
        form = self._tile_ops(tiles)
        nr, c = self.nr, self.c
        max_nnz = tiles.max_nnz
        out_bw = tiles.tile_rows  # output stripe height (A-role block width)
        perm = ring_perm(nr)
        unroll = self.unroll
        overlap = self.overlap
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

        def squeeze(t):
            return t.reshape(max_nnz)

        def ring(local, carry, tile0, shift_carry=None, home=False):
            """``carry = local(s, carry, tile)`` at each of the ``nr``
            steps while the tile travels. A ``shift_carry`` carry depends
            on the body, so it hops after it; ``home`` completes the
            round trip."""
            if overlap:
                return ring_loop_overlap(
                    nr, local, carry, tile0, shift,
                    shift_carry=shift_carry, final_shift=home,
                    unroll=unroll,
                )[0]

            def body(s, state):
                tile, carry = state
                return tile, local(s, carry, tile)

            def shift_state(state):
                tile, carry = state
                if shift_carry is None:
                    return shift(tile), carry
                return shift(tile), shift_carry(carry)

            return ring_loop(
                nr, body, (tile0, carry), shift_state,
                shift_final=shift_state if home else None, unroll=unroll,
            )[1]

        if op == "sddmm":
            # Partial dots accumulate onto the traveling tile; one full ring
            # trip returns them to the owner with the complete R sum.

            def prog(a_role, b_role, *targs):
                # a_role supplies the per-step output-side stripe; b_role is
                # replicated across layers (reference Arole/Brole split,
                # `15D_sparse_shift.hpp:176-199`).
                *fields, mask, vals = targs
                x_c = form.cols(replicate_stationary(b_role))
                tile0 = (form.squeeze(fields), squeeze(mask))
                acc0 = dvary(jnp.zeros((max_nnz,), mask.dtype))

                def local(s, acc, tile):
                    fields, mask = tile
                    stripe = lax.dynamic_index_in_dim(
                        a_role, my_stripe(s), axis=0, keepdims=False
                    ).reshape(out_bw, a_role.shape[-1])
                    return acc + form.sddmm(
                        form.tile(*fields), mask, form.rows(stripe), x_c
                    )

                # Index/mask arrays are body-independent: under overlap
                # they double-buffer. The accumulating dots depend on the
                # body, so they hop after it — the one leg of this
                # traveling tile that cannot overlap — and at the
                # ring_accum wire dtype (a changing partial sum must not
                # be re-rounded per hop); the final hop completes their
                # round trip home.
                acc = ring(local, acc0, tile0, shift_carry=shift_accum,
                           home=True)
                return (squeeze(vals) * acc).reshape(1, 1, 1, 1, max_nnz)

            in_specs = (
                _DENSE_SPEC, _DENSE_SPEC, *form.specs(_TILE_SPEC),
                _TILE_SPEC, _TILE_SPEC,
            )
            out_specs = _TILE_SPEC

        elif op == "spmm":
            # The tile (with its values) rotates; each step computes the
            # output stripe matching the tile currently held. The output
            # stays put, so under overlap every hop double-buffers.

            def prog(stat, *targs):
                *fields, vals = targs
                x_c = form.cols(replicate_stationary(stat))
                tile0 = (form.squeeze(fields), squeeze(vals))
                out0 = dvary(
                    jnp.zeros((nr, 1, out_bw, stat.shape[-1]), stat.dtype)
                )

                def local(s, out, tile):
                    fields, vals = tile
                    stripe = form.finish(
                        form.spmm(form.tile(*fields), vals, x_c), out
                    )
                    return lax.dynamic_update_index_in_dim(
                        out, stripe[None, :, :].astype(out.dtype),
                        my_stripe(s), axis=0,
                    )

                return ring(local, out0, tile0)

            in_specs = (_DENSE_SPEC, *form.specs(_TILE_SPEC), _TILE_SPEC)
            out_specs = _DENSE_SPEC

        else:
            raise ValueError(op)

        return self._shard_program(key, form, prog, in_specs, out_specs)

    # ------------------------------------------------------------------ #
    # Public ops
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
        return self._timed("spmmA", prog, B, *self._tile_args(t, s_vals))

    def spmm_b(self, A, B, st_vals):
        t = self.ST_tiles
        prog = self._program("spmm", use_st=True)
        return self._timed("spmmB", prog, A, *self._tile_args(t, st_vals))
