"""1.5D dense-shift algorithm with both SDDMM->SpMM fusion strategies.

TPU-native redesign of the reference's ``Sparse15D_Dense_Shift``
(`/root/reference/15D_dense_shift.hpp:48-385`):

* Process grid ``(p/c) x c x 1`` -> mesh axes ``rows x cols`` (layers unused).
* Sparse S stays put, block-row-replicated via the
  :class:`~distributed_sddmm_tpu.parallel.layouts.ShardedBlockCyclicColumn`
  layout; tiles are pre-skewed into step order at ingest so the shift loop
  indexes them statically.
* The stationary dense operand is replicated over the ``cols`` axis with
  ``lax.all_gather`` (reference ``MPI_Allgather`` over ``row_world``,
  `15D_dense_shift.hpp:306-314`), and SpMM partials are reduced with
  ``lax.psum_scatter`` (reference ``MPI_Reduce_scatter``,
  `15D_dense_shift.hpp:370-383`).
* The moving dense operand rotates around the ``rows`` axis with
  ``lax.ppermute`` (reference ``MPI_Sendrecv`` + ``BufferPair``,
  `distributed_sparse.h:351-361`); XLA double-buffers and overlaps the
  permute with the local kernels, which is what the reference's
  ``BufferPair`` achieved by hand.
* ``fusion_approach=2`` ("local kernel overlap", `15D_dense_shift.hpp:151-252`)
  runs SDDMM and SpMM per tile inside ONE shift loop: one all_gather + one
  psum_scatter total. ``fusion_approach=1`` ("replication reuse") shares one
  replicated buffer across back-to-back SDDMM and SpMM ring passes inside a
  single compiled program. Both produce identical results; unlike the
  reference (comment at `15D_dense_shift.hpp:250-251`), the fused path here
  does fill and return the SDDMM values.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from distributed_sddmm_tpu.common import MatMode, divide_round_up
from distributed_sddmm_tpu.ops.kernels import attn_merge_stats
from distributed_sddmm_tpu.parallel.base import DistributedSparse
from distributed_sddmm_tpu.parallel.loops import (
    abl_all_gather, abl_ppermute, abl_psum_scatter, ring_loop,
    ring_loop_overlap, ring_perm, vary,
)
from distributed_sddmm_tpu.parallel.layouts import ShardedBlockCyclicColumn
from distributed_sddmm_tpu.parallel.mesh import make_grid
from distributed_sddmm_tpu.parallel.sharding import build_tiles
from distributed_sddmm_tpu.utils.coo import HostCOO

_DENSE_SPEC = P(("rows", "cols"), None)
# The layers axis is unused (nh=1); leaving it out of the tile spec lets
# shard_map statically prove dense outputs are replicated over it.
_TILE_SPEC = P("rows", "cols", None, None, None)
#: The fused-attention programs: keys as values (``attn``), a value
#: operand of its own (``_v``), a per-row sink logit (``_sink``).
ATTN_OPS = ("attn", "attn_v", "attn_sink", "attn_v_sink")


class DenseShift15D(DistributedSparse):
    algorithm_name = "1.5D Block Row Replicated S Striped AB Cyclic Shift"
    proc_grid_names = ("# Rows", "# Layers")

    def __init__(
        self,
        S: HostCOO,
        R: int,
        c: int = 1,
        fusion_approach: int = 2,
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
        if fusion_approach not in (1, 2):
            raise ValueError("fusion_approach must be 1 or 2")
        grid = make_grid(p // c, c, 1, adjacency=adjacency, devices=devices)
        super().__init__(grid, S.M, S.N, R, c, kernel=kernel, dtype=dtype,
                         wire=wire)
        self.fusion_approach = fusion_approach
        #: ``overlap=True`` builds every ring program double-buffered
        #: (``ring_loop_overlap``): the next tile's ``ppermute`` is issued
        #: before the current tile's local kernel — the reference's
        #: ``BufferPair`` local-kernel-overlap strategy in program
        #: structure, bit-identical to the sequential loop (CLI
        #: ``--fusion overlap``).
        self.overlap = bool(overlap)
        self.cost_model_name = (
            "15d_fusion2" if fusion_approach == 2 else "15d_fusion1"
        )
        self.unroll = unroll
        self.nr = p // c

        # Padded uniform block geometry (reference divideAndRoundUp,
        # `15D_dense_shift.hpp:91-92`).
        self.localArows = divide_round_up(S.M, p)
        self.localBrows = divide_round_up(S.N, p)
        self.M_pad = self.localArows * p
        self.N_pad = self.localBrows * p
        self.a_spec = _DENSE_SPEC
        self.b_spec = _DENSE_SPEC

        layout_s = ShardedBlockCyclicColumn(self.M_pad, self.N_pad, p, c)
        layout_st = ShardedBlockCyclicColumn(self.N_pad, self.M_pad, p, c)
        block = getattr(self.kernel, "is_blocked", False)
        variant = getattr(self.kernel, "variant", None)
        self.S_tiles = build_tiles(
            S, grid, layout_s,
            tile_rows=self.localArows * c, tile_cols=self.localBrows, dtype=dtype,
            block=block, variant=variant, R=R,
        )
        self.ST_tiles = build_tiles(
            S.transpose(), grid, layout_st,
            tile_rows=self.localBrows * c, tile_cols=self.localArows, dtype=dtype,
            block=block, variant=variant, R=R,
        )
        self._note_tile_metrics()

    def set_r_value(self, R: int) -> None:
        """Change the inner dimension (reference ``setRValue``,
        `15D_dense_shift.hpp:128-140`). Programs retrace per distinct shape."""
        self.R = R

    def comm_profile(self, op: str, pairs: float = 1.0) -> list[dict]:
        """Per-collective word volumes from THIS strategy's layout math
        (not the cost model): the stationary operand's per-device block is
        ``localArows x R`` (all-gathered over the c-wide ``cols`` axis),
        the moving operand's is ``localBrows x R`` (ppermuted around the
        ``(p/c)``-long ``rows`` ring), and SpMM partials psum_scatter back
        over ``cols``. The in-model sum equals
        ``costmodel.pair_words(cost_model_name, M_pad, N_pad, ...)``
        exactly — the agreement the trace report (and a test) checks; the
        reduce-scatter is ``in_model=False`` because the notebook's
        models fold it out of the comparison.
        """
        R, c, nr = self.R, self.c, self.nr
        n_pass = 1 if self.fusion_approach == 2 else 2
        # B-output ops run on the transposed tiles: stationary/output rows
        # come from the N side, the A blocks ride the ring (the swap
        # carries into the byte column unchanged — bytes = words x the
        # role's wire width).
        stat_rows, mov_rows = self.localArows, self.localBrows
        if op.endswith("B"):
            stat_rows, mov_rows = mov_rows, stat_rows
        wire = self.wire
        repl_words = (c - 1) * stat_rows * R * pairs
        repl = {
            "collective": "all_gather", "axis": "cols",
            "count": (1 if c > 1 else 0) * pairs,
            "words": repl_words,
            "bytes": repl_words * wire.bytes_for("gather"),
            "in_model": True,
        }
        reduce_ = {
            "collective": "psum_scatter", "axis": "cols",
            "count": (1 if c > 1 else 0) * pairs,
            "words": repl_words,
            "bytes": repl_words * wire.bytes_for("reduce"),
            "in_model": False,
        }

        def ring(passes):
            words = (nr - 1) * mov_rows * R * passes * pairs
            return {
                "collective": "ppermute", "axis": "rows",
                "count": (nr - 1) * passes * pairs,
                "words": words,
                "bytes": words * wire.bytes_for("ring"),
                "in_model": True,
            }

        if op in ("fusedSpMM", "cgStep", "gatLayer", "fusedSpMMB", "cgStepB"):
            return [repl, ring(n_pass), reduce_]
        if op in ("fusedAttn", "fusedAttnB"):
            # Attention is structurally the twopass pair (the softmax
            # needs the complete SDDMM rotation) plus one [rows]-vector
            # max/denominator merge over the replication axis — tiny
            # next to the dense traffic, counted but out of model like
            # the reduce-scatter. The merge is ALWAYS f32 (4 B): exact
            # softmax row stats are what keep fused and unfused
            # attention bitwise-aligned, under every wire policy.
            merge_words = 2 * (c - 1) * stat_rows * pairs
            merge = {
                "collective": "pmax+psum", "axis": "cols",
                "count": (2 if c > 1 else 0) * pairs,
                "words": merge_words,
                "bytes": merge_words * 4,
                "in_model": False,
            }
            return [repl, ring(2), merge, reduce_]
        if op in ("sddmmA", "sddmmB"):
            return [repl, ring(1)]
        if op in ("spmmA", "spmmB"):
            return [ring(1), reduce_]
        return []

    # ------------------------------------------------------------------ #
    # shard_map programs
    # ------------------------------------------------------------------ #

    def _program_cache_key(self, op: str, use_st: bool) -> tuple:
        """Base key + the fusion build: overlap and sequential programs
        are distinct compilations (and distinct store entries)."""
        return (
            *super()._program_cache_key(op, use_st),
            "overlap" if self.overlap else "seq",
        )

    def _program(self, op: str, use_st: bool):
        """Build (and cache) the jitted shard_map program for one op.

        ``op`` in {"sddmm", "spmm", "fused", "fused_twopass",
        "attn_softmax"} or one of ``ATTN_OPS``; ``use_st`` selects the
        transposed tile set (B-output variants). The moving operand
        always rotates along the ``rows`` axis; the stationary operand
        is replicated over the ``cols`` axis. The local tile work goes
        through the tile seam (``_tile_ops``): flat XLA ops, or the
        feature-major Pallas tile kernels when the tile set carries
        chunk lists.
        """
        key = self._program_cache_key(op, use_st)
        if key in self._programs:
            return self._programs[key]

        tiles = self.ST_tiles if use_st else self.S_tiles
        form = self._tile_ops(tiles)
        nr, c = self.nr, self.c
        T, max_nnz = tiles.n_tiles, tiles.max_nnz
        perm = ring_perm(nr)
        unroll = self.unroll
        overlap = self.overlap
        # Wire-precision dtypes per collective role: the moving operand
        # is read-only on every dense-shift ring (ring role), the
        # stationary gather is input data, and the SpMM partial reduce
        # is an accumulation (f32 under the default bf16 policy).
        w_ring = self.wire.dtype_for("ring")
        w_gather = self.wire.dtype_for("gather")
        w_reduce = self.wire.dtype_for("reduce")

        def shift_one(mov):
            return abl_ppermute(mov, "rows", perm, wire=w_ring)

        def shift_mov(state):
            carry, mov = state
            return carry, shift_one(mov)

        def tile_at(arr, s):
            # s is a Python int when unrolled, a traced index when rolled.
            if unroll:
                return arr[s]
            return lax.dynamic_index_in_dim(arr, s, axis=0, keepdims=False)

        def tile(fields, s):
            return form.tile(*(tile_at(f, s) for f in fields))

        def replicate(stat_blk):
            if c == 1:
                return stat_blk
            return abl_all_gather(stat_blk, "cols", axis=0, tiled=True,
                                  size=c, wire=w_gather)

        def reduce_out(acc):
            if c == 1:
                return acc
            return abl_psum_scatter(
                acc, "cols", scatter_dimension=0, tiled=True, size=c,
                wire=w_reduce,
            )

        def squeeze(t):
            return t.reshape(T, max_nnz)

        def unpack(targs):
            *fields, vals = targs
            return form.squeeze(fields, (T,)), squeeze(vals)

        def dvary(x):
            return vary(x, ("rows", "cols"))

        def ring(local, carry, mov, complete_rotation=False):
            """``carry = local(s, carry, mov)`` at each of the ``nr``
            steps; ``complete_rotation`` brings ``mov`` home."""
            if overlap:
                return ring_loop_overlap(
                    nr, local, carry, mov, shift_one,
                    final_shift=complete_rotation, unroll=unroll,
                )

            def body(s, state):
                carry, mov = state
                return local(s, carry, mov), mov

            return ring_loop(
                nr, body, (carry, mov), shift_mov,
                shift_final=shift_mov if complete_rotation else None,
                unroll=unroll,
            )

        def sddmm_pass(x_r, mov, fields, vals, complete_rotation=False):
            def local(s, out_vals, mov):
                return out_vals.at[s].set(form.sddmm(
                    tile(fields, s), tile_at(vals, s), x_r, form.cols(mov)
                ))

            out_vals = dvary(jnp.zeros((T, max_nnz), vals.dtype))
            return ring(local, out_vals, mov, complete_rotation)

        def spmm_pass(mov, fields, vals):
            def local(s, acc, mov):
                return acc + form.spmm(
                    tile(fields, s), tile_at(vals, s), form.cols(mov)
                )

            acc, _ = ring(local, dvary(form.zeros(mov)), mov)
            return reduce_out(form.finish(acc, mov))

        # (stationary, moving, *tile fields, values)
        two_dense_in = (
            _DENSE_SPEC, _DENSE_SPEC, *form.specs(_TILE_SPEC), _TILE_SPEC,
        )
        pair_out = (_DENSE_SPEC, _TILE_SPEC)

        if op == "sddmm":

            def prog(stat, mov, *targs):
                fields, vals = unpack(targs)
                x_r = form.rows(replicate(stat))
                out_vals, _ = sddmm_pass(x_r, mov, fields, vals)
                return out_vals.reshape(1, 1, 1, T, max_nnz)

            in_specs, out_specs = two_dense_in, _TILE_SPEC

        elif op == "spmm":

            def prog(mov, *targs):
                fields, vals = unpack(targs)
                return spmm_pass(mov, fields, vals)

            in_specs = (_DENSE_SPEC, *form.specs(_TILE_SPEC), _TILE_SPEC)
            out_specs = _DENSE_SPEC

        elif op == "fused":
            # fusion 2, "local kernel overlap": SDDMM + SpMM per tile inside
            # one ring traversal (`15D_dense_shift.hpp:199-227`).

            @jax.named_scope("fused")
            def prog(stat, mov, *targs):
                fields, vals = unpack(targs)
                x_r = form.rows(replicate(stat))
                init = (
                    dvary(form.zeros(mov)),
                    dvary(jnp.zeros((T, max_nnz), vals.dtype)),
                )

                def local(s, carry, mov):
                    acc, out_vals = carry
                    return form.fused(
                        tile(fields, s), tile_at(vals, s), x_r,
                        form.cols(mov), acc,
                        lambda mid: out_vals.at[s].set(mid),
                    )

                (acc, out_vals), _ = ring(local, init, mov)
                return (reduce_out(form.finish(acc, mov)),
                        out_vals.reshape(1, 1, 1, T, max_nnz))

            in_specs, out_specs = two_dense_in, pair_out

        elif op in ("fused_twopass", *ATTN_OPS):
            # fusion 1, "replication reuse": one all_gather feeds two ring
            # passes (SDDMM then SpMM) in one compiled program — the
            # functional equivalent of `initial_replicate=false` on the
            # second call (`distributed_sparse.h:296-312`). Attention runs
            # the same two passes with the masked-softmax epilogue between
            # them: the SDDMM pass completes its rotation, so every logit
            # of the device's rows lands before any weight is formed
            # (segment stats + a [rows]-vector merge over the replication
            # axis) — one compiled program, no dense logits materialized.
            # ``attn_v*`` take a value operand of its own width, which the
            # SpMM pass rotates in the keys' place (the keys' pass then
            # need not bring them home); ``attn*_sink`` a per-row sink
            # logit ``[rows, 1]`` in the stationary operand's layout,
            # merged in after the cross-device merge (each device holds
            # only its columns' partial stats).
            with_v = op in ("attn_v", "attn_v_sink")
            with_sink = op.endswith("_sink")
            n_extra = int(with_v) + int(with_sink)

            def prog(stat, mov, *args):
                extra, targs = args[:n_extra], args[n_extra:]
                fields, vals = unpack(targs)
                x_r = form.rows(replicate(stat))
                out_vals, mov = sddmm_pass(
                    x_r, mov, fields, vals, complete_rotation=not with_v
                )
                if op != "fused_twopass":
                    merge = self._merge_stats_cols
                    if with_sink:
                        sink = extra[-1]
                        if c > 1:
                            # f32 on every wire, as the stat merge is.
                            sink = abl_all_gather(sink, "cols", axis=0,
                                                  tiled=True, size=c)
                        sink_stats = form.sink_stats(sink[:, 0])

                        def merge(m, d):
                            return attn_merge_stats(
                                [self._merge_stats_cols(m, d), sink_stats])
                    out_vals = form.softmax(fields, vals, out_vals, merge)
                if with_v:
                    mov = extra[0]
                return (spmm_pass(mov, fields, out_vals),
                        out_vals.reshape(1, 1, 1, T, max_nnz))

            in_specs = (_DENSE_SPEC, _DENSE_SPEC, *([_DENSE_SPEC] * n_extra),
                        *two_dense_in[2:])
            out_specs = pair_out

        elif op == "attn_softmax":
            # Standalone masked softmax over tile-layout logits — the
            # middle stage of the UNFUSED baseline; shares the exact
            # softmax with the fused program so the two paths stay
            # bit-aligned.

            def prog(*targs):
                *fields, vals, logits = targs
                fields = form.squeeze(form.softmax_fields(fields), (T,))
                probs = form.softmax(
                    fields, squeeze(vals), squeeze(logits),
                    self._merge_stats_cols,
                )
                return probs.reshape(1, 1, 1, T, max_nnz)

            in_specs = (*form.specs(_TILE_SPEC), _TILE_SPEC, _TILE_SPEC)
            out_specs = _TILE_SPEC

        else:
            raise ValueError(op)

        return self._shard_program(key, form, prog, in_specs, out_specs)

    def _merge_stats_cols(self, m, d):
        """Cross-device online-softmax merge over the replication axis:
        with c > 1 a row's nonzeros are column-cyclic across the
        ``cols`` devices (which share one stationary row frame), so the
        global max is a pmax and each local denominator rescales into
        it before the psum. Identity at c == 1."""
        if self.c == 1:
            return m, d
        mg = lax.pmax(m, "cols")
        dg = lax.psum(d * jnp.exp(m - mg), "cols")
        return mg, dg

    # ------------------------------------------------------------------ #
    # Public ops
    # ------------------------------------------------------------------ #

    def sddmm_a(self, A, B, s_vals):
        prog = self._program("sddmm", use_st=False)
        return self._timed(
            "sddmmA", prog, A, B, *self._tile_args(self.S_tiles, s_vals)
        )

    def sddmm_b(self, A, B, st_vals):
        prog = self._program("sddmm", use_st=True)
        return self._timed(
            "sddmmB", prog, B, A, *self._tile_args(self.ST_tiles, st_vals)
        )

    def spmm_a(self, A, B, s_vals):
        prog = self._program("spmm", use_st=False)
        return self._timed(
            "spmmA", prog, B, *self._tile_args(self.S_tiles, s_vals)
        )

    def spmm_b(self, A, B, st_vals):
        prog = self._program("spmm", use_st=True)
        return self._timed(
            "spmmB", prog, A, *self._tile_args(self.ST_tiles, st_vals)
        )

    # The raw-program accessors below return ``(f, operands)``: ``f``
    # takes the tile operands as trailing ARGUMENTS, and a jitted caller
    # must pass them as its own arguments too. Arrays a jitted function
    # closes over are baked into its program as constants: at logM=18
    # hundreds of MB of tile structure, which made every app program take
    # a minute to lower and compile on the chip and never hit JAX's
    # compilation cache (PR 21).

    def fused_program(self, s_vals, mode: MatMode = MatMode.A):
        """Public raw-program accessor: ``(f, operands)`` with
        ``f(A, B, *operands) -> (out, mid)`` running one compiled fused
        SDDMM->SpMM pair (no host-side timing wrappers); ``operands`` are
        the tile structure and ``s_vals``."""
        op = "fused" if self.fusion_approach == 2 else "fused_twopass"
        use_st = mode == MatMode.B
        tiles = self.ST_tiles if use_st else self.S_tiles
        prog = self._program(op, use_st)
        operands = self._tile_args(tiles, s_vals)
        if use_st:
            return (lambda A, B, *ops: prog(B, A, *ops)), operands
        return prog, operands

    def _structure(self, use_st: bool) -> tuple:
        """The tile operands of one tile set, values excluded."""
        tiles = self.ST_tiles if use_st else self.S_tiles
        return self._tile_args(tiles, None)[:-1]

    def sddmm_program(self, mode: MatMode = MatMode.A):
        """Raw-program accessor: ``(f, structure)`` with
        ``f(A, B, vals, *structure) -> tile vals`` and no host-side
        timing wrappers — composable inside a larger jitted program (the
        GAT per-layer chain builds logits with this, applies LeakyReLU,
        then aggregates through :meth:`spmm_program`, all in ONE
        compiled program per layer)."""
        use_st = mode == MatMode.B
        prog = self._program("sddmm", use_st)
        if use_st:
            f = lambda A, B, vals, *st: prog(B, A, *st, vals)  # noqa: E731
        else:
            f = lambda A, B, vals, *st: prog(A, B, *st, vals)  # noqa: E731
        return f, self._structure(use_st)

    def spmm_program(self, mode: MatMode = MatMode.A):
        """Raw-program accessor: ``(f, structure)`` with
        ``f(mov, vals, *structure) -> dense`` (``mov`` is the traveling
        operand — B for A-mode output, A for B-mode)."""
        use_st = mode == MatMode.B
        prog = self._program("spmm", use_st)
        return ((lambda mov, vals, *st: prog(mov, *st, vals)),
                self._structure(use_st))

    def fused_spmm(self, A, B, s_vals, mode: MatMode = MatMode.A):
        op = "fused" if self.fusion_approach == 2 else "fused_twopass"
        if mode == MatMode.A:
            prog = self._program(op, use_st=False)
            out, mid = self._timed(
                "fusedSpMM", prog, A, B, *self._tile_args(self.S_tiles, s_vals)
            )
            return out, mid
        prog = self._program(op, use_st=True)
        out, mid = self._timed(
            "fusedSpMM", prog, B, A, *self._tile_args(self.ST_tiles, s_vals),
            _comm_op="fusedSpMMB",
        )
        return out, mid

    # ------------------------------------------------------------------ #
    # Fused block-sparse attention (SDDMM → masked softmax → SpMM)
    # ------------------------------------------------------------------ #

    def fused_attention(self, A, B, s_vals, mode: MatMode = MatMode.A,
                        V=None, sink=None):
        """One compiled program: SDDMM logits at the mask pattern, a
        numerically-stable row-wise masked softmax over the sparse
        values (``s_vals != 0`` is the mask indicator; fully masked
        rows come back all-zero), and the SpMM aggregation — no dense
        logit matrix ever exists. Returns ``(new_dense, probs)`` with
        ``probs`` the attention weights in tile layout. Independent of
        ``fusion_approach`` (the softmax forces the twopass structure:
        a row's denominator needs its complete logit set).

        ``V`` (A mode): the aggregated operand, in B's rows and of its
        own width; by default the keys ``B`` themselves. ``sink``
        (A mode): a per-row sink logit ``[M_pad, 1]`` in A's sharding,
        which joins each row's softmax denominator and carries no
        value."""
        prog, operands = self.attention_program(
            s_vals, mode, v=V is not None, sink=sink is not None)
        extra = tuple(x for x in (V, sink) if x is not None)
        return self._timed(
            "fusedAttn", prog, A, B, *extra, *operands,
            _comm_op="fusedAttnB" if mode == MatMode.B else None,
        )

    @staticmethod
    def _attn_op(mode: MatMode, v: bool, sink: bool) -> str:
        """The fused-attention program's op for its operands."""
        if (v or sink) and mode != MatMode.A:
            raise NotImplementedError(
                "fused attention takes a value operand or sinks in A mode "
                "only (output rows are the mask's rows)")
        return "attn" + ("_v" if v else "") + ("_sink" if sink else "")

    def attention_softmax(self, s_vals, logits, mode: MatMode = MatMode.A):
        """Standalone masked softmax over tile-layout logit values — the
        middle dispatch of the unfused baseline (same softmax code the
        fused program inlines, so fused and unfused agree bitwise)."""
        use_st = mode == MatMode.B
        tiles = self.ST_tiles if use_st else self.S_tiles
        prog = self._program("attn_softmax", use_st)
        return self._timed(
            "attnSoftmax", prog, *self._tile_args(tiles, s_vals), logits
        )

    def attention_unfused(self, A, B, s_vals, mode: MatMode = MatMode.A):
        """The three-program baseline: SDDMM, softmax, SpMM as separate
        dispatches — the logits and weights round-trip through HBM
        twice, which is exactly the counted traffic the fused op
        eliminates (``bench er --app attention`` records both)."""
        mid = (self.sddmm_a if mode == MatMode.A else self.sddmm_b)(
            A, B, s_vals
        )
        probs = self.attention_softmax(s_vals, mid, mode=mode)
        out = (self.spmm_a if mode == MatMode.A else self.spmm_b)(
            A, B, probs
        )
        return out, probs

    def attention_program(self, s_vals, mode: MatMode = MatMode.A,
                          v: bool = False, sink: bool = False):
        """Raw-program accessor: ``(f, operands)`` with
        ``f(A, B, [V,] [sink,] *operands) -> (out, probs)`` running one
        compiled fused-attention dispatch (no host-side timing wrappers
        — models and AOT compiles chain this); ``V`` and ``sink`` as in
        :meth:`fused_attention`, present when ``v`` / ``sink`` say so;
        ``operands`` are the tile structure and ``s_vals``."""
        op = self._attn_op(mode, v, sink)
        use_st = mode == MatMode.B
        tiles = self.ST_tiles if use_st else self.S_tiles
        prog = self._program(op, use_st)
        operands = self._tile_args(tiles, s_vals)
        if use_st:
            return (lambda A, B, *ops: prog(B, A, *ops)), operands
        return prog, operands
