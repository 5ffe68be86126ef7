"""Pallas TPU kernels: one-hot MXU SDDMM / SpMM over blocked chunk lists.

TPUs have no vectorized random-row gather, so the classic SDDMM/SpMM inner
ops (gather A[row], gather B[col], scatter-add into out[row]) are re-cast as
dense matmuls against one-hot selector matrices built on the fly from the
chunk's indices:

    a_rT [R,128]   = A_T_block [R,BM] @ one_hotT [BM,128]      (gather)
    dots [1,128]   = sum_R (a_rT * b_rT) * s_vals              (VPU)
    acc  [R,BM]   += (b_rT * dots) [R,128] @ one_hotT^T        (scatter)

All matmuls are natural / B^T-form MXU contractions; the dense operands are
kept **feature-major** (``[R, rows]``) inside the kernel so no transposed
MXU loads are needed. One-hot selection in bfloat16 is exact (entries are
0/1); only the gathered dense values round to bf16, giving ~1e-3 relative
error in f32-land ("bf16" precision mode; "f32" mode skips the casts at
~4x the MXU cost).

The kernel grid is a 1-D walk over the tile's **active chunk list** (built
host-side by ``ops/blocked.py``): each step processes 128 nonzeros of one
(row_block, col_block) bucket; per-chunk packed metadata is scalar-prefetched
into SMEM and drives the BlockSpec index maps (which dense blocks to DMA)
plus the zero/flush conditionals of the output accumulator. Empty chunks
never run — load imbalance costs padding only inside a 128-lane chunk.

This is the TPU answer to the reference's ``StandardKernel`` hot loops: the
OpenMP COO dot loop (`/root/reference/sparse_kernels.cpp:44-55`) and MKL CSR
SpMM (`sparse_kernels.cpp:94-121`). It plugs into the same boundary
(`sparse_kernels.h:15-79` -> :class:`distributed_sddmm_tpu.ops.kernels.LocalKernel`)
and additionally exposes tile-level fused entry points the distributed
algorithms use for "local kernel overlap" fusion
(`15D_dense_shift.hpp:199-227`).
"""

from __future__ import annotations

import dataclasses
import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from distributed_sddmm_tpu import compat

from distributed_sddmm_tpu.ops import blocked
from distributed_sddmm_tpu.ops.blocked import (
    CHUNK, _GC_SHIFT, _GR_SHIFT, MAX_BLOCKS, unpack_meta,
)
from distributed_sddmm_tpu.ops.kernels import (
    ATTN_NEG, XlaKernel, attn_merge_stats,
)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class BlockedTile:
    """Per-(device, tile) chunk-list view passed into the tile kernels.

    Array fields are the per-bucket slices of :class:`ops.blocked.BlockedMeta`
    uploaded to the device; static fields replicate its geometry.
    """

    lr: jax.Array        # [C, CHUNK] int32
    lc: jax.Array        # [C, CHUNK] int32
    meta: jax.Array      # [C] int32 packed (gr, gc, first, last)
    bm: int = dataclasses.field(metadata=dict(static=True), default=512)
    bn: int = dataclasses.field(metadata=dict(static=True), default=512)
    gr_blocks: int = dataclasses.field(metadata=dict(static=True), default=1)
    gc_blocks: int = dataclasses.field(metadata=dict(static=True), default=1)
    group: int = dataclasses.field(metadata=dict(static=True), default=1)

    @property
    def n_chunks(self) -> int:
        return self.lr.shape[0]

    @property
    def rows_pad(self) -> int:
        return self.gr_blocks * self.bm

    @property
    def cols_pad(self) -> int:
        return self.gc_blocks * self.bn


def _dotg(a, b, ca, cb):
    # f32 operands ask for true-f32 MXU passes; at DEFAULT precision the TPU
    # would silently round them through bf16.
    prec = jax.lax.Precision.HIGHEST if a.dtype == jnp.float32 else None
    return jax.lax.dot_general(
        a, b, (((ca,), (cb,)), ((), ())),
        preferred_element_type=jnp.float32, precision=prec,
    )


def _meta_gr(m, t):
    # Mask like _meta_gc: the arithmetic shift of an int32 word would
    # sign-extend gr >= 16384.
    return (m[t] >> _GR_SHIFT) & (MAX_BLOCKS - 1)


def _meta_gc(m, t):
    return (m[t] >> _GC_SHIFT) & (MAX_BLOCKS - 1)


def _gathered(dense_ref, loc_row):
    """Gather rows of a feature-major block via one-hot MXU.

    ``loc_row`` is ``[1, W]`` (W = CHUNK, or G*CHUNK on the step-batched
    path). Returns ``(one_hotT [block, W], rows_T [R, W])``."""
    ohT = (
        jax.lax.broadcasted_iota(
            jnp.int32, (dense_ref.shape[1], loc_row.shape[1]), 0
        )
        == loc_row
    ).astype(dense_ref.dtype)
    return ohT, _dotg(dense_ref[:], ohT, 1, 0)


def _scattered(scT, ohT_r, loc_row, bm, form):
    """Scatter-add contribution ``[R, W] -> [R, BM]`` via one-hot MXU
    (``W`` = CHUNK, or G*CHUNK on the step-batched path).

    ``form`` selects the contraction orientation: "bt" contracts the gather
    one-hot's lane axis (an A.B^T-shaped dot_general, reusing ``ohT_r``);
    "nt" builds the one-hot already transposed (lane axis = BM) from a
    sublane-relayouted index vector, so the MXU sees a natural A.B
    contraction and Mosaic never has to transpose a [BM, W] operand."""
    if form == "bt":
        return _dotg(scT, ohT_r, 1, 1)
    w = scT.shape[1]
    oh = (
        jax.lax.broadcasted_iota(jnp.int32, (w, bm), 1)
        == loc_row.reshape(w, 1)
    ).astype(scT.dtype)
    return _dotg(scT, oh, 1, 0)


def _lane_concat(ref, G):
    """[1, G, CHUNK] chunk-data block -> [1, G*CHUNK] along lanes."""
    if G == 1:
        return ref[0, 0:1]
    return jnp.concatenate([ref[0, j : j + 1] for j in range(G)], axis=1)


def _gathered_cols(bt_refs, lc_ref, G):
    """Per-sub-chunk moving-side gathers, lane-concatenated to [R, G*CHUNK]
    (each sub-chunk has its own bt window, so these cannot batch)."""
    if G == 1:
        return _gathered(bt_refs[0], lc_ref[0, 0:1])[1]
    return jnp.concatenate(
        [_gathered(bt_refs[j], lc_ref[0, j : j + 1])[1] for j in range(G)],
        axis=1,
    )


def _write_mid(mid_ref, dots, G):
    """Scatter the [1, G*CHUNK] dots row back into the [1, G, CHUNK] mid
    output block, sub-chunk by sub-chunk."""
    for j in range(G):
        mid_ref[0, j : j + 1] = dots[:, j * CHUNK : (j + 1) * CHUNK]


def _step_boundaries(meta_ref, acc_ref, t, G):
    """Step-batched zero/flush: the group alignment of ``build_blocked``
    puts every (bucket, gr) group on whole-step boundaries, so the zero
    flag can only sit on the step's FIRST chunk and the flush flag only on
    its LAST."""

    @pl.when((meta_ref[t * G] & 1) == 1)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    return ((meta_ref[t * G + G - 1] >> 1) & 1) == 1


def _make_fused_body_batched(G, form):
    def body(meta_ref, lr_ref, lc_ref, sv_ref, at_ref, *rest):
        bt_refs = rest[:G]
        out_ref, mid_ref, acc_ref = rest[G], rest[G + 1], rest[G + 2]
        t = pl.program_id(0)
        bm = out_ref.shape[1]
        last = _step_boundaries(meta_ref, acc_ref, t, G)
        lr_all = _lane_concat(lr_ref, G)
        ohT_all, a_rT = _gathered(at_ref, lr_all)
        b_rT = _gathered_cols(bt_refs, lc_ref, G)
        sv_all = _lane_concat(sv_ref, G)
        dots = jnp.sum(a_rT * b_rT, axis=0, keepdims=True) * sv_all
        _write_mid(mid_ref, dots, G)
        scT = (b_rT * dots).astype(at_ref.dtype)
        acc_ref[:] += _scattered(scT, ohT_all, lr_all, bm, form)

        @pl.when(last)
        def _():
            out_ref[:] = acc_ref[:]

    return body


def _make_fused_body_single(G, form):
    """Dense-short-row band body (codegen): every (bucket, gr) group is
    host-proven to span EXACTLY one grid step with no trailing pad
    steps (``codegen.banded._single_step_provable``), so the zero/flush
    conditionals and the VMEM accumulator carry vanish — each step
    writes its output window once, unconditionally. Same arithmetic as
    the batched body (the accumulator add was ``0 + x``)."""

    def body(meta_ref, lr_ref, lc_ref, sv_ref, at_ref, *rest):
        bt_refs = rest[:G]
        out_ref, mid_ref = rest[G], rest[G + 1]
        bm = out_ref.shape[1]
        lr_all = _lane_concat(lr_ref, G)
        ohT_all, a_rT = _gathered(at_ref, lr_all)
        b_rT = _gathered_cols(bt_refs, lc_ref, G)
        sv_all = _lane_concat(sv_ref, G)
        dots = jnp.sum(a_rT * b_rT, axis=0, keepdims=True) * sv_all
        _write_mid(mid_ref, dots, G)
        scT = (b_rT * dots).astype(at_ref.dtype)
        out_ref[:] = _scattered(scT, ohT_all, lr_all, bm, form)

    return body


def _make_spmm_body_single(G, form):
    """SpMM variant of :func:`_make_fused_body_single` (same single-step
    precondition, no accumulator scratch, no scalar conditionals)."""

    def body(meta_ref, lr_ref, lc_ref, sv_ref, *rest):
        bt_refs = rest[:G]
        out_ref = rest[G]
        bm = out_ref.shape[1]
        lr_all = _lane_concat(lr_ref, G)
        b_rT = _gathered_cols(bt_refs, lc_ref, G)
        sv_all = _lane_concat(sv_ref, G)
        scT = (b_rT * sv_all).astype(bt_refs[0].dtype)
        if form == "bt":
            ohT_all = (
                jax.lax.broadcasted_iota(jnp.int32, (bm, G * CHUNK), 0)
                == lr_all
            ).astype(scT.dtype)
        else:
            ohT_all = None
        out_ref[:] = _scattered(scT, ohT_all, lr_all, bm, form)

    return body


def _make_spmm_body_batched(G, form):
    def body(meta_ref, lr_ref, lc_ref, sv_ref, *rest):
        bt_refs = rest[:G]
        out_ref, acc_ref = rest[G], rest[G + 1]
        t = pl.program_id(0)
        bm = out_ref.shape[1]
        last = _step_boundaries(meta_ref, acc_ref, t, G)
        lr_all = _lane_concat(lr_ref, G)
        b_rT = _gathered_cols(bt_refs, lc_ref, G)
        sv_all = _lane_concat(sv_ref, G)
        scT = (b_rT * sv_all).astype(bt_refs[0].dtype)
        if form == "bt":
            ohT_all = (
                jax.lax.broadcasted_iota(
                    jnp.int32, (bm, G * CHUNK), 0
                )
                == lr_all
            ).astype(scT.dtype)
        else:
            ohT_all = None
        acc_ref[:] += _scattered(scT, ohT_all, lr_all, bm, form)

        @pl.when(last)
        def _():
            out_ref[:] = acc_ref[:]

    return body


def _make_sddmm_body_batched(G):
    def body(meta_ref, lr_ref, lc_ref, sv_ref, at_ref, *rest):
        bt_refs = rest[:G]
        mid_ref = rest[G]
        lr_all = _lane_concat(lr_ref, G)
        _, a_rT = _gathered(at_ref, lr_all)
        b_rT = _gathered_cols(bt_refs, lc_ref, G)
        sv_all = _lane_concat(sv_ref, G)
        dots = jnp.sum(a_rT * b_rT, axis=0, keepdims=True) * sv_all
        _write_mid(mid_ref, dots, G)

    return body


def _sub_boundaries(meta_ref, acc_ref, t, G, j):
    """Zero the accumulator at a first-of-row-block sub-chunk and return the
    flush predicate for a last-of-row-block one. With group > 1 the grid
    step never straddles a row-block boundary (``build_blocked``'s gr
    alignment), so the step's output window is valid for every sub-chunk."""
    w = meta_ref[t * G + j]

    @pl.when((w & 1) == 1)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    return ((w >> 1) & 1) == 1


def _make_fused_body(G, form):
    def body(meta_ref, lr_ref, lc_ref, sv_ref, at_ref, *rest):
        bt_refs = rest[:G]
        out_ref, mid_ref, acc_ref = rest[G], rest[G + 1], rest[G + 2]
        t = pl.program_id(0)
        bm = out_ref.shape[1]
        for j in range(G):
            last = _sub_boundaries(meta_ref, acc_ref, t, G, j)
            ohT_r, a_rT = _gathered(at_ref, lr_ref[0, j : j + 1])
            _, b_rT = _gathered(bt_refs[j], lc_ref[0, j : j + 1])
            dots = jnp.sum(a_rT * b_rT, axis=0, keepdims=True) * sv_ref[0, j : j + 1]
            mid_ref[0, j : j + 1] = dots
            scT = (b_rT * dots).astype(bt_refs[j].dtype)
            acc_ref[:] += _scattered(scT, ohT_r, lr_ref[0, j : j + 1], bm, form)

            @pl.when(last)
            def _():
                out_ref[:] = acc_ref[:]

    return body


def _make_sddmm_body(G):
    def body(meta_ref, lr_ref, lc_ref, sv_ref, at_ref, *rest):
        bt_refs = rest[:G]
        mid_ref = rest[G]
        for j in range(G):
            _, a_rT = _gathered(at_ref, lr_ref[0, j : j + 1])
            _, b_rT = _gathered(bt_refs[j], lc_ref[0, j : j + 1])
            mid_ref[0, j : j + 1] = (
                jnp.sum(a_rT * b_rT, axis=0, keepdims=True) * sv_ref[0, j : j + 1]
            )

    return body


def _make_spmm_body(G, form):
    def body(meta_ref, lr_ref, lc_ref, sv_ref, *rest):
        bt_refs = rest[:G]
        out_ref, acc_ref = rest[G], rest[G + 1]
        t = pl.program_id(0)
        bm = out_ref.shape[1]
        for j in range(G):
            last = _sub_boundaries(meta_ref, acc_ref, t, G, j)
            _, b_rT = _gathered(bt_refs[j], lc_ref[0, j : j + 1])
            if form == "bt":
                ohT_r = (
                    jax.lax.broadcasted_iota(jnp.int32, (bm, CHUNK), 0)
                    == lr_ref[0, j : j + 1]
                ).astype(bt_refs[j].dtype)
            else:
                ohT_r = None  # "nt" builds its one-hot inside _scattered
            scT = (b_rT * sv_ref[0, j : j + 1]).astype(bt_refs[j].dtype)
            acc_ref[:] += _scattered(scT, ohT_r, lr_ref[0, j : j + 1], bm, form)

            @pl.when(last)
            def _():
                out_ref[:] = acc_ref[:]

    return body


# ------------------------------------------------------------------ #
# Masked-softmax attention epilogue kernels (chunk-list layout).
#
# The SDDMM mid values ARE the sparse attention logits; these kernels
# turn them into row-stochastic weights between the SDDMM and SpMM
# stages without materializing any dense [rows, cols] intermediate.
# Two launches ride the SAME chunk-list metadata the pair kernels use:
#
# * ``attn_reduce`` — streaming per-row max + denominator over each
#   (bucket, row block) group's chunks: two (bm, 1) VMEM scratches
#   carry the running max ``m`` and the rescaled denominator
#   ``d ← d·exp(m_old − m_new) + Σ exp(z − m_new)`` (the online-softmax
#   recurrence), zeroed/flushed on the group's first/last flags exactly
#   like the pair accumulator. Bands whose metadata proves one grid
#   step per row-block group get the PROVABLY-ONE-PASS body: no
#   scratch, no flags — each step computes its window's stats from its
#   own lanes and writes them once, unconditionally (the epilogue
#   counterpart of the conditional-free single-step pair bodies).
# * ``attn_norm`` — a pure map: gather each lane's row stats from the
#   (bm, 1) blocks via the one-hot row selector and emit
#   ``exp(z − m) / d`` (0 at masked lanes, pads, and d == 0 rows).
#
# Everything is VPU work in the [bm, W] orientation (lane-axis chunk
# entries vs sublane-axis rows): sublane/lane reductions and broadcasts
# only — no transposes, no MXU passes, so Mosaic lowers it next to the
# pair kernels it fuses with. The mask indicator is ``gate != 0`` where
# ``gate`` is the ORIGINAL value vector (pad lanes carry 0 by the
# TileSet contract; a zero mask value means "masked out" — logits that
# are legitimately 0.0 stay in).
# ------------------------------------------------------------------ #


def _attn_sel(lr_all, gv, bm):
    """One-hot row selector [bm, W] and its mask-gated refinement."""
    ohT = (
        jax.lax.broadcasted_iota(jnp.int32, (bm, lr_all.shape[1]), 0)
        == lr_all
    )
    return ohT, ohT & (gv != 0)


def _attn_chunk_stats(sel, zv, m_prev):
    """Streaming update from one grid step's lanes: returns
    ``(m_new [bm, 1], csum [bm, 1])`` where ``csum`` sums
    ``exp(z − m_new)`` over the step's selected lanes per row."""
    neg = jnp.float32(ATTN_NEG)
    zb = jnp.where(sel, zv, neg)                      # [bm, W]
    m_new = jnp.maximum(m_prev, jnp.max(zb, axis=1, keepdims=True))
    e = jnp.where(sel, jnp.exp(zb - m_new), 0.0)
    return m_new, jnp.sum(e, axis=1, keepdims=True)


def _make_attn_reduce_body(G):
    def body(meta_ref, lr_ref, gv_ref, zv_ref, m_out, d_out, m_acc, d_acc):
        t = pl.program_id(0)
        bm = m_out.shape[0]

        @pl.when((meta_ref[t * G] & 1) == 1)
        def _():
            m_acc[:] = jnp.full_like(m_acc, jnp.float32(ATTN_NEG))
            d_acc[:] = jnp.zeros_like(d_acc)

        last = ((meta_ref[t * G + G - 1] >> 1) & 1) == 1
        lr_all = _lane_concat(lr_ref, G)
        _, sel = _attn_sel(lr_all, _lane_concat(gv_ref, G), bm)
        m_old = m_acc[:]
        m_new, csum = _attn_chunk_stats(sel, _lane_concat(zv_ref, G), m_old)
        d_acc[:] = d_acc[:] * jnp.exp(m_old - m_new) + csum
        m_acc[:] = m_new

        @pl.when(last)
        def _():
            m_out[:] = m_acc[:]
            d_out[:] = d_acc[:]

    return body


def _make_attn_reduce_body_single(G):
    """One-pass epilogue variant: the band's metadata proves every
    (bucket, row block) group spans exactly ONE grid step with no
    trailing pad chunks (``codegen.banded._single_step_provable``), so
    the running-stat scratch and the zero/flush conditionals vanish —
    each step derives its window's max/denominator from its own lanes
    and writes both outputs once, unconditionally."""

    def body(meta_ref, lr_ref, gv_ref, zv_ref, m_out, d_out):
        bm = m_out.shape[0]
        lr_all = _lane_concat(lr_ref, G)
        _, sel = _attn_sel(lr_all, _lane_concat(gv_ref, G), bm)
        m0 = jnp.full((bm, 1), jnp.float32(ATTN_NEG))
        m_new, csum = _attn_chunk_stats(sel, _lane_concat(zv_ref, G), m0)
        m_out[:] = m_new
        d_out[:] = csum

    return body


def _make_attn_norm_body(G):
    def body(meta_ref, lr_ref, gv_ref, zv_ref, m_ref, d_ref, p_out):
        bm = m_ref.shape[0]
        neg = jnp.float32(ATTN_NEG)
        lr_all = _lane_concat(lr_ref, G)
        gv = _lane_concat(gv_ref, G)
        zv = _lane_concat(zv_ref, G)
        ohT, _ = _attn_sel(lr_all, gv, bm)
        # Per-lane row-stat gather via the one-hot selector: each lane
        # belongs to exactly one row, so a masked sublane max/sum pulls
        # its m/d into lane orientation without any transpose.
        m_g = jnp.max(jnp.where(ohT, m_ref[:], neg), axis=0, keepdims=True)
        d_g = jnp.sum(jnp.where(ohT, d_ref[:], 0.0), axis=0, keepdims=True)
        ok = (gv != 0) & (d_g > 0)                         # [1, W]
        # exp on the select-guarded argument: a masked lane's raw
        # ``z − m`` can overflow to +inf before the select otherwise.
        e = jnp.exp(jnp.where(ok, zv - m_g, 0.0))
        p = jnp.where(ok, e / jnp.where(ok, d_g, 1.0), 0.0)
        _write_mid(p_out, p, G)

    return body


#: Chunks whose packed metadata one kernel launch prefetches into scalar
#: memory (SMEM). The metadata is one int32 per chunk and a v5e core has
#: 1 MiB of SMEM, which 2^18 chunks fill exactly (the logM=18 R-mat at
#: 512x512 blocks is 262,144 chunks, and Mosaic refuses it). A longer
#: chunk list runs as consecutive launches of at most this many chunks
#: (:func:`_tile_call`, :func:`_attn_call`).
MAX_PREFETCH_CHUNKS = 1 << 17


def _segment_steps(group: int) -> int:
    """Grid steps of ``group`` chunks that one launch walks: a chunk
    list longer than :data:`MAX_PREFETCH_CHUNKS` runs as several."""
    return max(MAX_PREFETCH_CHUNKS // group, 1)


def launches(n_chunks: int, group: int) -> int:
    """Launches one chunk-list kernel op makes over ``n_chunks`` chunks
    (:func:`_tile_call`, :func:`_attn_call`)."""
    steps = max(n_chunks // group, 1)
    return -(-steps // _segment_steps(group))


def _open_close(m):
    """A segment's metadata ``m`` with its first chunk opening and its
    last closing the accumulator (set in turn, so that a one-chunk
    segment carries both flags)."""
    m = m.at[0].set(m[0] | 1)
    return m.at[-1].set(m[-1] | 2)


def _flushed(m, gr_blocks, single_step):
    """``[gr_blocks]`` bool: the row blocks a segment with metadata ``m``
    wrote, every step's window in the single-step bodies and the flushed
    groups' windows otherwise (the rest of its output is never
    written)."""
    wrote = jnp.ones(m.shape, bool) if single_step else ((m >> 1) & 1) == 1
    return jnp.zeros(gr_blocks, bool).at[_meta_gr(m, slice(None))].max(wrote)


def kernel_name(op: str) -> str:
    """A launch's name, ``<op>_tiles``: the kernel's Mosaic name and the
    name of its custom-call instruction in compiled HLO and in a device
    trace (``sddmm_tiles.3``), so a trace tells the ``fused``, ``sddmm``,
    ``spmm``, ``attn_reduce`` and ``attn_norm`` kernels apart."""
    return f"{op}_tiles"


@functools.partial(
    jax.jit,
    static_argnames=("op", "bm", "gr_blocks", "group", "interpret",
                     "single_step"),
)
def _attn_call(
    meta, lr, gv, zv, m, d, op, bm, gr_blocks, group, interpret,
    single_step=False,
):
    """Launch one attention-epilogue kernel over a chunk list.

    ``gv`` is the ORIGINAL (mask) value vector and ``zv`` the SDDMM
    logits, both in chunk layout [C, CHUNK]; ``m``/``d`` are the merged
    (rows_pad, 1) row stats (``attn_norm`` only). Returns ``(m, d)``
    for ``attn_reduce``, the normalized chunk values for ``attn_norm``.

    A chunk list longer than :data:`MAX_PREFETCH_CHUNKS` runs as one
    launch per segment of at most that many chunks, as
    :func:`_tile_call` does. An ``attn_reduce`` segment closes its
    running stats at its own ends, so a row-block group cut by a segment
    boundary leaves partial stats on each side; each segment's stats
    count only at the row blocks it flushed, and the segments' stats
    merge by :func:`ops.kernels.attn_merge_stats`. An ``attn_norm``
    segment writes its own chunks."""
    C = lr.shape[0]
    G = group
    if C % G:
        raise ValueError(f"chunk count {C} not a multiple of group {G}")
    steps = C // G
    lr3 = lr.reshape(steps, G, CHUNK)
    gv3 = gv.reshape(steps, G, CHUNK)
    zv3 = zv.reshape(steps, G, CHUNK)

    md_spec = pl.BlockSpec((bm, 1), lambda t, mm: (_meta_gr(mm, t * G), 0))
    md_shape = jax.ShapeDtypeStruct((gr_blocks * bm, 1), jnp.float32)

    if op == "attn_reduce":
        if single_step:
            body, scratch = _make_attn_reduce_body_single(G), []
        else:
            body = _make_attn_reduce_body(G)
            scratch = [pltpu.VMEM((bm, 1), jnp.float32),
                       pltpu.VMEM((bm, 1), jnp.float32)]
        operands = (lr3, gv3, zv3)
    elif op == "attn_norm":
        body, scratch = _make_attn_norm_body(G), []
        operands = (lr3, gv3, zv3, m, d)
    else:
        raise ValueError(op)

    def launch(mm, s0, n):
        """Steps ``[s0, s0 + n)`` with ``mm`` = their metadata."""
        if s0:
            chunk_in = pl.BlockSpec((1, G, CHUNK),
                                    lambda t, mm: (t + s0, 0, 0))
        else:
            chunk_in = pl.BlockSpec((1, G, CHUNK), lambda t, mm: (t, 0, 0))
        if op == "attn_reduce":
            in_specs = [chunk_in, chunk_in, chunk_in]
            out_specs, out_shapes = [md_spec, md_spec], [md_shape, md_shape]
        else:
            in_specs = [chunk_in, chunk_in, chunk_in, md_spec, md_spec]
            out_specs = [pl.BlockSpec((1, G, CHUNK), lambda t, mm: (t, 0, 0))]
            out_shapes = [jax.ShapeDtypeStruct((n, G, CHUNK), jnp.float32)]
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n,),
            in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=scratch,
        )
        outs = pl.pallas_call(
            body,
            grid_spec=grid_spec,
            out_shape=out_shapes,
            compiler_params=compat.pallas_tpu_compiler_params(
                dimension_semantics=("arbitrary",)
            ),
            interpret=interpret,
            name=kernel_name(op),
        )(mm, *operands)
        return outs if op == "attn_reduce" else outs[0]

    seg = _segment_steps(G)
    if steps <= seg:
        return launch(meta, 0, steps)

    parts = []
    for s0 in range(0, steps, seg):
        n = min(seg, steps - s0)
        mm = meta[s0 * G:(s0 + n) * G]
        if op == "attn_norm":
            parts.append(launch(mm, s0, n))
            continue
        if not single_step:
            mm = _open_close(mm)
        ms, ds = launch(mm, s0, n)
        rows = jnp.repeat(_flushed(mm, gr_blocks, single_step), bm)[:, None]
        parts.append((jnp.where(rows, ms, jnp.float32(ATTN_NEG)),
                      jnp.where(rows, ds, 0.0)))
    if op == "attn_norm":
        return jnp.concatenate(parts, axis=0)
    return attn_merge_stats(parts)


@functools.partial(
    jax.jit,
    static_argnames=(
        "op", "bm", "bn", "gr_blocks", "gc_blocks", "group", "interpret",
        "scatter_form", "batch_step", "single_step",
    ),
)
def _tile_call(
    meta, lr, lc, sv, at, bt, op, bm, bn, gr_blocks, gc_blocks, group,
    interpret, scatter_form="bt", batch_step=False, single_step=False,
):
    """Launch one chunk-list kernel. ``at``/``bt`` are feature-major padded
    dense operands [R, gr_blocks*bm] / [R, gc_blocks*bn]; ``sv`` is the
    chunk-layout values [C, CHUNK]. The grid walks ``group`` chunks per step
    (one semaphore round-trip and one chunk-data DMA amortized over G
    chunks); each sub-chunk gets its own bt window via a per-sub-chunk
    BlockSpec, while the at/out windows are shared (gr-aligned groups).
    Returns op-dependent outputs.

    A chunk list longer than :data:`MAX_PREFETCH_CHUNKS` runs as one
    launch per segment of at most that many chunks. Each segment flushes
    its accumulator at its own ends (its first chunk zeroes, its last
    flushes), so a row-block group cut by a segment boundary leaves one
    partial sum in each side's output; a segment's output is read only
    at the row blocks it flushed (the rest of it is never written), and
    the partials of a block add up."""
    C = lr.shape[0]
    G = group
    if C % G:
        raise ValueError(f"chunk count {C} not a multiple of group {G}")
    steps = C // G
    R = bt.shape[0]
    lr3 = lr.reshape(steps, G, CHUNK)
    lc3 = lc.reshape(steps, G, CHUNK)
    sv3 = sv.reshape(steps, G, CHUNK)

    at_spec = pl.BlockSpec((R, bm), lambda t, m: (0, _meta_gr(m, t * G)))
    bt_specs = [
        pl.BlockSpec((R, bn), (lambda j: lambda t, m: (0, _meta_gc(m, t * G + j)))(j))
        for j in range(G)
    ]
    out_spec = pl.BlockSpec((R, bm), lambda t, m: (0, _meta_gr(m, t * G)))
    out_shape = jax.ShapeDtypeStruct((R, gr_blocks * bm), jnp.float32)

    if op == "fused":
        if single_step:
            body, scratch = _make_fused_body_single(G, scatter_form), []
        else:
            body = (
                _make_fused_body_batched if batch_step else _make_fused_body
            )(G, scatter_form)
            scratch = [pltpu.VMEM((R, bm), jnp.float32)]
        dense_specs, dense = [at_spec, *bt_specs], (at, *([bt] * G))
    elif op == "sddmm":
        body = (
            _make_sddmm_body_batched(G) if batch_step else _make_sddmm_body(G)
        )
        dense_specs, dense, scratch = [at_spec, *bt_specs], (at, *([bt] * G)), []
    elif op == "spmm":
        if single_step:
            body, scratch = _make_spmm_body_single(G, scatter_form), []
        else:
            body = (
                _make_spmm_body_batched if batch_step else _make_spmm_body
            )(G, scatter_form)
            scratch = [pltpu.VMEM((R, bm), jnp.float32)]
        dense_specs, dense = list(bt_specs), tuple([bt] * G)
    else:
        raise ValueError(op)

    params = {"dimension_semantics": ("arbitrary",)}
    vmem = blocked.tile_vmem_bytes(bm, bn, R, G, bt.dtype.itemsize,
                                   batch_step or single_step)
    if vmem > blocked.DEFAULT_SCOPED_VMEM:
        params["vmem_limit_bytes"] = max(blocked.TILE_VMEM_LIMIT, vmem)

    def launch(m, s0, n):
        """Steps ``[s0, s0 + n)`` with ``m`` = their metadata."""
        chunk_in = pl.BlockSpec((1, G, CHUNK), lambda t, m: (t + s0, 0, 0))
        chunk_out = pl.BlockSpec((1, G, CHUNK), lambda t, m: (t, 0, 0))
        mid_shape = jax.ShapeDtypeStruct((n, G, CHUNK), jnp.float32)
        out_specs, out_shapes = {
            "fused": ([out_spec, chunk_out], [out_shape, mid_shape]),
            "sddmm": ([chunk_out], [mid_shape]),
            "spmm": ([out_spec], [out_shape]),
        }[op]
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n,),
            in_specs=[chunk_in, chunk_in, chunk_in, *dense_specs],
            out_specs=out_specs,
            scratch_shapes=scratch,
        )
        return pl.pallas_call(
            body,
            grid_spec=grid_spec,
            out_shape=out_shapes,
            compiler_params=compat.pallas_tpu_compiler_params(**params),
            interpret=interpret,
            name=kernel_name(op),
        )(m, lr3, lc3, sv3, *dense)

    seg = _segment_steps(G)
    if steps <= seg:
        return launch(meta, 0, steps)

    outs, mids = [], []
    for s0 in range(0, steps, seg):
        n = min(seg, steps - s0)
        m = meta[s0 * G:(s0 + n) * G]
        if op != "sddmm" and not single_step:
            m = _open_close(m)
        res = launch(m, s0, n)
        if op != "spmm":
            mids.append(res[-1])
        if op != "sddmm":
            flushed = _flushed(m, gr_blocks, single_step)
            part = res[0].reshape(R, gr_blocks, bm)
            outs.append(jnp.where(flushed[None, :, None], part, 0.0))
    result = []
    if op != "sddmm":
        result.append(sum(outs[1:], outs[0]).reshape(R, gr_blocks * bm))
    if op != "spmm":
        result.append(jnp.concatenate(mids, axis=0))
    return result


def _flat_indices(geom, meta, lr, lc):
    """Device-side reconstruction of the chunk lanes' block-frame indices:
    ``rows`` address the ``at``/output frame, ``cols`` the ``bt`` frame
    (whatever the encoding's orientation)."""
    bm, bn = geom[0], geom[1]
    gr, gc, _, _ = unpack_meta(meta)
    rows = (gr[:, None] * bm + lr).reshape(-1)
    cols = (gc[:, None] * bn + lc).reshape(-1)
    return rows, cols


# Differentiable tile ops: forward runs the Mosaic kernel, backward runs XLA
# gather/segment-sum formulas over indices reconstructed from the chunk
# metadata. Pad lanes contribute nothing to dense cotangents because value
# vectors are zero there (the TileSet mask contract); their d_sv entries are
# don't-cares that the pad positions of value vectors absorb. The integer
# metadata arrays are explicit arguments with float0 cotangents (custom_vjp
# must not close over tracers); ``geom`` = (bm, bn, gr_blocks, gc_blocks,
# group, interpret, scatter_form, batch_step, single_step) rides in
# nondiff_argnums (``single_step`` selects the codegen direct-write body).


def _geom_call(geom, op, meta, lr, lc, sv, at, bt):
    bm, bn, grb, gcb, group, interpret, form, batch, single = geom
    return tuple(
        _tile_call(
            meta, lr, lc, sv, at, bt, op=op, bm=bm, bn=bn,
            gr_blocks=grb, gc_blocks=gcb, group=group, interpret=interpret,
            scatter_form=form, batch_step=batch, single_step=single,
        )
    )


def _int_zeros(*arrays):
    import numpy as onp

    return tuple(onp.zeros(a.shape, dtype=jax.dtypes.float0) for a in arrays)


def _seg_t(contrib, idx, n, dtype):
    """Scatter-add [nnz_flat, R] rows -> feature-major [R, n]."""
    return jax.ops.segment_sum(contrib, idx, num_segments=n).T.astype(dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _sddmm_op(geom, meta, lr, lc, sv, at, bt):
    return _geom_call(geom, "sddmm", meta, lr, lc, sv, at, bt)[0]


def _sddmm_fwd(geom, meta, lr, lc, sv, at, bt):
    return _sddmm_op(geom, meta, lr, lc, sv, at, bt), (meta, lr, lc, sv, at, bt)


def _sddmm_bwd(geom, res, g):
    meta, lr, lc, sv, at, bt = res
    rows, cols = _flat_indices(geom, meta, lr, lc)
    a_g = at.T.astype(jnp.float32)[rows]
    b_g = bt.T.astype(jnp.float32)[cols]
    dots = jnp.sum(a_g * b_g, axis=-1)
    gf = g.reshape(-1).astype(jnp.float32)
    gs = (gf * sv.reshape(-1).astype(jnp.float32))[:, None]
    return _int_zeros(meta, lr, lc) + (
        (gf * dots).reshape(sv.shape).astype(sv.dtype),
        _seg_t(gs * b_g, rows, at.shape[1], at.dtype),
        _seg_t(gs * a_g, cols, bt.shape[1], bt.dtype),
    )


_sddmm_op.defvjp(_sddmm_fwd, _sddmm_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _spmm_op(geom, meta, lr, lc, sv, bt):
    return _geom_call(geom, "spmm", meta, lr, lc, sv, None, bt)[0]


def _spmm_fwd(geom, meta, lr, lc, sv, bt):
    return _spmm_op(geom, meta, lr, lc, sv, bt), (meta, lr, lc, sv, bt)


def _spmm_bwd(geom, res, g):
    meta, lr, lc, sv, bt = res
    rows, cols = _flat_indices(geom, meta, lr, lc)
    g_rows = g.T.astype(jnp.float32)[rows]
    b_g = bt.T.astype(jnp.float32)[cols]
    svf = sv.reshape(-1).astype(jnp.float32)[:, None]
    return _int_zeros(meta, lr, lc) + (
        jnp.sum(g_rows * b_g, axis=-1).reshape(sv.shape).astype(sv.dtype),
        _seg_t(svf * g_rows, cols, bt.shape[1], bt.dtype),
    )


_spmm_op.defvjp(_spmm_fwd, _spmm_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _fused_op(geom, meta, lr, lc, sv, at, bt):
    return _geom_call(geom, "fused", meta, lr, lc, sv, at, bt)


def _fused_fwd(geom, meta, lr, lc, sv, at, bt):
    outT, mid = _fused_op(geom, meta, lr, lc, sv, at, bt)
    return (outT, mid), (meta, lr, lc, sv, at, bt, mid)


def _fused_bwd(geom, res, cts):
    meta, lr, lc, sv, at, bt, mid = res
    g_out, g_mid = cts
    rows, cols = _flat_indices(geom, meta, lr, lc)
    a_g = at.T.astype(jnp.float32)[rows]
    b_g = bt.T.astype(jnp.float32)[cols]
    dots = jnp.sum(a_g * b_g, axis=-1)
    g_out_rows = g_out.T.astype(jnp.float32)[rows]
    # out = spmm(mid, bt) with mid = sv * dots: fold out's cotangent into mid's.
    g_mid_eff = g_mid.reshape(-1).astype(jnp.float32) + jnp.sum(
        g_out_rows * b_g, axis=-1
    )
    gs = (g_mid_eff * sv.reshape(-1).astype(jnp.float32))[:, None]
    midf = mid.reshape(-1).astype(jnp.float32)[:, None]
    return _int_zeros(meta, lr, lc) + (
        (g_mid_eff * dots).reshape(sv.shape).astype(sv.dtype),
        _seg_t(gs * b_g, rows, at.shape[1], at.dtype),
        _seg_t(gs * a_g + midf * g_out_rows, cols, bt.shape[1], bt.dtype),
    )


_fused_op.defvjp(_fused_fwd, _fused_bwd)


class PallasKernel:
    """TPU-native local kernel (one-hot MXU formulation).

    Its entry points are the blocked tile-level ones (``sddmm_tile`` /
    ``spmm_tile`` / ``fused_tile`` and their feature-major ``*_t``
    forms); the distributed algorithms call the ``*_t`` forms through
    :class:`BlockedTiles` on tile sets carrying blocked metadata. The flat
    :class:`~distributed_sddmm_tpu.ops.kernels.LocalKernel` protocol runs
    XLA formulas, and only in interpreter mode (CPU test meshes): on the
    TPU a tile set without blocked metadata raises instead of running XLA
    under the Pallas name.

    ``precision``: "bf16" (exact one-hot selection, dense values rounded to
    bf16) or "f32" (full f32 MXU, ~4x slower). Default: bf16 on TPU, f32 in
    interpreter mode (CPU executors lack bf16 matmuls).
    ``interpret``: run in the Pallas interpreter (CPU test meshes).
    ``scatter_form``: "bt" (reuse the gather one-hot, A.B^T contraction) or
    "nt" (build a transposed one-hot, natural A.B contraction); identical
    numerics, different Mosaic lowering — ``scripts/tune_blocks.py`` probes
    which is faster on hardware. Env default: ``DSDDMM_SCATTER_FORM``.
    ``batch_step``: batch the stationary-side gather and the scatter across
    a grid step's G sub-chunks into single [.., G*CHUNK]-wide MXU ops
    (legal because group alignment pins a step inside one row-block
    window); identical numerics. Env default: ``DSDDMM_BATCH_STEP``, on
    unless set to 0.
    """

    is_blocked = True
    #: Codegen specialization id carried by subclasses
    #: (``codegen.kernel.BankedPallasKernel``); None = the generic
    #: one-shape-fits-all kernel. Rides into program-store keys
    #: (``parallel/base._program_cache_key``) and bench records.
    variant_id: str | None = None
    variant = None

    def __init__(
        self,
        precision: str | None = None,
        interpret: bool | None = None,
        scatter_form: str | None = None,
        batch_step: bool | None = None,
    ):
        if interpret is None:
            interpret = jax.default_backend() != "tpu"
        self.interpret = interpret
        if precision is None:
            precision = "f32" if interpret else "bf16"
        if precision not in ("bf16", "f32"):
            raise ValueError(f"precision must be 'bf16' or 'f32', got {precision!r}")
        if scatter_form is None:
            # Construction-time env read (docstring contract), falling back
            # to blocked.py's import-time snapshot — the one home for every
            # kernel-knob default.
            scatter_form = os.environ.get(
                "DSDDMM_SCATTER_FORM", blocked.DEFAULT_SCATTER_FORM)
        if scatter_form not in ("bt", "nt"):
            raise ValueError(f"scatter_form must be 'bt' or 'nt', got {scatter_form!r}")
        if batch_step is None:
            raw = os.environ.get("DSDDMM_BATCH_STEP")
            batch_step = (raw not in ("", "0")) if raw is not None \
                else blocked.DEFAULT_BATCH_STEP
        self.precision = precision
        self.scatter_form = scatter_form
        self.batch_step = bool(batch_step)
        self._xla = XlaKernel()
        self.name = f"pallas-{precision}"

    # ------------- flat protocol (XLA, interpreter mode only) ------------ #

    def _flat(self) -> XlaKernel:
        if not self.interpret:
            raise RuntimeError(
                f"{self.name}: this tile set has no blocked (chunk-list) "
                "metadata, so the Pallas kernel cannot run it, and on the "
                "TPU it does not fall back to XLA under its own name. The "
                "blocked build was skipped (see parallel/sharding."
                "_try_build_blocked); pass kernel='xla' to run it on XLA."
            )
        return self._xla

    def sddmm(self, rows, cols, vals, A, B):
        return self._flat().sddmm(rows, cols, vals, A, B)

    def spmm(self, rows, cols, vals, B, out_rows: int):
        return self._flat().spmm(rows, cols, vals, B, out_rows)

    # ----------------------- blocked tile protocol ----------------------- #

    def _mxu_dtype(self):
        return jnp.bfloat16 if self.precision == "bf16" else jnp.float32

    def prep(self, X: jax.Array, rows_pad: int) -> jax.Array:
        """[rows, R] -> padded feature-major [R, rows_pad] in MXU dtype.

        Use for both operands: pad the output-side/stationary one to
        ``blk.rows_pad`` (hoist out of ring loops) and the gathered/moving
        one to ``blk.cols_pad`` (per ring step)."""
        pad = rows_pad - X.shape[0]
        Xp = jnp.pad(X, ((0, pad), (0, 0))) if pad else X
        return Xp.T.astype(self._mxu_dtype())

    def _chunk_vals(self, blk: BlockedTile, vals: jax.Array) -> jax.Array:
        """Flat [C * CHUNK] values -> [C, CHUNK]: the flat layout IS the
        chunk layout (pad lanes hold zero by the TileSet mask contract)."""
        return vals.reshape(blk.n_chunks, CHUNK).astype(jnp.float32)

    def _unchunk(self, blk: BlockedTile, chunked: jax.Array, dtype) -> jax.Array:
        """Chunk layout [C, 1, CHUNK] -> flat [C * CHUNK]."""
        return chunked.reshape(-1).astype(dtype)

    def sddmm_tile(self, blk: BlockedTile, vals, A, B):
        """Tile-level SDDMM: returns flat [max_nnz] ``vals * dots``."""
        at = self.prep(A, blk.rows_pad)
        bt = self.prep(B, blk.cols_pad)
        return self.sddmm_tile_t(blk, vals, at, bt, vals.dtype)

    def _geom(self, blk: BlockedTile) -> tuple:
        return (
            blk.bm, blk.bn, blk.gr_blocks, blk.gc_blocks, blk.group,
            self.interpret, self.scatter_form, self.batch_step, False,
        )

    def sddmm_tile_t(self, blk: BlockedTile, vals, at, bt, out_dtype):
        """Feature-major variant (operands already via ``prep``)."""
        mid = _sddmm_op(
            self._geom(blk), blk.meta, blk.lr, blk.lc,
            self._chunk_vals(blk, vals), at, bt,
        )
        return self._unchunk(blk, mid, out_dtype)

    def spmm_tile(self, blk: BlockedTile, vals, B, out_rows: int):
        """Tile-level SpMM partial: returns [out_rows, R] dense."""
        bt = self.prep(B, blk.cols_pad)
        outT = self.spmm_tile_t(blk, vals, bt)
        return outT.T[:out_rows].astype(B.dtype)

    def spmm_tile_t(self, blk: BlockedTile, vals, bt):
        """Feature-major variant: returns padded [R, rows_pad] f32 partial."""
        return _spmm_op(
            self._geom(blk), blk.meta, blk.lr, blk.lc,
            self._chunk_vals(blk, vals), bt,
        )

    def fused_tile(self, blk: BlockedTile, vals, A, B):
        """SDDMM -> SpMM with shared gathers ("local kernel overlap").

        Returns ``(partial [A_rows, R], mid_flat [max_nnz])``."""
        at = self.prep(A, blk.rows_pad)
        bt = self.prep(B, blk.cols_pad)
        outT, mid = self.fused_tile_t(blk, vals, at, bt, vals.dtype)
        return outT.T[: A.shape[0]].astype(A.dtype), mid

    def fused_tile_t(self, blk: BlockedTile, vals, at, bt, out_dtype):
        outT, mid = _fused_op(
            self._geom(blk), blk.meta, blk.lr, blk.lc,
            self._chunk_vals(blk, vals), at, bt,
        )
        return outT, self._unchunk(blk, mid, out_dtype)

    # ---------------- masked-softmax attention epilogue ---------------- #

    def attn_stats_tile_t(self, blk: BlockedTile, gate_vals, logit_vals):
        """Per-row masked-softmax stats ``(m, d)``, each
        ``[rows_pad, 1]`` f32, for one blocked tile's chunk values
        (``gate_vals`` = the original mask values, ``logit_vals`` = the
        SDDMM output). Partial by construction — tiles/devices merge
        via :func:`ops.kernels.attn_merge_stats`."""
        return _attn_call(
            blk.meta, blk.lr,
            self._chunk_vals(blk, gate_vals),
            self._chunk_vals(blk, logit_vals),
            None, None, op="attn_reduce", bm=blk.bm,
            gr_blocks=blk.gr_blocks, group=blk.group,
            interpret=self.interpret,
        )

    def attn_norm_tile_t(self, blk: BlockedTile, gate_vals, logit_vals,
                         m, d, out_dtype):
        """Normalized attention weights (flat [max_nnz]) from the MERGED
        row stats."""
        probs = _attn_call(
            blk.meta, blk.lr,
            self._chunk_vals(blk, gate_vals),
            self._chunk_vals(blk, logit_vals),
            m, d, op="attn_norm", bm=blk.bm,
            gr_blocks=blk.gr_blocks, group=blk.group,
            interpret=self.interpret,
        )
        return self._unchunk(blk, probs, out_dtype)

    # Flat-protocol attention softmax (interpreter mode only, like
    # sddmm/spmm).

    def attn_stats(self, rows, gate, logits, out_rows: int):
        return self._flat().attn_stats(rows, gate, logits, out_rows)

    def attn_normalize(self, rows, gate, logits, m, d):
        return self._flat().attn_normalize(rows, gate, logits, m, d)

    def attn_softmax(self, rows, gate, logits, out_rows: int):
        return self._flat().attn_softmax(rows, gate, logits, out_rows)


class BlockedTiles:
    """The blocked form of the tile seam the ring programs call: a tile
    is its chunk list ``(lr, lc, meta)`` and the local ops are the
    feature-major Pallas tile kernels. Same operations and meanings as
    :class:`~distributed_sddmm_tpu.ops.kernels.FlatTiles`; what differs
    is the data: dense operands enter through :meth:`rows` / :meth:`cols`
    (padded feature-major ``[R, pad]`` in the MXU dtype), and an SpMM
    accumulator stays feature-major ``[R, rows_pad]`` f32 until
    :meth:`finish`. A swapped encoding (``block_swap``) is already in
    the output's orientation, so ``swap`` only picks ``out_rows``."""

    # pallas_call out_shapes carry no varying-mesh-axes annotation, which
    # the strict checker rejects inside shard_map.
    check_vma = False

    def __init__(self, kernel, tiles, swap: bool = False):
        self.kernel = kernel
        self.tiles = tiles
        self.out_rows = tiles.tile_cols if swap else tiles.tile_rows
        bm, bn, grb, gcb, grp = tiles.blk_geom
        self.rows_pad, self.cols_pad = grb * bm, gcb * bn
        bands = getattr(tiles, "blk_bands", None)
        if bands:
            # The codegen banked encoding: one launch per row band.
            from distributed_sddmm_tpu.codegen.kernel import BankedTile

            self._make = functools.partial(
                BankedTile, bands=bands,
                rows_pad=self.rows_pad, cols_pad=self.cols_pad,
            )
        else:
            self._make = functools.partial(
                BlockedTile, bm=bm, bn=bn, gr_blocks=grb, gc_blocks=gcb,
                group=grp,
            )

    def fields(self) -> tuple:
        """The tile set's chunk arrays, in program-argument order."""
        return (self.tiles.blk_lr, self.tiles.blk_lc, self.tiles.blk_meta)

    @staticmethod
    def specs(tile_spec) -> tuple:
        chunk_spec = P(*tile_spec, None)
        return (chunk_spec, chunk_spec, tile_spec)

    @staticmethod
    def squeeze(fields, lead=()) -> tuple:
        """Per-device chunk blocks -> ``lead + [C, CHUNK]`` (meta
        ``lead + [C]``)."""
        lr, lc, meta = fields
        C = lr.shape[-2]
        return (
            lr.reshape(*lead, C, CHUNK),
            lc.reshape(*lead, C, CHUNK),
            meta.reshape(*lead, C),
        )

    @staticmethod
    def softmax_fields(fields) -> tuple:
        return tuple(fields)

    def tile(self, lr, lc, meta):
        return self._make(lr, lc, meta)

    def rows(self, X):
        return self.kernel.prep(X, self.rows_pad)

    def cols(self, X):
        return self.kernel.prep(X, self.cols_pad)

    def sddmm(self, tile, vals, x_r, x_c):
        return self.kernel.sddmm_tile_t(tile, vals, x_r, x_c, vals.dtype)

    def spmm(self, tile, vals, x_c):
        """SpMM partial, feature-major ``[R, rows_pad]`` f32."""
        return self.kernel.spmm_tile_t(tile, vals, x_c)

    def fused(self, tile, vals, x_r, x_c, acc, put):
        partial, mid = self.kernel.fused_tile_t(
            tile, vals, x_r, x_c, vals.dtype)
        return acc + partial, put(mid)

    def zeros(self, like):
        return jnp.zeros((like.shape[-1], self.rows_pad), jnp.float32)

    def finish(self, acc, like):
        return acc.T[:self.out_rows].astype(like.dtype)

    def softmax(self, fields, gate, logits, merge):
        """Per-tile Pallas reduce launches, a tile merge, ``merge(m, d)``
        across devices, then per-tile normalize launches (one launch
        pair per tile: the tile loop is static)."""
        T = gate.shape[0]
        tiles = [self.tile(*(f[s] for f in fields)) for s in range(T)]
        stats = [
            self.kernel.attn_stats_tile_t(tiles[s], gate[s], logits[s])
            for s in range(T)
        ]
        m, d = merge(*attn_merge_stats(stats))
        probs = [
            self.kernel.attn_norm_tile_t(
                tiles[s], gate[s], logits[s], m, d, gate.dtype)
            for s in range(T)
        ]
        return jnp.stack(probs)

    def sink_stats(self, sink):
        """A per-row sink logit ``[out_rows]`` as one more softmax
        partition in the ``[rows_pad, 1]`` stat frame of :meth:`softmax`:
        ``(m, d) = (sink, 1)``; pad rows hold no entries."""
        m = jnp.pad(sink, (0, self.rows_pad - sink.shape[0]))[:, None]
        return m, jnp.ones_like(m)
