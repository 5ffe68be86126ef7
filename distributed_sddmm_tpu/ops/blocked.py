"""Host-side 2-D block bucketing: flat COO tiles -> MXU chunk lists.

The TPU has no vectorized random gather, so the Pallas kernels
(``ops/pallas_kernels.py``) express SDDMM's A[row]/B[col] row gathers and
SpMM's row scatter as small dense matmuls with on-the-fly one-hot selector
matrices — MXU work instead of memory-system work. For that to pay off, each
matmul must touch only a small dense block, so every tile's nonzeros are
bucketed by ``(row_block, col_block)`` of size ``BM x BN`` and packed into
**chunks of 128** (one VPU lane row per nonzero).

The kernel then runs a 1-D grid over the chunk list; per-chunk scalar
metadata (which blocks to DMA, when to zero / flush the output accumulator)
is scalar-prefetched from SMEM. This mirrors how the reference tiles S into
block columns sized for cache (`/root/reference/SpmatLocal.hpp:541-563`) and
keeps max-size padded buffers for static shapes (`SpmatLocal.hpp:153-169`) —
here the padding target is the chunk grid instead of max_nnz.

Everything in this module is one-time numpy setup on the host, keyed off the
same ``scatter_index`` flat layout that ``parallel/sharding.build_tiles``
produces, so device-side relayout between the flat value layout and the
chunk layout is a cheap gather in both directions.
"""

from __future__ import annotations

import dataclasses
import os

import numpy as np

# Nonzeros per chunk. 128 = one VPU lane row per nonzero; 256 doubles the
# one-hot matmuls' N dimension (same FLOPs/nnz, fewer+larger MXU ops and
# half the per-sub-chunk fixed cost). Env-overridable for whole-process
# probes only (scripts/tune_blocks.py) — every module snapshots it at
# import, so it must never change inside a running process.
CHUNK = int(os.environ.get("DSDDMM_CHUNK", "128"))

# Chunks processed per Pallas grid step (see pallas_kernels._tile_call):
# amortizes the per-step semaphore/DMA fixed cost (scripts/tune_blocks.py
# probes this). Groups are gr-aligned, so larger values cost pad chunks in
# small row blocks. Env-overridable so benchmarks can compare group
# settings without code edits.
DEFAULT_GROUP = int(os.environ.get("DSDDMM_CHUNK_GROUP", "4"))

# Dense block sizes for the one-hot kernels' (row, col) windows. None (the
# default) lets build_blocked size them to the tile set's density
# (choose_blocks); DSDDMM_BLOCK_ROWS/COLS, or an autotune plan rebinding
# these attributes, pin them — bench.py applies the best (blocks, group,
# scatter form) combination measured in KERNELS_TPU.jsonl that way. A
# pinned axis with the other unset takes BASE_BLOCK on the other.
BASE_BLOCK = 512
DEFAULT_BLOCK_ROWS = (int(os.environ["DSDDMM_BLOCK_ROWS"])
                      if os.environ.get("DSDDMM_BLOCK_ROWS") else None)
DEFAULT_BLOCK_COLS = (int(os.environ["DSDDMM_BLOCK_COLS"])
                      if os.environ.get("DSDDMM_BLOCK_COLS") else None)

# Scatter contraction form ("bt"/"nt") and step batching for the Pallas
# kernels (consumed by ops/pallas_kernels.PallasKernel.__init__); defined
# here so every knob default lives in one module. Step batching is on:
# each grid step gathers the stationary side and scatters once for its G
# chunks (PERF.md section 5 has its chip cost).
DEFAULT_SCATTER_FORM = os.environ.get("DSDDMM_SCATTER_FORM", "bt")
DEFAULT_BATCH_STEP = os.environ.get("DSDDMM_BATCH_STEP", "1") not in ("", "0")


def knob_env_defaults() -> dict:
    """The effective kernel-knob values as the env-var strings bench.py
    passes to its workers — the single source of truth for its
    tuned-vs-first-rung dedup. Values reflect this process's environment
    (each knob is env-overridable), falling back to the literals above;
    unpinned block sizes read "auto" (sized by :func:`choose_blocks`)."""
    return {
        "DSDDMM_BLOCK_ROWS": str(DEFAULT_BLOCK_ROWS or "auto"),
        "DSDDMM_BLOCK_COLS": str(DEFAULT_BLOCK_COLS or "auto"),
        "DSDDMM_CHUNK_GROUP": str(DEFAULT_GROUP),
        "DSDDMM_SCATTER_FORM": DEFAULT_SCATTER_FORM,
        "DSDDMM_CHUNK": str(CHUNK),
        "DSDDMM_BATCH_STEP": "1" if DEFAULT_BATCH_STEP else "0",
    }

# meta word packing: | gr (15 bits) | gc (15 bits) | last | first |
_GR_SHIFT = 17
_GC_SHIFT = 2
MAX_BLOCKS = 1 << 15


def pick_block(frame: int, preferred: int = 512) -> int:
    """Largest power-of-two block size <= preferred that the padded frame
    supports. Frames are padded to a multiple of the result, so any
    power-of-two works; smaller frames use one block."""
    b = preferred
    while b > CHUNK and b >= 2 * frame:
        b //= 2
    return b


def pad_frame(frame: int, block: int) -> int:
    return -(-frame // block) * block


#: Scoped VMEM Mosaic gives a kernel by default on a v5e.
DEFAULT_SCOPED_VMEM = 16 << 20
#: Scoped VMEM the tile kernels may ask for: ``pallas_kernels._tile_call``
#: raises the limit to this when a kernel's windows outgrow the default,
#: and the density rule admits only geometries whose footprint fits it.
TILE_VMEM_LIMIT = 32 << 20


def tile_vmem_bytes(bm: int, bn: int, R: int, group: int,
                    itemsize: int = 2, batch_step: bool = True) -> int:
    """VMEM a fused tile kernel holds at one geometry: the double-buffered
    ``at`` window and ``group`` ``bt`` windows (MXU operands of
    ``itemsize`` bytes), the double-buffered float32 output window, its
    accumulator, and the gather one-hots. The step-batched body holds the
    stationary side's ``[bm, group*CHUNK]`` one-hot with its compare
    mask, one moving-side ``[bn, CHUNK]`` one-hot at a time, and the
    step's gathered float32 rows of both sides (``[R, group*CHUNK]``
    each); the per-chunk body a ``[bm, CHUNK]`` and a ``[bn, CHUNK]``
    one-hot with their int32 compares. Each count bounds the scoped VMEM
    that v5e compiles of that body asked for (fused, R=128 and 256, group
    4 and 8, 512 to 4096 on either axis; PERF.md section 5); SDDMM and
    SpMM hold a subset."""
    windows = 2 * R * bm * itemsize + 2 * group * R * bn * itemsize
    out = 2 * R * bm * 4 + R * bm * 4
    if batch_step:
        one_hots = ((group * bm + bn) * CHUNK * (itemsize + 1)
                    + 2 * R * group * CHUNK * 4)
    else:
        one_hots = (bm + bn) * CHUNK * (itemsize + 4)
    return windows + out + one_hots


# The density rule. A chunk costs the same whatever its fill: three
# one-hot matmuls of fixed extent (the stationary gather [R,bm]@[bm,128]
# and scatter [R,128]@[128,bm], the moving gather [R,bn]@[bn,128]) and a
# bt-window DMA. Blocks sized to the density therefore trade dearer
# chunks for fewer, fuller ones. The cost below is the step-batched fused
# kernel's (ALS and the pair run it; SDDMM and SpMM run a subset of its
# work): t = t0 + R * (k_m*bm + k_n*bn), the stationary (bm) side batched
# over a grid step's chunks, the moving (bn) side chunk by chunk, fitted
# to scripts/tune_blocks.py on a v5e at group 4 (logM 18, nnz/row 32,
# R=128 at 512x512, 1024x1024, 1024x2048, 2048x1024, 2048x2048 and
# 4096x1024, R=256 at 512x1024 and 1024x1024; within 4% of each; PERF.md
# section 5).
#: Block sizes the rule weighs on each axis (pick_block clamps them).
RULE_BLOCKS = (256, 512, 1024, 2048, 4096)
_CHUNK_FIXED_NS = 137.86     # t0: per chunk, whatever the geometry
_CHUNK_ROW_NS = 2.6303e-3    # k_m: per unit of R * bm (gather and scatter)
_CHUNK_COL_NS = 1.4577e-3    # k_n: per unit of R * bn (moving gather)
_HBM_BYTES_PER_NS = 819.0  # v5e HBM bandwidth, for the bt-window DMA
#: A geometry replaces BASE_BLOCK only where it models at least this much
#: cheaper: the fit misses single geometries by up to 4%, and 256-row
#: blocks ran 15% slower than the per-chunk body's fit said (not
#: measured step-batched).
_RULE_MARGIN = 0.9
#: Largest (bucket, row block, col block) grid at the smallest candidate
#: the rule counts; a larger tile set keeps BASE_BLOCK.
_RULE_MAX_CELLS = 1 << 25


def chunk_time_ns(bm: int, bn: int, R: int) -> float:
    """Modeled time of one fused-kernel chunk: the larger of its one-hot
    MXU work and its bt-window DMA (bfloat16 operands)."""
    mxu = _CHUNK_FIXED_NS + R * (_CHUNK_ROW_NS * bm + _CHUNK_COL_NS * bn)
    return max(mxu, R * bn * 2 / _HBM_BYTES_PER_NS)


def _grid_chunks(counts: np.ndarray, group: int) -> int:
    """Chunks a bucket of ``build_blocked`` at a [buckets, gr, gc] grid of
    pair counts: each pair's ceil(count / CHUNK), one pad chunk for an
    empty row block, each row block rounded up to ``group``, the most of
    any bucket."""
    per_gr = (-(-counts // CHUNK)).sum(axis=2)
    per_gr = np.maximum(per_gr, 1)
    per_gr += (-per_gr) % group
    C = max(int(per_gr.sum(axis=1).max(initial=0)), 1)
    return -(-C // group) * group


def block_chunk_counts(n_buckets, bucket, local_r, local_c, tile_rows,
                       tile_cols, group=1) -> dict:
    """Exact chunk count a bucket (``BlockedMeta.n_chunks``) that
    :func:`build_blocked` gives at each geometry ``(bm, bn)`` the sizes in
    ``RULE_BLOCKS`` clamp to, within the packed-meta block limit. One
    ``bincount`` over the smallest geometry's pair grid; a coarser
    geometry's pair counts are sums of that grid (every size is a power of
    two). Empty when that grid exceeds ``_RULE_MAX_CELLS``."""
    geoms = sorted({
        (pick_block(tile_rows, a), pick_block(tile_cols, b))
        for a in RULE_BLOCKS for b in RULE_BLOCKS
    })
    geoms = [(bm, bn) for bm, bn in geoms
             if -(-tile_rows // bm) <= MAX_BLOCKS
             and -(-tile_cols // bn) <= MAX_BLOCKS]
    if not geoms:
        return {}
    fm = min(bm for bm, _ in geoms)
    fn = min(bn for _, bn in geoms)
    big_m = max(bm for bm, _ in geoms) // fm
    big_n = max(bn for _, bn in geoms) // fn
    grf = -(-max(-(-tile_rows // fm), 1) // big_m) * big_m
    gcf = -(-max(-(-tile_cols // fn), 1) // big_n) * big_n
    cells = n_buckets * grf * gcf
    if cells > _RULE_MAX_CELLS:
        return {}
    key = np.asarray(bucket, dtype=np.int64) * grf
    key += np.asarray(local_r) // fm
    key *= gcf
    key += np.asarray(local_c) // fn
    fine = np.bincount(key, minlength=cells).reshape(n_buckets, grf, gcf)
    out = {}
    for bm, bn in geoms:
        sr, sc = bm // fm, bn // fn
        grb = max(-(-tile_rows // bm), 1)
        gcb = max(-(-tile_cols // bn), 1)
        counts = fine[:, :grb * sr, :gcb * sc].reshape(
            n_buckets, grb, sr, gcb, sc).sum(axis=(2, 4))
        out[(bm, bn)] = _grid_chunks(counts, group)
    return out


def choose_blocks(n_buckets, bucket, local_r, local_c, tile_rows, tile_cols,
                  R: int, group: int = 1) -> tuple:
    """``(bm, bn)`` for a tile set: the geometry of least modeled kernel
    time (exact chunk count × :func:`chunk_time_ns`) among those whose
    step-batched :func:`tile_vmem_bytes` at ``R`` fits
    :data:`TILE_VMEM_LIMIT`, ties to the smaller footprint. BASE_BLOCK
    square stays where the best is not ``_RULE_MARGIN`` of its time, or
    where there is nothing to count."""
    base = (pick_block(tile_rows, BASE_BLOCK), pick_block(tile_cols, BASE_BLOCK))
    chunks = block_chunk_counts(n_buckets, bucket, local_r, local_c,
                                tile_rows, tile_cols, group)
    fits = {
        g: c for g, c in chunks.items()
        if tile_vmem_bytes(*g, R, group) <= TILE_VMEM_LIMIT
    }
    if not fits:
        return base

    def cost(g):
        return fits[g] * chunk_time_ns(*g, R)

    best = min(fits, key=lambda g: (cost(g), tile_vmem_bytes(*g, R, group)))
    if base in fits and cost(best) > _RULE_MARGIN * cost(base):
        return base
    return best


@dataclasses.dataclass(frozen=True)
class BlockedMeta:
    """Host-side chunk-list encoding for every (device, tile) bucket.

    Arrays are indexed by flat bucket id ``b`` (device-major, tile-minor) —
    the same ordering as ``build_tiles``'s flat layout. The chunk layout IS
    the tile's flat nonzero layout: position ``b * C * CHUNK + chunk * CHUNK
    + lane`` holds one nonzero (or an inert pad), so value vectors need no
    relayout between the XLA and Pallas kernel paths.
    """

    lr: np.ndarray        # [NB, C, CHUNK] int32 — row within its row block
    lc: np.ndarray        # [NB, C, CHUNK] int32 — col within its col block
    meta: np.ndarray      # [NB, C] int32 — packed (gr, gc, first, last)
    host_to_chunk: np.ndarray  # [nnz] int64 — host nonzero -> absolute position
    pad_lane: np.ndarray  # [NB, C, CHUNK] bool — True at inert pad lanes
    bm: int               # row block size
    bn: int               # col block size
    gr_blocks: int        # row blocks per (padded) tile frame
    gc_blocks: int
    n_chunks: int         # C, padded axis-max chunks per bucket
    group: int = 1        # chunks per kernel grid step (gr-aligned groups)

    @property
    def rows_pad(self) -> int:
        return self.gr_blocks * self.bm

    @property
    def cols_pad(self) -> int:
        return self.gc_blocks * self.bn

    def global_rows(self) -> np.ndarray:
        """Tile-frame row index of every chunk lane, [NB, C, CHUNK] int32
        (pad lanes -> 0). Makes the chunk layout consumable by the flat
        gather/segment-sum kernels."""
        gr, _, _, _ = unpack_meta(self.meta)
        rows = gr[:, :, None] * self.bm + self.lr
        return np.where(self.pad_lane, 0, rows).astype(np.int32)

    def global_cols(self) -> np.ndarray:
        _, gc, _, _ = unpack_meta(self.meta)
        cols = gc[:, :, None] * self.bn + self.lc
        return np.where(self.pad_lane, 0, cols).astype(np.int32)


def padded_lane_count(meta) -> int:
    """Inert pad lanes in one chunk-list encoding (``BlockedMeta`` or
    codegen's ``BandedMeta``) — the counted waste metric the banked
    kernel variants exist to shrink."""
    return int(meta.pad_lane.sum())


def padded_lane_frac(meta) -> float:
    total = meta.pad_lane.size
    return float(meta.pad_lane.sum()) / total if total else 0.0


def pack_meta(gr, gc, first, last):
    return (
        (gr.astype(np.int64) << _GR_SHIFT)
        | (gc.astype(np.int64) << _GC_SHIFT)
        | (last.astype(np.int64) << 1)
        | first.astype(np.int64)
    ).astype(np.int32)


def build_blocked(
    n_buckets: int,
    bucket: np.ndarray,   # host nnz order -> flat (device, tile) bucket id
    local_r: np.ndarray,  # host nnz order, tile-local rows
    local_c: np.ndarray,
    tile_rows: int,
    tile_cols: int,
    block_rows: int | None = None,
    block_cols: int | None = None,
    group: int = 1,
    R: int | None = None,
) -> BlockedMeta:
    """Build the chunk-list encoding.

    The block geometry is ``block_rows x block_cols`` where given, else
    ``DEFAULT_BLOCK_ROWS/COLS`` where pinned; with neither, and the
    kernels' inner dimension ``R`` known, :func:`choose_blocks` sizes it
    to these nonzeros, else it is ``BASE_BLOCK`` square.

    Guarantees the kernels rely on:

    * chunks of one bucket are sorted by ``(gr, gc)``;
    * every row block ``gr`` of every bucket has >= 1 chunk (so the output
      accumulator is always zeroed and flushed, even for empty row blocks);
    * the ``first`` / ``last`` flags mark the boundary chunks of each
      bucket's ``gr`` group;
    * pad lanes carry ``lr = lc = 0`` and are flagged in ``pad_lane`` (value
      vectors must be zero there — ``build_tiles`` enforces this via the
      mask);
    * trailing bucket-pad chunks (to reach the shared C) have no flags set
      and gr = gr_blocks-1, gc = gc_blocks-1: they keep the kernel's output
      window pinned on the bucket's LAST (already flushed) row block. Pad
      chunks must never remap the output window — Pallas output buffers are
      write-only, so a remapped-but-unwritten window would flush stale VMEM
      over a correct block at grid end;
    * with ``group`` > 1, every bucket's ``gr`` group spans a multiple of
      ``group`` chunks and C is a multiple of ``group``, so a kernel grid
      step processing ``group`` consecutive chunks always stays inside one
      row-block window (the per-step output/stationary index maps read the
      step's first chunk). Group-pad chunks sit at the END of their gr
      group (appended to its last (gr, gc) pair) with all-pad lanes; since
      the first/last flags are derived from gr-group adjacency over the
      pad-EXPANDED chunk sequence, the ``last`` flag lands on the group's
      final chunk — a pad chunk when deficit padding was added. That is by
      design: the flush then happens at the group's true end (pads add
      nothing to the accumulator first), and a flag therefore does NOT
      imply the chunk carries real nonzeros.
    """
    if block_rows is None:
        block_rows = DEFAULT_BLOCK_ROWS
    if block_cols is None:
        block_cols = DEFAULT_BLOCK_COLS
    if block_rows is None and block_cols is None and R is not None:
        block_rows, block_cols = choose_blocks(
            n_buckets, bucket, local_r, local_c, tile_rows, tile_cols,
            R, group,
        )
    bm = pick_block(tile_rows, block_rows or BASE_BLOCK)
    bn = pick_block(tile_cols, block_cols or BASE_BLOCK)
    gr_blocks = max(-(-tile_rows // bm), 1)
    gc_blocks = max(-(-tile_cols // bn), 1)
    if gr_blocks > MAX_BLOCKS or gc_blocks > MAX_BLOCKS:
        raise ValueError(
            f"tile frame {tile_rows}x{tile_cols} exceeds the packed-meta "
            f"limit of {MAX_BLOCKS} blocks per axis"
        )

    nnz = local_r.size
    bucket = bucket.astype(np.int64)
    gr = (local_r // bm).astype(np.int64)
    gc = (local_c // bn).astype(np.int64)

    # Sort nonzeros by (bucket, gr, gc); stable keeps host order within.
    from distributed_sddmm_tpu import native

    key = (bucket * gr_blocks + gr) * gc_blocks + gc
    n_pairs = n_buckets * gr_blocks * gc_blocks
    pair_counts, order = native.bucket_sort(key, n_pairs)
    key_sorted = key[order]

    # Chunks per (bucket, gr, gc) pair.
    pair_chunks = -(-pair_counts // CHUNK)

    # Ensure >= 1 chunk for every (bucket, gr): give empty gr GROUPS one pad
    # chunk at gc = 0.
    group_chunks = pair_chunks.reshape(n_buckets, gr_blocks, gc_blocks)
    group_tot = group_chunks.sum(axis=2)
    need_pad_group = group_tot == 0
    pair_chunks = group_chunks.copy()
    pair_chunks[:, :, 0][need_pad_group] = 1
    if group > 1:
        # Pad every (bucket, gr) group to a multiple of `group` chunks so a
        # G-chunk grid step never straddles a row-block boundary; the pad
        # chunks ride on the group's last (gr, gc) pair, after its real
        # chunks.
        tot = pair_chunks.sum(axis=2)
        deficit = (-tot) % group
        pair_chunks[:, :, -1] += deficit
    pair_chunks = pair_chunks.reshape(-1)

    chunks_per_bucket = pair_chunks.reshape(n_buckets, -1).sum(axis=1)
    C = max(int(chunks_per_bucket.max(initial=0)), 1)
    C = -(-C // group) * group

    # Chunk start offset (within its bucket) for every pair.
    pair_chunk_start = np.zeros(n_pairs, dtype=np.int64)
    np.cumsum(pair_chunks[:-1], out=pair_chunk_start[1:])
    # pair_chunk_start counts from the global running total; rebase per bucket
    bucket_first_pair = (
        np.arange(n_buckets) * gr_blocks * gc_blocks
    )
    pair_chunk_start -= np.repeat(
        pair_chunk_start[bucket_first_pair], gr_blocks * gc_blocks
    )

    # Place each nonzero: chunk = pair's start + within//CHUNK, lane = within%CHUNK.
    pair_nnz_start = np.zeros(n_pairs, dtype=np.int64)
    np.cumsum(pair_counts[:-1], out=pair_nnz_start[1:])
    within = np.arange(nnz, dtype=np.int64) - pair_nnz_start[key_sorted]
    chunk_in_bucket = pair_chunk_start[key_sorted] + within // CHUNK
    lane = within % CHUNK
    pos_sorted = (bucket[order] * C + chunk_in_bucket) * CHUNK + lane

    total = n_buckets * C * CHUNK
    lr_flat = np.zeros(total, dtype=np.int32)
    lc_flat = np.zeros(total, dtype=np.int32)
    pad_lane = np.ones(total, dtype=bool)
    lr_flat[pos_sorted] = (local_r[order] % bm).astype(np.int32)
    lc_flat[pos_sorted] = (local_c[order] % bn).astype(np.int32)
    pad_lane[pos_sorted] = False

    host_to_chunk = np.empty(nnz, dtype=np.int64)
    host_to_chunk[order] = pos_sorted

    # Packed per-chunk metadata. Trailing bucket-pad chunks default to the
    # last (gr, gc) block with no flags, pinning the output window (see
    # docstring).
    meta = np.full(
        (n_buckets, C),
        int(pack_meta(
            np.int64(gr_blocks - 1), np.int64(gc_blocks - 1),
            np.int64(0), np.int64(0),
        )),
        dtype=np.int32,
    )
    pair_gr = (np.arange(n_pairs) // gc_blocks) % gr_blocks
    pair_gc = np.arange(n_pairs) % gc_blocks
    pair_bucket = np.arange(n_pairs) // (gr_blocks * gc_blocks)
    # Expand pairs to chunks; a bucket's chunks are consecutive and ordered
    # by (gr, gc), so positions within the bucket are just a running index.
    ch_bucket = np.repeat(pair_bucket, pair_chunks)
    ch_gr = np.repeat(pair_gr, pair_chunks)
    ch_gc = np.repeat(pair_gc, pair_chunks)
    bucket_chunk_offset = np.zeros(n_buckets, dtype=np.int64)
    np.cumsum(chunks_per_bucket[:-1], out=bucket_chunk_offset[1:])
    ch_pos = np.arange(ch_bucket.size, dtype=np.int64) - np.repeat(
        bucket_chunk_offset, chunks_per_bucket
    )
    # first/last chunk of each bucket's gr group (groups are contiguous).
    grp_key = ch_bucket * gr_blocks + ch_gr
    first = np.ones(ch_bucket.size, dtype=np.int64)
    first[1:] = grp_key[1:] != grp_key[:-1]
    last = np.ones(ch_bucket.size, dtype=np.int64)
    last[:-1] = grp_key[1:] != grp_key[:-1]
    meta[ch_bucket, ch_pos] = pack_meta(ch_gr, ch_gc, first, last)

    return BlockedMeta(
        lr=lr_flat.reshape(n_buckets, C, CHUNK),
        lc=lc_flat.reshape(n_buckets, C, CHUNK),
        meta=meta,
        host_to_chunk=host_to_chunk,
        pad_lane=pad_lane.reshape(n_buckets, C, CHUNK),
        bm=bm,
        bn=bn,
        gr_blocks=gr_blocks,
        gc_blocks=gc_blocks,
        n_chunks=C,
        group=group,
    )


def pad_chunk_count(meta: BlockedMeta, c_new: int) -> BlockedMeta:
    """Append trailing pad chunks to every bucket to reach ``c_new`` chunks.

    Used when the chunk-flat length must divide evenly (e.g. into fiber
    value slices). Pad chunks follow the window-pinning convention (last
    (gr, gc) block, no flags) and are all-pad lanes. ``c_new`` is rounded up
    to the encoding's group multiple."""
    C = meta.n_chunks
    c_new = -(-c_new // meta.group) * meta.group
    if c_new < C:
        raise ValueError(f"cannot shrink chunk count {C} -> {c_new}")
    if c_new == C:
        return meta
    nb = meta.lr.shape[0]
    extra = c_new - C
    pad_word = int(pack_meta(
        np.int64(meta.gr_blocks - 1), np.int64(meta.gc_blocks - 1),
        np.int64(0), np.int64(0),
    ))
    return dataclasses.replace(
        meta,
        lr=np.concatenate(
            [meta.lr, np.zeros((nb, extra, CHUNK), np.int32)], axis=1
        ),
        lc=np.concatenate(
            [meta.lc, np.zeros((nb, extra, CHUNK), np.int32)], axis=1
        ),
        meta=np.concatenate(
            [meta.meta, np.full((nb, extra), pad_word, np.int32)], axis=1
        ),
        pad_lane=np.concatenate(
            [meta.pad_lane, np.ones((nb, extra, CHUNK), bool)], axis=1
        ),
        host_to_chunk=(
            meta.host_to_chunk
            + (meta.host_to_chunk // (C * CHUNK)) * (extra * CHUNK)
        ),
        n_chunks=c_new,
    )


def unpack_meta(word):
    """Inverse of :func:`pack_meta` (numpy or jax arrays).

    gr is masked like gc: the word is int32, so an unmasked arithmetic shift
    would sign-extend gr >= 16384 into negative block indices."""
    gr = (word >> _GR_SHIFT) & (MAX_BLOCKS - 1)
    gc = (word >> _GC_SHIFT) & (MAX_BLOCKS - 1)
    last = (word >> 1) & 1
    first = word & 1
    return gr, gc, first, last
