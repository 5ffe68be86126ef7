"""Row-banked chunk-list construction (the banked half of codegen).

The generic encoding (``ops/blocked.build_blocked``) packs every
(row block, col block) pair's nonzeros into 128-lane chunks — padding is
bounded per chunk, but a SHORT row scattered over many column blocks
drags one mostly-empty chunk per touched pair. Banking partitions each
tile's rows by nnz/row and builds one chunk list per band with
band-specific geometry: the short-row band uses a single full-width
column block (one chunk-rounding per row block, however many column
blocks its rows touch) while heavy rows keep the generic blocked walk.

The bands CONCATENATE into one combined chunk list per bucket, so the
flat value layout / ``scatter_index`` contract of ``parallel/sharding``
is unchanged — value vectors serve the XLA and banked-Pallas kernel
paths with zero relayout, exactly as for the generic encoding. Each
band is a contiguous chunk range ``[c0, c1)`` that the banked kernel
slices STATICALLY and launches with its own geometry and body
(``codegen/kernel.py``).

Accumulator correctness across bands: every band's chunk list covers
every row block of the shared padded frame (``build_blocked``
guarantees >= 1 chunk per (bucket, row block), zero + flush flags
included), so each band's launch produces a full-frame partial with
exact zeros outside its own rows; partials combine by addition
(``x + 0.0 == x`` bitwise for the nonzero rows).
"""

from __future__ import annotations

import dataclasses

import numpy as np

from distributed_sddmm_tpu.ops.blocked import (
    CHUNK, BlockedMeta, build_blocked, pad_chunk_count, pick_block,
    pad_frame, unpack_meta,
)
from distributed_sddmm_tpu.codegen.variants import KernelVariant
from distributed_sddmm_tpu.utils import buckets

#: Density target for auto-width (``block_cols=0``) bands: widen the
#: band's column blocks (power-of-two merges of generic blocks, up to
#: full tile width) until the band averages at least this many full
#: chunks per touched (bucket, row block, col block) pair — the point
#: where per-pair chunk rounding stops dominating the band's lanes.
DENSITY_TARGET_CHUNKS = 2


@dataclasses.dataclass(frozen=True)
class Band:
    """One resolved band: a static chunk range + geometry + body.

    Hashable on purpose — band tuples ride inside
    :class:`~distributed_sddmm_tpu.codegen.kernel.BankedTile` as static
    pytree metadata and inside jit static arguments.
    """

    c0: int            # first chunk of this band in the combined list
    c1: int            # one past the last chunk
    bm: int
    bn: int
    gr_blocks: int
    gc_blocks: int
    group: int
    body: str          # "walk" | "batched" | "single" (resolved)


@dataclasses.dataclass(frozen=True)
class BandedMeta:
    """Combined banked encoding; a drop-in for ``BlockedMeta`` plus the
    per-band descriptors. Field conventions match ``BlockedMeta`` (the
    combined arrays ARE band-concatenated ``BlockedMeta`` arrays)."""

    lr: np.ndarray         # [NB, C_tot, CHUNK] int32
    lc: np.ndarray         # [NB, C_tot, CHUNK] int32
    meta: np.ndarray       # [NB, C_tot] int32 (gr/gc relative to its band)
    host_to_chunk: np.ndarray
    pad_lane: np.ndarray   # [NB, C_tot, CHUNK] bool
    bands: tuple[Band, ...]
    rows_pad: int          # shared padded tile frame (all bands agree)
    cols_pad: int
    n_chunks: int          # C_tot

    # --- BlockedMeta-compatible geometry (the LAST surviving band's
    # blocks over the shared frame — the heavy band when it has
    # nonzeros — so ``gr_blocks * bm == rows_pad`` still holds for
    # every consumer of ``blk_geom``). ---

    @property
    def bm(self) -> int:
        return self.bands[-1].bm

    @property
    def bn(self) -> int:
        return self.bands[-1].bn

    @property
    def gr_blocks(self) -> int:
        return self.rows_pad // self.bm

    @property
    def gc_blocks(self) -> int:
        return self.cols_pad // self.bn

    @property
    def group(self) -> int:
        return self.bands[-1].group

    def global_rows(self) -> np.ndarray:
        """Tile-frame row index per chunk lane (pad lanes -> 0), band by
        band — each band's meta words decode against its own block
        size."""
        out = np.zeros(self.lr.shape, dtype=np.int32)
        for band in self.bands:
            gr, _, _, _ = unpack_meta(self.meta[:, band.c0:band.c1])
            rows = gr[:, :, None] * band.bm + self.lr[:, band.c0:band.c1]
            out[:, band.c0:band.c1] = rows
        return np.where(self.pad_lane, 0, out).astype(np.int32)

    def global_cols(self) -> np.ndarray:
        out = np.zeros(self.lc.shape, dtype=np.int32)
        for band in self.bands:
            _, gc, _, _ = unpack_meta(self.meta[:, band.c0:band.c1])
            cols = gc[:, :, None] * band.bn + self.lc[:, band.c0:band.c1]
            out[:, band.c0:band.c1] = cols
        return np.where(self.pad_lane, 0, out).astype(np.int32)


# THE counted waste metric the banked variants exist to shrink. Owned
# by ops/blocked.py (they measure any encoding, generic included, and
# core tiling must not depend on this specialization package);
# re-exported here because codegen is the metric's consumer of record.
from distributed_sddmm_tpu.ops.blocked import (  # noqa: F401
    padded_lane_count, padded_lane_frac,
)


def _single_step_provable(bmeta: BlockedMeta) -> bool:
    """True when EVERY (bucket, row block) group of the band spans
    exactly one ``group``-chunk grid step AND no trailing bucket-pad
    chunks exist — the precondition for the conditional-free
    direct-write body: each step then zeroes-and-flushes trivially,
    and an unconditional ``out_ref[:] = contribution`` per step can
    never overwrite a flushed block with a pad step's zeros.

    Because every group is a multiple of ``group`` chunks with at least
    one, ``C == gr_blocks * group`` forces every bucket to exactly
    ``group`` chunks per group with zero trailing pads."""
    return bmeta.n_chunks == bmeta.gr_blocks * bmeta.group


def build_banded(
    n_buckets: int,
    bucket: np.ndarray,
    local_r: np.ndarray,
    local_c: np.ndarray,
    tile_rows: int,
    tile_cols: int,
    variant: KernelVariant,
) -> BandedMeta:
    """Build the banked encoding for one variant.

    Same contract as :func:`ops.blocked.build_blocked` (same argument
    meanings, same flat-layout guarantees via ``host_to_chunk``), with
    rows partitioned into the variant's nnz/row bands first. Bands that
    receive no nonzeros are dropped (their chunk lists would be pure
    padding) — including the heavy band when every row is short; only a
    zero-nnz tile keeps the heavy band alone (so the encoding still
    zeroes every block). The LAST SURVIVING band supplies the
    ``BlockedMeta``-compat geometry (:class:`BandedMeta` properties).
    """
    bucket = np.asarray(bucket, dtype=np.int64)
    local_r = np.asarray(local_r, dtype=np.int64)
    local_c = np.asarray(local_c, dtype=np.int64)
    nnz = local_r.size
    specs = variant.bands

    # nnz per (bucket, tile-local row), spread back per nonzero.
    if nnz:
        key = bucket * max(tile_rows, 1) + local_r
        _, inv, cnt = np.unique(key, return_inverse=True, return_counts=True)
        row_nnz = cnt[inv]
    else:
        cnt = np.zeros(0, dtype=np.int64)
        row_nnz = np.zeros(0, dtype=np.int64)

    band_of = np.full(nnz, len(specs) - 1, dtype=np.int64)
    unassigned = np.ones(nnz, dtype=bool)
    for i, spec in enumerate(specs):
        if spec.npr_max is None:
            continue
        m = unassigned & (row_nnz <= spec.npr_max)
        band_of[m] = i
        unassigned &= ~m

    # Structured-mask degeneration guard: banding pays by splitting a
    # SKEWED degree distribution (power-law R-mat rows) so the short-row
    # majority stops paying one mostly-empty chunk per touched column
    # block. A near-UNIFORM distribution — sliding-window and other
    # structured attention masks, where only edge rows dip below the
    # interior degree — can STRADDLE a pow2 band threshold and split
    # near-identical rows across two full-frame chunk lists: double the
    # per-row-block chunk rounding for zero density win. When the max
    # populated row degree sits within 2x of the median (one octave of
    # the shared pow2 ladder — no band boundary separates meaningfully
    # different populations) AND the assignment actually split, collapse
    # every row into the band holding the most nonzeros: one chunk list
    # with that band's (density-targeted or generic) geometry instead of
    # a pathological split (ROADMAP item 5's "degenerate gracefully").
    # A uniform population that already lands in ONE band — e.g. all-
    # short degree-1 rows, where full-width banding is a real win — is
    # untouched; the realized band tuple (and its program-key digest)
    # honestly reports whatever was built.
    if (
        len(specs) > 1
        and cnt.size
        and cnt.max() <= 2 * np.median(cnt)
    ):
        per_band = np.bincount(band_of, minlength=len(specs))
        if (per_band > 0).sum() > 1:
            band_of[:] = int(per_band.argmax())

    # Drop empty bands (their chunk lists would be pure padding — one
    # pad chunk per row block per bucket); a zero-nnz tile set keeps
    # the heavy band alone so the encoding still zeroes every block.
    live = [i for i in range(len(specs)) if (band_of == i).any()]
    if not live:
        live = [len(specs) - 1]
    lut = np.full(len(specs), len(live) - 1, dtype=np.int64)
    lut[live] = np.arange(len(live))
    band_of = lut[band_of]
    specs = tuple(specs[i] for i in live)

    # Shared padded frame: every band's blocks must tile the SAME frame
    # (dense operands are prepped once per program). Row blocks are
    # powers of two, so padding to the largest makes every smaller one
    # divide evenly. Auto-width (block_cols=0) bands resolve against the
    # fixed bands' floor: their width is a MERGE of floor blocks chosen
    # from the band's actual nonzero density — constrained to widths
    # that tile cols_pad EXACTLY (halve the block count while it stays
    # even, else jump to one full-width block), because gcb_full =
    # cols_pad/bn_floor can be any integer and a non-divisor width
    # would give the band a different implied frame than the one the
    # dense operands are prepped to.
    from distributed_sddmm_tpu.ops import blocked as blocked_mod

    bms = [pick_block(tile_rows, s.block_rows) for s in specs]
    rows_pad = pad_frame(max(tile_rows, 1), max(bms))
    fixed = [pick_block(tile_cols, s.block_cols) for s in specs if s.block_cols]
    bn_floor = max(fixed) if fixed else pick_block(
        tile_cols, blocked_mod.DEFAULT_BLOCK_COLS or blocked_mod.BASE_BLOCK
    )
    cols_pad = pad_frame(max(tile_cols, 1), bn_floor)
    gcb_full = cols_pad // bn_floor
    bns = []
    for i, s in enumerate(specs):
        if s.block_cols:
            bns.append(pick_block(tile_cols, s.block_cols))
            continue
        band_nnz = int((band_of == i).sum())
        grb = rows_pad // bms[i]
        gcb = gcb_full
        max_bn = s.max_block_cols or cols_pad
        while gcb > 1 and band_nnz < (
            n_buckets * grb * gcb * DENSITY_TARGET_CHUNKS * CHUNK
        ):
            nxt = gcb // 2 if gcb % 2 == 0 else 1
            if cols_pad // nxt > max_bn:
                break  # the VMEM cap (BandSpec.max_block_cols)
            gcb = nxt
        bns.append(cols_pad // gcb)

    parts: list[tuple[BlockedMeta, str, np.ndarray]] = []
    for i, spec in enumerate(specs):
        m = band_of == i
        bmeta = build_blocked(
            n_buckets, bucket[m], local_r[m], local_c[m],
            rows_pad, cols_pad,
            block_rows=bms[i], block_cols=bns[i], group=spec.group,
        )
        # Dyn-capacity builds (PR 20): pad each band's chunk count to a
        # pow2 rung BEFORE concatenation, so the Band (c0, c1) offsets —
        # static metadata in the traced program — are quantized and
        # survive pattern churn within the rung. Body resolution runs on
        # the padded meta: rung padding adds chunks per group, so
        # single-step is only provable against the realized count.
        cap = buckets.dyn_rung(bmeta.n_chunks, multiple=bmeta.group)
        if cap is not None and cap > bmeta.n_chunks:
            bmeta = pad_chunk_count(bmeta, cap)
        body = spec.body
        if body in ("batched", "single"):
            body = "single" if _single_step_provable(bmeta) else "batched"
        parts.append((bmeta, body, np.where(m)[0]))

    C_tot = sum(p[0].n_chunks for p in parts)
    host_to_chunk = np.empty(nnz, dtype=np.int64)
    bands: list[Band] = []
    c_off = 0
    for bmeta, body, idx in parts:
        C_k = bmeta.n_chunks
        b = bmeta.host_to_chunk // (C_k * CHUNK)
        within = bmeta.host_to_chunk % (C_k * CHUNK)
        host_to_chunk[idx] = b * (C_tot * CHUNK) + c_off * CHUNK + within
        bands.append(Band(
            c0=c_off, c1=c_off + C_k,
            bm=bmeta.bm, bn=bmeta.bn,
            gr_blocks=bmeta.gr_blocks, gc_blocks=bmeta.gc_blocks,
            group=bmeta.group, body=body,
        ))
        c_off += C_k

    return BandedMeta(
        lr=np.concatenate([p[0].lr for p in parts], axis=1),
        lc=np.concatenate([p[0].lc for p in parts], axis=1),
        meta=np.concatenate([p[0].meta for p in parts], axis=1),
        host_to_chunk=host_to_chunk,
        pad_lane=np.concatenate([p[0].pad_lane for p in parts], axis=1),
        bands=tuple(bands),
        rows_pad=rows_pad,
        cols_pad=cols_pad,
        n_chunks=C_tot,
    )
