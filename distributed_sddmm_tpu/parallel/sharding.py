"""Host-side nonzero redistribution: HostCOO + layout -> sharded device tiles.

Replaces the reference's ``redistribute_nonzeros`` / ``divideIntoBlockCols`` /
``initializeCSRBlocks`` pipeline (`/root/reference/SpmatLocal.hpp:314-462`):
instead of an ``MPI_Alltoallv`` shuffle followed by per-rank MKL COO->CSR
conversion, we bucket nonzeros on the host with one argsort and materialize a
single global ``jax.Array`` per field, sharded over the mesh.

Static-shape contract: every (device, tile) bucket is padded to the global
``max_nnz`` with inert entries (row=col=0, mask=0). This is the XLA-friendly
generalization of the reference's own max_nnz double buffers
(`SpmatLocal.hpp:153-169`).
"""

from __future__ import annotations

import dataclasses
import math

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from distributed_sddmm_tpu.common import divide_round_up
from distributed_sddmm_tpu.parallel.mesh import GridSpec
from distributed_sddmm_tpu.utils import buckets
from distributed_sddmm_tpu.utils.coo import HostCOO

TILE_SPEC = P("rows", "cols", "layers", None, None)


def put_sharded(host: np.ndarray, sharding) -> jax.Array:
    """Place a host array as a global sharded ``jax.Array``,
    materializing ONLY the addressable shards.

    Single-process: plain ``device_put`` (bit-identical, no callback
    overhead). Multi-controller: ``jax.make_array_from_callback`` — the
    runtime asks this process for exactly its addressable shards'
    index slices, so a host never uploads (or pins device-side) the
    non-addressable remainder of the global array. Under the SPMD
    ingest contract the host array passed here covers every index the
    callback can request (identical host data per process, or a
    partition-backed array whose rows cover this host's devices — see
    ``dist/ingest.py``).
    """
    import jax as _jax

    if _jax.process_count() == 1:
        return _jax.device_put(host, sharding)
    return _jax.make_array_from_callback(
        host.shape, sharding, lambda idx: host[idx]
    )


@dataclasses.dataclass
class TileSet:
    """Sharded, padded, struct-of-arrays sparse tiles.

    ``rows/cols/mask`` have global shape ``(nr, nc, nh, T, max_nnz)`` sharded
    over the first three (mesh) axes; each device sees its ``(T, max_nnz)``
    tiles inside shard_map. Values travel separately in the same shape (the
    reference's separation of structure from ``SValues`` vectors,
    `distributed_sparse.h:189-195`).
    """

    rows: jax.Array
    cols: jax.Array
    mask: jax.Array
    scatter_index: np.ndarray  # original nnz order -> flat padded position
    tile_rows: int  # local tile frame height (rows the local indices address)
    tile_cols: int
    nnz: int
    grid: GridSpec
    nnz_per_device: np.ndarray  # (nr, nc, nh) — load-imbalance observability
    # MXU chunk-list encoding (ops/blocked.py) for the Pallas kernels;
    # None when blocking was skipped. When present, the flat nonzero layout
    # (rows/cols/mask and every value vector) IS the chunk layout, so both
    # kernel families consume the same value arrays. blk_* arrays share the
    # mesh sharding of rows/cols with trailing per-bucket dims.
    blk_lr: jax.Array = None    # (nr, nc, nh, T, C, 128) int32
    blk_lc: jax.Array = None
    blk_meta: jax.Array = None  # (nr, nc, nh, T, C) int32 packed
    blk_geom: tuple = None      # (bm, bn, gr_blocks, gc_blocks)
    # Codegen banked encoding (codegen/banded.py): per-band static chunk
    # ranges + geometry when a kernel variant banded this tile set; None
    # for the generic encoding. blk_pad_* count the encoding's inert pad
    # lanes — the waste metric banked variants exist to shrink.
    blk_bands: tuple = None
    blk_pad_lanes: int = 0
    blk_pad_frac: float = None
    #: Variant id that ACTUALLY shaped the blocked encoding (None when
    #: generic or when a requested variant guard-felled to generic) —
    #: what records and program keys report, vs the kernel's identity.
    blk_variant: str = None
    #: Realized dyn-capacity rungs when this set was built under an
    #: active ``utils.buckets.dyn_capacity`` scope (dynstruct builds,
    #: PR 20); None for exact (static) builds. Feeds the capacity
    #: segment of program keys and the rebind fit-check.
    dyn_cap: tuple = None

    @property
    def has_blocked(self) -> bool:
        return self.blk_lr is not None

    @property
    def shape(self) -> tuple:
        return tuple(self.rows.shape)

    @property
    def max_nnz(self) -> int:
        return self.rows.shape[-1]

    @property
    def n_tiles(self) -> int:
        return self.rows.shape[-2]

    def _sharding(self) -> NamedSharding:
        return NamedSharding(self.grid.mesh, TILE_SPEC)

    def like_values(self, value: float) -> jax.Array:
        """Constant values at every real nonzero (reference ``like_S_values``,
        `distributed_sparse.h:189-191`)."""
        return self.mask * value

    def scatter_values(self, host_vals: np.ndarray) -> jax.Array:
        """Place a host vector (original nonzero order) into tile structure."""
        host_vals = np.asarray(host_vals)
        if host_vals.shape != (self.nnz,):
            raise ValueError(f"expected ({self.nnz},) values, got {host_vals.shape}")
        buf = np.zeros(int(np.prod(self.shape)), dtype=self.mask.dtype)
        buf[self.scatter_index] = host_vals
        return put_sharded(buf.reshape(self.shape), self._sharding())

    def gather_values(self, dev_vals: jax.Array) -> np.ndarray:
        """Extract values back to the original host nonzero order."""
        return np.asarray(dev_vals).reshape(-1)[self.scatter_index]


@dataclasses.dataclass
class ReplicatedTiles:
    """Tiles replicated across the ``layers`` fiber with values sharded 1/c
    per layer — the 2.5D sparse-replicating data layout
    (`25D_cannon_sparse.hpp:47-54` broadcast + ``shard_across_layers``,
    `SpmatLocal.hpp:338-356`).

    Structure (rows/cols/mask) has global shape ``(nr, nc, max_nnz)`` with
    spec ``P("rows", "cols", None)`` — omitting ``layers`` IS the broadcast
    under SPMD. Values have shape ``(nr, nc, c, owned_len)`` with spec
    ``P("rows", "cols", "layers", None)``; ``max_nnz = c * owned_len`` so a
    fiber all_gather of the owned slices reconstitutes full tile values and
    a fiber psum_scatter splits summed dots back into owned slices.
    """

    rows: jax.Array
    cols: jax.Array
    mask: jax.Array
    mask_owned: jax.Array
    scatter_index: np.ndarray  # host nnz order -> flat index into values shape
    owned_len: int
    tile_rows: int
    tile_cols: int
    nnz: int
    grid: GridSpec
    nnz_per_device: np.ndarray
    # Blocked (Pallas) chunk-list encoding; structure replicated over the
    # fiber like rows/cols. None when not built. The codegen banked
    # encoding is NOT supported on this layout (the chunk-flat length
    # must split into fiber value slices) — blk_bands stays None and a
    # requested variant falls back to the generic encoding.
    blk_lr: jax.Array = None    # (nr, nc, C, 128) int32
    blk_lc: jax.Array = None
    blk_meta: jax.Array = None  # (nr, nc, C) int32 packed
    blk_geom: tuple = None
    blk_bands: tuple = None
    blk_pad_lanes: int = 0
    blk_pad_frac: float = None
    #: Variant id that ACTUALLY shaped the blocked encoding (None when
    #: generic or when a requested variant guard-felled to generic) —
    #: what records and program keys report, vs the kernel's identity.
    blk_variant: str = None
    #: Realized dyn-capacity rungs (see TileSet.dyn_cap); None for
    #: exact builds.
    dyn_cap: tuple = None

    STRUCT_SPEC = P("rows", "cols", None)
    VALUES_SPEC = P("rows", "cols", "layers", None)

    @property
    def has_blocked(self) -> bool:
        return self.blk_lr is not None

    @property
    def max_nnz(self) -> int:
        return self.rows.shape[-1]

    def like_values(self, value: float) -> jax.Array:
        return self.mask_owned * value

    def scatter_values(self, host_vals: np.ndarray) -> jax.Array:
        host_vals = np.asarray(host_vals)
        if host_vals.shape != (self.nnz,):
            raise ValueError(f"expected ({self.nnz},) values, got {host_vals.shape}")
        shape = self.mask_owned.shape
        buf = np.zeros(int(np.prod(shape)), dtype=self.mask.dtype)
        buf[self.scatter_index] = host_vals
        return put_sharded(
            buf.reshape(shape), NamedSharding(self.grid.mesh, self.VALUES_SPEC)
        )

    def gather_values(self, dev_vals: jax.Array) -> np.ndarray:
        return np.asarray(dev_vals).reshape(-1)[self.scatter_index]


def build_replicated_tiles(
    S: HostCOO,
    grid: GridSpec,
    layout,
    tile_rows: int,
    tile_cols: int,
    dtype=jnp.float32,
    block: bool = False,
    variant=None,
    R: int | None = None,
) -> ReplicatedTiles:
    """Bucket nonzeros onto the 2-D grid floor, replicate structure across
    layers, shard values 1/c per layer (contiguous equal slices).
    ``block=True`` additionally builds the chunk-list (Pallas) encoding and
    makes it the flat layout, with the chunk count padded so the chunk-flat
    length splits evenly into fiber slices. A codegen ``variant`` is NOT
    bankable on this layout (band-concatenated chunk counts cannot be
    re-padded into fiber slices); banking falls back to the generic
    encoding and counts a ``codegen_generic_fallbacks``, but a
    non-banked variant's R-regime block geometry (a single chunk list)
    still applies."""
    nr, nc, nh = grid.nr, grid.nc, grid.nh
    res = layout(S.rows, S.cols)
    if res.i.size:
        assert res.i.max() < nr and res.j.max() < nc

    dev = res.i * nc + res.j
    n_buckets = nr * nc

    _dyn = buckets.dyn_capacity_state()
    _dyn_mark = len(_dyn.realized) if _dyn is not None else 0

    blocked = None
    if block:
        if variant is not None and getattr(variant, "banked", False):
            from distributed_sddmm_tpu.obs import metrics as obs_metrics

            obs_metrics.GLOBAL.add("codegen_generic_fallbacks")
            variant = None
        blocked, blk_variant = _try_build_blocked(
            n_buckets, dev, res, tile_rows, tile_cols, variant=variant, R=R,
        )
        if blocked is not None:
            from distributed_sddmm_tpu.ops.blocked import CHUNK, pad_chunk_count

            # Chunk-flat length must divide into nh equal value slices AND
            # stay a multiple of the kernel grid group.
            lcm_chunks = nh // math.gcd(CHUNK, nh)
            lcm_chunks *= blocked.group // math.gcd(lcm_chunks, blocked.group)
            C = divide_round_up(blocked.n_chunks, lcm_chunks) * lcm_chunks
            cap = buckets.dyn_rung(C, multiple=lcm_chunks)
            if cap is not None:
                C = max(C, cap)
            blocked = pad_chunk_count(blocked, C)

    if blocked is not None:
        from distributed_sddmm_tpu.ops.blocked import CHUNK

        max_nnz = blocked.n_chunks * CHUNK
        scatter_index = blocked.host_to_chunk
        rows_flat = blocked.global_rows().reshape(-1)
        cols_flat = blocked.global_cols().reshape(-1)
        mask_flat = (~blocked.pad_lane).reshape(-1).astype(np.dtype(dtype))
        counts = np.bincount(dev, minlength=n_buckets)
    else:
        order = np.argsort(dev, kind="stable")
        counts = np.bincount(dev[order], minlength=n_buckets)
        # Pad to a multiple of the fiber depth so value slices are equal-sized.
        raw_max = max(int(counts.max(initial=0)), 1)
        max_nnz = divide_round_up(raw_max, nh) * nh
        cap = buckets.dyn_rung(max_nnz, multiple=nh)
        if cap is not None:
            max_nnz = cap
        starts = np.zeros(n_buckets, dtype=np.int64)
        np.cumsum(counts[:-1], out=starts[1:])
        within = np.arange(S.nnz, dtype=np.int64) - starts[dev[order]]
        pos_sorted = dev[order] * max_nnz + within
        scatter_index = np.empty(S.nnz, dtype=np.int64)
        scatter_index[order] = pos_sorted

        total = n_buckets * max_nnz
        rows_flat = np.zeros(total, dtype=np.int32)
        cols_flat = np.zeros(total, dtype=np.int32)
        mask_flat = np.zeros(total, dtype=np.dtype(dtype))
        rows_flat[scatter_index] = res.local_r
        cols_flat[scatter_index] = res.local_c
        mask_flat[scatter_index] = 1

    owned_len = max_nnz // nh
    struct_shape = (nr, nc, max_nnz)
    values_shape = (nr, nc, nh, owned_len)
    struct_sharding = NamedSharding(grid.mesh, ReplicatedTiles.STRUCT_SPEC)
    values_sharding = NamedSharding(grid.mesh, ReplicatedTiles.VALUES_SPEC)

    blocked_fields = {}
    if blocked is not None:
        from distributed_sddmm_tpu.ops.blocked import (
            padded_lane_count, padded_lane_frac,
        )

        C = blocked.n_chunks
        chunk_spec = NamedSharding(grid.mesh, P("rows", "cols", None, None))
        meta_spec = NamedSharding(grid.mesh, P("rows", "cols", None))
        blocked_fields = dict(
            blk_lr=put_sharded(
                blocked.lr.reshape(nr, nc, C, blocked.lr.shape[-1]), chunk_spec
            ),
            blk_lc=put_sharded(
                blocked.lc.reshape(nr, nc, C, blocked.lc.shape[-1]), chunk_spec
            ),
            blk_meta=put_sharded(blocked.meta.reshape(nr, nc, C), meta_spec),
            blk_geom=(
                blocked.bm, blocked.bn, blocked.gr_blocks, blocked.gc_blocks,
                blocked.group,
            ),
            blk_pad_lanes=padded_lane_count(blocked),
            blk_pad_frac=padded_lane_frac(blocked),
            blk_variant=blk_variant,
        )

    return ReplicatedTiles(
        rows=put_sharded(rows_flat.reshape(struct_shape), struct_sharding),
        cols=put_sharded(cols_flat.reshape(struct_shape), struct_sharding),
        mask=put_sharded(mask_flat.reshape(struct_shape), struct_sharding),
        mask_owned=put_sharded(
            mask_flat.reshape(values_shape), values_sharding
        ),
        scatter_index=scatter_index,
        owned_len=owned_len,
        tile_rows=tile_rows,
        tile_cols=tile_cols,
        nnz=S.nnz,
        grid=grid,
        nnz_per_device=counts.reshape(nr, nc, 1),
        dyn_cap=(tuple(_dyn.realized[_dyn_mark:]) if _dyn is not None else None),
        **blocked_fields,
    )


def build_tiles(
    S: HostCOO,
    grid: GridSpec,
    layout,
    tile_rows: int,
    tile_cols: int,
    dtype=jnp.float32,
    min_pad: int = 1,
    block: bool = False,
    block_swap: bool = False,
    variant=None,
    R: int | None = None,
) -> TileSet:
    """Bucket ``S``'s nonzeros by (device, tile) and pad to a static shape.

    ``layout`` is called with ``(rows, cols)`` and must return a
    :class:`~distributed_sddmm_tpu.parallel.layouts.LayoutResult`; its
    ``n_tiles`` attribute fixes T. ``min_pad`` keeps max_nnz >= 1 so empty
    matrices still produce valid static shapes. ``block=True`` additionally
    builds the MXU chunk-list encoding (``ops/blocked.py``) consumed by the
    Pallas kernels (and makes the chunk layout the flat value layout, which
    inflates max_nnz by the chunk padding — only ask for it when the kernel
    consumes it); it is skipped automatically when the block-pair grid would
    be degenerate (see ``_BLOCK_PAIR_LIMIT``).

    ``block_swap=True`` builds the encoding in SWAPPED orientation: chunks
    are grouped by column block (``blk_lr`` holds column-locals, ``blk_lc``
    row-locals, ``blk_geom`` describes the (cols, rows) frames). Algorithms
    whose SpMM scatters into the tile's COLUMN dimension (Cannon dense,
    `25D_cannon_dense.hpp:271-305`) need this: the Pallas output-accumulator
    contract requires chunks grouped by the scatter dimension, and SDDMM is
    role-symmetric so it simply flips its dense operands. The flat
    rows/cols arrays remain in true (row, col) convention either way.

    ``variant`` (a ``codegen.KernelVariant``) banks the blocked encoding:
    one chunk list per nnz/row band (``codegen/banded.py``), the combined
    list presented through the same ``blk_*`` fields plus ``blk_bands``.
    When banking is impossible (degenerate block grids) the build falls
    back to the generic encoding and counts ``codegen_generic_fallbacks``.

    ``R``, the kernels' inner dimension, lets the generic encoding size
    its blocks to the tile set's density (``ops.blocked.choose_blocks``)
    where no geometry is pinned; without it the blocks are 512 square.
    """
    nr, nc, nh = grid.nr, grid.nc, grid.nh
    T = layout.n_tiles
    res = layout(S.rows, S.cols)
    if res.i.size:
        assert res.i.max() < nr and res.j.max() < nc and res.k.max() < nh, (
            "layout produced out-of-grid coordinates"
        )
        assert res.tile.max() < T, "layout produced out-of-range tile id"

    dev = (res.i * nc + res.j) * nh + res.k
    bucket = dev * T + res.tile
    n_buckets = nr * nc * nh * T

    _dyn = buckets.dyn_capacity_state()
    _dyn_mark = len(_dyn.realized) if _dyn is not None else 0

    blocked = None
    if block:
        blocked, blk_variant = _try_build_blocked(
            n_buckets, bucket, res, tile_rows, tile_cols, swap=block_swap,
            variant=variant, R=R,
        )
        # Banded encodings consume their rungs per band inside
        # build_banded; the generic encoding takes one rung on its total
        # chunk count here.
        if blocked is not None and getattr(blocked, "bands", None) is None:
            cap = buckets.dyn_rung(blocked.n_chunks, multiple=blocked.group)
            if cap is not None and cap > blocked.n_chunks:
                from distributed_sddmm_tpu.ops.blocked import pad_chunk_count

                blocked = pad_chunk_count(blocked, cap)

    if blocked is not None:
        # The chunk layout IS the flat layout: value vectors serve both the
        # flat (XLA) and blocked (Pallas) kernels with zero relayout cost.
        from distributed_sddmm_tpu.ops.blocked import CHUNK

        max_nnz = blocked.n_chunks * CHUNK
        scatter_index = blocked.host_to_chunk
        if block_swap:
            rows_flat = blocked.global_cols().reshape(-1)
            cols_flat = blocked.global_rows().reshape(-1)
        else:
            rows_flat = blocked.global_rows().reshape(-1)
            cols_flat = blocked.global_cols().reshape(-1)
        mask_flat = (~blocked.pad_lane).reshape(-1).astype(np.dtype(dtype))
    else:
        from distributed_sddmm_tpu import native

        counts, order = native.bucket_sort(bucket, n_buckets)
        sorted_bucket = bucket[order]
        max_nnz = max(int(counts.max(initial=0)), min_pad)
        cap = buckets.dyn_rung(max_nnz)
        if cap is not None:
            max_nnz = cap
        starts = np.zeros(n_buckets, dtype=np.int64)
        np.cumsum(counts[:-1], out=starts[1:])

        # Position of each (sorted) nonzero within its bucket.
        within = np.arange(S.nnz, dtype=np.int64) - starts[sorted_bucket]
        pos_sorted = sorted_bucket * max_nnz + within
        scatter_index = np.empty(S.nnz, dtype=np.int64)
        scatter_index[order] = pos_sorted

        total = n_buckets * max_nnz
        rows_flat = np.zeros(total, dtype=np.int32)
        cols_flat = np.zeros(total, dtype=np.int32)
        mask_flat = np.zeros(total, dtype=np.dtype(dtype))
        rows_flat[scatter_index] = res.local_r
        cols_flat[scatter_index] = res.local_c
        mask_flat[scatter_index] = 1

    shape = (nr, nc, nh, T, max_nnz)
    sharding = NamedSharding(grid.mesh, TILE_SPEC)
    nnz_per_device = np.bincount(dev, minlength=nr * nc * nh).reshape(nr, nc, nh)

    blocked_fields = {}
    if blocked is not None:
        from distributed_sddmm_tpu.ops.blocked import (
            padded_lane_count, padded_lane_frac,
        )

        C = blocked.n_chunks
        chunk_spec = NamedSharding(
            grid.mesh, P("rows", "cols", "layers", None, None, None)
        )
        meta_spec = NamedSharding(grid.mesh, P("rows", "cols", "layers", None, None))
        shape6 = (nr, nc, nh, T, C, blocked.lr.shape[-1])
        blocked_fields = dict(
            blk_lr=put_sharded(blocked.lr.reshape(shape6), chunk_spec),
            blk_lc=put_sharded(blocked.lc.reshape(shape6), chunk_spec),
            blk_meta=put_sharded(
                blocked.meta.reshape(nr, nc, nh, T, C), meta_spec
            ),
            blk_geom=(
                blocked.bm, blocked.bn, blocked.gr_blocks, blocked.gc_blocks,
                blocked.group,
            ),
            blk_bands=getattr(blocked, "bands", None),
            blk_pad_lanes=padded_lane_count(blocked),
            blk_pad_frac=padded_lane_frac(blocked),
            blk_variant=blk_variant,
        )

    return TileSet(
        rows=put_sharded(rows_flat.reshape(shape), sharding),
        cols=put_sharded(cols_flat.reshape(shape), sharding),
        mask=put_sharded(mask_flat.reshape(shape), sharding),
        scatter_index=scatter_index,
        tile_rows=tile_rows,
        tile_cols=tile_cols,
        nnz=S.nnz,
        grid=grid,
        nnz_per_device=nnz_per_device,
        dyn_cap=(tuple(_dyn.realized[_dyn_mark:]) if _dyn is not None else None),
        **blocked_fields,
    )


# Skip chunk-list blocking when the (bucket, row_block, col_block) pair grid
# would not fit comfortably in host memory — e.g. absurd T x frame combos.
_BLOCK_PAIR_LIMIT = 200_000_000


def _try_build_blocked(n_buckets, bucket, res, tile_rows, tile_cols,
                       swap=False, variant=None, R=None):
    """Returns ``(blocked_meta_or_None, realized_variant_id)``: the
    second element is the variant id ONLY when the variant actually
    shaped the encoding — a guard fallback returns None there, so
    records/keys never claim a specialization that did not build
    (``kernel_variant`` is a gate config axis; a mislabeled generic run
    would pool into the variant baseline). Inside a dynamic-capacity
    scope the generic encoding keeps BASE_BLOCK: a rebind must land on
    the geometry its programs were traced for, whatever the new
    pattern's density."""
    from distributed_sddmm_tpu.ops.blocked import (
        BASE_BLOCK, DEFAULT_BLOCK_COLS, DEFAULT_BLOCK_ROWS, DEFAULT_GROUP,
        build_blocked, pick_block,
    )

    if buckets.dyn_capacity_state() is not None:
        R = None

    local_r, local_c = res.local_r, res.local_c
    if swap:
        local_r, local_c = local_c, local_r
        tile_rows, tile_cols = tile_cols, tile_rows
    # Estimate the pair grid in the SAME orientation build_blocked will use
    # (i.e. post-swap) — with asymmetric block preferences the pre-swap
    # product differs and the guard would check the wrong count. A
    # variant builds with its heavy band's blocks (smaller in the rl
    # regime => more pairs), one full-frame chunk list PER band.
    def _est_pairs(pref_bm, pref_bn, n_lists):
        bm = pick_block(max(tile_rows, 1), pref_bm)
        bn = pick_block(max(tile_cols, 1), pref_bn)
        return (
            n_buckets
            * max(-(-tile_rows // bm), 1)
            * max(-(-tile_cols // bn), 1)
            * n_lists
        )

    if variant is not None:
        from distributed_sddmm_tpu.ops.blocked import MAX_BLOCKS

        heavy = variant.bands[-1]
        bm_v = pick_block(max(tile_rows, 1), heavy.block_rows)
        bn_v = pick_block(max(tile_cols, 1), heavy.block_cols)
        # Worst-case block counts are the heavy band's (auto-width bands
        # only MERGE columns, and every band shares block_rows).
        over_blocks = (
            -(-tile_rows // bm_v) > MAX_BLOCKS
            or -(-tile_cols // bn_v) > MAX_BLOCKS
        )
        if over_blocks or _est_pairs(
            heavy.block_rows, heavy.block_cols, len(variant.bands)
        ) > _BLOCK_PAIR_LIMIT:
            # The variant's geometry (smaller rl blocks => more blocks
            # per axis and more pairs, one full-frame list per band)
            # blows the packed-meta or host-side budget; the generic
            # encoding may still fit — fall back, don't raise and don't
            # go unblocked.
            from distributed_sddmm_tpu.obs import metrics as obs_metrics

            obs_metrics.GLOBAL.add("codegen_generic_fallbacks")
            variant = None
    if _est_pairs(DEFAULT_BLOCK_ROWS or BASE_BLOCK,
                  DEFAULT_BLOCK_COLS or BASE_BLOCK, 1) > _BLOCK_PAIR_LIMIT:
        return None, None
    if variant is not None and getattr(variant, "banked", False):
        from distributed_sddmm_tpu.codegen.banded import build_banded
        from distributed_sddmm_tpu.obs import metrics as obs_metrics

        banded = build_banded(
            n_buckets, bucket, local_r, local_c, tile_rows, tile_cols,
            variant,
        )
        obs_metrics.GLOBAL.add("codegen_variants_built")
        return banded, variant.variant_id
    if variant is not None:
        # Non-banked variant (pure R-regime tiling): count the build so
        # /metrics distinguishes "variant active" from "fell back".
        from distributed_sddmm_tpu.obs import metrics as obs_metrics

        obs_metrics.GLOBAL.add("codegen_variants_built")
        heavy = variant.bands[-1]
        return build_blocked(
            n_buckets, bucket, local_r, local_c, tile_rows, tile_cols,
            block_rows=heavy.block_rows, block_cols=heavy.block_cols,
            group=heavy.group,
        ), variant.variant_id
    return build_blocked(
        n_buckets, bucket, local_r, local_c, tile_rows, tile_cols,
        group=DEFAULT_GROUP, R=R,
    ), None
