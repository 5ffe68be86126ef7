"""Pluggable local SDDMM / SpMM kernels (single device, one sparse tile).

This is the framework's counterpart of the reference's plugin boundary
``KernelImplementation`` (`/root/reference/sparse_kernels.h:15-79`): the
distributed algorithms are written against the :class:`LocalKernel` interface
and any implementation can be swapped in. Implementations:

* :class:`XlaKernel` — pure jax.numpy gather-dot SDDMM and segment-sum SpMM.
  Works on every backend (CPU test meshes included); XLA fuses the gather with
  the rowwise multiply-reduce. This replaces the reference's OpenMP COO loop
  (`sparse_kernels.cpp:13-57`) and MKL ``mkl_sparse_d_mm``
  (`sparse_kernels.cpp:94-121`).
* ``PallasKernel`` (``ops/pallas_kernels.py``) — blocked kernels for peak TPU
  throughput on row-sorted tiles.

The ring programs call either through one tile seam: :class:`FlatTiles`
here (``(rows, cols)`` tiles, the flat protocol) or ``BlockedTiles``
beside ``PallasKernel`` (chunk-list tiles, the tile-level kernels). The
strategy owns the ring; the seam's form owns the local tile work.

Tile convention: a tile is a struct-of-arrays ``(rows, cols, vals)`` of static
length ``max_nnz``, padded with inert entries ``row = col = 0, val = 0``.
Zero-valued padding is harmless in both ops: SDDMM multiplies dots by the
input values (0 at pads) and SpMM scatters ``val * B[col]`` (0 contribution).
This mirrors the reference's own max_nnz double-buffering for in-flight
sparse shifts (`SpmatLocal.hpp:153-169`) — its solution to variable nnz is
already the static-shape solution XLA requires.
"""

from __future__ import annotations

import os
from typing import Protocol

import jax
import jax.numpy as jnp

# Element budget for the gathered/scattered [nnz, R] intermediates of the
# XLA kernel. Both ops materialize nnz*R-element arrays (A[rows]/B[cols]
# and the scatter contributions); past this budget they switch to a
# sequential scan over nnz segments so peak memory stays bounded — the
# reference grid's heavy corner (logM=16, nnz/row=128, R=512) needs
# ~17 GB per gather otherwise, more than a v5e chip's HBM. Shapes are
# static under jit, so this is a trace-time branch, not runtime control
# flow. The default (2^29 elements ≈ 2 GB f32 per intermediate) keeps the
# headline config (2^16 rows, nnz/row=32, R=128 → 2.7e8) on the fused
# single-pass path.
XLA_GATHER_BUDGET = int(os.environ.get("DSDDMM_XLA_GATHER_BUDGET", str(1 << 29)))

# Element budget for the one-shot masked-softmax row statistics of the
# attention epilogue (``attn_stats``). Past it the stats switch to the
# streaming max/denominator scan (the classic online-softmax
# recurrence), which holds one [out_rows] running max and denominator
# instead of [nnz] temporaries. The value array is 1-D, so this budget
# is far larger than the gather budget's per-R accounting.
ATTN_STREAM_BUDGET = int(
    os.environ.get("DSDDMM_ATTN_STREAM_BUDGET", str(1 << 24))
)

#: Finite stand-in for -inf in the masked-softmax passes: segment maxima
#: over empty/masked rows must stay finite (``-inf - -inf`` would NaN the
#: streaming rescale ``exp(m_old - m_new)``), and ``exp(z - NEG)`` of a
#: real logit still overflows to +inf, which every consumer guards with
#: a select. All softmax implementations (XLA flat, Pallas chunk, f64
#: oracle) share this constant so fused/unfused paths stay bit-aligned.
ATTN_NEG = -1e30


class LocalKernel(Protocol):
    """Local kernel plugin boundary (reference `sparse_kernels.h:15-79`)."""

    def sddmm(
        self,
        rows: jax.Array,
        cols: jax.Array,
        vals: jax.Array,
        A: jax.Array,
        B: jax.Array,
    ) -> jax.Array:
        """Return ``vals * rowwise_dot(A[rows], B[cols])``, shape [max_nnz]."""
        ...

    def spmm(
        self,
        rows: jax.Array,
        cols: jax.Array,
        vals: jax.Array,
        B: jax.Array,
        out_rows: int,
    ) -> jax.Array:
        """Return ``S_tile @ B`` as a dense [out_rows, R] array.

        Accumulate (beta=1) semantics are the caller's job: callers add the
        returned partial into their accumulation buffer, matching the
        reference's ``beta=1`` MKL call (`sparse_kernels.cpp:104-107`).
        """
        ...


class XlaKernel:
    """Gather-dot SDDMM + segment-sum SpMM in pure XLA ops.

    ``gather_budget`` overrides the module-level :data:`XLA_GATHER_BUDGET`
    for this instance — the autotuner's chunked-kernel candidate is exactly
    an ``XlaKernel`` with a budget below the tile's nnz*R footprint, which
    forces the sequential-scan path regardless of the env default.
    """

    name = "xla"

    def __init__(self, gather_budget: int | None = None):
        self._gather_budget = gather_budget

    @property
    def gather_budget(self) -> int:
        # Falls back to the module global at CALL time, so tests (and env
        # overrides applied after import) that rebind XLA_GATHER_BUDGET
        # still govern default-constructed kernels.
        if self._gather_budget is not None:
            return self._gather_budget
        return XLA_GATHER_BUDGET

    def sddmm(self, rows, cols, vals, A, B):
        n, r = rows.shape[0], A.shape[-1]
        budget = self.gather_budget
        if n * r <= budget:
            dots = jnp.sum(A[rows] * B[cols], axis=-1)
            return vals * dots.astype(vals.dtype)
        seg = max(1, budget // r)
        n_seg = -(-n // seg)
        pad = n_seg * seg - n
        rows_p = jnp.pad(rows, (0, pad)).reshape(n_seg, seg)
        cols_p = jnp.pad(cols, (0, pad)).reshape(n_seg, seg)
        dots = jax.lax.map(
            lambda rc: jnp.sum(A[rc[0]] * B[rc[1]], axis=-1), (rows_p, cols_p)
        ).reshape(-1)[:n]
        return vals * dots.astype(vals.dtype)

    def spmm(self, rows, cols, vals, B, out_rows: int):
        n, r = rows.shape[0], B.shape[-1]
        out_dtype = jnp.result_type(vals.dtype, B.dtype)
        budget = self.gather_budget
        if n * r <= budget:
            contrib = vals[:, None] * B[cols]
            return jax.ops.segment_sum(contrib, rows, num_segments=out_rows)
        seg = max(1, budget // r)
        n_seg = -(-n // seg)
        pad = n_seg * seg - n
        # Pad entries land at row 0 with val 0 — inert under accumulate,
        # exactly the tile padding convention documented above.
        rows_p = jnp.pad(rows, (0, pad)).reshape(n_seg, seg)
        cols_p = jnp.pad(cols, (0, pad)).reshape(n_seg, seg)
        vals_p = jnp.pad(vals, (0, pad)).reshape(n_seg, seg)

        def step(acc, rcv):
            rr, cc, vv = rcv
            return acc + jax.ops.segment_sum(
                vv[:, None] * B[cc], rr, num_segments=out_rows
            ), None

        out, _ = jax.lax.scan(
            step,
            jnp.zeros((out_rows, r), dtype=out_dtype),
            (rows_p, cols_p, vals_p),
        )
        return out


    # ------------------------------------------------------------------ #
    # Masked-softmax attention epilogue (flat COO layout)
    #
    # SDDMM ⊙ masked-softmax → SpMM *is* block-sparse attention: the
    # SDDMM values are the row-sparse logits, and these passes turn them
    # into row-stochastic attention weights without ever materializing a
    # dense [rows, cols] logit matrix. The mask indicator is ``gate !=
    # 0`` — the tile value vector doubles as the mask (pad lanes carry
    # 0 by the TileSet contract, and a zero-valued mask entry means
    # "present in the pattern but masked out"), so fully masked rows
    # degrade to an all-zero output row, never NaN.
    # ------------------------------------------------------------------ #

    def attn_stats(self, rows, gate, logits, out_rows: int):
        """Per-row masked max and sum-of-exp: ``(m [out_rows],
        d [out_rows])`` with ``m = ATTN_NEG`` and ``d = 0`` for rows
        with no unmasked entries. Beyond :data:`ATTN_STREAM_BUDGET`
        elements the computation streams: a scan over fixed-size
        segments carries the running max and a rescaled denominator
        (``d ← d·exp(m_old − m_new) + Σ exp(z − m_new)``) — the online
        softmax recurrence, so peak memory is one segment plus two
        [out_rows] vectors."""
        n = rows.shape[0]
        dt = logits.dtype
        neg = jnp.asarray(ATTN_NEG, dt)

        def seg_stats(r, g, z, m_floor):
            zsafe = jnp.where(g != 0, z, neg)
            cm = jax.ops.segment_max(zsafe, r, num_segments=out_rows)
            cm = jnp.maximum(cm, neg)  # empty segments: -inf -> finite
            m_new = jnp.maximum(m_floor, cm)
            e = jnp.where(g != 0, jnp.exp(z - m_new[r]), jnp.asarray(0, dt))
            cs = jax.ops.segment_sum(e, r, num_segments=out_rows)
            return m_new, cs

        budget = ATTN_STREAM_BUDGET
        if n <= budget:
            m0 = jnp.full((out_rows,), neg, dt)
            return seg_stats(rows, gate, logits, m0)
        seg = max(1, budget)
        n_seg = -(-n // seg)
        pad = n_seg * seg - n
        rows_p = jnp.pad(rows, (0, pad)).reshape(n_seg, seg)
        gate_p = jnp.pad(gate, (0, pad)).reshape(n_seg, seg)  # pads gate=0
        z_p = jnp.pad(logits, (0, pad)).reshape(n_seg, seg)

        def step(carry, rgz):
            m_run, d_run = carry
            r, g, z = rgz
            m_new, cs = seg_stats(r, g, z, m_run)
            d_new = d_run * jnp.exp(m_run - m_new) + cs
            return (m_new, d_new), None

        init = (jnp.full((out_rows,), neg, dt), jnp.zeros((out_rows,), dt))
        (m, d), _ = jax.lax.scan(step, init, (rows_p, gate_p, z_p))
        return m, d

    def attn_normalize(self, rows, gate, logits, m, d):
        """Masked-softmax weights from the row stats: ``exp(z − m[row]) /
        d[row]`` at unmasked entries, exactly 0 at masked entries, pad
        lanes, and fully masked rows (``d == 0``)."""
        dt = logits.dtype
        sel = (gate != 0) & (d[rows] > 0)
        # exp on the selected-safe argument: an unmasked overflow
        # (z - ATTN_NEG) would manufacture inf before the select.
        e = jnp.exp(jnp.where(sel, logits - m[rows], jnp.asarray(0, dt)))
        return jnp.where(sel, e / jnp.where(sel, d[rows], 1.0), 0.0).astype(dt)

    def attn_softmax(self, rows, gate, logits, out_rows: int):
        """Row-wise masked softmax over flat COO values (stats +
        normalize in one call — the single-tile convenience form; the
        distributed programs call the two halves around their cross-
        device max/denominator merge)."""
        m, d = self.attn_stats(rows, gate, logits, out_rows)
        return self.attn_normalize(rows, gate, logits, m, d)


class FlatTiles:
    """The flat form of the tile seam the ring programs call: a tile is
    its ``(rows, cols)`` index vectors and the local ops are the
    kernel's flat protocol. Its blocked twin is
    :class:`~distributed_sddmm_tpu.ops.pallas_kernels.BlockedTiles`;
    ``DistributedSparse._tile_ops`` picks one per tile set.

    The ops read the tile in the orientation of the accumulating output
    (``out_rows`` of it): ``x_r`` is the dense operand its rows gather
    from, ``x_c`` the one its columns gather from. ``swap`` puts the
    output on the tile's columns (the 2.5D dense-replicating ring)."""

    # The flat ops carry their varying-mesh-axes types, so shard_map
    # checks them.
    check_vma = True

    def __init__(self, kernel, tiles, swap: bool = False):
        self.kernel = kernel
        self.tiles = tiles
        self.swap = swap
        self.out_rows = tiles.tile_cols if swap else tiles.tile_rows

    def fields(self) -> tuple:
        """The tile set's structure arrays, in program-argument order."""
        return (self.tiles.rows, self.tiles.cols)

    @staticmethod
    def specs(tile_spec) -> tuple:
        return (tile_spec, tile_spec)

    @staticmethod
    def squeeze(fields, lead=()) -> tuple:
        """Per-device field blocks -> ``lead + [max_nnz]``."""
        return tuple(f.reshape(*lead, f.shape[-1]) for f in fields)

    @staticmethod
    def softmax_fields(fields) -> tuple:
        """The fields :meth:`softmax` reads: the row indices."""
        return tuple(fields[:1])

    @staticmethod
    def tile(rows, cols):
        return (rows, cols)

    @staticmethod
    def rows(X):
        return X

    @staticmethod
    def cols(X):
        return X

    def sddmm(self, tile, vals, x_r, x_c):
        rows, cols = tile
        if self.swap:
            return self.kernel.sddmm(rows, cols, vals, x_c, x_r)
        return self.kernel.sddmm(rows, cols, vals, x_r, x_c)

    def spmm(self, tile, vals, x_c):
        """SpMM partial ``[out_rows, R]``."""
        rows, cols = tile
        if self.swap:
            rows, cols = cols, rows
        return self.kernel.spmm(rows, cols, vals, x_c, self.out_rows)

    def fused(self, tile, vals, x_r, x_c, acc, put):
        """SDDMM then SpMM on one tile: ``(acc + partial, put(mid))``."""
        mid = self.sddmm(tile, vals, x_r, x_c)
        stored = put(mid)
        return acc + self.spmm(tile, mid, x_c), stored

    def zeros(self, like):
        return jnp.zeros((self.out_rows, like.shape[-1]), like.dtype)

    @staticmethod
    def finish(acc, like):
        return acc

    def softmax(self, fields, gate, logits, merge):
        """Row-wise masked softmax over ``[T, max_nnz]`` tile-layout
        values: segment stats over all tiles, ``merge(m, d)`` across
        devices, normalize."""
        shape = gate.shape
        rows_f = fields[0].reshape(-1)
        gate_f = gate.reshape(-1)
        z_f = logits.reshape(-1)
        m, d = self.kernel.attn_stats(rows_f, gate_f, z_f, self.out_rows)
        m, d = merge(m, d)
        return self.kernel.attn_normalize(
            rows_f, gate_f, z_f, m, d).reshape(shape)

    @staticmethod
    def sink_stats(sink):
        """A per-row sink logit ``[out_rows]`` as one more softmax
        partition in :meth:`softmax`'s stat frame: ``(m, d) = (sink, 1)``
        (it joins every denominator and carries no value)."""
        return sink, jnp.ones_like(sink)


def attn_merge_stats(stats):
    """Combine per-partition masked-softmax row stats into one frame.

    ``stats`` is a sequence of ``(m, d)`` pairs over the SAME row frame
    (per tile, per band, or per device after a gather): the merged max
    is the elementwise maximum and each partial denominator is rescaled
    into it — the online-softmax merge rule. Empty partitions
    (``m == ATTN_NEG, d == 0``) are absorbed exactly: ``exp(m_b − m)``
    underflows to 0 against any real max and its ``d_b`` is 0 against
    another empty one.
    """
    import functools

    m = functools.reduce(jnp.maximum, [s[0] for s in stats])
    d = sum(s[1] * jnp.exp(s[0] - m) for s in stats)
    return m, d


_REGISTRY = {"xla": XlaKernel}


def get_kernel(name: str) -> LocalKernel:
    """Kernel factory; Pallas registers lazily to keep CPU imports light.

    ``"auto"`` picks Pallas on real TPU backends and XLA elsewhere (the
    Pallas interpreter is not an honest non-TPU fallback).
    """
    if name == "auto":
        import jax

        name = "pallas" if jax.default_backend() == "tpu" else "xla"
    if name == "pallas" and "pallas" not in _REGISTRY:
        try:
            from distributed_sddmm_tpu.ops.pallas_kernels import PallasKernel
        except ImportError as e:
            raise NotImplementedError(
                "the 'pallas' kernel is not available in this build"
            ) from e
        _REGISTRY["pallas"] = PallasKernel
    if name not in _REGISTRY:
        raise ValueError(f"unknown kernel {name!r}; available: {sorted(_REGISTRY)}")
    return _REGISTRY[name]()
