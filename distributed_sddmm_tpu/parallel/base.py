"""Abstract distributed SDDMM/SpMM strategy: public API, buffers, perf.

TPU-native counterpart of the reference's ``Distributed_Sparse``
(`/root/reference/distributed_sparse.h:32-388`). Differences by design:

* **Functional, global-array API.** Dense operands are global ``jax.Array``s
  with a ``NamedSharding`` instead of per-rank Eigen buffers + submatrix
  descriptors; ops return new arrays instead of mutating. The reference's
  ``DenseSubmatrix`` bookkeeping (`distributed_sparse.h:20-30`) disappears:
  ``dummy_initialize``'s fill ``value = globalRow * R + globalCol``
  (`distributed_sparse.h:322-346`) becomes a global iota expression that XLA
  materializes shard-locally.
* **Sparse values travel in tile structure.** ``like_S_values`` returns a
  sharded padded array aligned with the tile layout (see
  ``parallel/sharding.py``) rather than a per-rank flat vector.
* **Perf counters time whole public calls** around ``block_until_ready``; the
  reference's intra-call region timers (`distributed_sparse.h:205-261`)
  cannot exist inside one fused XLA program — use ``jax.profiler`` traces for
  region-level attribution instead.
"""

from __future__ import annotations

import abc
import time
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from distributed_sddmm_tpu.common import KernelMode, MatMode
from distributed_sddmm_tpu.obs import log as obs_log
from distributed_sddmm_tpu.obs import metrics as obs_metrics
from distributed_sddmm_tpu.obs import profiler as obs_profiler
from distributed_sddmm_tpu.obs import trace as obs_trace
from distributed_sddmm_tpu.obs import watchdog as obs_watchdog
from distributed_sddmm_tpu.ops.kernels import LocalKernel, XlaKernel
from distributed_sddmm_tpu.parallel.mesh import GridSpec
from distributed_sddmm_tpu.parallel.sharding import TileSet


def _band_sig(tiles) -> str:
    """Short digest of the REALIZED band structure (``.b<hex>``; "" for
    an un-banked encoding). The banked kernel bakes the band tuple —
    chunk ranges, merged widths, body upgrades — STATICALLY into the
    traced program, and all of it is data-dependent (row-degree
    distribution), while the autotune fingerprint only hashes aggregate
    stats and the aval signature only sees ``[C_tot, CHUNK]`` shapes.
    Without this segment, two same-fingerprint matrices with different
    skew could alias one store entry and slice chunks at the wrong
    static band boundary — silently wrong output. The digest is a pure
    function of frozen int/str dataclasses, so it is cross-process
    stable like every other key component."""
    bands = getattr(tiles, "blk_bands", None)
    if not bands:
        return ""
    import hashlib

    return ".b" + hashlib.sha256(repr(bands).encode()).hexdigest()[:10]


def realized_kernel_variant(alg):
    """THE resolution rule for "what variant did this run actually
    execute" — bench records (``harness``) and serve-ladder keys
    (``serve/workloads``) both resolve through here so they can never
    drift apart and split one run across gate baselines. Prefers the
    strategy's :attr:`DistributedSparse.kernel_variant_realized` (None
    there MEANS generic, e.g. a guard fallback); only an object without
    that property falls back to the kernel's identity."""
    missing = object()
    realized = getattr(alg, "kernel_variant_realized", missing)
    if realized is not missing:
        return realized
    return getattr(getattr(alg, "kernel", None), "variant_id", None)


class DistributedSparse(abc.ABC):
    """Base class for the four communication-avoiding strategies."""

    algorithm_name: str = ""
    proc_grid_names: tuple = ()
    #: The ``tools/costmodel.py`` model this strategy's layout realizes
    #: (None = no analytic model; comm counters then stay zero). Set by
    #: subclasses; DenseShift15D chooses per fusion approach.
    cost_model_name: str | None = None

    def __init__(
        self,
        grid: GridSpec,
        M: int,
        N: int,
        R: int,
        c: int,
        kernel: Optional[LocalKernel] = None,
        dtype=jnp.float32,
        wire=None,
    ):
        from distributed_sddmm_tpu.parallel.wire import wire_policy

        self.grid = grid
        self.p = grid.p
        self.M, self.N, self.R, self.c = M, N, R, c
        self.kernel = kernel if kernel is not None else XlaKernel()
        self.dtype = dtype
        #: Realized wire-precision policy (``parallel/wire.py``): which
        #: dtype each collective payload role crosses the ICI in. The
        #: default (None, no env knobs) is the f32 identity wire —
        #: bit-identical programs, unchanged cache keys.
        self.wire = wire_policy(wire)
        self.r_split = False  # overridden by R-splitting strategies
        #: Per-op attribution registry (kernel vs retry/fault overhead,
        #: comm words, FLOPs). Replaces the unsynchronized total_time /
        #: call_count dicts; see the compat properties below.
        self.metrics = obs_metrics.OpMetrics()
        self._op_cost_cache: dict = {}
        self._trace_meta_emitted = False
        self._programs: dict = {}
        #: Optional program-store binder (``programs.bind_strategy``):
        #: ``binder(op_key, jit_fn) -> callable``. When set, strategies
        #: pass every shard_map op program they build through
        #: :meth:`_finalize_program` so compiles resolve via the
        #: persistent AOT store instead of always tracing live.
        self._program_binder = None

        # Subclasses must set these before use:
        self.M_pad: int = -1
        self.N_pad: int = -1
        self.a_spec: P = None
        self.b_spec: P = None
        self.S_tiles: TileSet = None
        self.ST_tiles: TileSet = None

    # ------------------------------------------------------------------ #
    # Canonical dense representation hooks.
    #
    # Most strategies store A as a plain (M_pad, R) array; R-splitting
    # strategies (1.5D sparse-shift, 2.5D) use higher-rank canonical shapes
    # whose leading dims encode a striped row order. A strategy defines the
    # shape and the global-row index of each leading position; everything
    # else (fills, dummy init, host converters) derives from those.
    # ------------------------------------------------------------------ #

    def dense_shape(self, mode: MatMode) -> tuple:
        n_rows = self.M_pad if mode == MatMode.A else self.N_pad
        return (n_rows, self.R)

    def _dense_global_rows(self, mode: MatMode) -> jax.Array:
        """Global row index for every leading position of the canonical
        shape; shape == dense_shape(mode)[:-1]. Row-major reshape to
        (n_rows_pad, R) must recover global row order (the default does
        trivially)."""
        n_rows = self.M_pad if mode == MatMode.A else self.N_pad
        return jnp.arange(n_rows, dtype=self.dtype)

    # ------------------------------------------------------------------ #
    # Dense buffer factories (reference `distributed_sparse.h:197-203`)
    # ------------------------------------------------------------------ #

    def a_sharding(self) -> NamedSharding:
        return NamedSharding(self.grid.mesh, self.a_spec)

    def b_sharding(self) -> NamedSharding:
        return NamedSharding(self.grid.mesh, self.b_spec)

    def _fill_program(self, shape: tuple, sharding):
        """Cached constant-fill factory (value stays a traced argument so one
        compile serves every fill value)."""
        key = ("fill", shape, sharding)
        if key not in self._programs:
            self._programs[key] = jax.jit(
                lambda v: jnp.full(shape, v, self.dtype),
                out_shardings=sharding,
            )
        return self._programs[key]

    def like_a_matrix(self, value: float) -> jax.Array:
        return self._fill_program(self.dense_shape(MatMode.A), self.a_sharding())(value)

    def like_b_matrix(self, value: float) -> jax.Array:
        return self._fill_program(self.dense_shape(MatMode.B), self.b_sharding())(value)

    def dummy_initialize(self, mode: MatMode) -> jax.Array:
        """Deterministic ``value = globalRow * R + globalCol`` fill.

        Layout-independent by construction — the verification protocol
        requires every strategy to produce identical global results from it
        (`distributed_sparse.h:322-346`, `scratch.cpp:26-76`).
        """
        shape = self.dense_shape(mode)
        sharding = self.a_sharding() if mode == MatMode.A else self.b_sharding()
        key = ("dummy", shape, sharding)
        if key not in self._programs:

            def make():
                rows = self._dense_global_rows(mode)[..., None]
                col = jnp.arange(self.R, dtype=self.dtype)
                return rows * self.R + col

            self._programs[key] = jax.jit(make, out_shardings=sharding)
        return self._programs[key]()

    def put_a(self, host: np.ndarray) -> jax.Array:
        """Place a host (M, R) matrix (padded to M_pad) onto the mesh."""
        buf = np.zeros((self.M_pad, self.R), dtype=self.dtype)
        buf[: host.shape[0]] = host
        return jax.device_put(
            buf.reshape(self.dense_shape(MatMode.A)), self.a_sharding()
        )

    def put_b(self, host: np.ndarray) -> jax.Array:
        buf = np.zeros((self.N_pad, self.R), dtype=self.dtype)
        buf[: host.shape[0]] = host
        return jax.device_put(
            buf.reshape(self.dense_shape(MatMode.B)), self.b_sharding()
        )

    def host_a(self, A: jax.Array) -> np.ndarray:
        """Fetch A to host in global (M, R) row order, stripping padding."""
        return np.asarray(A).reshape(self.M_pad, self.R)[: self.M]

    def host_b(self, B: jax.Array) -> np.ndarray:
        return np.asarray(B).reshape(self.N_pad, self.R)[: self.N]

    # ------------------------------------------------------------------ #
    # Sparse value factories (reference `distributed_sparse.h:189-195`)
    # ------------------------------------------------------------------ #

    def like_s_values(self, value: float) -> jax.Array:
        return self.S_tiles.like_values(value)

    def like_st_values(self, value: float) -> jax.Array:
        return self.ST_tiles.like_values(value)

    def scatter_s_values(self, host_vals: np.ndarray) -> jax.Array:
        return self.S_tiles.scatter_values(host_vals)

    def gather_s_values(self, dev_vals: jax.Array) -> np.ndarray:
        return self.S_tiles.gather_values(dev_vals)

    def scatter_st_values(self, host_vals: np.ndarray) -> jax.Array:
        return self.ST_tiles.scatter_values(host_vals)

    def gather_st_values(self, dev_vals: jax.Array) -> np.ndarray:
        return self.ST_tiles.gather_values(dev_vals)

    # ------------------------------------------------------------------ #
    # Distributed ops — the public capability surface
    # (reference `distributed_sparse.h:274-320`)
    # ------------------------------------------------------------------ #

    @abc.abstractmethod
    def sddmm_a(self, A: jax.Array, B: jax.Array, s_vals: jax.Array) -> jax.Array:
        """``vals = s_vals * (A @ B^T sampled at pattern(S))`` (tile layout)."""

    @abc.abstractmethod
    def sddmm_b(self, A: jax.Array, B: jax.Array, st_vals: jax.Array) -> jax.Array:
        """SDDMM with values in S^T's tile layout."""

    @abc.abstractmethod
    def spmm_a(self, A: jax.Array, B: jax.Array, s_vals: jax.Array) -> jax.Array:
        """Return ``S @ B`` in A's sharding (reference zeroes then accumulates,
        `distributed_sparse.h:274-277`)."""

    @abc.abstractmethod
    def spmm_b(self, A: jax.Array, B: jax.Array, st_vals: jax.Array) -> jax.Array:
        """Return ``S^T @ A`` in B's sharding."""

    def fused_spmm(
        self,
        A: jax.Array,
        B: jax.Array,
        s_vals: jax.Array,
        mode: MatMode = MatMode.A,
    ) -> tuple[jax.Array, jax.Array]:
        """SDDMM -> SpMM fusion. Returns ``(new_dense, sddmm_vals)``.

        Base implementation chains the two ops ("replication reuse" shape,
        `distributed_sparse.h:296-312`); subclasses override with fused
        single-loop programs ("local kernel overlap").
        """
        if mode == MatMode.A:
            mid = self.sddmm_a(A, B, s_vals)
            return self.spmm_a(A, B, mid), mid
        mid = self.sddmm_b(A, B, s_vals)
        return self.spmm_b(A, B, mid), mid

    def fused_attention(
        self,
        A: jax.Array,
        B: jax.Array,
        s_vals: jax.Array,
        mode: MatMode = MatMode.A,
    ) -> tuple[jax.Array, jax.Array]:
        """Fused block-sparse attention: SDDMM → row-wise masked softmax
        → SpMM in ONE compiled program, no dense logits materialized.
        Returns ``(new_dense, attention_weights)``.

        Base implementation: NOT supported. The row denominator must see
        every logit of its row before any SpMM contribution flows, which
        the 1.5D dense-shift layout satisfies between its two ring
        passes (the device's tiles plus a [rows]-vector merge over the
        replication axis cover each row exactly); the sparse-shift and
        Cannon layouts move values/structure with the ring, so the
        denominator cannot ride the traveling accumulator — requesting
        attention on them is a configuration error (same gating pattern
        as ``--fusion overlap``), not a silent fallback.
        """
        raise NotImplementedError(
            f"fused attention is not implemented for "
            f"{self.algorithm_name or type(self).__name__}: the softmax "
            "row denominator cannot ride this strategy's traveling "
            "accumulator (use the 1.5D dense-shift strategies)"
        )

    def _unskew_cols(self, X: jax.Array, mode: MatMode):
        """Resident layout -> global column order (identity unless the
        strategy skews its resident R layout)."""
        return X

    def _skew_cols(self, X: jax.Array, mode: MatMode):
        """Global column order -> resident layout (identity default)."""
        return X

    def bind_program_store(self, binder) -> None:
        """Install a program-store binder (``binder(op_key, jit_fn) ->
        callable``; see ``programs.bind_strategy``). Cached op programs
        are dropped so they rebuild through the binder — the jits
        re-trace on their next call, exactly when they would have
        compiled anyway, so binding costs nothing it wasn't going to
        spend."""
        self._program_binder = binder
        self._programs.clear()

    def _finalize_program(self, op_key, fn):
        """Route one freshly built op program through the binder (when
        bound). ``op_key`` is the strategy's program-cache key — op
        name, tile set, ablation mode (and fusion variant where it
        shapes the program); stringified into the store key so ablated
        or overlap variants can never answer for the real program."""
        if self._program_binder is None:
            return fn
        return self._program_binder("-".join(str(k) for k in op_key), fn)

    def _program_cache_key(self, op: str, use_st: bool) -> tuple:
        """The strategy's program-cache key for one op under the CURRENT
        ablation mode — the single shape ``_program`` and
        ``inject_program`` must agree on (strategies with additional
        program variants, e.g. the shift strategies' fusion builds,
        override to append their segments).

        A codegen-specialized kernel (``codegen/``) appends its variant
        id: the banked programs trace different Pallas launches from
        the generic ones, so a program-store entry compiled under one
        variant must never answer for another (or for the generic
        kernel — whose keys are UNCHANGED, so pre-variant store entries
        keep hitting)."""
        from distributed_sddmm_tpu.parallel.loops import ablation

        key = (op, use_st, ablation())
        if getattr(self.kernel, "variant_id", None):
            # The REALIZED variant of the tiles this op consumes, not
            # the kernel's identity: when the build guard-felled to the
            # generic encoding, the traced program IS the generic one
            # and must share (not duplicate) its store entry.
            tiles = self.ST_tiles if use_st else self.S_tiles
            vid = getattr(tiles, "blk_variant", None)
            if vid:
                key += (f"variant={vid}{_band_sig(tiles)}",)
        # Wire-precision segment (``w<dtype>``): a bf16-wire program
        # traces different casts and must never answer for (or alias)
        # the f32 one. The identity policy appends NOTHING, so default
        # keys — and every pre-PR-15 store entry — stay byte-identical.
        wseg = self.wire.key_segment()
        if wseg:
            key += (wseg,)
        # Dyn-capacity segment (PR 20, ``dynstruct/``): a bucketed build
        # sizes its arrays to pow2 rungs, so the realized rungs — not
        # the exact pattern — are what the traced program depends on.
        # Exact builds have no dyn_cap and append NOTHING (old store
        # entries keep hitting); a bucketed key can never alias an exact
        # one. The band digest above stays in the key but is itself
        # rung-quantized for dyn builds (bands pad to rungs before
        # concatenation), so it survives pattern churn within a bucket
        # while still separating genuinely different band structure —
        # dropping it would let two same-rung, different-band patterns
        # answer for each other's programs.
        tiles = self.ST_tiles if use_st else self.S_tiles
        cap = getattr(tiles, "dyn_cap", None)
        if cap:
            key += ("cap=" + "x".join(str(c) for c in cap),)
        return key

    def inject_program(self, op: str, use_st: bool, loaded) -> None:
        """Install a pre-built executable (e.g. a `deserialize_and_load`
        result from an offline AOT compile, `scripts/aot_compile_apps.py`)
        as this op's cached program under the CURRENT ablation mode.

        Loaded executables are shape-rigid while the jitted program
        retraces, so the installed wrapper falls back to the strategy's
        own jit whenever the executable rejects a call (e.g. GAT's
        per-layer feature widths) — correctness never depends on the
        injection, only compile latency does.
        """
        key = self._program_cache_key(op, use_st)
        fallback = self._program(op, use_st)
        warned = []

        def dispatch(*args):
            try:
                return loaded(*args)
            except Exception as e:  # noqa: BLE001 — any rejection -> jit
                if not warned:
                    warned.append(1)
                    obs_log.warn(
                        "aot",
                        f"injected {op}/{use_st} program rejected a call; "
                        "jit fallback",
                        error=f"{type(e).__name__}: {e}",
                    )
                return fallback(*args)

        self._programs[key] = dispatch

    def dense_project(self, X: jax.Array, W: jax.Array, mode: MatMode) -> jax.Array:
        """Local dense projection ``X @ W`` in the canonical layout (the
        GAT per-head GEMM, reference `gat.hpp:88`). W is (R_in, R_out) in
        global column order."""
        self.set_r_value(W.shape[1])
        sharding = self.a_sharding() if mode == MatMode.A else self.b_sharding()
        key = ("project", mode, X.shape, W.shape, sharding)
        if key not in self._programs:
            self._programs[key] = jax.jit(
                lambda x, w: self._skew_cols(
                    jnp.einsum("...r,rk->...k", self._unskew_cols(x, mode), w), mode
                ),
                out_shardings=sharding,
            )
        return self._programs[key](X, W)

    def concat_heads(self, heads: list, mode: MatMode) -> jax.Array:
        """Concatenate per-head outputs along the feature dim in the
        canonical layout (reference per-head column-block writes,
        `gat.hpp:103`)."""
        self.set_r_value(sum(h.shape[-1] for h in heads))
        sharding = self.a_sharding() if mode == MatMode.A else self.b_sharding()
        key = ("concat", mode, tuple(h.shape for h in heads), sharding)
        if key not in self._programs:
            self._programs[key] = jax.jit(
                lambda *hs: self._skew_cols(
                    jnp.concatenate(
                        [self._unskew_cols(h, mode) for h in hs], axis=-1
                    ),
                    mode,
                ),
                out_shardings=sharding,
            )
        return self._programs[key](*heads)

    def set_r_value(self, R: int) -> None:
        self.R = R

    # ------------------------------------------------------------------ #
    # Blocked (Pallas) kernel dispatch, shared by every strategy
    # ------------------------------------------------------------------ #

    def _use_blocked(self, tiles) -> bool:
        """True when the kernel consumes chunk-list metadata and the tile
        set carries it (``ops/blocked.py``)."""
        return getattr(self.kernel, "is_blocked", False) and tiles.has_blocked

    def _blk_tile_factory(self, tiles):
        """Constructor for the kernel's per-tile chunk-list view:
        ``f(lr [C, CHUNK], lc [C, CHUNK], meta [C]) -> tile view``.

        Returns a :class:`~distributed_sddmm_tpu.codegen.kernel.
        BankedTile` builder when the tile set carries the banked
        encoding (``blk_bands``), else the generic ``BlockedTile``
        builder — the one place every strategy's blocked program binds
        the kernel to the tile geometry."""
        bands = getattr(tiles, "blk_bands", None)
        if bands:
            from distributed_sddmm_tpu.codegen.kernel import BankedTile

            bm, bn, grb, gcb, _ = tiles.blk_geom
            rows_pad, cols_pad = grb * bm, gcb * bn

            def make(lr, lc, meta):
                return BankedTile(
                    lr, lc, meta, bands=bands,
                    rows_pad=rows_pad, cols_pad=cols_pad,
                )

            return make
        from distributed_sddmm_tpu.ops.pallas_kernels import BlockedTile

        bm, bn, grb, gcb, grp = tiles.blk_geom

        def make(lr, lc, meta):
            return BlockedTile(
                lr, lc, meta, bm=bm, bn=bn, gr_blocks=grb,
                gc_blocks=gcb, group=grp,
            )

        return make

    @property
    def kernel_variant_realized(self):
        """The codegen variant id that actually shaped this strategy's
        tile encodings (None = generic, including a requested variant
        that guard-felled to the generic build). Bench records and
        serve keys report THIS, so a fallback run never pools into the
        variant gate baseline nor claims a specialization that did not
        run. If EITHER tile set realized the variant (the S/ST guards
        can trip asymmetrically on rectangular matrices), the run is
        labeled with it — a half-banked run has variant-shaped timings
        and must not pool into the pure-generic baseline."""
        return (
            getattr(self.S_tiles, "blk_variant", None)
            or getattr(self.ST_tiles, "blk_variant", None)
        )

    def _note_tile_metrics(self) -> None:
        """Record the counted padded-lane fraction of each tile set as a
        per-op metric gauge (scraped via ``/metrics`` and landed in
        bench records) — the waste the codegen banked variants exist to
        shrink. Called by strategy constructors once tiles exist."""
        a_side = ("sddmmA", "spmmA", "fusedSpMM", "cgStep", "gatLayer")
        b_side = ("sddmmB", "spmmB")
        for tiles, ops in ((self.S_tiles, a_side), (self.ST_tiles, b_side)):
            frac = getattr(tiles, "blk_pad_frac", None)
            if frac is None:
                continue
            for op in ops:
                self.metrics.note(op, padded_lane_frac=round(frac, 6))

    def _sddmm_args(self, tiles, vals) -> tuple:
        """Tile operands following the dense args for sddmm programs."""
        if self._use_blocked(tiles):
            return (tiles.blk_lr, tiles.blk_lc, tiles.blk_meta, tiles.mask, vals)
        return (tiles.rows, tiles.cols, tiles.mask, vals)

    def _spmm_args(self, tiles, vals) -> tuple:
        if self._use_blocked(tiles):
            return (tiles.blk_lr, tiles.blk_lc, tiles.blk_meta, vals)
        return (tiles.rows, tiles.cols, vals)

    def initial_shift(self, A, B, mode: KernelMode):
        """Pre-skew dense operands if the strategy needs it (no-op default;
        reference `distributed_sparse.h:266-268`)."""
        return A, B

    def de_shift(self, A, B, mode: KernelMode):
        return A, B

    # ------------------------------------------------------------------ #
    # Placement observability (reference `distributed_sparse.h:363-387`
    # ``print_nonzero_distribution`` + `FlexibleGrid.hpp:142-157`)
    # ------------------------------------------------------------------ #

    def nonzero_distribution_report(self) -> str:
        """Human-readable per-device nonzero/tile placement report."""
        lines = [
            f"{self.algorithm_name or type(self).__name__}: "
            f"M={self.M} N={self.N} R={self.R} c={self.c}",
            self.grid.pretty_print(),
        ]
        for label, tiles in (("S", self.S_tiles), ("S^T", self.ST_tiles)):
            if tiles is None:
                continue
            per_dev = np.asarray(tiles.nnz_per_device).reshape(-1)
            mean = per_dev.mean() if per_dev.size else 0.0
            # Real entries over padded slots, device-resident copies counted
            # on both sides — valid for sharded AND replicated tile classes.
            slots = float(tiles.rows.size)
            occ = per_dev.sum() / slots if slots else 1.0
            lines.append(
                f"  {label}: nnz={tiles.nnz}, tile frame "
                f"{tiles.tile_rows}x{tiles.tile_cols}, padded max_nnz/device="
                f"{tiles.max_nnz}, load imbalance max/mean="
                f"{per_dev.max() / mean if mean else 1.0:.3f}, "
                f"slot occupancy={occ:.3f}"
            )
            shape = np.asarray(tiles.nnz_per_device).shape
            for flat, nnz in enumerate(per_dev):
                coords = np.unravel_index(flat, shape)
                lines.append(
                    f"    device {tuple(int(x) for x in coords)}: nnz={int(nnz)}"
                )
            if tiles.has_blocked:
                geom = tiles.blk_geom
                lines.append(
                    f"    blocked: bm={geom[0]} bn={geom[1]} "
                    f"blocks={geom[2]}x{geom[3]} group={geom[4]}"
                )
        return "\n".join(lines)

    def print_nonzero_distribution(self) -> None:
        print(self.nonzero_distribution_report())  # cli-output

    # ------------------------------------------------------------------ #
    # Verification fingerprints (reference `scratch.cpp:26-76`)
    # ------------------------------------------------------------------ #

    @staticmethod
    def fingerprint(x: jax.Array) -> float:
        x64 = np.asarray(x, dtype=np.float64)
        return float(np.sum(x64 * x64))

    # ------------------------------------------------------------------ #
    # Performance counters (reference `distributed_sparse.h:205-261`)
    # ------------------------------------------------------------------ #

    @property
    def total_time(self):
        """Compat view of the old ``total_time`` dict: ``{op: kernel
        seconds}`` (successful attempts only — retry/fault overhead now
        lives in ``metrics.to_dict()[op]["overhead_s"]``; MIGRATING.md
        documents the change). Returns a snapshot, not a live dict."""
        return self.metrics.time_view()

    @property
    def call_count(self):
        """Compat view of the old ``call_count`` dict (snapshot)."""
        return self.metrics.calls_view()

    def _op_cost(self, op: str, pairs: float) -> tuple:
        """(model comm words, comm bytes, folded-out comm words, global
        FLOPs) for one call of ``op`` at the current R — cached, so the
        per-dispatch cost on the fast path is one dict hit.

        ``comm_words`` keeps its pre-PR-15 meaning (per-device float
        ELEMENTS moved — derived as bytes / element width, so gate
        history keeps comparing across the wire-precision change);
        ``comm_bytes`` is the dtype-aware volume the wire policy
        actually moves."""
        key = (op, self.R, pairs)
        hit = self._op_cost_cache.get(key)
        if hit is None:
            from distributed_sddmm_tpu.resilience import faults

            profile = self.comm_profile(op, pairs)
            in_model = [e for e in profile if e.get("in_model")]
            words = sum(e["words"] for e in in_model)
            nbytes = sum(e.get("bytes", e["words"] * 4) for e in in_model)
            extra = sum(e["words"] for e in profile if not e.get("in_model"))
            # Fault hook for comm-accounting drift: a `skew` spec at
            # comm:<op> scales the counted words. Applied on the cache
            # miss, so a firing sticks until the cost cache is next
            # cleared (reset_performance_timers) — the shape of a real
            # layout-math regression (the watchdog's comm-vs-costmodel
            # check is what must notice). The site counter advances once
            # per cache computation, not per dispatch. Bytes scale with
            # words: a layout-math drift moves both together.
            scaled = faults.scale_value(f"comm:{op}", words)
            if words and scaled != words:
                nbytes *= scaled / words
            words = scaled
            nnz = self.S_tiles.nnz if self.S_tiles is not None else 0
            flops = obs_metrics.op_flops(op, nnz, self.R, pairs)
            hit = self._op_cost_cache[key] = (words, nbytes, extra, flops)
        return hit

    def comm_profile(self, op: str, pairs: float = 1.0) -> list[dict]:
        """Per-call collective profile: ``[{"collective", "axis", "count",
        "words", "bytes", "in_model"}, ...]`` with per-device volumes —
        ``words`` in float elements (the pre-PR-15 unit, wire-dtype
        independent), ``bytes`` dtype-aware under the strategy's
        :attr:`wire` policy (entries omitting it are priced at 4 B/elem).

        The base implementation charges the strategy's analytic model
        volume (``tools/costmodel.pair_words`` scaled by the op's pair
        fraction) as one aggregate entry; strategies whose layout math is
        implemented here override with a genuine per-collective breakdown
        (see ``DenseShift15D.comm_profile``) — the cross-check between
        the two is what the trace report's model column surfaces.
        ``in_model=False`` entries (the SpMM reduce-scatter the notebook
        folds out of its comparison) are counted separately.
        """
        model = self.cost_model_name
        frac = obs_metrics.OP_PAIRS.get(op)
        if model is None or frac is None or self.S_tiles is None:
            return []
        from distributed_sddmm_tpu.tools import costmodel

        try:
            w = costmodel.pair_words(
                model, self.M_pad, self.N_pad, self.R,
                self.S_tiles.nnz, self.p, self.c,
            )
            b = costmodel.pair_bytes(
                model, self.M_pad, self.N_pad, self.R,
                self.S_tiles.nnz, self.p, self.c, wire=self.wire,
            )
        except ValueError:
            return []
        return [{
            "collective": "modeled", "axis": None, "count": 0,
            "words": w * frac * pairs, "bytes": b * frac * pairs,
            "in_model": True,
        }]

    def _emit_strategy_meta(self) -> None:
        """One ``strategy`` trace event per instance: the static layout
        facts the report tool needs to recompute model predictions."""
        if self._trace_meta_emitted or not obs_trace.enabled():
            return
        self._trace_meta_emitted = True
        obs_trace.event(
            "strategy",
            algorithm=self.algorithm_name,
            cost_model=self.cost_model_name,
            M=self.M, N=self.N, M_pad=self.M_pad, N_pad=self.N_pad,
            R=self.R, nnz=self.S_tiles.nnz if self.S_tiles else 0,
            p=self.p, c=self.c,
            kernel=getattr(self.kernel, "name", type(self.kernel).__name__),
            wire=self.wire.label,
        )

    def _timed(
        self, name: str, fn, *args, _pairs: float = 1.0,
        _comm_op: str | None = None,
    ):
        """Dispatch one compiled program with full attribution: kernel
        time (the successful attempt) separate from retry/fault overhead,
        comm words + FLOPs from the layout model, a trace span when
        tracing, a profiler annotation when capturing. ``_pairs`` scales
        the comm/FLOP charge for multi-pair programs (GAT layers dispatch
        one fused pair per head); ``_comm_op`` overrides the cost-op name
        when the counter name does not determine the layout (B-mode fused
        dispatches charge ``fusedSpMMB``/``cgStepB`` while still counting
        under the public op name)."""
        from distributed_sddmm_tpu.resilience import faults, guards
        from distributed_sddmm_tpu.utils.platform import force_fetch

        cost_op = _comm_op or name
        resilient = faults.active() is not None or guards.enabled()
        wd = obs_watchdog.active()
        if not (resilient or obs_trace.enabled() or obs_profiler.active()):
            # Hot path: two clock reads + one locked counter update.
            t0 = time.perf_counter()
            out = fn(*args)
            # Execution barrier over every output leaf
            # (utils.platform.force_fetch); one scalar per leaf is
            # negligible next to any timed op.
            force_fetch(out)
            kernel_s = time.perf_counter() - t0
            words, nbytes, extra, flops = self._op_cost(cost_op, _pairs)
            self.metrics.record(
                name, kernel_s, comm_words=words, comm_bytes=nbytes,
                comm_words_extra=extra, flops=flops,
            )
            if wd is not None:
                # After metrics.record: a strict-mode alarm must not lose
                # the observation that raised it.
                wd.observe_dispatch(
                    self, name, kernel_s, counted_words=words,
                    pairs=_pairs, cost_op=cost_op,
                )
            return out

        self._emit_strategy_meta()
        words, nbytes, extra, flops = self._op_cost(cost_op, _pairs)
        with obs_trace.span(name, R=self.R, pairs=_pairs) as sp:
            t0 = time.perf_counter()
            if resilient:
                out, kernel_s, attempts = self._resilient_call(name, fn, *args)
            else:
                with obs_profiler.annotate(name):
                    out = fn(*args)
                    force_fetch(out)
                kernel_s = time.perf_counter() - t0
                attempts = 1
            overhead_s = max(time.perf_counter() - t0 - kernel_s, 0.0)
            self.metrics.record(
                name, kernel_s, overhead_s=overhead_s, retries=attempts - 1,
                comm_words=words, comm_bytes=nbytes, comm_words_extra=extra,
                flops=flops,
            )
            sp.set(
                kernel_s=round(kernel_s, 9), overhead_s=round(overhead_s, 9),
                retries=attempts - 1, comm_words=words, comm_bytes=nbytes,
                flops=flops,
            )
        if wd is not None:
            # Outside the span so a strict-mode WatchdogAlarm cannot leave
            # the span unclosed; the anomaly event still references the
            # enclosing (app-level) span as its parent.
            wd.observe_dispatch(
                self, name, kernel_s, counted_words=words,
                pairs=_pairs, cost_op=cost_op,
            )
        return out

    def _resilient_call(self, name: str, fn, *args):
        """Every compiled-program dispatch, hardened: synthetic fault hooks
        fire first (``execute:<op>`` raises, ``output:<op>`` corrupts), the
        call runs under the shared retry/timeout utility, and — when guards
        are on — outputs pass a NaN/Inf sentinel before being trusted.

        Transient failures (timeouts, OOMs, tripped sentinels) retry up to
        ``DSDDMM_EXEC_RETRIES`` times (default 1): an injected one-shot
        fault heals invisibly, a persistent one surfaces as a clean typed
        exception after bounded attempts — never a hang, never a silently
        poisoned array flowing into the next op.

        Returns ``(out, kernel_s, attempts)``: ``kernel_s`` times the
        attempt that actually succeeded, so failed attempts and backoff
        sleeps land in the caller's overhead bucket instead of inflating
        kernel time (the double-count the old ``total_time`` dict had).
        """
        import os

        from distributed_sddmm_tpu.resilience import faults, guards
        from distributed_sddmm_tpu.resilience.retry import Backoff, retry_call
        from distributed_sddmm_tpu.utils.platform import force_fetch

        attempts = [0]

        def attempt():
            attempts[0] += 1
            t0 = time.perf_counter()
            faults.maybe_raise(f"execute:{name}")
            with obs_profiler.annotate(name):
                out = fn(*args)
                out = faults.corrupt_outputs(f"output:{name}", out)
                force_fetch(out)
            if guards.enabled():
                # raise-mode trips the retry below; repair-mode degrades
                # in place (nan_to_num + a structured warning).
                out = guards.guard_output(name, out)
            return out, time.perf_counter() - t0

        def on_retry(i: int, err: BaseException) -> None:
            obs_metrics.GLOBAL.add("exec_retries")
            obs_trace.event(
                "retry", op=name, attempt=i, error=type(err).__name__,
            )

        out, kernel_s = retry_call(
            attempt,
            retries=int(os.environ.get("DSDDMM_EXEC_RETRIES", "1")),
            timeout_s=float(os.environ.get("DSDDMM_EXEC_TIMEOUT", "0")),
            backoff=Backoff(base_s=0.05, max_delay_s=2.0),
            retry_on=(TimeoutError, MemoryError, guards.NumericalFault,
                      faults.FaultError),
            label=f"execute:{name}",
            on_retry=on_retry,
        )
        return out, kernel_s, attempts[0]

    def reset_performance_timers(self) -> None:
        self.metrics.clear()
        self._op_cost_cache.clear()

    def measure_breakdown(
        self,
        A: jax.Array,
        B: jax.Array,
        s_vals: jax.Array,
        op: str = "fusedSpMM",
        trials: int = 3,
    ) -> dict:
        """Region-level {Replication, Propagation, Computation} attribution.

        The reference brackets every replication/shift/compute region with
        named timers between barriers (`distributed_sparse.h:205-261`,
        counter keys per algorithm at `15D_dense_shift.hpp:70-74`). Inside
        one fused XLA program regions cannot be bracketed, so this times
        three separately compiled variants of the op program with
        collectives selectively replaced by local shape-preserving ops
        (``parallel/loops.ablation_mode``):

        * Computation  = t(local)            — all collectives ablated
        * Replication  = t(no_ring) - t(local) — gathers/reduce-scatters real
        * Propagation  = t(full) - t(no_ring)  — ring permutes real

        Times are TOTALS over ``trials`` calls per variant (matching the
        ``_timed`` counter unit in :meth:`json_perf_statistics`).

        Returns counters under the names the chart pipeline maps
        (``tools/charts.py``): the op name (Computation), ``replication``,
        ``ppermute``, plus ``<op>_total``. Overlap between comm and compute
        makes the split approximate — exactly as the reference's
        barrier-separated timing was.

        Timing relies on ``block_until_ready``.
        """
        from distributed_sddmm_tpu.parallel.loops import ablation_mode

        runners = {
            "fusedSpMM": lambda: self.fused_spmm(A, B, s_vals),
            "sddmmA": lambda: self.sddmm_a(A, B, s_vals),
            "spmmA": lambda: self.spmm_a(A, B, s_vals),
        }
        if op not in runners:
            raise ValueError(f"op must be one of {sorted(runners)}")
        times = {}
        for mode in ("full", "no_ring", "local"):
            with ablation_mode(mode):
                jax.block_until_ready(runners[op]())  # compile + warm
                t0 = time.perf_counter()
                for _ in range(trials):
                    out = runners[op]()
                jax.block_until_ready(out)
                # Totals over `trials` calls — the same unit as the _timed
                # counters in json_perf_statistics, so records mix cleanly.
                times[mode] = time.perf_counter() - t0
        comp = times["local"]
        repl = max(times["no_ring"] - comp, 0.0)
        prop = max(times["full"] - times["no_ring"], 0.0)
        return {
            op: comp,
            "replication": repl,
            "ppermute": prop,
            f"{op}_total": times["full"],
        }

    def json_perf_statistics(self) -> dict:
        """Per-op kernel seconds (sorted). Retry/fault overhead is NOT in
        these numbers — bench records carry the full split under
        ``metrics`` (see :class:`obs.metrics.OpMetrics`)."""
        view = self.metrics.time_view()
        return {k: view[k] for k in sorted(view)}

    def json_algorithm_info(self) -> dict:
        """Same record schema as the reference (`distributed_sparse.h:131-179`)."""
        dims = [self.grid.nr, self.grid.nc, self.grid.nh]
        return {
            "alg_name": self.algorithm_name,
            "m": self.M,
            "n": self.N,
            "nnz": self.S_tiles.nnz if self.S_tiles else 0,
            "r": self.R,
            "adjacency_mode": self.grid.adjacency,
            "p": self.p,
            "c": self.c,
            "dim_interpretations": list(self.proc_grid_names),
            "dim_values": dims[: len(self.proc_grid_names)],
            "nnz_procs": self.S_tiles.nnz_per_device.reshape(-1).tolist()
            if self.S_tiles
            else [],
            "nnz_tpose_procs": self.ST_tiles.nnz_per_device.reshape(-1).tolist()
            if self.ST_tiles
            else [],
        }
