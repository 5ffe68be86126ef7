"""The kernel-variant space and its fingerprint-keyed selector.

A :class:`KernelVariant` is everything the banked builder and kernel
need that the generic path does not carry: the nnz/row band split
(which rows go into the short-row chunk lists) and per-band chunk
geometry + body style. Variants are PURE FUNCTIONS of the autotune
fingerprint terms (npr_bucket, R, dtype) — two processes selecting for
the same problem MUST produce the same variant, for the same reason
fingerprints must agree (``autotune/fingerprint.py`` module doc): plan
records, program-store keys and bench records all carry the variant id
and must mean the same thing everywhere.

The id grammar is ``v1.rb<thr>.<regime>``:

* ``v1`` — variant-generation version. Any change to the geometry this
  module derives from an id MUST bump it: the id is baked into
  program-store keys, and a stale generation must miss-and-recompile,
  never alias (``codegen/`` is also part of ``code_hash`` for the same
  reason — belt and braces).
* ``rb<thr>`` — the short-row band threshold: rows with nnz <= thr go
  to the full-width short-row band. ``rb0`` = no banding (pure
  R-regime tiling specialization).
* ``<regime>`` — the R tiling regime: ``rs`` (R <= 64), ``rm`` (the
  headline 128-512 band), ``rl`` (R >= 1024, VMEM-bounded blocks).

Selection derives the threshold from the SHARED npr bucketing
(``utils/buckets.pow2_bucket``) so codegen bands exactly where the
fingerprint buckets.
"""

from __future__ import annotations

import dataclasses
import re

from distributed_sddmm_tpu.utils.buckets import pow2_bucket

#: Bump on ANY change to the geometry derived from a variant id (see
#: module doc — ids live inside program-store keys).
VARIANT_VERSION = 1

#: R-regime tile geometry: (heavy-band block_rows, block_cols, group).
#: ``rs``/``rm`` keep the blocks measured best at logM 16, nnz/row 32,
#: R=128 (KERNELS_TPU.jsonl), where a 512x512 block holds 128 nonzeros,
#: one full chunk; sparser matrices fill them less (the generic
#: encoding sizes its blocks to the density: ops/blocked.choose_blocks).
#: ``rl`` halves both so the [R, bm] f32 accumulator and dense windows
#: stay VMEM-resident at R >= 1024 (512x1024 f32 = 2 MiB per operand
#: before double buffering).
_REGIMES = {
    "rs": (512, 512, 4),
    "rm": (512, 512, 4),
    "rl": (256, 256, 2),
}

#: Widest column block an auto-width band may merge up to, per regime
#: (absolute lanes). Bounds the banked kernel's [R, bn] f32 dense
#: window to ~4 MiB so it stays VMEM-resident with double buffering —
#: unbounded merging on a full-width tile (SparseShift15D tiles carry
#: tile_cols = N_pad) would otherwise emit windows Mosaic cannot fit:
#: rs assumes R <= 64, rm R <= 512, rl R ~ 1024-2048.
_MAX_BAND_COLS = {
    "rs": 16384,
    "rm": 2048,
    "rl": 512,
}


def r_regime(R: int) -> str:
    """The R tiling regime name for an inner dimension."""
    if R <= 64:
        return "rs"
    if R < 1024:
        return "rm"
    return "rl"


@dataclasses.dataclass(frozen=True)
class BandSpec:
    """One row band's chunk-list geometry and kernel-body style.

    ``npr_max`` — rows with nnz <= npr_max belong to this band (None =
    the residual heavy band). ``block_cols=0`` means DENSITY-TARGETED
    width: the builder widens this band's column blocks (merging
    generic blocks, power-of-two steps up to full tile width) until the
    band's nonzeros average at least ~2 full chunks per touched
    (row block, col block) pair — short rows then stop paying one
    mostly-empty 128-lane chunk per touched column block. ``body`` is
    the requested kernel-body style; the builder may UPGRADE
    ``batched`` to ``single`` when the built metadata proves every
    row-block group spans exactly one grid step (same arithmetic, no
    scalar conditionals) — see ``codegen/banded.py``.
    """

    npr_max: int | None
    block_rows: int
    block_cols: int
    group: int
    body: str  # "walk" | "batched" | "single"
    #: Cap (absolute lanes) on the density-targeted width of an
    #: auto-width band — the VMEM bound (``_MAX_BAND_COLS``). 0 = fixed
    #: width, no merging. Derived from the variant id's regime, so id
    #: round-trips reconstruct it deterministically.
    max_block_cols: int = 0


@dataclasses.dataclass(frozen=True)
class KernelVariant:
    """A fully resolved specialization: id + band specs."""

    variant_id: str
    bands: tuple[BandSpec, ...]

    @property
    def banked(self) -> bool:
        return len(self.bands) > 1


def _bands_for(thr: int, regime: str) -> tuple[BandSpec, ...]:
    bm, bn, group = _REGIMES[regime]
    heavy = BandSpec(npr_max=None, block_rows=bm, block_cols=bn,
                     group=group, body="walk")
    if thr <= 0:
        return (heavy,)
    # Short band (rows at/below the fingerprint's npr bucket) and a mid
    # band one octave ladder up (<= 8x): both density-targeted
    # (block_cols=0), so each pays ~one chunk rounding per row block
    # instead of one per touched column block; group=1 avoids
    # deficit-pad chunks in sparse row blocks. Truly heavy rows keep the
    # measured headline geometry — their pairs are dense already, and
    # widening their gather windows would trade MXU work for nothing.
    # The short band requests the batched (lane-concatenated) body; the
    # builder upgrades it to the conditional-free single-step body when
    # provable. The mid band keeps the accumulator walk.
    cap = _MAX_BAND_COLS[regime]
    short = BandSpec(npr_max=thr, block_rows=bm, block_cols=0,
                     group=1, body="batched", max_block_cols=cap)
    mid = BandSpec(npr_max=8 * thr, block_rows=bm, block_cols=0,
                   group=1, body="walk", max_block_cols=cap)
    return (short, mid, heavy)


_ID_RE = re.compile(r"^v(\d+)\.rb(\d+)\.(rs|rm|rl)$")


def variant_from_id(variant_id: str) -> KernelVariant:
    """Reconstruct the variant a stable id names (plan records and
    program keys carry only the id). Unknown generations raise — a
    caller holding a ``v2`` id against ``v1`` code must fall back to
    generic, not guess geometry."""
    m = _ID_RE.match(variant_id)
    if not m:
        raise ValueError(f"unparseable kernel variant id {variant_id!r}")
    version, thr, regime = int(m.group(1)), int(m.group(2)), m.group(3)
    if version != VARIANT_VERSION:
        raise ValueError(
            f"kernel variant generation v{version} != current "
            f"v{VARIANT_VERSION} ({variant_id!r})"
        )
    return KernelVariant(
        variant_id=variant_id, bands=_bands_for(thr, regime)
    )


def select_variant(problem) -> KernelVariant:
    """The specialized variant for one autotune ``Problem``.

    The short-band threshold is the problem's npr bucket (the SAME
    power-of-two rounding the fingerprint uses): rows at or below the
    bucketed mean are "short" — in skewed (R-mat) degree distributions
    that is most rows, which is exactly the population paying the
    generic geometry's chunk-rounding tax. Very heavy buckets
    (npr_bucket >= 128) stop banding (rows fill chunks on their own)
    and keep only the R-regime tiling specialization.
    """
    thr = pow2_bucket(problem.nnz_per_row)
    if thr >= 128:
        thr = 0
    regime = r_regime(problem.R)
    vid = f"v{VARIANT_VERSION}.rb{thr}.{regime}"
    return KernelVariant(variant_id=vid, bands=_bands_for(thr, regime))


def variant_ids_for(problem) -> tuple[str, ...]:
    """Variant ids worth registering as autotune candidates for one
    problem (currently the single fingerprint-selected variant; the
    cost model and measured trials arbitrate against the generic
    kernel like any other candidate).

    A non-banked ``rs``/``rm`` variant is geometry-identical to the
    generic kernel (``_REGIMES`` keeps the measured headline blocks),
    so registering it would measure the same configuration twice and
    split byte-identical runs across gate baselines — skip it. The
    non-banked ``rl`` variant stays: its halved blocks are a real
    specialization."""
    v = select_variant(problem)
    if not v.banked and not v.variant_id.endswith(".rl"):
        return ()
    return (v.variant_id,)


def variant_cost_factor(problem, variant_id: str) -> float:
    """First-order multiplicative adjustment on the analytic pair time
    for a variant candidate, mirroring how the chunked XLA kernel is
    charged a 1.1x overhead: the model's flops term assumes zero
    padding, so the variant's relative worth is the ratio of estimated
    padded-lane overheads. Coarse by design — it orders what to
    MEASURE first; trials are the arbiter.
    """
    try:
        variant = variant_from_id(variant_id)
    except ValueError:
        return 1.0
    if not variant.banked:
        return 1.0
    waste_g = estimated_pad_frac(problem, banked=False)
    waste_b = estimated_pad_frac(problem, banked=True)
    factor = (1.0 + waste_b) / (1.0 + waste_g)
    return min(max(factor, 0.6), 1.1)


def estimated_pad_frac(problem, banked: bool) -> float:
    """Crude expected pad-lanes-per-real-lane for the generic vs banked
    encodings: every touched (row block, col block) pair rounds its
    chunk list up to CHUNK lanes (~CHUNK/2 expected waste); banking
    collapses the short rows' column-block dimension, leaving ~one
    rounding per row block."""
    from distributed_sddmm_tpu.ops import blocked

    bm = blocked.DEFAULT_BLOCK_ROWS or blocked.BASE_BLOCK
    bn = blocked.DEFAULT_BLOCK_COLS or blocked.BASE_BLOCK
    grb = max(-(-problem.M // bm), 1)
    gcb = max(-(-problem.N // bn), 1)
    nnz = max(problem.nnz, 1)
    if banked:
        # Density-targeted bands hold ~target chunks per touched pair;
        # the residual heavy rows' pairs are dense. ~a few roundings
        # per row block survive.
        pairs = grb * 6
    else:
        # Expected touched pairs under a uniform scatter, capped by nnz.
        cells = grb * gcb
        import math

        pairs = cells * (1.0 - math.exp(-nnz / cells))
    pairs = min(pairs, nnz)
    return (pairs * blocked.CHUNK / 2.0) / nnz
