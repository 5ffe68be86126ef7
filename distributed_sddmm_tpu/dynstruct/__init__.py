"""Compile-free dynamic sparse structure (PR 20).

Every compiled program in the stack freezes S's nonzero pattern at
trace time: flat ``max_nnz`` paddings, chunk counts, band ``(c0, c1)``
offsets and dense frames are all exact functions of the pattern, so
fold-in growth (``append_rows``), graph edge churn, or a per-request
attention mask forces a full retrace. This package adds the missing
half of the codegen story — structure as *data* bound at runtime, not
*code* baked at trace time:

* :func:`build` constructs any named strategy under a
  ``utils.buckets.dyn_capacity`` scope: every structure-sizing decision
  (flat max_nnz, chunk counts, per-band chunk ranges) pads up to a
  pow2 capacity rung, and the declared row count reserves a growth
  rung. Structure arrays are already program *inputs* (``_tile_args``
  passes the tile seam's rows/cols or ``blk_*`` chunk lists, and the
  mask, per call), so any pattern landing in the same rungs presents
  byte-identical avals and static metadata to jax — zero retraces by
  construction.
* :func:`rebind` re-derives chunk lists and band assignments for a
  mutated pattern on the host and binds them into the *existing*
  strategy (and hence its existing compiled programs) when they fit the
  bucket; a pattern that outgrows its rungs spills to the next rung as
  a full replacement build, warmed from the ProgramStore when one is
  bound — never a live compile on the request path.
* ``programs/keys.py`` / ``parallel/base.py`` grow a capacity-bucket
  key segment for dyn-built programs (exact-build keys stay
  byte-identical; bucketed keys never alias exact ones), and
  ``serve/engine.py`` gains the structure-change path
  (``rebind_structure`` + per-request dynamic attention masks).

Results are bit-identical to a freshly-traced program of the same
capacity bucket (the serve/ discipline) — pinned by
``scripts/dynstruct_smoke.py`` and the DYNSTRUCT_HLO.json structural
gate (:mod:`distributed_sddmm_tpu.dynstruct.hlo`).
"""

from distributed_sddmm_tpu.dynstruct.capacity import (  # noqa: F401
    default_grow_rows,
    default_headroom,
    row_capacity,
    with_row_capacity,
)
from distributed_sddmm_tpu.dynstruct.rebind import (  # noqa: F401
    DynHandle,
    StructureUpdate,
    build,
    note_rebind,
    rebind,
)
from distributed_sddmm_tpu.utils.buckets import (  # noqa: F401
    dyn_capacity,
    dyn_capacity_state,
    pow2_at_least,
)
