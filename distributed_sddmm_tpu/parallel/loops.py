"""Shared ring-loop machinery for the shift algorithms.

Every strategy's inner loop is `n` steps of compute + rotate. Two build
modes:

* ``unroll=True`` (default): Python-unrolled — XLA sees each step statically
  and can software-pipeline the collective permutes behind the local kernels
  (the role of the reference's ``BufferPair`` double buffering,
  `common.h:49-93`).
* ``unroll=False``: a ``lax.fori_loop`` bounding compile time on large
  meshes; step indices become traced values (use
  ``lax.dynamic_index_in_dim`` in bodies — they accept Python ints too, so
  one body serves both modes).

The shift after the final step is often pure waste (the rotated operand is
discarded), but sometimes required (an accumulator or output traveling the
ring must complete its round trip home). Callers express this precisely with
``shift_final``: ``None`` skips the trailing shift entirely; otherwise it is
applied once after the last step (it may shift fewer arrays than
``shift_between`` — e.g. return the traveling output home but drop the spent
input).
"""

from __future__ import annotations

import contextlib
from typing import Callable, Optional

import jax.numpy as jnp
from jax import lax


def ring_perm(n: int) -> list:
    """The +1 ring permutation for an axis of size n."""
    return [(k, (k + 1) % n) for k in range(n)]


# --------------------------------------------------------------------- #
# Trace-time collective ablation for region-level performance attribution
# (the TPU answer to the reference's barrier-bracketed region timers,
# `/root/reference/distributed_sparse.h:205-261`). Timers cannot bracket
# regions inside one fused XLA program, so attribution instead times three
# separately compiled variants of the SAME op program:
#
#   "full"    — the real program;
#   "no_ring" — ring ppermutes replaced by identity (compute + replication
#               collectives remain);
#   "local"   — ALL collectives replaced by shape-preserving local ops
#               (compute only).
#
# Computation ~= t(local); Replication ~= t(no_ring) - t(local);
# Propagation ~= t(full) - t(no_ring). Every strategy reads the active mode
# at trace time through the abl_* wrappers below and includes it in its
# program-cache key. Ablated programs produce WRONG numerics by design —
# they exist only to be timed.
# --------------------------------------------------------------------- #

_ABLATION = "full"
ABLATION_MODES = ("full", "no_ring", "local")


def ablation() -> str:
    return _ABLATION


@contextlib.contextmanager
def ablation_mode(mode: str):
    if mode not in ABLATION_MODES:
        raise ValueError(f"unknown ablation mode {mode!r}; expected {ABLATION_MODES}")
    global _ABLATION
    prev = _ABLATION
    _ABLATION = mode
    try:
        yield
    finally:
        _ABLATION = prev


# --------------------------------------------------------------------- #
# Wire-precision boundary casts (parallel/wire.py): payloads downcast
# JUST before the collective and upcast right after, so compute and
# every accumulation stay in the resident dtype. Only float32 payloads
# cast — integer tile indices (and already-reduced-precision data) pass
# through untouched. ``wire="f32"`` is the identity: the traced program
# is byte-for-byte the pre-wire one.
# --------------------------------------------------------------------- #


def _wire_down(x, wire: str):
    if wire == "bf16" and x.dtype == jnp.float32:
        return x.astype(jnp.bfloat16)
    return x


def _wire_up(y, orig_dtype):
    if y.dtype != orig_dtype:
        return y.astype(orig_dtype)
    return y


def abl_ppermute(x, axis_name, perm, *, wire: str = "f32"):
    """Ring hop; identity under "no_ring"/"local" (Propagation).

    ``wire="bf16"`` halves the hop's bytes for f32 payloads (downcast
    before, upcast after). Rounding is idempotent, so a READ-ONLY
    payload riding k hops is rounded once total; accumulators that
    travel (sparse-shift dots, Cannon's rotating output) must be
    shifted with the policy's ``ring_accum`` dtype instead — a per-hop
    downcast of a changing partial sum compounds with ring length."""
    if _ABLATION != "full":
        return x
    y = lax.ppermute(_wire_down(x, wire), axis_name, perm)
    return _wire_up(y, x.dtype)


def abl_all_gather(x, axis_name, *, axis, tiled=True, size, wire: str = "f32"):
    """Replication gather; local concat of ``size`` copies under "local"."""
    if _ABLATION == "local":
        return jnp.concatenate([x] * size, axis=axis)
    y = lax.all_gather(_wire_down(x, wire), axis_name, axis=axis, tiled=tiled)
    return _wire_up(y, x.dtype)


def abl_psum_scatter(x, axis_name, *, scatter_dimension, tiled=True, size,
                     wire: str = "f32"):
    """Replication reduce-scatter; local 1/``size`` slice under "local".

    ``wire="bf16"`` here accumulates ON THE WIRE in bf16 — the default
    bf16 :class:`~distributed_sddmm_tpu.parallel.wire.WirePolicy` keeps
    this role f32 for exactly that reason (always-f32 accumulation),
    and only an explicit ``reduce=bf16`` override reaches this cast."""
    if _ABLATION == "local":
        n = x.shape[scatter_dimension] // size
        return lax.slice_in_dim(x, 0, n, axis=scatter_dimension)
    y = lax.psum_scatter(
        _wire_down(x, wire), axis_name,
        scatter_dimension=scatter_dimension, tiled=tiled,
    )
    return _wire_up(y, x.dtype)


def vary(x, axes):
    """Mark loop-carry inits as device-varying over ``axes`` so rolled
    fori_loop carries type-match after collectives touch them
    (``lax.pcast(..., to="varying")`` via compat.pvary)."""
    from distributed_sddmm_tpu.compat import pvary

    return pvary(x, axes)


def ring_loop_overlap(
    n: int,
    body: Callable,
    carry,
    mov,
    shift_mov: Callable,
    shift_carry: Optional[Callable] = None,
    final_shift: bool = False,
    unroll: bool = True,
):
    """Double-buffered ring loop — the paper's *local kernel overlap*
    (reference ``BufferPair``, `common.h:49-93`), expressed in program
    structure: each step ISSUES the next tile's hop of the moving
    operand **before** the body consumes the resident buffer, so the
    collective's input never depends on the step's compute and the TPU
    latency-hiding scheduler can split the ``ppermute`` into
    ``collective-permute-start``/``-done`` bracketing the local kernel
    (the structural evidence ``bench overlap --fusion-hlo`` gates on).

    ``body(s, carry, mov) -> carry`` computes on the resident ``mov``;
    ``shift_mov(mov)`` is the ring hop (a pytree hop for traveling
    struct-of-arrays tiles). ``shift_carry`` is the escape hatch for
    state that must travel but *depends on the body* (1.5D sparse-shift
    SDDMM's accumulating dots): it hops AFTER the body, sequentially —
    only the body-independent operands double-buffer. ``final_shift``
    runs the hop(s) after the last step too (a traveling operand
    completing its round trip home); hop counts then match the
    sequential ``ring_loop`` exactly: ``n-1`` hops without it, ``n``
    with. Returns ``(carry, mov)``.

    Bit-identical to the sequential loop by construction: every step's
    body consumes exactly the buffers the sequential path would, in the
    same order — only the issue position of the hop moves.
    """

    # n == 1: every operand is already home — mirror ``ring_loop``'s
    # ``n > 1`` guard on the trailing shift instead of emitting a
    # self-loop permute.
    final_shift = final_shift and n > 1

    def step(s, state):
        c, m = state
        nxt = shift_mov(m)  # issued BEFORE the body: no data dependence
        c = body(s, c, m)
        if shift_carry is not None:
            c = shift_carry(c)
        return c, nxt

    if unroll:
        state = (carry, mov)
        for s in range(n):
            if s < n - 1 or final_shift:
                state = step(s, state)
            else:
                c, m = state
                state = (body(s, c, m), m)
        return state
    if final_shift:
        # Uniform step (hop every iteration incl. the last): fori-able.
        return lax.fori_loop(0, n, step, (carry, mov))
    if n > 1:
        carry, mov = lax.fori_loop(0, n - 1, step, (carry, mov))
    return body(n - 1, carry, mov), mov


def ring_loop(
    n: int,
    body: Callable,
    state,
    shift_between: Callable,
    shift_final: Optional[Callable] = None,
    unroll: bool = True,
):
    """Run ``state = body(s, state)`` for s in 0..n-1 with
    ``shift_between`` applied between steps and ``shift_final`` (if any)
    after the last."""
    if unroll:
        for s in range(n):
            state = body(s, state)
            if s < n - 1:
                state = shift_between(state)
        if shift_final is not None and n > 1:
            state = shift_final(state)
        return state

    if shift_final is not None:
        # Uniform step (shift every iteration) only if the final shift is the
        # full between-step shift; otherwise peel the last step.
        if shift_final is shift_between:
            return lax.fori_loop(
                0, n, lambda s, st: shift_between(body(s, st)), state
            )
    if n > 1:
        state = lax.fori_loop(
            0, n - 1, lambda s, st: shift_between(body(s, st)), state
        )
    state = body(n - 1, state)
    if shift_final is not None and n > 1:
        state = shift_final(state)
    return state
