"""Numerical guardrails: NaN/Inf sentinels and CG divergence detection.

Guards are OFF by default — a finite-check is one device-side reduction
plus a scalar transfer per guarded op: a host sync on every guarded
dispatch. They switch on when a fault plan is
active (a fault-matrix run that cannot *detect* the injected NaNs would be
vacuous), when ``DSDDMM_GUARDS=1``, or per-object where the apps expose a
``guard`` knob.

``DSDDMM_GUARD_MODE`` selects what a tripped sentinel does: ``raise``
(default — a :class:`NumericalFault` naming the op) or ``repair``
(``nan_to_num`` the offending leaves and warn; the graceful-degradation
setting for long unattended runs where a poisoned activation is worse than
a damped one).
"""

from __future__ import annotations

import os

from distributed_sddmm_tpu.resilience import faults


class NumericalFault(ArithmeticError):
    """A guarded output contained NaN/Inf."""


def enabled() -> bool:
    """True when guards should run (env opt-in or an active fault plan)."""
    env = os.environ.get("DSDDMM_GUARDS", "").lower()
    if env in ("1", "on", "true", "yes"):
        return True
    if env in ("0", "off", "false", "no"):
        return False
    return faults.active() is not None


def guard_mode() -> str:
    mode = os.environ.get("DSDDMM_GUARD_MODE", "raise").lower()
    return mode if mode in ("raise", "repair") else "raise"


def _float_leaves(tree) -> list:
    import jax
    import jax.numpy as jnp

    return [
        leaf
        for leaf in jax.tree.leaves(tree)
        if hasattr(leaf, "dtype") and jnp.issubdtype(leaf.dtype, jnp.floating)
    ]


def all_finite(tree) -> bool:
    """One device reduction + scalar fetch per floating leaf."""
    import jax.numpy as jnp

    return all(bool(jnp.isfinite(leaf).all()) for leaf in _float_leaves(tree))


def check_finite(name: str, tree) -> None:
    """Raise :class:`NumericalFault` naming ``name`` on any NaN/Inf."""
    if not all_finite(tree):
        raise NumericalFault(f"non-finite values in output of {name}")


def guard_output(name: str, tree, mode: str | None = None):
    """Sentinel + degradation in one call: returns ``tree`` (possibly
    repaired). ``raise`` mode raises :class:`NumericalFault`; ``repair``
    mode ``nan_to_num``s the poisoned leaves (sharding preserved) and
    warns on stderr."""
    if all_finite(tree):
        return tree
    if (mode or guard_mode()) == "raise":
        raise NumericalFault(f"non-finite values in output of {name}")

    import jax
    import jax.numpy as jnp

    def repair_leaf(leaf):
        if not (hasattr(leaf, "dtype") and jnp.issubdtype(leaf.dtype, jnp.floating)):
            return leaf
        if isinstance(leaf, jax.Array):
            fn = jax.jit(jnp.nan_to_num, out_shardings=leaf.sharding)
            return fn(leaf)
        import numpy as np

        return np.nan_to_num(leaf)

    from distributed_sddmm_tpu.obs import log, metrics, trace

    metrics.GLOBAL.add("guard_repairs")
    trace.event("guard_repair", op=name)
    log.warn("guards", "repaired non-finite output", op=name)
    return jax.tree.map(repair_leaf, tree)


class CGGuard:
    """Residual-divergence detector for the batched-CG inner loop.

    CG on the ridge normal equations must drive the summed squared
    residual down (modulo float noise); sustained growth means the Gram
    operator went inconsistent — a poisoned tile, a collective returning
    garbage, or a genuinely indefinite system. Trips after ``patience``
    consecutive iterations of ``rs > growth_tol * best_rs`` or instantly
    on a non-finite residual.
    """

    def __init__(self, growth_tol: float = 10.0, patience: int = 2):
        self.growth_tol = growth_tol
        self.patience = patience
        self.best: float | None = None
        self.strikes = 0

    def update(self, rs: float) -> bool:
        """Feed one iteration's summed squared residual; True = diverged."""
        import math

        if not math.isfinite(rs):
            return True
        if self.best is None or rs < self.best:
            self.best = rs
            self.strikes = 0
            return False
        if rs > self.growth_tol * max(self.best, 1e-30):
            self.strikes += 1
        else:
            self.strikes = 0
        return self.strikes >= self.patience
