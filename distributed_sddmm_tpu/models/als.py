"""ALS collaborative filtering via batched conjugate gradients.

TPU-native redesign of the reference's ``ALS_CG`` / ``Distributed_ALS``
(`/root/reference/als_conjugate_gradients.{h,cpp}`): alternating
optimization of embeddings A (M x R) and B (N x R) against observed sparse
entries, each half-step solving the ridge normal equations with a batched
(per-row) CG whose matrix-vector product is the fused SDDMM->SpMM pair
(`als_conjugate_gradients.cpp:265-301`).

Key deviation by design: the reference manually allreduces CG dot products
over the R-split communicators when ``r_split`` is set
(`als_conjugate_gradients.cpp:74-76,95-97`). Here the embeddings are global
``jax.Array``s in each strategy's canonical sharding, and the batched dots
are plain ``jnp.sum(x * y, axis=-1)`` under jit — XLA inserts the psum over
the sharded R dimension automatically. The r_split bookkeeping disappears
from application code entirely; that is the point of the global-array
programming model.

The ridge term uses ``lambda=1e-6`` by default rather than the reference's
1e-13 (`als_conjugate_gradients.cpp:271`), which is below float32 epsilon
relative to typical Gram-matrix scales; pass ``ridge_lambda`` to override.

Resilience (none of which the reference had — it assumed a healthy MPI
world and a clean run from step 0):

* **Checkpoint/resume**: ``run_cg(checkpoint=store, checkpoint_every=k,
  resume=True)`` persists the factor matrices atomically after every k-th
  alternating step and resumes from the newest loadable checkpoint. The
  factors round-trip bit-exactly, and each alternating step is a pure
  deterministic function of (A, B), so a killed-and-resumed run converges
  to factors bit-identical to an uninterrupted one.
* **CG divergence ladder** (active when guards are on): a growing or
  non-finite residual first triggers a damped-λ restart of the half-step
  (ridge stiffened by ``damp_factor`` from the pre-step factors), and if
  that diverges too, ALS degrades to the single-node oracle solver
  (``models/serial_als.py`` — pass ``S_host`` to enable) rather than
  walking poisoned factors forward.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

import time

from distributed_sddmm_tpu.common import KernelMode, MatMode
from distributed_sddmm_tpu.obs import log as obs_log
from distributed_sddmm_tpu.obs import trace as obs_trace
from distributed_sddmm_tpu.obs import watchdog as obs_watchdog
from distributed_sddmm_tpu.parallel.base import DistributedSparse
from distributed_sddmm_tpu.resilience import faults, guards
from distributed_sddmm_tpu.resilience.guards import CGGuard, NumericalFault


class CGDivergence(ArithmeticError):
    """The batched-CG residual grew (or went non-finite) past the guard's
    tolerance — the Gram operator is inconsistent or the system is too
    ill-conditioned for the current ridge."""


def _batch_dot(x: jax.Array, y: jax.Array) -> jax.Array:
    """Per-row dot products (reference ``batch_dot_product``,
    `als_conjugate_gradients.cpp:9-11`); any canonical dense shape with R
    last."""
    return jnp.sum(x * y, axis=-1)


def _scale_rows(scale: jax.Array, mat: jax.Array) -> jax.Array:
    return mat * scale[..., None]


def _cg_vector_update(X, r, p, rsold, Mp, eps):
    """One CG iteration's vector algebra given the Gram product Mp
    (`als_conjugate_gradients.cpp:38-141`) — the single copy both the
    jit-chained program and the per-op fallback loop trace through."""
    bdot = _batch_dot(p, Mp) + eps
    alpha = (rsold + eps) / bdot
    X = X + _scale_rows(alpha, p)
    r = r - _scale_rows(alpha, Mp)
    rsnew = _batch_dot(r, r)
    beta = rsnew / (rsold + eps)
    p = r + _scale_rows(beta, p)
    return X, r, p, rsnew


def _supports_programs(d_ops: DistributedSparse) -> bool:
    """True when the strategy exposes raw jitted programs AND its public
    ops need no pre/post skew (base-class no-op shifts) — the conditions
    under which a whole CG iteration can compile as one program."""
    return (
        hasattr(d_ops, "fused_program")
        and type(d_ops).initial_shift is DistributedSparse.initial_shift
        and type(d_ops).de_shift is DistributedSparse.de_shift
    )


def donation_enabled() -> bool:
    """Whether the chained programs donate their carry buffers.

    Donation invalidates input buffers after every call, which is
    exactly right for CG/layer carries (each call's inputs are the
    previous call's outputs, never reused) — but it is incompatible with
    the resilience ladder's retry rung: a retry re-invokes the program
    with the SAME argument buffers, which a donating first attempt
    already consumed. ``_timed`` only routes through the retrying
    ``_resilient_call`` when a fault plan or output guards are active,
    so donation follows the inverse of that predicate exactly.
    ``DSDDMM_DONATE=0`` is the kill switch.
    """
    import os

    if os.environ.get("DSDDMM_DONATE", "1").lower() in (
        "0", "off", "false", "no"
    ):
        return False
    return faults.active() is None and not guards.enabled()


class DistributedALS:
    """Alternating least squares over any distributed strategy.

    ``use_programs``: ``"auto"`` (default) routes the CG inner loop
    through ONE jitted program per CG step when the strategy supports it
    (:func:`_supports_programs` — the 1.5D dense-shift strategies via
    their ``fused_program`` accessor); ``False`` forces the per-call op
    dispatch path. The jit-chained path is what makes ALS fast on
    dispatch-dominated backends: per-op counters then show ``cgStep``
    once per CG iteration instead of ``fusedSpMM`` per inner call
    (`APPS_TPU.jsonl` round-5 ALS ran at 0.063 GFLOP/s purely from
    per-call dispatch).
    """

    def __init__(
        self,
        d_ops: DistributedSparse,
        seed: int = 0,
        ridge_lambda: float = 1e-6,
        artificial_groundtruth: bool = True,
        ground_truth_vals: np.ndarray | None = None,
        ground_truth_vals_transpose: np.ndarray | None = None,
        use_programs: str | bool = "auto",
        S_host=None,
        guard: str | bool = "auto",
        damp_factor: float = 1e3,
    ):
        self.d_ops = d_ops
        self.ridge_lambda = ridge_lambda
        # Resilience knobs: ``guard`` "auto" follows guards.enabled() (on
        # under an active fault plan or DSDDMM_GUARDS); S_host enables the
        # final rung of the degradation ladder (serial oracle fallback).
        self.S_host = S_host
        self._guard = guard
        self.damp_factor = damp_factor
        self.degraded: str | None = None
        if use_programs == "auto":
            self._use_programs = _supports_programs(d_ops)
        else:
            self._use_programs = bool(use_programs) and _supports_programs(d_ops)
        self._cg_programs: dict = {}
        key = jax.random.key(seed)
        k1, k2, k3, k4 = jax.random.split(key, 4)

        if artificial_groundtruth:
            # Synthesize observations by an SDDMM of small random factors
            # (`als_conjugate_gradients.cpp:157-184`): a correct solver must
            # then drive the residual toward zero.
            Agt = self._random_like(k1, MatMode.A) / d_ops.R
            Bgt = self._random_like(k2, MatMode.B) / d_ops.R
            ones = d_ops.like_s_values(1.0)
            Agt_s, Bgt_s = d_ops.initial_shift(Agt, Bgt, KernelMode.SDDMM_A)
            self.ground_truth = d_ops.sddmm_a(Agt_s, Bgt_s, ones)
            ones_t = d_ops.like_st_values(1.0)
            Agt_s, Bgt_s = d_ops.initial_shift(Agt, Bgt, KernelMode.SDDMM_B)
            self.ground_truth_transpose = d_ops.sddmm_b(Agt_s, Bgt_s, ones_t)
        else:
            if ground_truth_vals is None:
                raise ValueError(
                    "ground_truth_vals required when artificial_groundtruth=False"
                )
            self.ground_truth = d_ops.scatter_s_values(ground_truth_vals)
            # B half-steps need the observations in S^T's canonical nonzero
            # order (S.with_values(obs).transpose().vals); without them only
            # A-mode optimization is possible.
            self.ground_truth_transpose = (
                d_ops.scatter_st_values(ground_truth_vals_transpose)
                if ground_truth_vals_transpose is not None
                else None
            )

        self.A = None
        self.B = None
        self._init_keys = (k3, k4)

    def _random_like(self, key, mode: MatMode) -> jax.Array:
        shape = self.d_ops.dense_shape(mode)
        sharding = (
            self.d_ops.a_sharding() if mode == MatMode.A else self.d_ops.b_sharding()
        )
        fn = jax.jit(
            lambda k: jax.random.uniform(
                k, shape, self.d_ops.dtype, minval=-1.0, maxval=1.0
            ),
            out_shardings=sharding,
        )
        return fn(key)

    def initialize_embeddings(self) -> None:
        """Reference ``initializeEmbeddings``
        (`als_conjugate_gradients.cpp:221-233`)."""
        R = self.d_ops.R
        self.A = self._random_like(self._init_keys[0], MatMode.A) / R * 1.4
        self.B = self._random_like(self._init_keys[1], MatMode.B) / R / 1.3

    # ------------------------------------------------------------------ #
    # Normal-equation pieces
    # ------------------------------------------------------------------ #

    def compute_rhs(self, mode: MatMode) -> jax.Array:
        """``rhs = S_gt @ B`` (or transpose), `als_conjugate_gradients.cpp:192-205`."""
        d = self.d_ops
        if mode == MatMode.A:
            zero, B_s = d.initial_shift(d.like_a_matrix(0.0), self.B, KernelMode.SPMM_A)
            out = d.spmm_a(zero, B_s, self.ground_truth)
            out, _ = d.de_shift(out, None, KernelMode.SPMM_A)
            return out
        if self.ground_truth_transpose is None:
            raise ValueError(
                "B-mode optimization requires transposed ground-truth values: "
                "pass ground_truth_vals_transpose (observations in "
                "S.transpose() nonzero order) to DistributedALS"
            )
        A_s, zero = d.initial_shift(self.A, d.like_b_matrix(0.0), KernelMode.SPMM_B)
        out = d.spmm_b(A_s, zero, self.ground_truth_transpose)
        _, out = d.de_shift(None, out, KernelMode.SPMM_B)
        return out

    def compute_queries(
        self, A, B, mode: MatMode, lam: float | None = None
    ) -> jax.Array:
        """Apply the Gram operator: ``fusedSpMM + lambda*X``
        (`als_conjugate_gradients.cpp:265-301`). ``lam`` overrides the
        ridge for damped restarts; default is the configured lambda."""
        lam = self.ridge_lambda if lam is None else lam
        d = self.d_ops
        if mode == MatMode.A:
            ones = d.like_s_values(1.0)
            A_s, B_s = d.initial_shift(A, B, KernelMode.SDDMM_A)
            out, _ = d.fused_spmm(A_s, B_s, ones, MatMode.A)
            out, _ = d.de_shift(out, None, KernelMode.SPMM_A)
            return out + lam * A
        ones = d.like_st_values(1.0)
        A_s, B_s = d.initial_shift(A, B, KernelMode.SDDMM_B)
        out, _ = d.fused_spmm(A_s, B_s, ones, MatMode.B)
        _, out = d.de_shift(None, out, KernelMode.SPMM_B)
        return out + lam * B

    # ------------------------------------------------------------------ #
    # Batched CG (`als_conjugate_gradients.cpp:38-141`)
    # ------------------------------------------------------------------ #

    def _cg_iter_program(self, mode: MatMode, lam: float):
        """``(prog, operands)``: ONE jitted program for a full CG
        iteration, ``prog(X, other, r, p, rsold, *operands)``, with the
        tile operands passed as arguments (see ``DenseShift15D.
        fused_program``). It chains the fused Gram
        operator (via the strategy's raw ``fused_program``) chained with
        every vector update. Same math as the open-coded loop below —
        the difference is dispatch: one compiled call per iteration
        instead of one per distributed op. Keyed by λ too: a damped
        restart recompiles with the stiffer ridge baked in.

        The CG carries (X, r, p, rsold) are **donated**: each call's
        inputs are the previous call's outputs and are never read again,
        so XLA updates them in place instead of allocating four fresh
        buffers per iteration (``_cg_run`` copy-protects the two
        entry-point aliases — see there). Donation follows
        :func:`donation_enabled` (off under the resilience ladder's
        retry rung; ``DSDDMM_DONATE=0``). The stationary ``other``
        factor is deliberately NOT donated — the caller reuses it every
        iteration.

        Models over a store-bound strategy (``programs.bind_strategy``
        — the Plan.instantiate and bench-harness paths) additionally
        resolve the compiled iteration through the persistent program
        store under the strategy's fingerprint + config, so a repeat run
        recalls ``cgStep`` from disk instead of compiling.
        """
        donate = donation_enabled() and self._use_programs
        key = (mode, self.d_ops.R, lam, donate)
        if key in self._cg_programs:
            return self._cg_programs[key]
        d = self.d_ops
        ones = d.like_s_values(1.0) if mode == MatMode.A else d.like_st_values(1.0)
        fused, operands = d.fused_program(ones, mode)
        eps = 1e-8

        def one_iter(X, other, r, p, rsold, *operands):
            if mode == MatMode.A:
                out, _ = fused(p, other, *operands)
            else:
                out, _ = fused(other, p, *operands)
            Mp = out + lam * p
            return _cg_vector_update(X, r, p, rsold, Mp, eps)

        prog = jax.jit(
            one_iter, donate_argnums=(0, 2, 3, 4) if donate else ()
        )
        from distributed_sddmm_tpu import programs

        prog = programs.chained_program(
            d, f"cgStep-{mode.name}-{lam:g}-{'don' if donate else 'nodon'}",
            prog,
        )
        self._cg_programs[key] = prog, operands
        return prog, operands

    def _guard_active(self) -> bool:
        if self._guard == "auto":
            return guards.enabled()
        return bool(self._guard)

    def _cg_run(self, mode: MatMode, cg_max_iter: int, lam: float) -> jax.Array:
        """One guarded half-step solve from the CURRENT factors; returns
        the new X without committing it. Raises :class:`CGDivergence` when
        the residual guard trips (only checked while guarding — the check
        is one scalar host sync per CG iteration)."""
        eps = 1e-8  # nan_avoidance_constant, cpp:40
        guarding = self._guard_active()
        cg_guard = CGGuard() if guarding else None
        X = self.A if mode == MatMode.A else self.B
        with obs_trace.span(
            "als:half_step", mode=mode.name, lam=lam, cg_iters=cg_max_iter,
        ):
            rhs = self.compute_rhs(mode)
            # The initial residual and every iteration must see the SAME
            # ridge — a damped restart that only damped the iterations would
            # solve an inconsistent system (and the base-λ one would not
            # restart at all).
            Mx = self.compute_queries(self.A, self.B, mode, lam=lam)

            r = rhs - Mx
            p = r
            rsold = _batch_dot(r, r)

            use_programs = self._use_programs
            prog, operands = (self._cg_iter_program(mode, lam)
                              if use_programs else (None, ()))
            other = self.B if mode == MatMode.A else self.A
            if use_programs and donation_enabled():
                # The donating program consumes its carry buffers; the
                # two entry-point aliases must not be donated away:
                # ``X`` aliases the live factor attribute (self.A /
                # self.B — still the committed state if this half-step
                # is abandoned), and ``p`` aliases ``r`` (donating one
                # buffer through two parameters is a runtime error).
                # One copy each per half-step, against four saved
                # allocations per CG iteration.
                X = jnp.copy(X)
                p = jnp.copy(r)
            for _ in range(cg_max_iter):
                faults.maybe_raise("als:cg_iter")
                if use_programs:
                    # B half-steps run the fused pair on the transposed
                    # tiles; the cost-op alias charges that layout's comm.
                    X, r, p, rsold = self.d_ops._timed(
                        "cgStep", prog, X, other, r, p, rsold, *operands,
                        _comm_op="cgStep" if mode == MatMode.A else "cgStepB",
                    )
                else:
                    if mode == MatMode.A:
                        Mp = self.compute_queries(p, self.B, mode, lam=lam)
                    else:
                        Mp = self.compute_queries(self.A, p, mode, lam=lam)
                    X, r, p, rsold = _cg_vector_update(X, r, p, rsold, Mp, eps)
                if cg_guard is not None and cg_guard.update(
                    float(jnp.sum(rsold))
                ):
                    raise CGDivergence(
                        f"CG residual diverged in {mode.name} half-step "
                        f"(λ={lam:g})"
                    )
        return X

    def cg_optimizer(self, mode: MatMode, cg_max_iter: int = 10) -> None:
        """One half-step through the degradation ladder: solve, and on
        divergence (or a poisoned op surfacing as :class:`NumericalFault`)
        retry once from the pre-step factors with a ``damp_factor``-stiffer
        ridge. A second failure propagates :class:`CGDivergence` — `run_cg`
        owns the final rung (serial fallback)."""
        try:
            X = self._cg_run(mode, cg_max_iter, self.ridge_lambda)
        except (CGDivergence, NumericalFault) as first:
            if not self._guard_active():
                raise
            damped = self.ridge_lambda * self.damp_factor
            obs_trace.event(
                "als_damped_restart", mode=mode.name, lam=damped,
                cause=type(first).__name__,
            )
            obs_log.warn(
                "als", f"{type(first).__name__} in {mode.name} half-step; "
                f"damped-λ restart", lam=f"{damped:g}",
            )
            try:
                X = self._cg_run(mode, cg_max_iter, damped)
            except (CGDivergence, NumericalFault) as second:
                raise CGDivergence(
                    f"{mode.name} half-step diverged at λ={self.ridge_lambda:g} "
                    f"and at damped λ={damped:g}: {second}"
                ) from second
        if mode == MatMode.A:
            self.A = X
        else:
            self.B = X

    # ------------------------------------------------------------------ #
    # Checkpoint / resume / degradation
    # ------------------------------------------------------------------ #

    def save_checkpoint(self, store, step: int) -> None:
        """Atomically persist the factors as alternating-step ``step``.
        Host copies of the canonical (padded, possibly >2-D) device arrays
        round-trip bit-exactly through the npz store."""
        store.save(
            step,
            {"A": np.asarray(self.A), "B": np.asarray(self.B)},
            meta={"kind": "als", "R": self.d_ops.R,
                  "M": self.d_ops.M, "N": self.d_ops.N},
        )

    def restore_checkpoint(self, store) -> int:
        """Load the newest valid checkpoint into the factor matrices;
        returns the alternating step to resume FROM (0 = fresh start)."""
        loaded = store.load_latest()
        if loaded is None:
            return 0
        step, arrays, meta = loaded
        if meta and meta.get("kind") not in (None, "als"):
            return 0  # foreign store; do not resurrect GAT weights as factors
        # Shape gate: a checkpoint dir shared across sweep configs (the CLI
        # passes one --checkpoint-dir to every config) must never restore
        # another problem's factors as this one's.
        want_a = tuple(self.d_ops.dense_shape(MatMode.A))
        want_b = tuple(self.d_ops.dense_shape(MatMode.B))
        if (
            "A" not in arrays or "B" not in arrays
            or tuple(arrays["A"].shape) != want_a
            or tuple(arrays["B"].shape) != want_b
        ):
            obs_log.warn(
                "als", "ignoring checkpoint with mismatched factor shapes; "
                "fresh start", want_a=want_a, want_b=want_b,
            )
            return 0
        self.A = jax.device_put(arrays["A"], self.d_ops.a_sharding())
        self.B = jax.device_put(arrays["B"], self.d_ops.b_sharding())
        return step

    def degrade_to_serial(self, n_steps: int, cg_iters: int = 10) -> None:
        """Final ladder rung: continue the optimization on the single-node
        oracle solver, seeded from the current factors. Needs ``S_host``."""
        from distributed_sddmm_tpu.models.serial_als import SerialALS

        if self.S_host is None:
            raise NumericalFault(
                "distributed ALS diverged and no S_host was provided for "
                "the serial fallback; pass S_host=<HostCOO> to DistributedALS"
            )
        d = self.d_ops
        serial = SerialALS(
            self.S_host, d.R,
            ridge_lambda=self.ridge_lambda * self.damp_factor,
            artificial_groundtruth=False,
            ground_truth_vals=d.gather_s_values(self.ground_truth),
        )
        serial.A = d.host_a(self.A).astype(np.float64)
        serial.B = d.host_b(self.B).astype(np.float64)
        serial.run_cg(n_steps, cg_iters=cg_iters)
        self.A = d.put_a(serial.A.astype(np.float32))
        self.B = d.put_b(serial.B.astype(np.float32))
        self.degraded = "serial"
        obs_trace.event("als_degraded", to="serial", remaining_steps=n_steps)
        obs_log.warn("als", "degraded to serial oracle solver",
                     remaining_steps=n_steps)

    def run_cg(
        self,
        n_alternating_steps: int,
        cg_iters: int = 10,
        *,
        checkpoint=None,
        checkpoint_every: int = 1,
        resume: bool = False,
    ) -> None:
        """`als_conjugate_gradients.cpp:235-263`, plus resilience: pass a
        :class:`~distributed_sddmm_tpu.resilience.CheckpointStore` to
        persist the factors every ``checkpoint_every`` alternating steps;
        ``resume=True`` restarts from the newest valid checkpoint instead
        of step 0 (corrupt checkpoints scan back; none ⇒ fresh start)."""
        checkpoint_every = max(1, int(checkpoint_every))  # 0 would div-by-zero
        start = 0
        if checkpoint is not None and resume:
            start = self.restore_checkpoint(checkpoint)
        if self.A is None:
            self.initialize_embeddings()
        step = start
        wd = obs_watchdog.active()
        while step < n_alternating_steps:
            faults.maybe_raise("als:step")
            try:
                t_step = time.perf_counter()
                with obs_trace.span("als:step", step=step):
                    self.cg_optimizer(MatMode.A, cg_iters)
                    self.cg_optimizer(MatMode.B, cg_iters)
                if wd is not None:
                    # Whole-step cadence on top of the per-dispatch hook:
                    # creep across alternating steps (the long-run drift
                    # the watchdog exists for) shows here even when each
                    # individual cgStep stays under its own spike bar.
                    try:
                        wd.observe("als:step", time.perf_counter() - t_step)
                    except obs_watchdog.WatchdogAlarm as alarm:
                        # Strict mode: a step-cadence anomaly enters the
                        # ladder at the divergence rung (degrade, don't
                        # abort) — per-dispatch alarms are already
                        # laddered inside cg_optimizer, and this hook
                        # must not be the one path that escapes.
                        raise CGDivergence(str(alarm)) from alarm
            except CGDivergence as e:
                obs_log.error("als", str(e))
                self.degrade_to_serial(n_alternating_steps - step, cg_iters)
                return
            step += 1
            if checkpoint is not None and (
                step % checkpoint_every == 0 or step == n_alternating_steps
            ):
                self.save_checkpoint(checkpoint, step)

    @classmethod
    def from_plan(
        cls, S, R: int, plan=None, devices=None, plan_mode: str = "model",
        **kw,
    ) -> "DistributedALS":
        """Build ALS on an autotune-selected strategy.

        ``plan=None`` requests one from the plan cache / cost model
        (:func:`distributed_sddmm_tpu.autotune.get_plan`); pass a
        :class:`~distributed_sddmm_tpu.autotune.Plan` to reuse a prior
        selection. The selected plan is kept on ``self.plan``. On the
        dense-shift strategies the plan route lands the CG loop on the
        jit-chained ``fused_program`` path automatically.
        """
        from distributed_sddmm_tpu.autotune import Problem, get_plan

        if plan is None:
            plan = get_plan(
                Problem.from_coo(S, R), devices, S=S, mode=plan_mode
            )
        alg = plan.instantiate(S, R=R, devices=devices)
        kw.setdefault("S_host", S)  # enables the serial-fallback ladder rung
        model = cls(alg, **kw)
        model.plan = plan
        return model

    def item_factors(self) -> np.ndarray:
        """The warm item-factor matrix (N, R) in global row order on the
        host — what the serving fold-in endpoint scores new users
        against (``serve/workloads.py::ALSFoldInTopK``)."""
        if self.B is None:
            raise ValueError(
                "no factors yet: run initialize_embeddings()/run_cg() "
                "or restore a checkpoint first"
            )
        return self.d_ops.host_b(self.B)

    def compute_residual(self) -> float:
        """||sddmm(A, B) - ground_truth||_2 (`als_conjugate_gradients.cpp:207-219`)."""
        d = self.d_ops
        ones = d.like_s_values(1.0)
        A_s, B_s = d.initial_shift(self.A, self.B, KernelMode.SDDMM_A)
        pred = d.sddmm_a(A_s, B_s, ones)
        diff = np.asarray(pred, dtype=np.float64) - np.asarray(
            self.ground_truth, dtype=np.float64
        )
        return float(np.sqrt(np.sum(diff * diff)))
