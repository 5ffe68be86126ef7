"""The Plan record and the ``get_plan`` selection entry point.

Selection order, fastest knowledge first:

1. **Cache hit** — a stored plan for this exact fingerprint returns
   immediately: zero measured trials, zero strategy builds, well under a
   second.
2. **Warm start** — committed sweep/heatmap records seed a candidate
   (verified for legality against the current mesh before being trusted).
3. **Cost model** — candidates enumerated, HBM-guarded, and ranked by the
   analytic models.
4. **Measurement** (``mode="measure"`` or ``mode="auto"`` with the sparse
   matrix available) — the top-ranked few candidates run short trials
   through the bench harness under per-trial timeouts; the measured winner
   takes the plan. Every measurement failure mode degrades to step 3's
   ranking — a dead backend can cost selection quality, never a hang or an
   exception.

The chosen plan is stored back under the fingerprint key, so the next
process with the same problem, mesh, backend and code generation takes
path 1.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Optional

from distributed_sddmm_tpu.autotune import cache as cache_mod
from distributed_sddmm_tpu.autotune import candidates as cand_mod
from distributed_sddmm_tpu.autotune import measure as measure_mod
from distributed_sddmm_tpu.autotune.cache import PlanCache
from distributed_sddmm_tpu.autotune.candidates import Candidate
from distributed_sddmm_tpu.autotune.fingerprint import (
    Problem, machine_signature, make_fingerprint,
)

MODES = ("auto", "model", "measure")


@dataclasses.dataclass
class Plan:
    """A selected execution configuration for one fingerprinted problem."""

    algorithm: str
    c: int
    kernel: str = "xla"
    block: tuple | None = None
    gather_budget: int | None = None
    #: Codegen kernel-variant id (``codegen/variants.py``); None = the
    #: generic kernel. Optional field — pre-PR-9 cached plans load with
    #: None, and a plan carrying an unknown variant generation falls
    #: back to the generic kernel at build time.
    variant: str | None = None
    #: Wire-precision comm dtype (``parallel/wire.py``); None = the f32
    #: identity wire. Optional field — pre-PR-15 cached plans load with
    #: None and build byte-identical strategies.
    wire: str | None = None
    source: str = "model"            # model | measured | seed
    predicted_ms: float | None = None
    measured_gflops: float | None = None
    fingerprint_key: str = ""

    def to_dict(self) -> dict:
        d = dataclasses.asdict(self)
        d["block"] = list(self.block) if self.block else None
        return d

    @classmethod
    def from_dict(cls, d: dict) -> "Plan":
        block = d.get("block")
        return cls(
            algorithm=d["algorithm"],
            c=int(d["c"]),
            kernel=d.get("kernel", "xla"),
            block=tuple(block) if block else None,
            gather_budget=d.get("gather_budget"),
            variant=d.get("variant"),
            wire=d.get("wire"),
            source=d.get("source", "model"),
            predicted_ms=d.get("predicted_ms"),
            measured_gflops=d.get("measured_gflops"),
            fingerprint_key=d.get("fingerprint_key", ""),
        )

    def candidate(self) -> Candidate:
        return Candidate(
            algorithm=self.algorithm, c=self.c, kernel=self.kernel,
            block=self.block, gather_budget=self.gather_budget,
            variant=self.variant, wire=self.wire,
        )

    def make_kernel(self):
        return measure_mod._build_kernel(self.candidate())

    def instantiate(self, S, R: int, devices=None, program_store=None, **kw):
        """Build the planned strategy for a concrete sparse matrix through
        the harness factory (same five magic strings). ``R`` is passed
        explicitly — plans are selected per problem and do not carry the
        problem with them.

        When the persistent program store is active (``programs/``;
        ``program_store`` overrides, ``DSDDMM_PROGRAMS=0`` vetoes), the
        strategy is bound to it under this plan's fingerprint key: every
        shard_map program the strategy compiles is then recalled from
        ``artifacts/programs/`` when a previous process already built it,
        and persisted when not — the zero-live-compile warm start the
        plan cache gives selection, extended to compilation."""
        from distributed_sddmm_tpu.bench.harness import make_algorithm

        with measure_mod.block_knobs(self.candidate()):
            alg = make_algorithm(
                self.algorithm, S, R=R, c=self.c,
                kernel=self.make_kernel(), devices=devices,
                wire=self.wire, **kw
            )
        if self.fingerprint_key:
            from distributed_sddmm_tpu import programs

            programs.bind_strategy(
                alg, self.fingerprint_key, store=program_store,
            )
        return alg


def _seed_candidate(
    problem: Problem, p: int, backend: str, kernels: tuple[str, ...],
) -> Optional[Candidate]:
    """A legality-checked candidate from committed offline records.

    Only a matching *winner* record (algorithm + c actually measured on
    this problem shape) seeds a candidate; the kernel-family records can
    refine its kernel choice but never fabricate an algorithm/c on their
    own — without a winner match, the cost model's ranking stands (it
    already weighs kernel families through their measured rates).
    """
    seed = cache_mod.seed_winner_plan(problem, p)
    if seed is None:
        return None
    algorithm, c = seed.get("algorithm"), seed.get("c")
    kernel = cache_mod.seed_kernel_family(problem, backend)
    kernel = kernel if kernel in kernels else "xla"
    if algorithm not in cand_mod.ALGORITHM_MODELS:
        return None
    if c not in cand_mod.legal_c_values(algorithm, p, problem.R):
        return None
    cand = Candidate(algorithm=algorithm, c=int(c), kernel=kernel)
    return cand_mod.hbm_guard(problem, cand, p)


def get_plan(
    problem: Problem,
    devices=None,
    S=None,
    *,
    mode: str = "auto",
    cache: Optional[PlanCache] = None,
    machine=None,
    top_k: int = 3,
    trials: int = 2,
    warmup: int = 1,
    timeout_s: float = 120.0,
    retries: int = 1,
    backoff_s: float = 2.0,
    jitter: float = 0.25,
    max_elapsed_s: float = 900.0,
    trial_fn: Optional[Callable] = None,
) -> Plan:
    """Select (or recall) the execution plan for a fingerprinted problem.

    ``mode``: ``"model"`` never measures; ``"measure"`` requires ``S`` and
    measures the top-``top_k`` model-ranked candidates; ``"auto"``
    measures only when ``S`` is provided. All modes hit the cache first
    and store their result.

    ``trial_fn`` (tests, alternative backends) replaces the harness trial:
    ``trial_fn(S, problem, candidate, trials, warmup) -> record``.
    """
    if mode not in MODES:
        raise ValueError(f"mode must be one of {MODES}")
    if mode == "measure" and S is None:
        raise ValueError("mode='measure' needs the sparse matrix S")

    from distributed_sddmm_tpu.obs import metrics as obs_metrics
    from distributed_sddmm_tpu.obs import trace as obs_trace

    p, backend, kernels = machine_signature(devices)
    fp = make_fingerprint(problem, p, backend, kernels)
    cache = cache if cache is not None else PlanCache()

    hit = cache.load(fp.key)
    if hit is not None:
        # An explicit measure request upgrades a cached model/seed guess:
        # serving it would make '--plan-mode measure' a silent no-op
        # forever after any model-mode call warmed the key. Measured
        # plans always serve (zero-trial hits are the point).
        if not (mode == "measure" and hit.get("source") != "measured"):
            obs_metrics.GLOBAL.add("plan_cache_hits")
            obs_trace.event(
                "plan_cache_hit", key=fp.key,
                algorithm=hit.get("algorithm"), c=hit.get("c"),
                source=hit.get("source"),
            )
            return Plan.from_dict(hit)
    obs_metrics.GLOBAL.add("plan_cache_misses")

    cands = cand_mod.enumerate_candidates(problem, p, kernels)
    if not cands:
        raise ValueError(
            f"no constructible algorithm configuration for {problem} "
            f"on p={p} (check R divisibility constraints)"
        )
    ranked = cand_mod.rank_candidates(problem, cands, p, machine)

    seed = _seed_candidate(problem, p, backend, kernels)
    seeded_first = ranked
    if seed is not None:
        seeded_first = [cs for cs in ranked if cs[0] == seed]
        seeded_first += [cs for cs in ranked if cs[0] != seed]
        if not seeded_first or seeded_first[0][0] != seed:
            # Seed survived legality but not enumeration (e.g. guard
            # rewrote it) — score it explicitly and lead with it.
            seeded_first = [
                (seed, cand_mod.model_cost(problem, seed, p, machine))
            ] + ranked

    measured: list = []
    if mode == "measure" or (mode == "auto" and S is not None):
        short_list = [cand for cand, _ in seeded_first[:top_k]]
        measured = measure_mod.measure_candidates(
            S, problem, short_list,
            trials=trials, warmup=warmup, timeout_s=timeout_s,
            retries=retries, backoff_s=backoff_s, jitter=jitter,
            max_elapsed_s=max_elapsed_s, trial_fn=trial_fn,
        )

    if measured:
        best_cand, rec = measured[0]
        plan = Plan(
            algorithm=best_cand.algorithm, c=best_cand.c,
            kernel=best_cand.kernel, block=best_cand.block,
            gather_budget=best_cand.gather_budget,
            variant=best_cand.variant,
            wire=best_cand.wire,
            source="measured",
            predicted_ms=_predicted_ms(problem, best_cand, p, machine),
            measured_gflops=rec.get("overall_throughput"),
            fingerprint_key=fp.key,
        )
    else:
        best_cand, cost = seeded_first[0]
        plan = Plan(
            algorithm=best_cand.algorithm, c=best_cand.c,
            kernel=best_cand.kernel, block=best_cand.block,
            gather_budget=best_cand.gather_budget,
            variant=best_cand.variant,
            wire=best_cand.wire,
            source="seed" if seed is not None and best_cand == seed else "model",
            predicted_ms=cost * 1e3,
            fingerprint_key=fp.key,
        )

    obs_trace.event(
        "plan_selected", key=fp.key, algorithm=plan.algorithm, c=plan.c,
        kernel=plan.kernel, source=plan.source,
        measured=len(measured),
    )
    cache.store(fp.key, plan.to_dict())
    return plan


def _predicted_ms(problem, cand, p, machine) -> float:
    return cand_mod.model_cost(problem, cand, p, machine) * 1e3
