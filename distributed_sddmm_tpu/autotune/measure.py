"""Short measured trials for candidate plans, with timeout and backoff.

The measurement path exists because the analytic models are first-order:
when the backend is healthy, a few short trials through the existing
``bench/harness.py`` timing path beat any model. A trial can still hang
or fail (a compile that never returns, a device lost mid-run), so every
trial runs under a per-trial timeout with retry-and-exponential-backoff,
and a candidate whose trials all fail is simply dropped. When every candidate drops, the
caller (``plan.get_plan``) falls back to cost-model ranking: a flaky
backend degrades selection quality, it never hangs or raises.

Timeouts run through the shared resilience utility
(``resilience.retry.call_with_timeout``): a daemon-thread join bound that
works from ANY thread — the SIGALRM path this replaced could only arm on
the main thread, so worker-thread autotuning ran unbounded. Backoff
between retries carries proportional jitter (fixed steps re-synchronize
workers that failed together) and a max-elapsed cap (a dead backend fails
fast instead of compounding exponential sleeps). The trial function is
injectable (``trial_fn``) so tests simulate timeouts and count
invocations without ever touching a backend.
"""

from __future__ import annotations

import contextlib
import time
from typing import Callable, Optional

from distributed_sddmm_tpu.autotune.candidates import Candidate
from distributed_sddmm_tpu.autotune.fingerprint import Problem
from distributed_sddmm_tpu.resilience.retry import Backoff, CallTimeout, call_with_timeout


class MeasureTimeout(CallTimeout):
    """One measured trial exceeded its wall-clock budget."""


def _build_kernel(cand: Candidate):
    """The kernel instance a candidate names (chunked XLA = budget
    override; Pallas block config applied via :func:`block_knobs`;
    codegen variant id -> the banked specialized kernel, falling back
    to the generic Pallas kernel when the id's variant generation is
    unknown to this code)."""
    from distributed_sddmm_tpu.ops.kernels import XlaKernel, get_kernel

    if cand.kernel == "xla":
        return XlaKernel(gather_budget=cand.gather_budget)
    if cand.variant:
        from distributed_sddmm_tpu import codegen
        from distributed_sddmm_tpu.obs import log as obs_log

        try:
            return codegen.make_banked_kernel(cand.variant)
        except ValueError as e:
            obs_log.warn(
                "codegen",
                "unknown kernel variant; generic pallas fallback",
                variant=cand.variant, error=str(e),
            )
            from distributed_sddmm_tpu.obs import metrics as obs_metrics

            obs_metrics.GLOBAL.add("codegen_generic_fallbacks")
    return get_kernel(cand.kernel)


@contextlib.contextmanager
def block_knobs(cand: Candidate):
    """Apply a candidate's Pallas block config while its strategy is
    BUILT (the blocked tile chunk lists bake geometry at ingest).

    The knob defaults live as module attributes of ``ops.blocked``,
    initialized from env at first import — so a per-candidate config must
    rebind the module attributes; mutating the env vars here would be a
    silent no-op (the snapshot already happened)."""
    if cand.kernel != "pallas" or cand.block is None:
        yield
        return
    from distributed_sddmm_tpu.ops import blocked

    saved = (blocked.DEFAULT_BLOCK_ROWS, blocked.DEFAULT_BLOCK_COLS)
    blocked.DEFAULT_BLOCK_ROWS, blocked.DEFAULT_BLOCK_COLS = cand.block
    try:
        yield
    finally:
        blocked.DEFAULT_BLOCK_ROWS, blocked.DEFAULT_BLOCK_COLS = saved


def default_trial(
    S, problem: Problem, cand: Candidate, trials: int, warmup: int
) -> dict:
    """One short measured run through the bench harness timing path.
    Returns the harness record (``overall_throughput`` in GFLOP/s)."""
    from distributed_sddmm_tpu.bench.harness import benchmark_algorithm
    from distributed_sddmm_tpu.obs import store as obs_store

    # A candidate trial is a probe, not a run: keep it out of the run
    # store (it would share the real run's fingerprint key AND config
    # axes, silently skewing the regression gate's rolling baseline).
    with obs_store.suppressed(), block_knobs(cand):
        return benchmark_algorithm(
            S,
            cand.algorithm,
            None,
            fused=True,
            R=problem.R,
            c=cand.c,
            trials=trials,
            warmup=warmup,
            kernel=_build_kernel(cand),
            wire=cand.wire,
        )


def measure_candidates(
    S,
    problem: Problem,
    cands: list[Candidate],
    *,
    trials: int = 2,
    warmup: int = 1,
    timeout_s: float = 120.0,
    retries: int = 1,
    backoff_s: float = 2.0,
    jitter: float = 0.25,
    max_elapsed_s: float = 900.0,
    trial_fn: Optional[Callable] = None,
    sleep: Callable[[float], None] = time.sleep,
    monotonic: Callable[[], float] = time.monotonic,
    rng=None,
) -> list[tuple[Candidate, dict]]:
    """Measure each candidate; return the (candidate, record) pairs that
    produced a number, fastest-first by measured throughput.

    Per candidate: up to ``retries + 1`` attempts, each under ``timeout_s``
    wall-clock, with ``backoff_s * 2**attempt * (1 + U(0, jitter))`` sleeps
    between (a transient failure often clears within one backoff window;
    the jitter keeps a fleet of workers that timed out together from re-arriving
    together). ``max_elapsed_s`` caps the whole candidate's attempt budget
    — a dead backend fails fast instead of serializing 600s hangs across
    the whole candidate list. Construction errors (divisibility, kernel
    availability) drop the candidate immediately — retrying a deterministic
    failure wastes budget.

    Every trial attempt runs under an ``autotune:trial`` trace span
    (algorithm/c/kernel/attempt, plus the measured throughput or the
    failure), so a traced run makes plan selection explainable: the
    report shows which candidates were tried, how long each took, and
    why losers lost.
    """
    from distributed_sddmm_tpu.obs import log, metrics, trace

    run = trial_fn or default_trial
    out = []
    for cand in cands:
        backoff = Backoff(
            base_s=backoff_s, jitter=jitter, max_delay_s=float("inf"),
            max_elapsed_s=max_elapsed_s, rng=rng,
        )
        t_start = monotonic()
        last_err = None
        for attempt in range(retries + 1):
            with trace.span(
                "autotune:trial", algorithm=cand.algorithm, c=cand.c,
                kernel=cand.kernel, attempt=attempt,
            ) as sp:
                try:
                    rec = call_with_timeout(
                        lambda: run(S, problem, cand, trials, warmup),
                        timeout_s, label=f"trial:{cand.algorithm}",
                    )
                    sp.set(gflops=rec.get("overall_throughput"))
                    out.append((cand, rec))
                    last_err = None
                    break
                except ValueError as e:
                    sp.set(failed=f"{type(e).__name__}")
                    last_err = e
                    break  # unconstructible; enumeration bug or stale seed
                except Exception as e:  # noqa: BLE001 — failure = drop+note
                    sp.set(failed=f"{type(e).__name__}")
                    last_err = e
            if last_err is not None and attempt < retries:
                d = backoff.delay(attempt)
                if not backoff.budget_left(monotonic() - t_start, d):
                    break  # elapsed cap: fail this candidate fast
                metrics.GLOBAL.add("autotune_trial_retries")
                sleep(d)
        if last_err is not None:
            # The degradation (candidate dropped, possibly down to pure
            # cost-model ranking) must be observable, not silent.
            metrics.GLOBAL.add("autotune_candidates_dropped")
            trace.event(
                "autotune_candidate_dropped", algorithm=cand.algorithm,
                c=cand.c, kernel=cand.kernel,
                error=type(last_err).__name__,
            )
            log.warn(
                "autotune",
                f"dropped {cand.algorithm} c={cand.c} kernel={cand.kernel}",
                error=f"{type(last_err).__name__}: {last_err}",
            )
    out.sort(
        key=lambda cr: cr[1].get("overall_throughput", 0.0), reverse=True
    )
    return out
