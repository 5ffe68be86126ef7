"""Resilience layer: fault injection, retry/timeout, guards, checkpoints.

The production counterpart of the reference's healthy-MPI-world assumption
(Bharadwaj et al., IPDPS 2022 run from step 0 on a clean communicator):
the strategies, apps, bench harness, and autotuner all execute through
this package's hooks so that a preempted chip, a lost device, a
torn cache write, or a diverging solver degrades a run instead of
poisoning or hanging it.

* :mod:`.faults`     — seeded, deterministic fault-injection plans
  (env/CLI-activated); every hook is a no-op without an active plan
* :mod:`.chaos`      — seeded fleet-level chaos schedules (kill /
  wedge / partition / slow / corrupt) compiled to a reproducible
  timeline; ``bench fleet --chaos`` drills run on this
* :mod:`.retry`      — thread-safe call timeouts + exponential backoff
  with jitter and a max-elapsed cap (replaced the SIGALRM path)
* :mod:`.guards`     — NaN/Inf output sentinels, CG divergence detection
* :mod:`.checkpoint` — atomic versioned step checkpoints with
  digest-verified, scan-back resume

The degradation ladder, top to bottom: retry the call (transient faults
heal), restart damped (CG divergence re-solves with a stiffer ridge),
fall back (distributed ALS hands off to the serial oracle solver;
autotune falls to cost-model ranking), and finally fail *loudly* — a
clean typed exception, never a hang, never a silently wrong result.
"""

from distributed_sddmm_tpu.resilience.chaos import (
    ChaosAction, ChaosEngine, ChaosSchedule,
)
from distributed_sddmm_tpu.resilience.checkpoint import (
    CheckpointStore, default_checkpoint_dir,
)
from distributed_sddmm_tpu.resilience.faults import (
    FaultError, FaultPlan, FaultSpec, InjectedFault, InjectedOOM,
    InjectedTimeout, fault_plan,
)
from distributed_sddmm_tpu.resilience.guards import CGGuard, NumericalFault
from distributed_sddmm_tpu.resilience.retry import (
    Backoff, CallTimeout, call_with_timeout, retry_call,
)

__all__ = [
    "Backoff",
    "CGGuard",
    "CallTimeout",
    "ChaosAction",
    "ChaosEngine",
    "ChaosSchedule",
    "CheckpointStore",
    "FaultError",
    "FaultPlan",
    "FaultSpec",
    "InjectedFault",
    "InjectedOOM",
    "InjectedTimeout",
    "NumericalFault",
    "call_with_timeout",
    "default_checkpoint_dir",
    "fault_plan",
    "retry_call",
]
