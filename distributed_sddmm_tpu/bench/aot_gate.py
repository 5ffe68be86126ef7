"""Shared policy for gating AOT-compile modes on the probe's verdict.

Single home for three decisions that bench.py, scripts/kernel_sweep.py,
scripts/dist_gap.py and scripts/tpu_apps.py previously each hand-rolled
(and let drift):

* which probe program vouches for a given kernel choice
  (`probe_program`),
* whether AOT_LOAD.json (written by scripts/aot_load_probe.py) validates
  re-homed loads for that program (`probe_validated`),
* when repeated AOT-precompile timeouts justify a permanent ok:false
  tombstone (`timeout_strike`).

Deliberately jax-free: the callers are orchestrator processes that must
not initialize any backend.

Reference analog: none (see bench/aot.py's module docstring).
"""

from __future__ import annotations

import json
import pathlib

from distributed_sddmm_tpu.obs import clock
from distributed_sddmm_tpu.utils.atomic import atomic_write_text

# Strikes closer together than this are treated as one load episode —
# a retry loop or a sibling script hitting the same machine-load spike
# minutes later is not independent evidence of a deterministic hang.
STRIKE_WINDOW_S = 1800.0

# Per-program probe-chain versions — THE single home (the probe script
# imports these). Bump a program's version when its chain changes: every
# gate then rejects that program's recorded verdict until the probe
# re-answers with the current chain, while sibling verdicts keep working.
# Entries recorded before per-program versioning carry no program_version
# field; those chains were version 1.
PROGRAM_VERSIONS = {
    "pallas_fused": 1,
    "xla_matmul": 2,  # v2: pinned to Precision.HIGHEST
}


def probe_program(kernel: str) -> str:
    """The aot_load_probe program whose verdict vouches for ``kernel``."""
    return "xla_matmul" if kernel == "xla" else "pallas_fused"


def _entry_current(name: str, entry: dict) -> bool:
    return entry.get("program_version", 1) == PROGRAM_VERSIONS.get(name)


def load_verdict(path: str | pathlib.Path) -> dict:
    """AOT_LOAD.json contents, or {} when absent/unreadable."""
    try:
        return json.loads(pathlib.Path(path).read_text())
    except (OSError, json.JSONDecodeError, ValueError):
        return {}


def probe_validated(rep: dict, program: str | None = None) -> bool:
    """Did the probe validate re-homed loads (for one program, or — with
    no argument — for ALL programs)? Multi-device backends never qualify
    (the offline compilers target one device), and a verdict earned by an
    older probe chain never qualifies — staleness must bind every gate,
    not only the queue's --check-stale pruning pass."""
    try:
        if int(rep.get("n_devices", 1)) != 1:
            return False
    except (TypeError, ValueError):
        return False
    progs = rep.get("programs") or {}
    if program is not None:
        entry = progs.get(program, {})
        return bool(entry.get("ok")) and _entry_current(program, entry)
    return bool(rep.get("ok")) and set(progs) >= set(PROGRAM_VERSIONS) and all(
        _entry_current(n, progs[n]) for n in PROGRAM_VERSIONS)


def timeout_strike(out_dir: str | pathlib.Path, *,
                   full_budget: bool = True) -> bool:
    """Record one AOT-precompile timeout strike against ``out_dir``.

    Returns True when the history now shows two strikes from independent
    load episodes (>= STRIKE_WINDOW_S apart) — only then should the
    caller write its permanent ok:false tombstone. A timeout under a
    capped budget (``full_budget=False``) neither counts nor is recorded:
    a healthy compile can exceed a ~30s remaining-window cap, so it is
    no evidence about this config at all.

    The strike file holds one epoch timestamp per line; tokens that are
    not plausible epochs (e.g. the pre-policy integer counters) are
    ignored rather than misread as 1970-era strikes.
    """
    out = pathlib.Path(out_dir)
    out.mkdir(parents=True, exist_ok=True)
    f = out / "timeouts"
    now = clock.epoch()
    times: list[float] = []
    try:
        for tok in f.read_text().split():
            try:
                v = float(tok)
            except ValueError:
                continue
            if v > 1e9:
                times.append(v)
    except OSError:
        pass
    if not full_budget:
        return False
    conclusive = any(now - t >= STRIKE_WINDOW_S for t in times)
    atomic_write_text(f, "\n".join(f"{t:.0f}" for t in [*times, now]))
    return conclusive
