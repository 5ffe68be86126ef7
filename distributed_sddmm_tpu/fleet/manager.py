"""Process-per-replica fleet lifecycle.

A replica is ONE OS process serving one warm engine behind an admin
server (``bench serve --serve-http --admin-port <ephemeral>``). The
manager owns spawn/reap/replace:

* **Spawning is cheap by design**: every replica shares the process-
  wide ProgramStore directory, so a replacement's compile-ahead warmup
  resolves the whole bucket ladder from disk (``disk_hits``) instead of
  compiling — the acceptance bar is 0 request-path live compiles on a
  respawn.
* **Reaping reuses the elastic discipline** (``dist/elastic.py``):
  temp-file stdout/stderr (a chatty child must never block on a full
  pipe) and the last-JSON-line record convention — a drained replica's
  final stdout line is its serving record, collected into
  :attr:`FleetManager.records`.
* **Generations**: a replaced replica keeps its name and bumps its
  generation, mirroring the elastic supervisor's recovery-generation
  bookkeeping — fleet telemetry can tell "r1 gen 2" (respawned twice)
  from a fresh slot.
* **Tuner discipline**: exactly one replica (the first ``serve``-role
  spawn, by default) gets ``DSDDMM_TUNER=1`` overlaid — the canary that
  shadow-tests challengers. :meth:`rollout` then replaces the other
  replicas one at a time so their warmups pick the promoted plan out of
  the shared plan cache: canary → all, never the whole fleet at once.

The manager is deliberately transport-agnostic: it talks to replicas
only through their admin HTTP surface (``/healthz``, ``/readyz``,
``/snapshot``) and POSIX signals (SIGTERM = drain-and-exit-with-record,
SIGKILL = chaos, SIGSTOP/SIGCONT = gray-failure wedge).

Gray-failure lifecycle (PR 17):

* **Wedge** (:meth:`FleetManager.wedge`): SIGSTOP — the process is
  alive but answers nothing, the canonical gray fault. Every teardown
  path (``drain``/``stop_all``) SIGCONTs a wedged replica *first*: a
  stopped process cannot handle SIGTERM, so without the continue the
  drain would time out into a kill, lose the record, and — if the
  harness died before its timeout — leak a stopped ``bench serve``
  process forever.
* **Quarantine** (:meth:`FleetManager.quarantine`): a replica the
  router caught returning byzantine bytes (or whose breaker opened) is
  drained out of routing — excluded from :meth:`replicas` so the
  router drops it on the next poll — but kept ALIVE for autopsy; its
  flight record is dumped (``obs/flightrec``) and a warm replacement
  is spawned immediately. ``stop_all`` still drains it at teardown, so
  its serving record is collected like any other replica's.
"""

from __future__ import annotations

import os
import signal
import subprocess
import threading
import time
from typing import Callable, Optional

from distributed_sddmm_tpu.dist.elastic import (
    collect_output, free_port, last_json_line, spawn_process,
)
from distributed_sddmm_tpu.obs import log as obs_log
from distributed_sddmm_tpu.utils import platform


class Replica:
    """One managed replica process (live or just-reaped)."""

    def __init__(self, name: str, port: int, proc: subprocess.Popen,
                 role: str = "serve", generation: int = 0,
                 tuner: bool = False):
        self.name = name
        self.port = port
        self.proc = proc
        self.role = role
        self.generation = generation
        self.tuner = tuner
        self.t_spawn = time.monotonic()
        #: Filled at reap time: exit code and last-JSON-line record.
        self.rc: Optional[int] = None
        self.record: Optional[dict] = None
        #: SIGSTOPped by a chaos wedge (must be SIGCONTed on teardown).
        self.wedged = False
        #: Pulled from routing for autopsy (byzantine/breaker verdict).
        self.quarantined = False
        self.quarantine_reason: Optional[str] = None

    @property
    def alive(self) -> bool:
        return self.proc.poll() is None

    def describe(self) -> dict:
        return {
            "name": self.name, "port": self.port, "role": self.role,
            "generation": self.generation, "tuner": self.tuner,
            "alive": self.alive, "rc": self.rc,
            "wedged": self.wedged, "quarantined": self.quarantined,
            "quarantine_reason": self.quarantine_reason,
        }


class FleetManager:
    """Spawn, watch, replace, and drain a pool of serving replicas.

    ``replica_argv(name, port, role)`` builds one replica's full command
    line (``bench fleet`` points it at ``bench serve --serve-http``;
    tests point it at a cheap stub worker). ``env_overlay(name, port,
    role, tuner)`` returns extra environment for one replica — the
    manager itself only adds the tuner arming.
    """

    def __init__(
        self,
        replica_argv: Callable[[str, int, str], list],
        *,
        env_overlay: Optional[Callable] = None,
        cwd: Optional[str] = None,
        tuner_canary: bool = True,
    ):
        self.replica_argv = replica_argv
        self.env_overlay = env_overlay
        self.cwd = cwd
        #: Arm the background tuner on exactly one serve-role replica.
        self.tuner_canary = tuner_canary
        self._replicas: dict[str, Replica] = {}
        self._next_id = 0
        self._generation: dict[str, int] = {}
        #: Records collected from exited replicas (last JSON stdout
        #: line — the ``bench serve`` record), in reap order.
        self.records: list[dict] = []
        self.spawns = 0
        #: Replicas that died WITHOUT being asked (chaos kills, crashes).
        self.losses = 0
        #: Replicas pulled from routing on a byzantine/breaker verdict.
        self.quarantines = 0
        #: Quarantine verdicts in arrival order, monotonic-stamped —
        #: the chaos drill's detection-deadline judge reads this.
        self.quarantine_log: list[dict] = []
        self._quarantine_lock = threading.Lock()
        #: Trace shards harvested from replicas (reap/quarantine time):
        #: ``{"name", "generation", "pid", "path", "at"}`` rows, dedup'd
        #: by path. ``bench fleet`` merges these with the router's own
        #: trace into the fleet-wide causal tree.
        self.trace_shards: list[dict] = []

    def _harvest_shard(self, rep: Replica, at: str) -> None:
        """Record ``rep``'s per-process trace shard if the fleet run is
        traced. Replica tracers are line-buffered, so a shard is
        readable mid-flight (quarantine autopsy) and complete once the
        process exited (reap). Idempotent per path — a quarantined
        replica is harvested again at teardown without duplicating."""
        from distributed_sddmm_tpu.obs import trace as obs_trace

        shard_dir = obs_trace.shard_dir()
        if shard_dir is None:
            return
        path = obs_trace.find_shard(shard_dir, rep.proc.pid)
        if path is None:
            return
        if any(s["path"] == path for s in self.trace_shards):
            return
        self.trace_shards.append({
            "name": rep.name, "generation": rep.generation,
            "pid": rep.proc.pid, "path": path, "at": at,
        })
        obs_log.info("fleet", "trace shard harvested", name=rep.name,
                     at=at, path=path)

    # -- introspection -------------------------------------------------- #

    def replicas(self, role: Optional[str] = None,
                 include_quarantined: bool = False) -> list[Replica]:
        """Live routable replicas (optionally one role), spawn order.
        Quarantined replicas are alive but NOT routable — the router
        reads this list on every poll tick, so excluding them here IS
        the drain-out-of-routing mechanism."""
        return [r for r in self._replicas.values()
                if r.alive and (role is None or r.role == role)
                and (include_quarantined or not r.quarantined)]

    def get(self, name: str) -> Optional[Replica]:
        return self._replicas.get(name)

    def describe(self) -> dict:
        return {
            "replicas": [r.describe() for r in self._replicas.values()],
            "spawns": self.spawns,
            "losses": self.losses,
            "quarantines": self.quarantines,
            "records_collected": len(self.records),
            "trace_shards": len(self.trace_shards),
        }

    def _tuner_armed(self) -> bool:
        return any(r.tuner for r in self._replicas.values() if r.alive)

    # -- lifecycle ------------------------------------------------------ #

    def spawn(self, role: str = "serve", name: Optional[str] = None
              ) -> Replica:
        """Launch one replica on a fresh ephemeral admin port. A reused
        ``name`` (respawn) bumps that slot's generation; the tuner
        arms on the first serve-role replica only — one canary,
        never a fleet of independently-tuning engines."""
        if name is None:
            name = f"r{self._next_id}"
            self._next_id += 1
        generation = self._generation.get(name, -1) + 1
        self._generation[name] = generation
        port = free_port()
        tuner = (self.tuner_canary and role == "serve"
                 and not self._tuner_armed())
        env = dict(os.environ)
        if tuner:
            env["DSDDMM_TUNER"] = "1"
        else:
            env.pop("DSDDMM_TUNER", None)
        if self.env_overlay is not None:
            env.update(self.env_overlay(name, port, role, tuner) or {})
        self._check_chip_room(env)
        proc = spawn_process(
            list(self.replica_argv(name, port, role)), env=env, cwd=self.cwd,
        )
        rep = Replica(name, port, proc, role=role, generation=generation,
                      tuner=tuner)
        self._replicas[name] = rep
        self.spawns += 1
        obs_log.info("fleet", "replica spawned", name=name, port=port,
                     role=role, generation=generation, tuner=tuner)
        return rep

    def _check_chip_room(self, env: dict) -> None:
        """Refuse a TPU replica when every chip on the host is taken: a
        chip belongs to one process, so the extra replica would fail or
        hang at backend start. Alive replicas hold one chip each, the
        quarantined ones included, and this process holds whatever TPU
        devices its own backend took. CPU replicas are not limited."""
        if not platform.uses_tpu(env):
            return
        chips = platform.tpu_chips_on_host(env)
        replicas = sum(1 for r in self._replicas.values() if r.alive)
        own = platform.tpu_chips_held()
        if replicas + own >= chips:
            raise RuntimeError(
                f"cannot start another TPU replica: the host's {chips} TPU "
                f"chip(s) are held by {replicas} replica process(es) and "
                f"{own} by this process, and a chip serves one process; "
                "pin this process or the replicas to the CPU with "
                "JAX_PLATFORMS=cpu, or run fewer replicas"
            )

    def wait_ready(self, timeout_s: float = 120.0,
                   names: Optional[list] = None) -> bool:
        """Poll each replica's ``/readyz`` until all are ready (True) or
        the deadline passes (False). A replica that *dies* while we
        wait fails fast — waiting out the full timeout on a corpse
        would hide a crash-on-boot as a timeout."""
        from distributed_sddmm_tpu.obs.httpexp import fetch_json

        want = names if names is not None else [
            r.name for r in self.replicas()
        ]
        deadline = time.monotonic() + timeout_s
        pending = set(want)
        while pending and time.monotonic() < deadline:
            for name in sorted(pending):
                rep = self._replicas.get(name)
                if rep is None or not rep.alive:
                    obs_log.warn("fleet", "replica died before ready",
                                 name=name)
                    return False
                try:
                    body = fetch_json("127.0.0.1", rep.port, "/readyz",
                                      timeout_s=1.0)
                except OSError:
                    continue  # not listening yet
                if body.get("ready"):
                    pending.discard(name)
            if pending:
                time.sleep(0.1)
        return not pending

    def _reap(self, rep: Replica, expected: bool) -> None:
        rep.proc.wait()
        out, err = collect_output(rep.proc)
        rep.rc = rep.proc.returncode
        rep.record = last_json_line(out)
        self._harvest_shard(rep, at="reap")
        if rep.record is not None:
            self.records.append(rep.record)
        if not expected:
            self.losses += 1
            obs_log.warn(
                "fleet", "replica lost", name=rep.name, rc=rep.rc,
                generation=rep.generation, stderr_tail=(err or "")[-300:],
            )
        else:
            obs_log.info("fleet", "replica reaped", name=rep.name,
                         rc=rep.rc, generation=rep.generation)

    def poll(self) -> list[Replica]:
        """Reap replicas that died on their own since the last poll;
        returns them (records collected, ``losses`` bumped)."""
        dead = [r for r in self._replicas.values()
                if r.rc is None and not r.alive]
        for rep in dead:
            self._reap(rep, expected=False)
        return dead

    def respawn_dead(self) -> list[Replica]:
        """The self-healing move: reap losses, then relaunch each under
        its old name (generation+1). The replacement's warmup resolves
        its ladder from the shared ProgramStore — disk hits, not
        request-path compiles."""
        replaced = []
        for rep in self.poll():
            replaced.append(self.spawn(role=rep.role, name=rep.name))
        return replaced

    def kill(self, name: str) -> None:
        """Chaos move: SIGKILL — no drain, no record, in-flight work
        dies with the process (the router's retry path owns it)."""
        rep = self._replicas.get(name)
        if rep is None or not rep.alive:
            raise ValueError(f"no live replica {name!r}")
        obs_log.warn("fleet", "replica killed (chaos)", name=name)
        rep.proc.kill()

    def wedge(self, name: str) -> None:
        """Gray-failure chaos move: SIGSTOP — the process stays alive
        (and holds its ports) but answers nothing. Reversed by
        :meth:`unwedge`; every teardown path SIGCONTs first."""
        rep = self._replicas.get(name)
        if rep is None or not rep.alive:
            raise ValueError(f"no live replica {name!r}")
        rep.proc.send_signal(signal.SIGSTOP)
        rep.wedged = True
        obs_log.warn("fleet", "replica wedged (chaos)", name=name)

    def unwedge(self, name: str) -> None:
        """SIGCONT a wedged replica. Idempotent; a no-op on a corpse."""
        rep = self._replicas.get(name)
        if rep is None:
            return
        if rep.alive and rep.wedged:
            rep.proc.send_signal(signal.SIGCONT)
            obs_log.info("fleet", "replica unwedged", name=name)
        rep.wedged = False

    def _continue_for_teardown(self, rep: Replica) -> None:
        """A SIGSTOPped process cannot handle SIGTERM — it would sit in
        the stopped state until the drain timeout killed it (record
        lost) or, if the harness died first, leak forever. SIGCONT
        before any teardown signal so the drain contract holds."""
        if rep.wedged and rep.alive:
            try:
                rep.proc.send_signal(signal.SIGCONT)
            except (OSError, ValueError):
                pass
            rep.wedged = False

    def quarantine(self, name: str, reason: str = "",
                   evidence: Optional[dict] = None,
                   respawn: bool = True) -> Optional[Replica]:
        """Byzantine/breaker verdict: pull ``name`` out of routing but
        keep it ALIVE for autopsy. Dumps a flight-record snapshot when
        the recorder is armed, bumps the quarantine ledger, and spawns
        a warm replacement (fresh name — the quarantined slot still
        exists). Returns the replacement (None when ``respawn`` is off
        or the replica was already quarantined/dead)."""
        from distributed_sddmm_tpu.obs import flightrec, metrics
        from distributed_sddmm_tpu.obs import trace as obs_trace

        with self._quarantine_lock:
            rep = self._replicas.get(name)
            if rep is None or not rep.alive or rep.quarantined:
                return None
            rep.quarantined = True
            rep.quarantine_reason = reason or "quarantined"
            self.quarantines += 1
            self.quarantine_log.append({
                "t": time.monotonic(), "name": name, "reason": reason,
                "generation": rep.generation,
            })
        metrics.GLOBAL.add("fleet_quarantines")
        obs_trace.event("fleet_quarantine", replica=name, reason=reason)
        self._harvest_shard(rep, at="quarantine")
        obs_log.warn("fleet", "replica quarantined", name=name,
                     reason=reason, generation=rep.generation)
        fr = flightrec.active()
        if fr is not None:
            fr.dump("fleet_quarantine", op="fleet", attrs={
                "name": name, "reason": reason,
                "generation": rep.generation, "role": rep.role,
                "evidence": evidence or {},
            })
        if not respawn:
            return None
        replacement = self.spawn(role=rep.role)
        obs_log.info("fleet", "quarantine replacement spawned",
                     quarantined=name, replacement=replacement.name)
        return replacement

    def drain(self, name: str, timeout_s: float = 60.0) -> Optional[dict]:
        """Graceful exit: SIGTERM → the replica closes admission, drains
        its queue, prints its record, exits 0. Returns the record."""
        rep = self._replicas.get(name)
        if rep is None or not rep.alive:
            raise ValueError(f"no live replica {name!r}")
        self._continue_for_teardown(rep)
        rep.proc.send_signal(signal.SIGTERM)
        try:
            rep.proc.wait(timeout_s)
        except subprocess.TimeoutExpired:
            obs_log.warn("fleet", "drain timed out; killing", name=name)
            rep.proc.kill()
        self._reap(rep, expected=True)
        return rep.record

    def stop_all(self, timeout_s: float = 60.0) -> list[dict]:
        """Drain every live replica (wedged ones are SIGCONTed first,
        quarantined ones included — their records still count); returns
        all collected records."""
        live = [r for r in self._replicas.values() if r.alive]
        for rep in live:
            self._continue_for_teardown(rep)
            rep.proc.send_signal(signal.SIGTERM)
        deadline = time.monotonic() + timeout_s
        for rep in live:
            remain = max(0.1, deadline - time.monotonic())
            try:
                rep.proc.wait(remain)
            except subprocess.TimeoutExpired:
                rep.proc.kill()
            self._reap(rep, expected=True)
        return list(self.records)

    # -- fleet-wide tuner rollout --------------------------------------- #

    def rollout(self, ready_timeout_s: float = 120.0) -> list[str]:
        """Canary → all: after the tuner replica promotes a challenger
        (its promotion stores the winning plan in the shared plan
        cache), replace every OTHER serve replica one at a time — drain,
        respawn under the same name, wait ready — so each replacement
        warms straight onto the winner. One replica's worth of capacity
        is out at any instant; a bad challenger is caught by the
        canary's shadow validation before this ever runs."""
        rolled = []
        targets = [r.name for r in self.replicas(role="serve")
                   if not r.tuner]
        for name in targets:
            role = self._replicas[name].role
            self.drain(name)
            self.spawn(role=role, name=name)
            if not self.wait_ready(ready_timeout_s, names=[name]):
                obs_log.warn("fleet", "rollout replacement not ready",
                             name=name)
                break
            rolled.append(name)
        obs_log.info("fleet", "rollout complete", replaced=rolled)
        return rolled

    # -- telemetry ------------------------------------------------------ #

    def snapshots(self) -> dict:
        """Live ``/snapshot`` per replica (None where unreachable) —
        the autoscaler's input stream."""
        from distributed_sddmm_tpu.obs.httpexp import fetch_json

        out = {}
        for rep in self.replicas():
            try:
                out[rep.name] = fetch_json("127.0.0.1", rep.port,
                                           "/snapshot", timeout_s=1.0)
            except (OSError, ValueError):
                out[rep.name] = None
        return out
