"""Fleet-manager lifecycle tests against the stub worker
(``tests/_fleet_worker.py``) — a real OS process speaking the full
replica contract (admin surface, SIGTERM → record → exit 0) with no
engine behind it, so spawn/reap/respawn semantics are exercised on real
processes in milliseconds.
"""

import os
import sys
import time

import pytest

from distributed_sddmm_tpu.fleet import FleetManager

WORKER = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "_fleet_worker.py")
READY_S = 30.0


def _argv(name, port, role):
    return [sys.executable, WORKER, "--admin-port", str(port),
            "--name", name, "--role", role]


def _crash_argv(name, port, role):
    return _argv(name, port, role) + ["--crash-after", "0.1"]


@pytest.fixture
def manager():
    mgr = FleetManager(_argv, tuner_canary=False)
    yield mgr
    mgr.stop_all(timeout_s=10.0)


class TestLifecycle:
    def test_spawn_wait_ready_snapshot(self, manager):
        rep = manager.spawn()
        assert rep.name == "r0" and rep.generation == 0
        assert manager.wait_ready(READY_S)
        snaps = manager.snapshots()
        assert snaps["r0"]["name"] == "r0"
        assert snaps["r0"]["buckets"]["inner"] == [4, 8]

    def test_drain_collects_record(self, manager):
        manager.spawn()
        manager.spawn()
        assert manager.wait_ready(READY_S)
        record = manager.drain("r0")
        assert record["name"] == "r0"
        assert record["app"] == "fleet-worker-stub"
        assert manager.get("r0").rc == 0
        assert record in manager.records
        assert [r.name for r in manager.replicas()] == ["r1"]
        assert manager.losses == 0

    def test_kill_poll_respawn_bumps_generation(self, manager):
        manager.spawn()
        manager.spawn()
        assert manager.wait_ready(READY_S)
        manager.kill("r1")
        deadline = time.monotonic() + 10.0
        while manager.get("r1").alive and time.monotonic() < deadline:
            time.sleep(0.05)
        replaced = manager.respawn_dead()
        assert [r.name for r in replaced] == ["r1"]
        assert replaced[0].generation == 1
        assert manager.losses == 1  # unplanned death is a loss...
        assert manager.wait_ready(READY_S, names=["r1"])
        assert manager.spawns == 3
        # ...and a SIGKILLed replica leaves no record behind.
        assert all(r.get("name") != "r1" for r in manager.records)

    def test_wait_ready_fails_fast_on_dead_replica(self):
        mgr = FleetManager(_crash_argv, tuner_canary=False)
        try:
            mgr.spawn()
            t0 = time.monotonic()
            assert mgr.wait_ready(timeout_s=60.0) is False
            assert time.monotonic() - t0 < 30.0  # no full-timeout wait
        finally:
            mgr.stop_all(timeout_s=10.0)

    def test_stop_all_reaps_everything(self, manager):
        manager.spawn()
        manager.spawn()
        assert manager.wait_ready(READY_S)
        records = manager.stop_all(timeout_s=10.0)
        assert {r["name"] for r in records} == {"r0", "r1"}
        assert manager.replicas() == []


class TestChipGuard:
    @pytest.mark.parametrize("platforms,refused", [("tpu", True),
                                                   ("cpu", False)])
    def test_tpu_replicas_capped_at_chip_count(self, monkeypatch,
                                               platforms, refused):
        from distributed_sddmm_tpu.utils import platform

        monkeypatch.setattr(platform, "tpu_chips_on_host", lambda env: 1)
        monkeypatch.setattr(platform, "tpu_chips_held", lambda: 0)
        mgr = FleetManager(
            _argv, tuner_canary=False,
            env_overlay=lambda *a: {"JAX_PLATFORMS": platforms})
        try:
            mgr.spawn()
            if refused:
                with pytest.raises(RuntimeError, match="TPU chip"):
                    mgr.spawn()
                assert len(mgr.replicas()) == 1
            else:
                mgr.spawn()
                assert len(mgr.replicas()) == 2
        finally:
            mgr.stop_all(timeout_s=10.0)


    def test_parent_holding_the_chip_refuses_the_first_replica(
            self, monkeypatch):
        from distributed_sddmm_tpu.utils import platform

        monkeypatch.setattr(platform, "tpu_chips_on_host", lambda env: 1)
        monkeypatch.setattr(platform, "tpu_chips_held", lambda: 1)
        mgr = FleetManager(_argv, tuner_canary=False,
                           env_overlay=lambda *a: {"JAX_PLATFORMS": "tpu"})
        with pytest.raises(RuntimeError, match="1 by this process"):
            mgr.spawn()
        assert mgr.replicas() == []


class TestGrayFailure:
    def test_quarantine_lifecycle(self, manager):
        manager.spawn()
        manager.spawn()
        assert manager.wait_ready(READY_S)
        replacement = manager.quarantine(
            "r0", reason="byzantine reply mismatch (audit)",
            evidence={"disagreed_with": ["r1"]},
        )
        # Drained out of routing, alive for autopsy.
        rep = manager.get("r0")
        assert rep.quarantined is True and rep.alive is True
        assert [r.name for r in manager.replicas()] == ["r1", "r2"]
        assert "r0" in [r.name for r in
                        manager.replicas(include_quarantined=True)]
        # Ledger + warm replacement under a FRESH name (the
        # quarantined slot still exists for the autopsy).
        assert replacement is not None and replacement.name == "r2"
        assert manager.quarantines == 1
        (entry,) = manager.quarantine_log
        assert entry["name"] == "r0"
        assert "byzantine" in entry["reason"]
        assert manager.wait_ready(READY_S, names=["r2"])
        # Teardown still collects the quarantined replica's record.
        records = manager.stop_all(timeout_s=10.0)
        assert "r0" in {r["name"] for r in records}
        assert manager.get("r0").rc == 0

    def test_quarantine_is_idempotent(self, manager):
        manager.spawn()
        manager.spawn()
        assert manager.wait_ready(READY_S)
        assert manager.quarantine("r0", respawn=False) is None
        assert manager.quarantine("r0") is None  # already quarantined
        assert manager.quarantines == 1
        assert manager.spawns == 2  # respawn=False spawned nothing

    def test_wedged_replica_freezes_and_teardown_reaps(self, manager):
        """Satellite 6: SIGSTOP freezes the admin surface, and
        ``stop_all`` SIGCONTs before SIGTERM so a wedged replica still
        drains promptly with a record instead of leaking a stopped
        process or losing the drain to the kill timeout."""
        from distributed_sddmm_tpu.obs.httpexp import fetch_json

        manager.spawn()
        manager.spawn()
        assert manager.wait_ready(READY_S)
        port = manager.get("r0").port
        manager.wedge("r0")
        assert manager.get("r0").wedged is True
        with pytest.raises(OSError):
            fetch_json("127.0.0.1", port, "/readyz", timeout_s=1.0)
        t0 = time.monotonic()
        records = manager.stop_all(timeout_s=10.0)
        assert time.monotonic() - t0 < 8.0  # no drain-timeout kill
        assert {r["name"] for r in records} == {"r0", "r1"}
        rep = manager.get("r0")
        assert rep.alive is False and rep.rc == 0 and not rep.wedged

    def test_unwedge_restores_the_admin_surface(self, manager):
        from distributed_sddmm_tpu.obs.httpexp import fetch_json

        manager.spawn()
        assert manager.wait_ready(READY_S)
        port = manager.get("r0").port
        manager.wedge("r0")
        manager.unwedge("r0")
        assert manager.get("r0").wedged is False
        body = fetch_json("127.0.0.1", port, "/readyz", timeout_s=5.0)
        assert body.get("ready") is True


class TestTunerDiscipline:
    def test_exactly_one_canary(self):
        mgr = FleetManager(_argv, tuner_canary=True)
        try:
            a = mgr.spawn()
            b = mgr.spawn()
            assert a.tuner is True and b.tuner is False
            assert mgr.wait_ready(READY_S)
        finally:
            records = mgr.stop_all(timeout_s=10.0)
        armed = {r["name"]: r["tuner_armed"] for r in records}
        assert armed == {"r0": True, "r1": False}

    def test_canary_respawn_rearms(self):
        mgr = FleetManager(_argv, tuner_canary=True)
        try:
            mgr.spawn()
            mgr.spawn()
            assert mgr.wait_ready(READY_S)
            mgr.kill("r0")  # the canary dies...
            deadline = time.monotonic() + 10.0
            while mgr.get("r0").alive and time.monotonic() < deadline:
                time.sleep(0.05)
            (rep,) = mgr.respawn_dead()
            # ...and its replacement is the one that re-arms.
            assert rep.name == "r0" and rep.tuner is True
        finally:
            mgr.stop_all(timeout_s=10.0)

    def test_rollout_replaces_non_canary_one_at_a_time(self):
        mgr = FleetManager(_argv, tuner_canary=True)
        try:
            mgr.spawn()
            mgr.spawn()
            mgr.spawn()
            assert mgr.wait_ready(READY_S)
            rolled = mgr.rollout(ready_timeout_s=READY_S)
            assert rolled == ["r1", "r2"]  # canary r0 untouched
            assert mgr.get("r0").generation == 0
            assert mgr.get("r1").generation == 1
            assert mgr.get("r2").generation == 1
            # The drained pre-rollout replicas handed in records.
            assert {r["name"] for r in mgr.records} == {"r1", "r2"}
        finally:
            mgr.stop_all(timeout_s=10.0)
