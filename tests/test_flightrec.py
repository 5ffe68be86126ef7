"""Flight recorder: span ring, anomaly-triggered dumps, CLI end to end.

The contract (ISSUE 8 acceptance): a watchdog-fired anomaly produces a
flight-record file whose path appears in BOTH the anomaly trace event
and the bench record's ``anomalies`` summary — and the dump itself
carries the span ring, global metrics, and any registered telemetry
sources from the moment it fired.
"""

import json

import pytest

from distributed_sddmm_tpu.obs import (
    flightrec, metrics as obs_metrics, trace, watchdog,
)


@pytest.fixture(autouse=True)
def _clean(monkeypatch):
    monkeypatch.delenv("DSDDMM_TRACE", raising=False)
    monkeypatch.delenv("DSDDMM_FLIGHTREC", raising=False)
    monkeypatch.delenv("DSDDMM_WATCHDOG", raising=False)
    watchdog.disable()
    flightrec.disable()
    trace.disable()
    yield
    watchdog.disable()
    flightrec.disable()
    trace.disable()


class TestSpanRing:
    def test_bounded_rotation(self):
        ring = trace.arm_ring(4)
        for i in range(10):
            trace.event("tick", i=i)
        recs = ring.records()
        assert len(recs) == 4
        # Oldest rotated out; the count of everything ever seen remains.
        assert [r["attrs"]["i"] for r in recs] == [6, 7, 8, 9]
        assert ring.appended >= 10

    def test_memory_tracer_flows_without_file(self):
        assert not trace.enabled()
        ring = trace.arm_ring(16)
        assert trace.enabled()  # spans/events flow...
        assert trace.trace_path() is None  # ...but nothing hits disk
        with trace.span("work", x=1):
            pass
        types = [r["type"] for r in ring.records()]
        assert types == ["begin", "span"]
        trace.disarm_ring()
        assert not trace.enabled()

    def test_ring_taps_active_file_tracer(self, tmp_path):
        tr = trace.enable(tmp_path / "t.jsonl")
        ring = trace.arm_ring(16)
        trace.event("both")
        trace.disable()
        assert any(r.get("name") == "both" for r in ring.records())
        text = (tmp_path / "t.jsonl").read_text()
        assert '"both"' in text  # file tracer untouched by the ring

    def test_arm_is_idempotent(self):
        a = trace.arm_ring(8)
        b = trace.arm_ring(32)
        assert a is b and a.capacity == 8


class TestFlightRecorder:
    def _spike(self):
        wd = watchdog.enable("warn", min_samples=2, spike_factor=2.0,
                             min_abs_s=0.0)
        wd.observe("op", 0.01)
        wd.observe("op", 0.01)
        wd.observe("op", 5.0)  # spike
        return wd

    def test_anomaly_dumps_ring_and_stamps_path(self, tmp_path):
        fr = flightrec.enable(tmp_path)
        with trace.span("before", i=1):
            pass
        wd = self._spike()
        summary = wd.summary()
        paths = summary.get("snapshots")
        assert paths and len(paths) == 1
        # Stamped into the grouped record too (bench record shape).
        group = summary["anomalies"][0]
        assert group["first"]["snapshot_path"] == paths[0]
        rec = json.loads(open(paths[0]).read())
        assert rec["anomaly"]["kind"] == "step_time_spike"
        assert rec["run_id"] == fr.run_id
        assert any(r.get("name") == "before" for r in rec["ring"])
        assert "global" in rec["metrics"]
        # The anomaly trace event itself carries the path: it is in the
        # ring of a SECOND dump... simpler: the tracer ring now holds
        # the emitted anomaly event.
        anomaly_evs = [r for r in trace.ring().records()
                       if r.get("name") == "anomaly"]
        assert anomaly_evs
        assert anomaly_evs[0]["attrs"]["snapshot_path"] == paths[0]

    def test_dump_budget_bounds_files(self, tmp_path):
        flightrec.enable(tmp_path, max_dumps=2)
        wd = watchdog.enable("warn", min_samples=2, spike_factor=2.0,
                             min_abs_s=0.0)
        wd.observe("op", 0.01)
        wd.observe("op", 0.01)
        for _ in range(5):
            wd.observe("op", 5.0)
        files = list(flightrec.active().out_dir.glob("*.json"))
        assert len(files) == 2  # budget, not one per anomaly
        assert obs_metrics.GLOBAL.get("flightrec_dumps") >= 2

    def test_registered_source_lands_and_errors_contained(self, tmp_path):
        fr = flightrec.enable(tmp_path)
        fr.register_source("good", lambda: {"depth": 3})
        fr.register_source("bad", lambda: 1 / 0)
        self._spike()
        path = flightrec.active().paths[0]
        rec = json.loads(open(path).read())
        assert rec["sources"]["good"] == {"depth": 3}
        assert "ZeroDivisionError" in rec["sources"]["bad"]["error"]

    def test_profile_window_recorded(self, tmp_path, monkeypatch):
        from distributed_sddmm_tpu.obs import profiler

        calls = []
        monkeypatch.setattr(
            profiler, "capture_window",
            lambda logdir, duration_s, block: calls.append(
                (logdir, duration_s, block)) or True,
        )
        flightrec.enable(tmp_path, profile_window_s=0.1)
        self._spike()
        rec = json.loads(open(flightrec.active().paths[0]).read())
        assert rec["profile"]["started"] is True
        assert calls and calls[0][1] == 0.1 and calls[0][2] is False

    def test_env_spec_grammar(self, tmp_path):
        assert flightrec.parse_env_spec(None) == (False, None)
        assert flightrec.parse_env_spec("off") == (False, None)
        assert flightrec.parse_env_spec("1") == (True, None)
        on, root = flightrec.parse_env_spec(str(tmp_path))
        assert on and root == tmp_path

    def test_disabled_watchdog_path_unchanged(self):
        # No recorder armed: anomalies record exactly as before, no
        # snapshot_path anywhere.
        wd = self._spike()
        summary = wd.summary()
        assert "snapshots" not in summary
        assert "snapshot_path" not in summary["anomalies"][0]["first"]


class TestServeCLIEndToEnd:
    def test_bench_serve_anomaly_produces_linked_flight_record(
        self, tmp_path, capsys
    ):
        """`bench serve --watchdog --flightrec --admin-port 0` with one
        injected 0.5s straggler: the spike anomaly dumps a flight
        record whose path rides the bench record AND the anomaly trace
        event; the record carries admin_port."""
        from distributed_sddmm_tpu.bench import cli

        out_file = tmp_path / "serve.json"
        trace_file = tmp_path / "serve-trace.jsonl"
        # One delay fault at live-batch call 8: by then the per-batch
        # EWMA has its warmup baseline, so +0.5s is a guaranteed spike.
        faults = json.dumps([
            {"site": "execute:serveBatch", "kind": "delay", "at": [8],
             "param": 0.5},
        ])
        from distributed_sddmm_tpu.resilience import faults as res_faults

        try:
            rc = cli.main([
                "serve", "--app", "als", "--log-m", "6", "--edge-factor",
                "6", "--R", "8", "--duration", "2.0", "--rate", "30",
                "--max-batch", "4", "--train-steps", "1", "--oracle-every",
                "0", "--watchdog", "warn", "--flightrec",
                str(tmp_path / "fr"), "--admin-port", "0", "--trace",
                str(trace_file), "--faults", faults, "--no-runstore", "-o",
                str(out_file),
            ])
        finally:
            # --faults installs the plan process-wide; later tests in
            # this process must not inherit it.
            res_faults.clear()
        assert rc == 0
        record = json.loads(out_file.read_text().splitlines()[-1])
        assert record["admin_port"] > 0
        anomalies = record.get("anomalies") or {}
        spikes = [a for a in anomalies.get("anomalies", ())
                  if a["kind"] == "step_time_spike"]
        assert spikes, anomalies
        snap = spikes[0]["first"].get("snapshot_path")
        assert snap and json.loads(open(snap).read())["anomaly"]["kind"] \
            == "step_time_spike"
        assert snap in (anomalies.get("snapshots") or ())
        assert record["flightrec_dir"] in snap
        # The anomaly trace event carries the same path.
        events = [
            json.loads(line)
            for line in trace_file.read_text().splitlines()
            if '"anomaly"' in line
        ]
        stamped = [e for e in events
                   if e.get("type") == "event" and e.get("name") == "anomaly"
                   and e["attrs"].get("snapshot_path") == snap]
        assert stamped
