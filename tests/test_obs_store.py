"""Run-store contract: round-trip, index derivation, baseline queries,
the harness join, and the historical backfill.

The store is the substrate the regression gate stands on, so the tests
pin the properties the gate assumes: documents round-trip exactly, the
index is derived state (corrupt → rebuilt, never trusted), ``matching``
selects only same-key runs and excludes the run under judgment, and the
joined document actually carries the trace aggregate + manifest the
compare columns come from.
"""

import json

import pytest

from distributed_sddmm_tpu.obs.store import (
    RunStore, backfill_historical, build_run_doc,
)

ROOT = __import__("pathlib").Path(__file__).resolve().parents[1]


def _doc(run_id, key="k1", backend="cpu", t=1.0, extra=None):
    d = {
        "run_id": run_id, "key": key, "backend": backend,
        "code_hash": "deadbeef",
        "record": {
            "algorithm": "15d_fusion2", "app": "vanilla", "R": 64, "c": 2,
            "fused": True, "elapsed": t, "overall_throughput": 1.0 / t,
            "metrics": {
                "fusedSpMM": {"calls": 5, "kernel_s": t, "overhead_s": 0.0,
                              "retries": 0, "comm_words": 100.0,
                              "comm_words_extra": 0.0, "flops": 1e6},
            },
        },
    }
    if extra:
        d.update(extra)
    return d


class TestRoundTrip:
    def test_put_get_roundtrip(self, tmp_path):
        store = RunStore(tmp_path)
        doc = _doc("run-a")
        store.put(doc)
        got = store.get("run-a")
        assert got["record"] == doc["record"]
        assert got["key"] == "k1"
        assert got["schema"] == 1
        assert got["created_epoch"] > 0

    def test_get_missing_returns_none(self, tmp_path):
        assert RunStore(tmp_path).get("nope") is None

    def test_reput_overwrites_not_duplicates(self, tmp_path):
        store = RunStore(tmp_path)
        store.put(_doc("run-a", t=1.0))
        store.put(_doc("run-a", t=2.0))
        assert len(store.index()) == 1
        assert store.get("run-a")["record"]["elapsed"] == 2.0

    def test_unsafe_run_id_becomes_safe_filename(self, tmp_path):
        store = RunStore(tmp_path)
        store.put(_doc("../evil/run:1"))
        files = list((tmp_path / "runs").glob("*.json"))
        assert len(files) == 1
        assert not files[0].name.startswith(".")
        assert "/" not in files[0].stem
        # resolvable under its original (unsafe) id
        assert store.get("../evil/run:1")["run_id"] == "../evil/run:1"


class TestIndex:
    def test_index_rows_carry_summary_fields(self, tmp_path):
        store = RunStore(tmp_path)
        store.put(_doc("run-a", t=0.5))
        (row,) = store.index()
        assert row["algorithm"] == "15d_fusion2"
        assert row["overall_throughput"] == 2.0
        assert row["key"] == "k1"
        assert row["backend"] == "cpu"

    def test_corrupt_index_rebuilt_from_docs(self, tmp_path):
        store = RunStore(tmp_path)
        store.put(_doc("run-a"))
        store.put(_doc("run-b"))
        store.index_path.write_text("{ not json")
        rows = store.index()
        assert {r["run_id"] for r in rows} == {"run-a", "run-b"}
        # and the rebuilt file is valid again
        assert len(json.loads(store.index_path.read_text())) == 2

    def test_rebuild_skips_torn_doc(self, tmp_path):
        store = RunStore(tmp_path)
        store.put(_doc("run-a"))
        (store.runs_dir / "torn.json").write_text('{"run_id": "x", ')
        rows = store.rebuild_index()
        assert [r["run_id"] for r in rows] == ["run-a"]


class TestQueries:
    def _seed(self, tmp_path):
        store = RunStore(tmp_path)
        for i in range(4):
            store.put(_doc(f"k1-{i}", key="k1"))
        store.put(_doc("k2-0", key="k2"))
        store.put(_doc("other-backend", key="k1", backend="tpu"))
        return store

    def test_history_filters_key_and_limit(self, tmp_path):
        store = self._seed(tmp_path)
        rows = store.history(key="k1")
        assert len(rows) == 5  # 4 cpu + 1 tpu
        rows = store.history(key="k1", backend="cpu", limit=2)
        assert [r["run_id"] for r in rows] == ["k1-2", "k1-3"]

    def test_matching_same_key_same_backend_excludes_self(self, tmp_path):
        store = self._seed(tmp_path)
        doc = store.get("k1-3")
        base = store.matching(doc, limit=10)
        ids = {d["run_id"] for d in base}
        assert ids == {"k1-0", "k1-1", "k1-2"}  # no self, no k2, no tpu

    def test_matching_excludes_other_configurations(self, tmp_path):
        """Same fingerprint key, different config (a heatmap sweep runs
        every algorithm on one problem) — those runs must not pool into
        the gate's baseline."""
        store = RunStore(tmp_path)
        store.put(_doc("same-cfg"))
        other_alg = _doc("other-alg")
        other_alg["record"]["algorithm"] = "25d_dense_replicate"
        store.put(other_alg)
        unfused = _doc("unfused")
        unfused["record"]["fused"] = False
        store.put(unfused)
        other_app = _doc("other-app")
        other_app["record"]["app"] = "als"
        store.put(other_app)
        # A codegen-variant run must not pool into the generic kernel's
        # baseline (kernel_variant is a PR-9 config axis) — and its
        # index row must carry the variant id.
        varianted = _doc("banked")
        varianted["record"]["kernel_variant"] = "v1.rb8.rm"
        store.put(varianted)
        assert next(
            r for r in store.index() if r["run_id"] == "banked"
        )["kernel_variant"] == "v1.rb8.rm"
        store.put(_doc("judged"))
        base = store.matching(store.get("judged"), limit=10)
        assert {d["run_id"] for d in base} == {"same-cfg"}

    def test_resolve_specs(self, tmp_path):
        store = self._seed(tmp_path)
        assert store.resolve("k2-0")["run_id"] == "k2-0"
        assert store.resolve("latest")["run_id"] == "other-backend"
        assert store.resolve("latest~1")["run_id"] == "k2-0"
        assert store.resolve("other-")["run_id"] == "other-backend"
        with pytest.raises(ValueError, match="ambiguous"):
            store.resolve("k1-")  # 4 runs share this prefix
        assert store.resolve("latest~99") is None
        assert store.resolve("zzz") is None

    def test_history_limit_zero_is_empty(self, tmp_path):
        store = self._seed(tmp_path)
        assert store.history(limit=0) == []


class TestJoin:
    def test_build_run_doc_joins_trace_and_manifest(self, tmp_path):
        """A record pointing at a real trace gains phases + manifest."""
        trace_path = tmp_path / "r1.jsonl"
        trace_path.write_text(
            json.dumps({"type": "begin", "schema": 1, "run_id": "r1",
                        "t0_epoch": 0.0}) + "\n"
            + json.dumps({"type": "span", "name": "fusedSpMM", "id": 1,
                          "tid": 1, "t0": 0.0, "t1": 0.5, "dur_s": 0.5,
                          "attrs": {"kernel_s": 0.5, "comm_words": 10.0,
                                    "flops": 100.0}}) + "\n"
        )
        (tmp_path / "r1.manifest.json").write_text(json.dumps({
            "schema": 1, "run_id": "r1", "backend": "cpu",
            "device_count": 8, "git_rev": "abc", "env": {},
        }))
        record = {
            "run_id": "r1", "trace_path": str(trace_path),
            "algorithm": "15d_fusion2", "app": "vanilla", "R": 64, "c": 2,
            "alg_info": {"m": 64, "n": 64, "nnz": 512, "p": 8},
            "metrics": {},
        }
        doc = build_run_doc(record)
        assert doc["phases"]["fusedSpMM"]["calls"] == 1
        assert doc["manifest"]["backend"] == "cpu"
        assert doc["backend"] == "cpu"  # manifest backend wins
        assert doc["key"]  # fingerprinted
        assert doc["fingerprint"]["M"] == 64

    def test_same_problem_same_key_different_problem_different_key(self):
        rec = {
            "run_id": "a", "algorithm": "x", "app": "vanilla", "R": 64,
            "alg_info": {"m": 64, "n": 64, "nnz": 512, "p": 8},
        }
        k1 = build_run_doc(rec)["key"]
        k2 = build_run_doc(dict(rec, run_id="b"))["key"]
        k3 = build_run_doc(dict(rec, run_id="c", R=128))["key"]
        assert k1 == k2 != k3

    def test_ingest_record_persists(self, tmp_path):
        store = RunStore(tmp_path)
        doc = store.ingest_record({
            "run_id": "r2", "algorithm": "15d_fusion2", "app": "vanilla",
            "R": 64, "alg_info": {"m": 64, "n": 64, "nnz": 512, "p": 8},
            "metrics": {},
        })
        assert store.get("r2")["key"] == doc["key"]

    def test_sweep_records_sharing_run_id_get_distinct_docs(self, tmp_path):
        """A traced sweep stamps one tracer run_id into every record;
        each must survive as its own store doc, not overwrite."""
        store = RunStore(tmp_path)
        rec = {
            "run_id": "sweep-1", "algorithm": "15d_fusion2",
            "app": "vanilla", "R": 64,
            "alg_info": {"m": 64, "n": 64, "nnz": 512, "p": 8},
            "metrics": {},
        }
        store.ingest_record(dict(rec))
        store.ingest_record(dict(rec, algorithm="15d_fusion1"))
        store.ingest_record(dict(rec, algorithm="15d_sparse"))
        ids = [r["run_id"] for r in store.index()]
        assert sorted(ids) == ["sweep-1", "sweep-1-2", "sweep-1-3"]
        assert store.get("sweep-1-3")["record"]["algorithm"] == "15d_sparse"

    def test_multi_bench_trace_phases_not_attached(self, tmp_path):
        """A trace holding several bench spans (a sweep's shared file)
        must not donate its whole-file aggregate to one record."""
        begin = json.dumps({"type": "begin", "schema": 1, "run_id": "r",
                            "t0_epoch": 0.0})
        span = {"type": "span", "name": "bench", "id": 1, "tid": 1,
                "t0": 0.0, "t1": 1.0, "dur_s": 1.0, "attrs": {}}
        one = tmp_path / "one.jsonl"
        one.write_text(begin + "\n" + json.dumps(span) + "\n")
        two = tmp_path / "two.jsonl"
        two.write_text(begin + "\n" + json.dumps(span) + "\n"
                       + json.dumps(dict(span, id=2)) + "\n")
        rec = {"run_id": "r", "algorithm": "x", "app": "vanilla", "R": 8,
               "alg_info": {"m": 8, "n": 8, "nnz": 8, "p": 1},
               "metrics": {}}
        assert "phases" in build_run_doc(dict(rec, trace_path=str(one)))
        assert "phases" not in build_run_doc(dict(rec, trace_path=str(two)))


class TestCliAutoWrite:
    """The harness auto-write path end-to-end through the bench CLI."""

    def _reset_module_state(self, monkeypatch):
        from distributed_sddmm_tpu.obs import store as obs_store

        monkeypatch.setattr(obs_store, "_active", None)
        monkeypatch.setattr(obs_store, "_env_checked", False)

    def test_env_spec_persists_bench_record(self, tmp_path, monkeypatch,
                                            capsys):
        from distributed_sddmm_tpu.bench import cli

        root = tmp_path / "envstore"
        monkeypatch.setenv("DSDDMM_RUNSTORE", str(root))
        self._reset_module_state(monkeypatch)
        assert cli.main(["er", "5", "4", "15d_fusion2", "8", "1",
                         "--trials", "1", "--kernel", "xla"]) == 0
        capsys.readouterr()
        docs = list((root / "runs").glob("*.json"))
        assert len(docs) == 1
        doc = json.loads(docs[0].read_text())
        assert doc["record"]["algorithm"] == "15d_fusion2"
        assert doc["key"]

    def test_no_runstore_flag_beats_env(self, tmp_path, monkeypatch,
                                        capsys):
        """The explicit opt-out wins even when DSDDMM_RUNSTORE names a
        store — the flag must disable, not merely skip enabling."""
        from distributed_sddmm_tpu.bench import cli

        root = tmp_path / "envstore"
        monkeypatch.setenv("DSDDMM_RUNSTORE", str(root))
        self._reset_module_state(monkeypatch)
        assert cli.main(["er", "5", "4", "15d_fusion2", "8", "1",
                         "--trials", "1", "--kernel", "xla",
                         "--no-runstore"]) == 0
        capsys.readouterr()
        assert not root.exists()


class TestSuppression:
    def test_suppressed_hides_active_store(self, tmp_path, monkeypatch):
        """Autotune candidate trials run through benchmark_algorithm;
        suppressed() must make store.active() blind to them (nested and
        restoring)."""
        from distributed_sddmm_tpu.obs import store as obs_store

        monkeypatch.setattr(obs_store, "_active", RunStore(tmp_path))
        monkeypatch.setattr(obs_store, "_env_checked", True)
        monkeypatch.setattr(obs_store, "_suppress_count", 0)
        assert obs_store.active() is not None
        with obs_store.suppressed():
            assert obs_store.active() is None
            with obs_store.suppressed():
                assert obs_store.active() is None
            assert obs_store.active() is None
        assert obs_store.active() is not None


def _write_rounds(root):
    """Round records in the committed files' shape (bench.py's parsed
    headline; the multi-chip dry-run verdict)."""
    head = ("fused SDDMM+SpMM GFLOP/s/chip (R-mat 2^16, nnz/row=32, "
            "R=128, pallas-bf16 kernel, 1 tpu chip(s))")
    for n, value in ((1, 0.0), (5, 168.729)):
        (root / f"BENCH_r0{n}.json").write_text(json.dumps({
            "rc": 0, "parsed": {
                "metric": head, "value": value, "unit": "GFLOP/s/chip",
                "vs_baseline": value / 6.47, "backend": "tpu",
                "code_hash": "02ab040eb9"}}))
    (root / "MULTICHIP_r05.json").write_text(json.dumps(
        {"n_devices": 8, "rc": 0, "ok": True, "skipped": False}))
    return root


class TestBackfill:
    def test_backfill_ingests_committed_rounds(self, tmp_path):
        """BENCH_r0*/MULTICHIP_r0* records become store history — the
        round trajectory the dashboard opens with."""
        store = RunStore(tmp_path / "s")
        docs = backfill_historical(store, root=_write_rounds(tmp_path))
        ids = {d["run_id"] for d in docs}
        assert ids == {"backfill-bench_r01", "backfill-bench_r05",
                       "backfill-multichip_r05"}
        # The r05 headline parsed into a fingerprinted, valued doc.
        r5 = store.get("backfill-bench_r05")
        assert r5["record"]["overall_throughput"] == pytest.approx(168.729)
        assert r5["backend"] == "tpu"
        assert r5["record"]["alg_info"]["m"] == 1 << 16
        # Historical code hash, never today's: backfilled numbers must
        # not alias a live run's baseline key.
        assert r5["code_hash"] != "unset"
        from distributed_sddmm_tpu.autotune.fingerprint import code_hash

        assert r5["code_hash"] != code_hash()

    def test_backfill_idempotent(self, tmp_path):
        store = RunStore(tmp_path / "s")
        root = _write_rounds(tmp_path)
        n1 = len(backfill_historical(store, root=root))
        n2 = len(backfill_historical(store, root=root))
        assert n1 == n2 == len(store.index())

    def test_backfill_empty_root_is_noop(self, tmp_path):
        store = RunStore(tmp_path / "s")
        assert backfill_historical(store, root=tmp_path / "empty") == []

    def test_backfill_sorts_before_live_runs(self, tmp_path):
        """Historical rounds are history: `latest` must keep resolving
        to the live run even when the backfill ran a second ago."""
        store = RunStore(tmp_path / "s")
        store.put(_doc("live-run"))  # real created_epoch (now)
        backfill_historical(store, root=_write_rounds(tmp_path))
        assert store.resolve("latest")["run_id"] == "live-run"
        assert store.index()[0]["run_id"].startswith("backfill-")
