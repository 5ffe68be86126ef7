"""Pin scripts/aot_load_probe.py's verdict-staleness protocol.

A re-probe runs only when ``--check-stale`` says so; a wrong answer
either spends chip time re-answering a current verdict or lets a
stale one keep (mis)gating AOT modes. The matrix here mirrors the manual
verification the protocol shipped with."""

import importlib.util
import json
import pathlib

import pytest


@pytest.fixture(scope="module")
def probe():
    spec = importlib.util.spec_from_file_location(
        "aot_load_probe",
        pathlib.Path(__file__).resolve().parents[1]
        / "scripts" / "aot_load_probe.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _write(tmp_path, payload):
    f = tmp_path / "AOT_LOAD.json"
    f.write_text(json.dumps(payload))
    return f


def test_missing_file_needs_probe(probe, tmp_path):
    assert probe.check_stale(tmp_path / "AOT_LOAD.json") == 3


def test_corrupt_file_unlinked(probe, tmp_path):
    f = tmp_path / "AOT_LOAD.json"
    f.write_text("{not json")
    assert probe.check_stale(f) == 3
    assert not f.exists()


def test_current_complete_verdict_stands(probe, tmp_path):
    progs = {n: {"ok": True, "program_version": v}
             for n, v in probe.PROGRAM_VERSIONS.items()}
    f = _write(tmp_path, {"ok": True, "programs": progs})
    assert probe.check_stale(f) == 0
    assert f.exists()


def test_stale_sibling_pruned_valid_kept(probe, tmp_path):
    """A bumped program loses its verdict; the unchanged sibling keeps
    gating its own AOT modes while the probe re-answers."""
    names = sorted(probe.PROGRAM_VERSIONS)
    stale_name, kept_name = names[-1], names[0]
    progs = {
        kept_name: {"ok": True,
                    "program_version": probe.PROGRAM_VERSIONS[kept_name]},
        stale_name: {"ok": True,
                     "program_version":
                         probe.PROGRAM_VERSIONS[stale_name] + 1},
    }
    f = _write(tmp_path, {"ok": True, "programs": progs})
    assert probe.check_stale(f) == 3
    rep = json.loads(f.read_text())
    assert list(rep["programs"]) == [kept_name]
    assert rep["ok"] is False  # a program's verdict is now missing


def test_all_stale_unlinks(probe, tmp_path):
    progs = {n: {"ok": True, "program_version": v + 1}
             for n, v in probe.PROGRAM_VERSIONS.items()}
    f = _write(tmp_path, {"ok": True, "programs": progs})
    assert probe.check_stale(f) == 3
    assert not f.exists()


def test_phase_a_record_current_stands(probe, tmp_path):
    f = _write(tmp_path, {"ok": False, "stage": "phase-a",
                          "program_versions": dict(probe.PROGRAM_VERSIONS)})
    assert probe.check_stale(f) == 0


def test_phase_a_record_stale_unlinked(probe, tmp_path):
    old = {n: v - 1 for n, v in probe.PROGRAM_VERSIONS.items()}
    f = _write(tmp_path, {"ok": False, "stage": "phase-a",
                          "program_versions": old})
    assert probe.check_stale(f) == 3
    assert not f.exists()


def test_conclusive_error_classification(probe):
    """A deserialize-format version mismatch is deterministic for the
    (serializer, runtime build) pair — phase B records the "no"
    immediately instead of spending the 3-attempt exception budget; a
    generic transient failure stays retryable."""
    fmt = ("JaxRuntimeError: INVALID_ARGUMENT: "
           "PJRT_Executable_DeserializeAndLoad: cached executable is tpu "
           "format v269857241, this build is v9 — clear the JAX persistent "
           "cache")
    assert probe.conclusive_error(fmt)
    assert not probe.conclusive_error(
        "JaxRuntimeError: UNAVAILABLE: connection reset by peer")
    assert not probe.conclusive_error(
        "TimeoutError: backend init hung")
    # A generic deserialize failure (e.g. a truncated payload) is NOT
    # conclusive — only the version-mismatch phrase is.
    assert not probe.conclusive_error(
        "JaxRuntimeError: INVALID_ARGUMENT: "
        "PJRT_Executable_DeserializeAndLoad: failed to parse serialized "
        "executable: wire format error")


def test_merge_write_flake_cannot_clobber_settled(probe, tmp_path):
    """Review-pinned scenario: a recorded ok verdict must survive a
    sibling re-probe in which its own program hits a transient flake."""
    names = sorted(probe.PROGRAM_VERSIONS)
    a, b = names[0], names[1 % len(names)]
    f = _write(tmp_path, {"ok": False, "programs": {
        a: {"ok": True, "program_version": probe.PROGRAM_VERSIONS[a]}}})
    report = {"phase": "b", "programs": {
        a: {"ok": False, "program_version": probe.PROGRAM_VERSIONS[a],
            "error": "JaxRuntimeError: UNAVAILABLE: connection reset"},
        b: {"ok": True, "program_version": probe.PROGRAM_VERSIONS[b]}}}
    merged = probe._merge_write(f, report, report["programs"])
    assert merged["programs"][a]["ok"] is True  # prior settled kept
    assert merged["programs"][b]["ok"] is True
    assert merged["ok"] is (set(names) <= {a, b})
    on_disk = json.loads(f.read_text())
    assert on_disk["programs"][a]["ok"] is True


def test_merge_write_fresh_settled_wins(probe, tmp_path):
    names = sorted(probe.PROGRAM_VERSIONS)
    a = names[0]
    f = _write(tmp_path, {"ok": False, "programs": {
        a: {"ok": True, "program_version": probe.PROGRAM_VERSIONS[a]}}})
    fmt_err = ("PJRT_Executable_DeserializeAndLoad: cached executable is "
               "tpu format v1, this build is v9")
    report = {"programs": {
        a: {"ok": False, "program_version": probe.PROGRAM_VERSIONS[a],
            "error": fmt_err}}}
    merged = probe._merge_write(f, report, report["programs"])
    # conclusive error = settled: the fresh "no" replaces the stale "yes"
    assert merged["programs"][a]["ok"] is False
    assert merged["ok"] is False


def test_merge_write_drops_chain_stale_prior(probe, tmp_path):
    names = sorted(probe.PROGRAM_VERSIONS)
    a = names[0]
    f = _write(tmp_path, {"ok": True, "programs": {
        a: {"ok": True,
            "program_version": probe.PROGRAM_VERSIONS[a] + 1}}})
    report = {"programs": {}}
    merged = probe._merge_write(f, report, {})
    assert merged["programs"] == {}
    assert merged["ok"] is False


def test_probe_key_json_roundtrip_stable(probe):
    """cache_is_fresh compares against the JSON round-trip of PROBE_KEY;
    tuples would never equal their round-tripped lists."""
    rt = json.loads(json.dumps(list(probe.PROBE_KEY)))
    assert rt == list(probe.PROBE_KEY)
