"""Probe: can AOT-compiled TPU executables built without a chip LOAD on one?

The Mosaic/TPU compiler runs on a host without a chip against a
`jax.experimental.topologies` AOT target (PREFLIGHT.json: 22 configs in
~4s each). This probe tests the missing link: serialize such an
executable (`jax.experimental.serialize_executable`) and
deserialize_and_load it onto the real device, re-homed via
``execution_devices``. If the answer is yes, sweep compiles can move off
the chip's clock.

Two phases, each its own subprocess (the AOT phase runs with
JAX_PLATFORMS=cpu so it never claims the chip):

  A (offline): AOT-compile a Pallas fused-tile chain + an XLA matmul chain
     for one v5e topology device; serialize both + their arg pytrees.
  B (needs the chip): load both onto the real chip, run, compare
     numerics against the interpreter oracle, time load vs on-device
     compile of the same program.

Usage: python scripts/aot_load_probe.py [--phase a|b|both] [-o AOT_LOAD.json]
Phase B exits 2 (retryable) when the backend is unreachable.
"""

from __future__ import annotations

import argparse
import json
import os
import pathlib
import pickle
import subprocess
import sys
import time

REPO = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))

CACHE = REPO / "artifacts" / "aot_cache"
TOPOLOGY = "v5e:2x4"
# Small on purpose: the probe answers "does a re-homed executable LOAD and
# produce correct numerics", not a perf question, and phase A replays the
# Pallas chain in the interpreter for the oracle fingerprints.
LOG_M, NPR, R, TRIALS = 10, 8, 128, 3
# bf16 TPU kernel vs f32 interpreter oracle: bf16 rounding bounds the
# relative fingerprint error; f32-vs-f32 matmul differs only by
# accumulation order.
RTOL = {"pallas_fused": 2e-2, "xla_matmul": 1e-3}
# Per-program chain versions live in aot_gate (the shared gate-policy
# module) so the gates and this probe can never disagree about which
# verdicts are current. File import: the package __init__ would pull jax
# into the light --check-stale path the queue runs every cycle.
def _load_aot_gate():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "_aot_gate_file",
        str(REPO / "distributed_sddmm_tpu" / "bench" / "aot_gate.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


PROGRAM_VERSIONS = _load_aot_gate().PROGRAM_VERSIONS
# Identity of the cached phase-A outputs; any program change re-builds.
# JSON-normalized (lists, not tuples): cache_is_fresh compares against the
# json round-trip of this value, and ["a", 1] != ("a", 1) in Python —
# tuples here would make the cache permanently "stale".
PROBE_VERSION = max(PROGRAM_VERSIONS.values())
PROBE_KEY = [TOPOLOGY, LOG_M, NPR, R, TRIALS,
             [[n, v] for n, v in sorted(PROGRAM_VERSIONS.items())]]


def check_stale(out_path: pathlib.Path) -> int:
    """Decide whether the recorded verdict still answers the current
    probe programs. Exit 0 = current and complete (no re-probe needed);
    exit 3 = the probe should (re-)run. Stale program entries are pruned
    in place so still-valid siblings keep gating their own AOT modes."""
    if not out_path.exists():
        return 3
    try:
        rep = json.loads(out_path.read_text())
    except (OSError, json.JSONDecodeError):
        out_path.unlink(missing_ok=True)
        return 3
    if rep.get("stage") == "phase-a":
        # Local deterministic failure record: stands only while EVERY
        # program chain is unchanged (a scalar max() would miss a bump
        # that doesn't raise the max).
        if rep.get("program_versions") == PROGRAM_VERSIONS:
            return 0
        out_path.unlink()
        return 3
    progs = rep.get("programs") or {}
    # Entries written before per-program versioning carry no
    # program_version; those chains were version 1, so default to 1 —
    # a still-chain-valid verdict must survive a sibling's bump.
    pruned = {n: e for n, e in progs.items()
              if e.get("program_version", 1) == PROGRAM_VERSIONS.get(n)}
    if set(pruned) == set(PROGRAM_VERSIONS):
        return 0
    if not pruned:
        out_path.unlink()
        return 3
    if pruned != progs:
        rep["programs"] = pruned
        rep["ok"] = False  # a program's verdict is now missing
        out_path.write_text(json.dumps(rep, indent=1))
        print(f"[aot-probe] pruned stale program verdicts; kept "
              f"{sorted(pruned)}", file=sys.stderr)
    return 3


def cache_is_fresh() -> bool:
    try:
        meta = json.loads((CACHE / "meta.json").read_text())
    except (OSError, json.JSONDecodeError):
        return False
    if meta.get("probe_key") != list(PROBE_KEY):
        return False
    names = [f"{k}_{n}.pkl" for k in ("pallas_fused", "xla_matmul")
             for n in (1, 1 + TRIALS)]
    return all((CACHE / f).exists() for f in names)


def build_programs():
    """The two chained-trial programs the sweep would time, plus concrete
    host inputs and the interpreter-oracle fingerprint."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    from functools import partial

    from distributed_sddmm_tpu.ops.blocked import CHUNK, build_blocked
    from distributed_sddmm_tpu.ops.pallas_kernels import BlockedTile, PallasKernel
    from distributed_sddmm_tpu.utils.coo import HostCOO

    S = HostCOO.rmat(log_m=LOG_M, edge_factor=NPR, seed=0)
    S = S.with_values(np.random.default_rng(1).standard_normal(S.nnz))
    rng = np.random.default_rng(0)
    A_h = rng.standard_normal((S.M, R)).astype(np.float32)
    B_h = rng.standard_normal((S.N, R)).astype(np.float32)
    meta = build_blocked(1, np.zeros(S.nnz, np.int64), S.rows, S.cols,
                         S.M, S.N, block_rows=512, block_cols=512, group=4)
    vals_np = np.zeros(meta.n_chunks * CHUNK, np.float32)
    vals_np[meta.host_to_chunk] = S.vals

    def make_chain(kern_kwargs):
        kern = PallasKernel(**{"precision": "bf16", **kern_kwargs})

        def step(state):
            # acc accumulates the RAW kernel output so the fingerprint is
            # fully sensitive to it (the 1e-12-scaled feedback into Bs
            # exists only to chain the steps data-dependently; on its own
            # it would let a garbage kernel still "match" sum(B)).
            acc, Bs, lr, lc, m, cv, a = state
            blk = BlockedTile(lr=lr, lc=lc, meta=m, bm=meta.bm, bn=meta.bn,
                              gr_blocks=meta.gr_blocks,
                              gc_blocks=meta.gc_blocks, group=meta.group)
            o, _mid = kern.fused_tile(blk, cv, a, Bs)
            return (acc + o[: S.N], Bs + o[: S.N] * 1e-12, lr, lc, m, cv, a)

        def chain_n(n):
            # Trip count closed over (not static_argnums): the serialized
            # executable then has a plain array-only calling convention.
            @jax.jit
            def chain(state):
                return jax.lax.fori_loop(0, n, lambda _, s: step(s), state)
            return chain

        return chain_n

    def make_xla_chain():
        def chain_n(n):
            @jax.jit
            def chain(state):
                def body(_, s):
                    x, w = s
                    # HIGHEST keeps the TPU matmul in f32 passes: the CPU
                    # oracle is f32, and the default TPU precision (bf16
                    # passes) can exceed the 1e-3 fingerprint rtol — a
                    # numerics "mismatch" that would conclusively (and
                    # wrongly) record ok:false and foreclose AOT mode.
                    y = jnp.matmul(x, w, precision=jax.lax.Precision.HIGHEST)
                    return (jnp.tanh(y), w)
                return jax.lax.fori_loop(0, n, body, state)
            return chain
        return chain_n

    state = (np.zeros_like(B_h), B_h, np.asarray(meta.lr[0]),
             np.asarray(meta.lc[0]), np.asarray(meta.meta[0]), vals_np, A_h)
    xla_state = (A_h[:1024, :], A_h[: R, : R])
    return make_chain, make_xla_chain, state, xla_state


def phase_a() -> None:
    """AOT-compile + serialize against one topology device (offline)."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    from jax.experimental import topologies
    from jax.experimental import serialize_executable as se

    make_chain, make_xla_chain, state, xla_state = build_programs()
    topo = topologies.get_topology_desc(platform="tpu", topology_name=TOPOLOGY)
    dev = topo.devices[0]
    sharding = jax.sharding.SingleDeviceSharding(dev)

    def sds_of(x):
        import numpy as np
        x = np.asarray(x)
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)

    CACHE.mkdir(parents=True, exist_ok=True)
    records = {"probe_key": list(PROBE_KEY)}
    for name, chain_n, oracle_chain_n, st in (
        ("pallas_fused", make_chain({"interpret": False}),
         make_chain({"interpret": True, "precision": "f32"}), state),
        ("xla_matmul", make_xla_chain(), make_xla_chain(), xla_state),
    ):
        for n in (1, 1 + TRIALS):
            t0 = time.monotonic()
            compiled = chain_n(n).lower(
                tuple(sds_of(x) for x in st)).compile()
            payload = se.serialize(compiled)
            (CACHE / f"{name}_{n}.pkl").write_bytes(pickle.dumps(payload))
            # Ground-truth fingerprint from the interpreter/CPU execution
            # of the same chain — phase B must reproduce it or the load
            # does not count as working.
            import numpy as np
            ref = oracle_chain_n(n)(tuple(np.asarray(x) for x in st))
            records[f"{name}_{n}"] = {
                "compile_s": round(time.monotonic() - t0, 2),
                "bytes": (CACHE / f"{name}_{n}.pkl").stat().st_size,
                "oracle_fp": float(np.asarray(ref[0], np.float64).sum()),
            }
    (CACHE / "meta.json").write_text(json.dumps(records, indent=1))
    # Fresh programs get a fresh exception budget in phase B.
    (CACHE / "phase_b_attempts").unlink(missing_ok=True)
    print(json.dumps({"phase": "a", "ok": True, **records}))


def conclusive_error(msg: str) -> bool:
    """Exception text that proves re-homing can NEVER work here, as opposed
    to a transient failure. A deserialize-format version mismatch ("cached
    executable is <format> vX, this build is vY") is a property of the
    (serializer, runtime build) pair: an executable serialized by this
    libtpu can never load on that runtime, so the "no" is recorded at once
    instead of spending the exception retry budget. The match is the
    specific version-mismatch phrase — a generic deserialize failure (e.g.
    a truncated payload) stays retryable."""
    return ("PJRT_Executable_DeserializeAndLoad" in msg
            and " format v" in msg and "this build is" in msg)


def _settled(entry: dict) -> bool:
    """An entry that answers its program's question for good: a success,
    a numerics verdict, or a conclusive (deterministic) error. Retryable
    transient failures are not settled."""
    return "error" not in entry or conclusive_error(entry["error"])


def _merge_write(out_path: pathlib.Path, report: dict,
                 new_programs: dict) -> dict:
    """Write ``new_programs`` over the still-chain-current entries already
    on disk. A transient outcome (sibling flake, retry pass) must never
    clobber a settled recorded verdict — the same guard PREFLIGHT.json
    applies to its ok records. Returns the merged report as written."""
    try:
        prior = json.loads(out_path.read_text()).get("programs") or {}
    except (OSError, json.JSONDecodeError, AttributeError):
        prior = {}
    progs = {}
    for n in set(prior) | set(new_programs):
        pe, ne = prior.get(n), new_programs.get(n)
        prior_settled = (
            pe is not None
            and pe.get("program_version", 1) == PROGRAM_VERSIONS.get(n)
            and _settled(pe))
        if ne is not None and _settled(ne):
            progs[n] = ne  # a fresh settled verdict always wins
        elif prior_settled:
            progs[n] = pe
        elif ne is not None:
            progs[n] = ne  # both unsettled: record the freshest attempt
        # prior chain-stale entries drop here (check_stale would prune)
    merged = dict(report, programs=progs)
    merged["ok"] = (set(progs) >= set(PROGRAM_VERSIONS)
                    and all(p.get("ok") for p in progs.values()))
    out_path.write_text(json.dumps(merged, indent=1))
    return merged


def phase_b() -> int:
    """Load the serialized executables onto the real chip.

    Returns 0 (every program's answer recorded, good or bad) or 2
    (retryable: backend unreachable, or some program hit a transient
    exception with retry budget left — settled sibling verdicts ARE
    merge-recorded before returning, so their gates stop waiting)."""
    import numpy as np
    import jax

    from jax.experimental import serialize_executable as se

    try:
        dev = jax.devices()[0]
        if dev.platform == "cpu":
            print("[aot-probe] no TPU backend (cpu only) — retry later",
                  file=sys.stderr)
            return 2
    except Exception as e:  # noqa: BLE001 — backend init is the flaky part
        print(f"[aot-probe] backend init failed (retryable): {e}",
              file=sys.stderr)
        return 2

    meta = json.loads((CACHE / "meta.json").read_text())
    report = {"phase": "b", "probe_version": PROBE_VERSION,
              "platform": dev.platform,
              "device": str(dev), "n_devices": jax.device_count(),
              "programs": {}}
    make_chain, make_xla_chain, state, xla_state = build_programs()

    for name, st in (("pallas_fused", state), ("xla_matmul", xla_state)):
        entry = {"program_version": PROGRAM_VERSIONS[name]}
        try:
            dev_state = tuple(jax.device_put(np.asarray(x), dev) for x in st)
            fp_ok = []
            for n in (1, 1 + TRIALS):
                payload = pickle.loads((CACHE / f"{name}_{n}.pkl").read_bytes())
                serialized, in_tree, out_tree = payload
                t0 = time.monotonic()
                loaded = se.deserialize_and_load(
                    serialized, in_tree, out_tree, backend=dev.client,
                    execution_devices=[dev])
                entry[f"load_s_n{n}"] = round(time.monotonic() - t0, 3)
                t0 = time.monotonic()
                out = loaded(dev_state)
                # Host fetch: returns only once the program has run.
                fp = float(np.asarray(out[0], np.float64).sum())
                entry[f"first_run_s_n{n}"] = round(time.monotonic() - t0, 3)
                oracle = meta[f"{name}_{n}"]["oracle_fp"]
                entry[f"run_fp_n{n}"] = fp
                entry[f"oracle_fp_n{n}"] = oracle
                fp_ok.append(
                    abs(fp - oracle) <= RTOL[name] * max(abs(oracle), 1.0))
            entry["numerics_ok"] = all(fp_ok)
            entry["ok"] = entry["numerics_ok"]
        except Exception as e:  # noqa: BLE001 — probe records any failure mode
            entry["ok"] = False
            entry["error"] = f"{type(e).__name__}: {e}"[:500]
        report["programs"][name] = entry

    report["ok"] = all(p.get("ok") for p in report["programs"].values())
    out_path = pathlib.Path(
        os.environ.get("AOT_LOAD_OUT", str(REPO / "AOT_LOAD.json")))
    # An exception mid-phase is ambiguous: a genuine re-homing
    # incompatibility OR a transient failure after the init check. Don't
    # let one transient failure permanently foreclose AOT mode — only record a "no" once
    # exceptions have repeated enough to be deterministic (numerics
    # mismatches and conclusive_error texts, by contrast, are conclusive
    # immediately).
    unsettled = {n: e for n, e in report["programs"].items()
                 if not _settled(e)}
    if unsettled:
        attempts_file = CACHE / "phase_b_attempts"
        try:
            attempts = int(attempts_file.read_text()) + 1
        except (OSError, ValueError):
            attempts = 1
        attempts_file.write_text(str(attempts))
        if attempts < 3:
            print(json.dumps(report, indent=1))
            # Programs that ARE answered must not wait on a flaky
            # sibling's retry budget: merge-record them now so their
            # gates stop re-probing; check_stale keeps the queue
            # retrying for whatever is still missing.
            answered = {n: e for n, e in report["programs"].items()
                        if _settled(e)}
            if answered:
                merged = _merge_write(out_path, report, answered)
                if all(_settled(e) for e in merged["programs"].values()) \
                        and set(merged["programs"]) >= set(PROGRAM_VERSIONS):
                    # Prior settled verdicts fill the gap this run's
                    # flakes left: everything is answered after all.
                    print("[aot-probe] all programs settled after merge",
                          file=sys.stderr)
                    return 0
                print(f"[aot-probe] recorded {sorted(answered)}; sibling "
                      f"exception retryable (attempt {attempts}/3)",
                      file=sys.stderr)
            else:
                print(f"[aot-probe] inconclusive (exception, attempt "
                      f"{attempts}/3) — not recording; will retry next "
                      "cycle", file=sys.stderr)
            return 2
        report["inconclusive_after_attempts"] = attempts

    print(json.dumps(report, indent=1))
    _merge_write(out_path, report, report["programs"])
    return 0


def _run_phase(phase: str, env: dict, timeout_s: float) -> int | None:
    """Run one phase in its own session; kill the whole process group on
    timeout (children otherwise outlive the parent and hold the device). Returns the rc, or None on timeout."""
    import signal

    proc = subprocess.Popen(
        [sys.executable, __file__, "--phase", phase], env=env,
        start_new_session=True)
    try:
        return proc.wait(timeout=timeout_s)
    except subprocess.TimeoutExpired:
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except (ProcessLookupError, PermissionError):
            proc.kill()
        try:
            proc.wait(timeout=10)
        except subprocess.TimeoutExpired:
            pass
        return None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--phase", choices=("a", "b", "both"), default="both")
    ap.add_argument("--check-stale", action="store_true",
                    help="exit 0 if the recorded verdict is current and "
                         "complete, 3 if the probe should (re-)run")
    args = ap.parse_args(argv)

    if args.check_stale:
        return check_stale(pathlib.Path(
            os.environ.get("AOT_LOAD_OUT", str(REPO / "AOT_LOAD.json"))))
    if args.phase == "a":
        phase_a()
        return 0
    if args.phase == "b":
        return phase_b()

    out_path = pathlib.Path(
        os.environ.get("AOT_LOAD_OUT", str(REPO / "AOT_LOAD.json")))
    if cache_is_fresh():
        # Phase A is deterministic; while the backend flakes (phase B exit
        # 2) the queue re-invokes us each cycle — don't recompile
        # identical bytes.
        print("[aot-probe] phase A cache fresh; skipping rebuild",
              file=sys.stderr)
        ra = 0
    else:
        env_a = dict(os.environ, JAX_PLATFORMS="cpu",
                     PYTHONPATH=f"{REPO}:{os.environ.get('PYTHONPATH', '')}")
        ra = _run_phase("a", env_a, 600)
    if ra != 0:
        # Phase A is fully local: a failure (or 600s hang) is deterministic,
        # so write the answer file — no later run should spend chip time
        # re-running it.
        out_path.write_text(json.dumps(
            {"ok": False, "stage": "phase-a",
             "program_versions": PROGRAM_VERSIONS,
             "error": "local AOT compile/serialize failed "
                      f"(rc={ra}; timeout if None)"}, indent=1))
        print(f"[aot-probe] phase A failed (rc={ra}); recorded", file=sys.stderr)
        return 1
    env_b = dict(os.environ,
                 PYTHONPATH=f"{REPO}:{os.environ.get('PYTHONPATH', '')}")
    rb = _run_phase("b", env_b, 600)
    if rb is None:
        print("[aot-probe] phase B timed out (backend down?) — retryable",
              file=sys.stderr)
        return 2
    return rb


if __name__ == "__main__":
    sys.exit(main())
