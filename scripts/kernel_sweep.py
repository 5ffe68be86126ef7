"""Resilient TPU kernel-sweep orchestrator.

The per-chip analog of the reference's `local_kernel_benchmark` sweep
(`/root/reference/local_kernel_benchmark.cpp:276-280`), run the way
bench.py runs its attempts: every (logM, npr, R, kernel)
config runs in its OWN worker subprocess (scripts/tune_blocks.py) under a
hard timeout with process-group kill, failures are retried with backoff,
and finished configs are checkpointed to the output JSONL so a re-run
resumes where it left off.

Usage:
    python scripts/kernel_sweep.py plan.json out.jsonl [--timeout 900]

plan.json: list of {"logM": int, "npr": int, "R": int, "kernel": "xla"|
"pallas", optional "blocks": "BMxBN", "group": int, "fused_only": bool,
"trials": int}.
"""

from __future__ import annotations

import argparse
import json
import os
import pathlib
import signal
import subprocess
import sys
import time

REPO = pathlib.Path(__file__).resolve().parents[1]


def config_key(cfg: dict) -> tuple:
    # Defaults must mirror run_worker's env defaults, or an entry without an
    # explicit "blocks" never matches its own output record and re-runs on
    # every resume.
    default_blocks = "512x512" if cfg["kernel"] == "pallas" else ""
    return (
        cfg["logM"], cfg["npr"], cfg["R"], cfg["kernel"],
        cfg.get("blocks", default_blocks), cfg.get("group", 1),
        cfg.get("scatter", "bt") if cfg["kernel"] == "pallas" else "",
        cfg.get("chunk", 128) if cfg["kernel"] == "pallas" else 0,
        bool(cfg.get("batch")) if cfg["kernel"] == "pallas" else False,
    )


def record_key(rec: dict) -> tuple:
    # Prefer the REQUESTED blocks echoed by tune_blocks (blocks_req): the
    # realized bm/bn can differ when pick_block clamps the preference, and
    # keying on the realized pair would re-run such configs forever. Records
    # predating the echo fall back to the realized pair (never clamped in
    # the committed data).
    blocks = rec.get("blocks_req") or (
        f"{rec['bm']}x{rec['bn']}" if "bm" in rec else "")
    is_pallas = rec["kernel"].startswith("pallas")
    return (
        rec["logM"], rec["npr"], rec["R"],
        "pallas" if is_pallas else rec["kernel"],
        blocks, rec.get("group", 1),
        rec.get("scatter_form", "bt") if is_pallas else "",
        rec.get("chunk", 128) if is_pallas else 0,
        bool(rec.get("batch_step")) if is_pallas else False,
    )


def preflight_key(cfg: dict) -> tuple:
    """Kernel-configuration identity used by scripts/preflight_kernels.py
    (grid size excluded — compile validity doesn't depend on logM/npr).
    ``or``-normalized because preflight records carry explicit nulls for
    absent knobs while plan configs simply omit them."""
    return (cfg.get("blocks") or "512x512", cfg.get("group") or 1,
            cfg.get("chunk") or 128, cfg.get("scatter") or "bt",
            bool(cfg.get("batch")), cfg["R"])


def failed_preflight_keys(path: pathlib.Path) -> set:
    """Kernel configs the offline Mosaic AOT check proved uncompilable —
    running them on the chip would only spend chip time on a
    deterministic failure. Only ``compile-error`` counts: a preflight
    timeout or garbled output is not proof the config can't compile."""
    try:
        report = json.loads(path.read_text())
    except (OSError, json.JSONDecodeError):
        return set()
    return {preflight_key(rec) for rec in report.get("configs", [])
            if rec.get("status") == "compile-error"}


def done_keys(out_path: pathlib.Path) -> set:
    keys = set()
    if out_path.exists():
        for line in out_path.read_text().splitlines():
            try:
                keys.add(record_key(json.loads(line)))
            except (json.JSONDecodeError, KeyError):
                continue
    return keys


_AOT_GATE = None


def _aot_gate():
    """Shared AOT-gate policy module, imported from its FILE — the package
    __init__ would pull jax into this backend-free orchestrator."""
    global _AOT_GATE
    if _AOT_GATE is None:
        import importlib.util

        p = REPO / "distributed_sddmm_tpu" / "bench" / "aot_gate.py"
        spec = importlib.util.spec_from_file_location("_aot_gate_file", str(p))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _AOT_GATE = mod
    return _AOT_GATE


def aot_validated(program: str | None = None) -> bool:
    """True when the AOT-load probe recorded that locally compiled
    executables load and produce correct numerics on this backend
    (AOT_LOAD.json, written by scripts/aot_load_probe.py).

    ``program`` gates on one probe program ("pallas_fused"/"xla_matmul") —
    one program's failure must not foreclose AOT mode for the other; with
    no argument, ALL programs must be validated. Policy shared with
    bench.py via aot_gate."""
    if os.environ.get("KERNEL_SWEEP_NO_AOT", "") not in ("", "0"):
        return False
    gate = _aot_gate()
    return gate.probe_validated(
        gate.load_verdict(REPO / "AOT_LOAD.json"), program)


def _aot_code_hash() -> str:
    """Fingerprint of the sources that determine the compiled kernels —
    stale serialized executables must never be timed as current code."""
    import hashlib

    h = hashlib.sha256()
    for rel in ("distributed_sddmm_tpu/ops/pallas_kernels.py",
                "distributed_sddmm_tpu/ops/blocked.py",
                "distributed_sddmm_tpu/bench/aot.py",
                "scripts/tune_blocks.py",
                "scripts/aot_compile_kernels.py"):
        h.update((REPO / rel).read_bytes())
    return h.hexdigest()[:10]


def aot_precompile(cfg: dict, env: dict, timeout_s: float = 420.0) -> str | None:
    """Build this config's serialized chain pairs offline (CPU-pinned
    subprocess, Mosaic compile on the host — seconds). Returns the
    cache dir to pass as TUNE_LOAD_DIR, or None to use on-device compile.
    The cache key carries fused_only (op set differs) and a source hash
    (old binaries must not masquerade as current kernels)."""
    key = "_".join(str(p) for p in config_key(cfg)).replace("/", "-")
    out_dir = REPO / "artifacts" / "aot_kernels" / (
        key + f"_t{cfg.get('trials', 5)}"
        + f"_f{1 if cfg.get('fused_only') else 0}_{_aot_code_hash()}")
    meta_path = out_dir / "meta.json"
    if meta_path.exists():
        try:
            ok = bool(json.loads(meta_path.read_text()).get("ok"))
        except (OSError, json.JSONDecodeError):
            ok = False
        return str(out_dir) if ok else None
    def tombstone(reason: str) -> None:
        # Negative cache: a deterministic local compile failure must not
        # re-spend its ~420s timeout on every retry of every queue cycle.
        out_dir.mkdir(parents=True, exist_ok=True)
        meta_path.write_text(json.dumps({"ok": False, "error": reason}))

    # Set unconditionally: a stray AOTC_KERNEL in the inherited env must
    # never flip a pallas precompile into the xla branch (or vice versa).
    cenv = dict(env, JAX_PLATFORMS="cpu", AOTC_KERNEL=cfg["kernel"])
    try:
        proc = subprocess.run(
            [sys.executable, str(REPO / "scripts" / "aot_compile_kernels.py"),
             str(cfg["logM"]), str(cfg["npr"]), str(cfg["R"]),
             str(cfg.get("trials", 5)), str(out_dir)],
            env=cenv, capture_output=True, text=True, timeout=timeout_s)
    except subprocess.TimeoutExpired:
        # A timeout on a loaded machine is not proof of a deterministic
        # failure (the preflight treats timeouts as non-conclusive).
        # aot_gate.timeout_strike tombstones only after strikes from two
        # INDEPENDENT load episodes (>=30 min apart) — the retry loop's
        # same-spike repeats count as one.
        if _aot_gate().timeout_strike(out_dir):
            tombstone(f"repeated timeouts ({timeout_s:.0f}s budget)")
        print(f"[sweep] AOT precompile timed out for {config_key(cfg)}; "
              "using on-device compile", flush=True)
        return None
    if proc.returncode != 0 or not (out_dir / "meta.json").exists():
        tail = (proc.stderr or "").strip().splitlines()[-3:]
        print(f"[sweep] AOT precompile failed for {config_key(cfg)} "
              f"(rc={proc.returncode}, {tail}); using on-device compile",
              flush=True)
        if proc.returncode >= 0 and not (out_dir / "meta.json").exists():
            # Negative rc = signal kill (OOM etc.) — transient, no tombstone.
            tombstone(f"rc={proc.returncode}: {tail}")
        return None
    return str(out_dir)


def worker_env(cfg: dict) -> dict:
    """The tune_blocks subprocess env for one plan config — also what the
    offline AOT compiler keys its cache on, so prewarm and run must agree."""
    env = dict(os.environ)
    env["PYTHONPATH"] = f"{REPO}:{env.get('PYTHONPATH', '')}"
    if cfg["kernel"] == "xla":
        env["TUNE_BLOCKS"] = "0x0"  # no pallas configs
    else:
        env["TUNE_SKIP_XLA"] = "1"
        env["TUNE_BLOCKS"] = cfg.get("blocks", "512x512")
        env["TUNE_GROUP"] = str(cfg.get("group", 1))
        env["TUNE_SCATTER"] = cfg.get("scatter", "bt")
        env["DSDDMM_CHUNK"] = str(cfg.get("chunk", 128))
        env["TUNE_BATCH"] = "1" if cfg.get("batch") else "0"
        if cfg.get("fused_only"):
            env["TUNE_FUSED_ONLY"] = "1"
    return env


def run_worker(cfg: dict, timeout_s: float) -> list[dict] | None:
    env = worker_env(cfg)
    if aot_validated(_aot_gate().probe_program(cfg["kernel"])):
        load_dir = aot_precompile(cfg, env)
        if load_dir:
            env["TUNE_LOAD_DIR"] = load_dir
    proc = subprocess.Popen(
        [sys.executable, str(REPO / "scripts" / "tune_blocks.py"),
         str(cfg["logM"]), str(cfg["npr"]), str(cfg["R"]),
         str(cfg.get("trials", 5))],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        text=True, start_new_session=True,
    )
    try:
        stdout, stderr = proc.communicate(timeout=timeout_s)
    except subprocess.TimeoutExpired:
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except (ProcessLookupError, PermissionError):
            proc.kill()
        try:
            proc.communicate(timeout=10)
        except subprocess.TimeoutExpired:
            pass
        print(f"[sweep] {config_key(cfg)}: timeout {timeout_s:.0f}s", flush=True)
        return None
    recs = []
    for line in (stdout or "").splitlines():
        try:
            recs.append(json.loads(line))
        except json.JSONDecodeError:
            continue
    if not recs:
        tail = (stderr or "").strip().splitlines()[-3:]
        print(f"[sweep] {config_key(cfg)}: rc={proc.returncode}, no records; "
              f"stderr tail: {tail}", flush=True)
        return None
    return recs


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("plan")
    ap.add_argument("output")
    ap.add_argument("--timeout", type=float, default=900.0,
                    help="per-config hard timeout (seconds)")
    ap.add_argument("--retries", type=int, default=2)
    ap.add_argument("--backoff", type=float, default=45.0)
    ap.add_argument("--kernel-filter", default=None, choices=("xla", "pallas"),
                    help="run only this kernel's configs from the plan")
    ap.add_argument("--preflight", default=str(REPO / "PREFLIGHT.json"),
                    help="offline Mosaic compile report; configs it marks "
                         "failed are skipped (pass an absent path to disable)")
    ap.add_argument("--prewarm", action="store_true",
                    help="only build the offline AOT caches for every "
                         "not-yet-measured config (CPU-pinned, no TPU "
                         "touched) so a healthy window spends zero time "
                         "on local compiles; ignores the AOT-load verdict "
                         "because it runs BEFORE the verdict exists")
    args = ap.parse_args(argv)

    plan = json.loads(pathlib.Path(args.plan).read_text())
    if args.kernel_filter:
        plan = [cfg for cfg in plan if cfg["kernel"] == args.kernel_filter]
    out_path = pathlib.Path(args.output)
    done = done_keys(out_path)
    bad = failed_preflight_keys(pathlib.Path(args.preflight))
    not_done = [cfg for cfg in plan if config_key(cfg) not in done]
    skipped = [cfg for cfg in not_done if cfg["kernel"] == "pallas"
               and preflight_key(cfg) in bad]
    for cfg in skipped:
        print(f"[sweep] skipping {config_key(cfg)}: failed offline Mosaic "
              f"preflight ({args.preflight})", flush=True)

    todo = [cfg for cfg in not_done if cfg not in skipped]
    print(f"[sweep] {len(plan)} planned, {len(plan) - len(not_done)} "
          f"already done, {len(skipped)} preflight-skipped, "
          f"{len(todo)} to run", flush=True)
    if args.prewarm:
        warmed = failures = 0
        # Yield when the verdict file is WRITTEN after we start (the
        # probe re-answering = a healthy window just began); a verdict
        # merely left over from a past window must not no-op prewarm.
        t_start = time.time()

        def _healthy_window_began() -> bool:
            try:
                return (REPO / "AOT_LOAD.json").stat().st_mtime > t_start
            except OSError:
                return False

        for n, cfg in enumerate(todo):
            if _healthy_window_began():
                # Stop competing for the single CPU core with real
                # measurements — the sweep warms remaining caches lazily.
                print("[prewarm] AOT_LOAD.json refreshed; yielding to the "
                      "healthy-tier pipeline", flush=True)
                break
            d = aot_precompile(cfg, worker_env(cfg))
            warmed += d is not None
            failures += d is None
            print(f"[prewarm] {n + 1}/{len(todo)} {config_key(cfg)} "
                  f"{'ok' if d else 'FAILED'}", flush=True)
        print(f"[prewarm] {warmed}/{len(todo)} caches ready", flush=True)
        return 1 if failures else 0
    failures = 0
    for n, cfg in enumerate(todo):
        for attempt in range(1 + args.retries):
            if attempt:
                time.sleep(args.backoff * attempt)
            t0 = time.time()
            recs = run_worker(cfg, args.timeout)
            if recs is not None:
                with out_path.open("a") as f:
                    for rec in recs:
                        f.write(json.dumps(rec) + "\n")
                print(f"[sweep] {n + 1}/{len(todo)} {config_key(cfg)} ok "
                      f"({time.time() - t0:.0f}s)", flush=True)
                break
        else:
            failures += 1
            print(f"[sweep] {config_key(cfg)} FAILED after retries", flush=True)
    print(f"[sweep] complete, {failures} failures", flush=True)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
