"""Headline benchmark: fused SDDMM+SpMM GFLOP/s per chip at R=128.

Prints ONE JSON line: {"metric": ..., "value": N, "unit": ..., "vs_baseline": N}.

Mirrors the reference's primary entry point `bench_erdos_renyi`
(`/root/reference/bench_erdos_renyi.cpp`) + `benchmark_algorithm`
(`/root/reference/benchmark_dist.cpp:117-149`): Graph500-style R-mat input,
fused SDDMM->SpMM pairs, throughput = 2*nnz*2*R*trials / elapsed.

The script is split in two so that only one process ever touches the chip:

* orchestrator (default): never imports JAX. It runs the measurement as a
  ``--worker`` subprocess per attempt (kernel-knob settings, see
  :func:`main`), each under a hard timeout with a process-group kill, and
  prints the best record. When no attempt produces one it exits 1 and
  prints no record.
* worker (``--worker``): the chained-trial measurement on the TPU. Trials
  are data-dependently chained inside one jitted fori_loop ending in a
  scalar host fetch, and timed as the difference of two trip counts so the
  constant dispatch and fetch overhead cancels. A worker that finds no TPU
  exits non-zero: this benchmark measures the chip or nothing.

Baseline denominator: the only absolute figure recoverable from the reference
repo is the weak-scaling point ~6.47 GFLOP/s (15d_sparse fused, 256 Cori-KNL
ranks; ipdps_chart_generator.ipynb cell 10, see BASELINE.md). vs_baseline is
value / 6.47 — i.e. this chip vs. a 256-rank Cori KNL job on the recoverable
number.
"""

import json
import os
import signal
import subprocess
import sys
import time

BASELINE_GFLOPS = 6.47  # see module docstring


def make_headline_chain(prog, n: int):
    """The chained-trials headline program for one trip count: the full
    fused shard_map program applied ``n`` times with a data dependence
    between passes. Every device buffer is an ARGUMENT (not a closure
    capture) so the identical computation can be AOT-compiled in an
    offline process and loaded here (`scripts/aot_compile_bench.py`)."""
    import jax

    @jax.jit
    def chain(A_t, B, *targs):
        def body(_, A_t):
            out, _mid = prog(A_t, B, *targs)
            return A_t + out * 1e-12

        return jax.lax.fori_loop(0, n, body, A_t)

    return chain


def build_headline(kernel, devices=None):
    """Construct the headline benchmark's strategy and operands (shared
    with the offline AOT compiler, which retargets the mesh afterwards).
    Returns (alg, prog, A, B, targs)."""
    from distributed_sddmm_tpu.common import MatMode
    from distributed_sddmm_tpu.parallel.dense_shift_15d import DenseShift15D
    from distributed_sddmm_tpu.utils.coo import HostCOO

    log_m = int(os.environ.get("BENCH_LOG_M", "16"))
    nnz_per_row = int(os.environ.get("BENCH_NNZ_PER_ROW", "32"))
    R = int(os.environ.get("BENCH_R", "128"))

    S = HostCOO.rmat(log_m=log_m, edge_factor=nnz_per_row, seed=0)
    alg = DenseShift15D(S, R=R, c=1, fusion_approach=2, kernel=kernel,
                        devices=devices)
    A = alg.dummy_initialize(MatMode.A)
    B = alg.like_b_matrix(0.01)
    s_vals = alg.like_s_values(1.0)
    prog = alg._program("fused", use_st=False)
    targs = alg._tile_args(alg.S_tiles, s_vals)
    return alg, prog, A, B, targs


def worker() -> int:
    """The measurement itself; runs in a subprocess under the orchestrator."""
    import jax

    from distributed_sddmm_tpu.ops import get_kernel
    from distributed_sddmm_tpu.utils.platform import enable_compile_cache

    if jax.default_backend() != "tpu":
        print(f"[bench-worker] backend is {jax.default_backend()!r}, not "
              "'tpu'; nothing measured", file=sys.stderr)
        return 1
    enable_compile_cache()

    log_m = int(os.environ.get("BENCH_LOG_M", "16"))
    nnz_per_row = int(os.environ.get("BENCH_NNZ_PER_ROW", "32"))
    R = int(os.environ.get("BENCH_R", "128"))
    trials = int(os.environ.get("BENCH_TRIALS", "5"))
    kernel_name = os.environ.get("BENCH_KERNEL", "auto")

    kernel = get_kernel(kernel_name)

    n_dev = jax.device_count()
    alg, prog, A, B, targs = build_headline(kernel)
    nnz = alg.S_tiles.nnz

    # Pre-serialized executables from an offline AOT compile
    # (scripts/aot_compile_bench.py) when BENCH_AOT_DIR names them; the
    # offline compiler targets ONE device. Any failure to load or run them
    # is reported and the chain compiles on the device instead.
    chains = None
    used_aot = False
    aot_dir = os.environ.get("BENCH_AOT_DIR", "")
    if aot_dir and n_dev == 1:
        try:
            from distributed_sddmm_tpu.bench import aot

            # The offline compiler lowers with the same positional args the
            # jitted chain takes, so the loaded callables are drop-ins.
            chains = aot.load_chain_pair(aot_dir, "headline", trials,
                                         jax.devices()[0])
            # Probe one real execution NOW: an executable that loads but
            # does not run here must not be timed.
            float(chains[1](A, B, *targs).sum())
            used_aot = True
        except Exception as e:  # noqa: BLE001 — reported, then on-device jit
            print(f"[bench-worker] AOT executables from {aot_dir} unusable "
                  f"({type(e).__name__}: {e}); compiling on the device "
                  "instead", file=sys.stderr)
            chains = None
    if chains is None:
        chains = {n: make_headline_chain(prog, n) for n in (1, 1 + trials)}

    def run(n):
        return float(chains[n](A, B, *targs).sum())

    # Warmup / compile both trip counts, then time by difference so the
    # constant per-fetch overhead cancels.
    run(1)
    run(1 + trials)
    t0 = time.perf_counter()
    run(1)
    t_one = time.perf_counter() - t0
    t0 = time.perf_counter()
    run(1 + trials)
    t_full = time.perf_counter() - t0
    elapsed = t_full - t_one
    if elapsed <= 0:
        # Difference timing can go negative under dispatch noise at tiny
        # sizes; fall back to assuming uniform per-iteration cost.
        elapsed = t_full * trials / (1 + trials)

    # Reference throughput formula (`benchmark_dist.cpp:147-149`).
    flops = 2.0 * nnz * 2.0 * R * trials
    gflops = flops / elapsed / 1e9
    gflops_per_chip = gflops / n_dev

    rec = {
        "metric": f"fused SDDMM+SpMM GFLOP/s/chip (R-mat 2^{log_m}, "
        f"nnz/row={nnz_per_row}, R={R}, {kernel.name} kernel, "
        f"{n_dev} {jax.default_backend()} chip(s))",
        "value": round(gflops_per_chip, 3),
        "unit": "GFLOP/s/chip",
        "vs_baseline": round(gflops_per_chip / BASELINE_GFLOPS, 3),
        "backend": jax.default_backend(),
    }
    if used_aot:
        rec["aot"] = True
    print(json.dumps(rec))
    return 0


def _headline_pallas_records() -> list:
    """Pallas records from KERNELS_TPU.jsonl matching the headline
    (logM, nnz/row, R) config, malformed lines skipped."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "KERNELS_TPU.jsonl")
    want = (
        int(os.environ.get("BENCH_LOG_M", "16")),
        int(os.environ.get("BENCH_NNZ_PER_ROW", "32")),
        int(os.environ.get("BENCH_R", "128")),
    )
    recs = []
    try:
        with open(path) as f:
            for line in f:
                try:
                    r = json.loads(line)
                except json.JSONDecodeError:
                    continue
                if not str(r.get("kernel", "")).startswith("pallas"):
                    continue
                if (r.get("logM"), r.get("npr"), r.get("R")) == want:
                    recs.append(r)
    except OSError:
        pass
    return recs


def _best_measured_env() -> dict | None:
    """Env overrides from the best Pallas record in KERNELS_TPU.jsonl for the
    headline config, so the sweep's tuning carries into the headline number.
    Returns None when no matching record exists (fresh checkout / pre-sweep)."""
    best = None
    for r in _headline_pallas_records():
        g = r.get("fused_pair_gflops")
        if g and (best is None or g > best.get("fused_pair_gflops", 0)):
            best = r
    if best is None or "bm" not in best:
        return None
    return {
        "DSDDMM_BLOCK_ROWS": str(best["bm"]),
        "DSDDMM_BLOCK_COLS": str(best["bn"]),
        "DSDDMM_CHUNK_GROUP": str(best.get("group", 1)),
        "DSDDMM_SCATTER_FORM": best.get("scatter_form", "bt"),
        "DSDDMM_CHUNK": str(best.get("chunk", 128)),
        "DSDDMM_BATCH_STEP": "1" if best.get("batch_step") else "0",
    }


_AOT_GATE = None


def _aot_gate():
    """The shared AOT-gate policy module, imported from its FILE — going
    through the package would execute distributed_sddmm_tpu/__init__ and
    pull jax into this deliberately backend-free orchestrator process."""
    global _AOT_GATE
    if _AOT_GATE is None:
        import importlib.util

        p = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "distributed_sddmm_tpu", "bench", "aot_gate.py")
        spec = importlib.util.spec_from_file_location("_aot_gate_file", p)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _AOT_GATE = mod
    return _AOT_GATE


def _aot_validated(program: str | None = None) -> bool:
    """AOT_LOAD.json (scripts/aot_load_probe.py) recorded that re-homed
    executables load correctly on this backend. ``program`` gates on one
    probe program ("pallas_fused"/"xla_matmul") so one program's failure
    doesn't foreclose AOT mode for the other; no argument = ALL programs.
    Policy lives in aot_gate (shared with the sweep/apps/dist-gap)."""
    if os.environ.get("BENCH_NO_AOT", "") not in ("", "0"):
        return False
    gate = _aot_gate()
    rep = gate.load_verdict(
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "AOT_LOAD.json"))
    return gate.probe_validated(rep, program)


def _bench_code_hash() -> str:
    """Fingerprint of the sources that determine the headline program, so
    stale serialized executables are never timed as current code. Every
    package source is hashed — enumerating 'the files that matter' proved
    error-prone (ring/ablation/ingest code all shape the program), and
    over-invalidation only costs a ~3s local recompile."""
    import hashlib
    import pathlib

    here = pathlib.Path(os.path.dirname(os.path.abspath(__file__)))
    h = hashlib.sha256()
    files = [here / "bench.py", here / "scripts" / "aot_compile_bench.py"]
    files += sorted((here / "distributed_sddmm_tpu").rglob("*.py"))
    for f in files:
        h.update(f.read_bytes())
    return h.hexdigest()[:10]


def _run_attempt(env_extra: dict, timeout_s: float) -> dict | None:
    """Run one worker subprocess; return its JSON record or None.

    The worker runs in its own session so a timeout kills the whole process
    GROUP — any helper process it started would otherwise inherit our pipes
    and keep ``communicate()`` blocked past the kill.
    """
    env = dict(os.environ)
    env.update(env_extra)
    proc = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--worker"],
        env=env,
        stdout=subprocess.PIPE,
        stderr=subprocess.PIPE,
        text=True,
        start_new_session=True,
    )
    try:
        stdout, stderr = proc.communicate(timeout=timeout_s)
    except subprocess.TimeoutExpired:
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except (ProcessLookupError, PermissionError):
            proc.kill()
        stderr = ""
        try:
            _, stderr = proc.communicate(timeout=10)
        except subprocess.TimeoutExpired:
            pass
        print(f"[bench] attempt timed out after {timeout_s:.0f}s", file=sys.stderr)
        for ln in (stderr or "").strip().splitlines()[-15:]:
            print(f"[bench]   {ln}", file=sys.stderr)
        return None
    for line in reversed((stdout or "").strip().splitlines()):
        try:
            rec = json.loads(line)
            if isinstance(rec, dict) and "value" in rec:
                return rec
        except json.JSONDecodeError:
            continue
    tail = (stderr or "").strip().splitlines()[-15:]
    print(
        f"[bench] attempt rc={proc.returncode}, no JSON record; stderr tail:",
        file=sys.stderr,
    )
    for ln in tail:
        print(f"[bench]   {ln}", file=sys.stderr)
    return None


def main() -> int:
    """Attempt schedule, all on the TPU: the kernel knobs of the best
    Pallas record in KERNELS_TPU.jsonl (when one exists and differs from
    the first fixed rung), then chunk group 4, then chunk group 1. Prints
    the best record; exits 1 when no attempt produced one."""
    total = float(os.environ.get("BENCH_TOTAL_TIMEOUT", "2100"))
    start = time.monotonic()
    attempts = [
        ({"DSDDMM_CHUNK_GROUP": "4"}, 0.4),
        ({"DSDDMM_CHUNK_GROUP": "1"}, 0.3),
    ]
    tuned = _best_measured_env()
    if tuned is not None and tuned != {**_knob_env_defaults(),
                                       **attempts[0][0]}:
        attempts.insert(0, (tuned, 0.4))
    best = None
    for env_extra, share in attempts:
        remaining = total - (time.monotonic() - start)
        if best is not None and remaining < 120:
            break  # have a record; don't risk the budget tail
        timeout_s = min(total * share, remaining)
        if timeout_s < 30:
            continue
        rec = _run_attempt(env_extra, timeout_s)
        if rec is not None and (best is None or rec["value"] > best["value"]):
            best = rec
    if best is None:
        print("[bench] no attempt produced a TPU record", file=sys.stderr)
        return 1
    print(json.dumps(best))
    return 0


def _knob_env_defaults() -> dict:
    """``ops.blocked.knob_env_defaults()``, read from the module's FILE:
    importing it through the package would pull JAX into the orchestrator,
    which must leave the chip to its worker."""
    import importlib.util

    p = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "distributed_sddmm_tpu", "ops", "blocked.py")
    spec = importlib.util.spec_from_file_location("_blocked_file", p)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod  # dataclasses resolve their module
    spec.loader.exec_module(mod)
    return mod.knob_env_defaults()


if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    sys.exit(worker() if "--worker" in sys.argv else main())
