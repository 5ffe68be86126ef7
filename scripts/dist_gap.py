"""Measure the tile-kernel vs distributed-program gap on one chip.

The headline bench times the FULL 1.5D dense-shift fused program (shard_map
ring + relayouts + the Pallas tile kernel); scripts/tune_blocks.py times the
bare tile kernel. Round 2 recorded 47 GFLOP/s for the former when the latter
measured 73 — this script pins down how much of that gap remains by timing
both in ONE process on the same matrix at the tuned kernel config, plus the
transpose/pad relayouts (`PallasKernel.prep`) alone.

AOT mode: when AOT_LOAD.json validates re-homed loads, the three distinct
programs (tile, prep, dist) are loaded from offline-compiled executables
instead of paying three on-device Mosaic compiles. The dist program is
byte-identical to bench.py's headline chain, so it reuses bench's AOT
cache; tile/prep get their own (`--aot-compile` builds them, CPU-pinned).
Any AOT failure falls back to on-device jit per program.

Appends one JSON record to DIST_GAP.jsonl. Resumable: skips when a record
for the current (logM, npr, R, blocks, group, scatter, chunk, batch,
backend) configuration exists.

Usage: python scripts/dist_gap.py [logM npr R trials]
       python scripts/dist_gap.py --aot-compile OUT_DIR [logM npr R trials]
"""

from __future__ import annotations

import importlib.util
import json
import os
import pathlib
import subprocess
import sys

REPO = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))

import numpy as np

OUT = REPO / "DIST_GAP.jsonl"


def _load_bench():
    spec = importlib.util.spec_from_file_location("bench", REPO / "bench.py")
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)
    return bench


def _apply_tuned_env(bench, log_m: int, npr: int, R: int) -> None:
    """Measure the SAME kernel config the headline bench would run: apply
    bench.py's best-measured env overrides (explicit env still wins). Must
    run before the package import — the knobs snapshot at import time."""
    os.environ.setdefault("BENCH_LOG_M", str(log_m))
    os.environ.setdefault("BENCH_NNZ_PER_ROW", str(npr))
    os.environ.setdefault("BENCH_R", str(R))
    tuned = bench._best_measured_env() or {}
    for k, v in tuned.items():
        os.environ.setdefault(k, v)


def build_tile_setup(kern, log_m: int, npr: int, R: int):
    """The bare-tile and relayout step functions + states (shared between
    the measuring process and the offline AOT compiler)."""
    import jax.numpy as jnp

    from distributed_sddmm_tpu.ops.blocked import (
        CHUNK, DEFAULT_BLOCK_COLS, DEFAULT_BLOCK_ROWS, DEFAULT_GROUP,
        build_blocked,
    )
    from distributed_sddmm_tpu.ops.pallas_kernels import BlockedTile
    from distributed_sddmm_tpu.utils.coo import HostCOO

    S = HostCOO.rmat(log_m=log_m, edge_factor=npr, seed=0)
    rng = np.random.default_rng(0)
    meta = build_blocked(
        1, np.zeros(S.nnz, np.int64), S.rows, S.cols, S.M, S.N,
        block_rows=DEFAULT_BLOCK_ROWS, block_cols=DEFAULT_BLOCK_COLS,
        group=DEFAULT_GROUP, R=R,
    )
    blk = BlockedTile(
        lr=jnp.array(meta.lr[0]), lc=jnp.array(meta.lc[0]),
        meta=jnp.array(meta.meta[0]), bm=meta.bm, bn=meta.bn,
        gr_blocks=meta.gr_blocks, gc_blocks=meta.gc_blocks, group=meta.group,
    )
    vals_np = np.zeros(meta.n_chunks * CHUNK, np.float32)
    vals_np[meta.host_to_chunk] = 1.0
    cvals = jnp.array(vals_np)
    A = jnp.array(rng.standard_normal((S.M, R)), jnp.float32)
    B = jnp.array(rng.standard_normal((S.N, R)), jnp.float32)

    def tile_step(state):
        Bs, _ = state
        o, _mid = kern.fused_tile(blk, cvals, A, Bs)
        return (Bs + o[: S.N] * 1e-12, _)

    def prep_step(state):
        # Both operands ride the loop carry: a closure-constant prep would
        # be hoisted out of the timed fori_loop by invariant code motion.
        As, Bs = state
        at = kern.prep(As, meta.rows_pad)
        bt = kern.prep(Bs, meta.cols_pad)
        s = at.astype(jnp.float32).sum() + bt.astype(jnp.float32).sum()
        return (As + s * 1e-30, Bs + s * 1e-30)

    steps = {"tile": (tile_step, (B, cvals)), "prep": (prep_step, (A, B))}
    return S, meta, steps


def _tile_cache_dir(bench, log_m: int, npr: int, R: int, trials: int) -> pathlib.Path:
    """Cache key: grid + trials + every kernel knob's RESOLVED value (the
    tuned env changes without source changes — a bt-compiled executable
    must not be timed under a cfg that says nt) + bench's all-sources hash
    + this file (the step functions live here)."""
    import hashlib

    from distributed_sddmm_tpu.ops.blocked import knob_env_defaults

    h = hashlib.sha256()
    h.update(bench._bench_code_hash().encode())
    h.update(pathlib.Path(__file__).read_bytes())
    knobs = "_".join(f"{k}={os.environ.get(k, '')}"
                     for k in sorted(knob_env_defaults()))
    h.update(knobs.encode())
    return REPO / "artifacts" / "aot_bench" / (
        f"distgap_{log_m}_{npr}_{R}_t{trials}_{h.hexdigest()[:10]}")


def aot_compile(out_dir: pathlib.Path, log_m: int, npr: int, R: int,
                trials: int) -> int:
    """Offline (CPU-pinned): compile + serialize the tile/prep chains."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    from jax.experimental import topologies

    from distributed_sddmm_tpu.bench import aot
    from distributed_sddmm_tpu.ops.pallas_kernels import PallasKernel

    kern = PallasKernel(precision="bf16", interpret=False)
    _S, _meta, steps = build_tile_setup(kern, log_m, npr, R)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x4")
    report = {"ok": True, "compile_s": {}}
    for name, (step, state) in steps.items():
        report["compile_s"][name] = aot.compile_chain_pair(
            step, state, trials, topo.devices[0], out_dir, name)
    (out_dir / "meta.json").write_text(json.dumps(report, indent=1))
    print(json.dumps(report))
    return 0


def _timed(name: str, step, state, trials: int, load_dir) -> tuple[float, bool]:
    """AOT-loaded timing when available, on-device `_chain_time` otherwise;
    returns (seconds, used_aot)."""
    import jax

    from distributed_sddmm_tpu.bench.kernels import _chain_time

    if load_dir is not None:
        from distributed_sddmm_tpu.bench import aot

        try:
            loaded = aot.load_chain_pair(load_dir, name, trials,
                                         jax.devices()[0])
            return aot.chain_time_loaded(loaded, state, trials), True
        except Exception as e:  # noqa: BLE001 — any AOT failure -> jit path
            print(f"[dist-gap] AOT path failed for {name} "
                  f"({type(e).__name__}: {e}); on-device compile",
                  file=sys.stderr)
    return _chain_time(step, state, trials), False


def main() -> int:
    argv = [a for a in sys.argv[1:]]
    compile_dir = None
    if argv and argv[0] == "--aot-compile":
        compile_dir = pathlib.Path(argv[1])
        argv = argv[2:]
    log_m = int(argv[0]) if len(argv) > 0 else 16
    npr = int(argv[1]) if len(argv) > 1 else 32
    R = int(argv[2]) if len(argv) > 2 else 128
    trials = int(argv[3]) if len(argv) > 3 else 5

    bench = _load_bench()
    _apply_tuned_env(bench, log_m, npr, R)
    # bench's AOT cache + compiler read the trip count from the env; a
    # mismatch would serialize pairs the loader can never find. When
    # BENCH_TRIALS was already exported it wins over argv, so re-derive
    # trials from the env — loader and compiler must agree on the names.
    os.environ.setdefault("BENCH_TRIALS", str(trials))
    try:
        trials = int(os.environ["BENCH_TRIALS"])
    except ValueError:
        # Malformed export: fall back to argv and force agreement.
        os.environ["BENCH_TRIALS"] = str(trials)

    if compile_dir is not None:
        compile_dir.mkdir(parents=True, exist_ok=True)
        return aot_compile(compile_dir, log_m, npr, R, trials)

    import jax
    import jax.numpy as jnp

    from distributed_sddmm_tpu.ops.pallas_kernels import PallasKernel
    from distributed_sddmm_tpu.ops.blocked import (
        CHUNK, DEFAULT_BLOCK_COLS, DEFAULT_BLOCK_ROWS, DEFAULT_GROUP,
    )

    kern = PallasKernel()
    cfg = {
        "logM": log_m, "npr": npr, "R": R,
        "blocks": f"{DEFAULT_BLOCK_ROWS or 'auto'}x"
                  f"{DEFAULT_BLOCK_COLS or 'auto'}",
        "group": DEFAULT_GROUP, "scatter_form": kern.scatter_form,
        "chunk": CHUNK, "batch_step": kern.batch_step,
        "backend": jax.default_backend(),
    }
    if OUT.exists():
        for line in OUT.read_text().splitlines():
            try:
                rec = json.loads(line)
            except json.JSONDecodeError:
                continue
            if all(rec.get(k) == v for k, v in cfg.items()):
                print(f"skip (done): {cfg}", flush=True)
                return 0

    S, meta, steps = build_tile_setup(kern, log_m, npr, R)
    flops_pair = 2.0 * S.nnz * 2.0 * R

    # Offline-compile the tile/prep chains when loads are validated (the
    # subprocess is local + seconds; failures fall back per program).
    tile_dir = None
    if jax.device_count() == 1 and bench._aot_validated("pallas_fused"):
        d = _tile_cache_dir(bench, log_m, npr, R, trials)
        if not (d / "meta.json").exists():
            env = dict(os.environ, JAX_PLATFORMS="cpu")
            fail = None
            try:
                proc = subprocess.run(
                    [sys.executable, __file__, "--aot-compile", str(d),
                     str(log_m), str(npr), str(R), str(trials)],
                    env=env, capture_output=True, text=True, timeout=420)
                if proc.returncode > 0:
                    fail = "\n".join(
                        (proc.stderr or "").strip().splitlines()[-5:])
                elif proc.returncode < 0:
                    # Signal kill (OOM etc.) — transient, no tombstone.
                    print(f"[dist-gap] AOT precompile killed "
                          f"(rc={proc.returncode}); on-device compile "
                          "this run", file=sys.stderr)
            except subprocess.TimeoutExpired:
                # Same strike policy as bench/kernel_sweep (aot_gate):
                # skip AOT this run; tombstone only after timeouts from
                # two independent load episodes.
                print("[dist-gap] AOT precompile timed out; on-device "
                      "compile this run", file=sys.stderr)
                if bench._aot_gate().timeout_strike(d):
                    fail = "repeated timeouts (420s budget)"
            if fail is not None and not (d / "meta.json").exists():
                # Negative cache + diagnostics: a deterministic local
                # compile failure must not re-spend its timeout each run.
                # An existing meta is the compiler's own verdict (written
                # as its final act) — never clobber it with ours.
                print(f"[dist-gap] AOT precompile failed: {fail}",
                      file=sys.stderr)
                d.mkdir(parents=True, exist_ok=True)
                (d / "meta.json").write_text(
                    json.dumps({"ok": False, "error": fail}))
        try:
            if json.loads((d / "meta.json").read_text()).get("ok"):
                tile_dir = d
        except (OSError, json.JSONDecodeError):
            tile_dir = None

    tile_step, tile_state = steps["tile"]
    prep_step, prep_state = steps["prep"]
    t_tile, aot_tile = _timed("tile", tile_step, tile_state, trials, tile_dir)
    t_prep, aot_prep = _timed("prep", prep_step, prep_state, trials, tile_dir)

    # --- full distributed fused program (bench.py's measurement) --------- #
    # Identical to the headline chain; reuse bench's builder + AOT cache.
    alg, prog, Ad, Bd, targs = bench.build_headline(kern)
    dist_dir = bench._maybe_aot_dir({}) if jax.device_count() == 1 else None
    t_dist = None
    aot_dist = False
    if dist_dir:
        from distributed_sddmm_tpu.bench import aot

        try:
            chains = aot.load_chain_pair(dist_dir, "headline", trials,
                                         jax.devices()[0])

            def run(n):
                return float(chains[n](Ad, Bd, *targs).sum())

            t_dist = aot.timed_difference(run, trials)
            aot_dist = True
        except Exception as e:  # noqa: BLE001 — fall back to on-device jit
            print(f"[dist-gap] AOT dist path failed ({type(e).__name__}: "
                  f"{e}); on-device compile", file=sys.stderr)
            t_dist = None
    if t_dist is None:
        from distributed_sddmm_tpu.bench.kernels import _chain_time

        def dist_step(state):
            Ab, _ = state
            out, _mid = prog(Ab, Bd, *targs)
            return (Ab + out * 1e-12, _)

        t_dist = _chain_time(dist_step, (Ad, jnp.zeros(())), trials)

    rec = dict(cfg)
    rec.update(
        tile_ms=t_tile * 1e3, dist_ms=t_dist * 1e3, prep_ms=t_prep * 1e3,
        tile_gflops=flops_pair / t_tile / 1e9,
        dist_gflops=flops_pair / t_dist / 1e9,
        dist_over_tile=t_dist / t_tile,
        aot={"tile": aot_tile, "prep": aot_prep, "dist": aot_dist},
    )
    with OUT.open("a") as f:
        f.write(json.dumps(rec) + "\n")
    print(json.dumps(rec), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
