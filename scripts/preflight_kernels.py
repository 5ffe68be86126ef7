"""Offline Mosaic compile pre-flight for planned kernel-sweep configs.

`artifacts/multichip_hlo/run_pallas.py` established that the Mosaic/TPU
compiler runs on a host without a chip against a
`jax.experimental.topologies` target. So before any plan config spends
chip time, this validator AOT-compiles its exact Pallas kernel
configuration (blocks,
group, chunk, scatter form, step batching, R) for a single v5e core on a
tiny R-mat and records ok / compile-error per config. A config that cannot
compile here cannot compile on the chip either (same compiler), so the
queue can skip it instead of timing out on it.

The reference has no analog (its kernels are prebuilt MKL/cuSPARSE calls,
`sparse_kernels.cpp:94-121`).

Usage: python scripts/preflight_kernels.py [plan.json ...] [-o PREFLIGHT.json]
Defaults to every scripts/plans/*.json. Exit code 1 when any config fails.
"""

from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys
import time

REPO = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import importlib.util

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import topologies

# Compile for one core of the same generation the queue measures on.
TOPOLOGY = "v5e:2x4"

# The config-identity key is OWNED by the consumer (kernel_sweep skips
# failed keys); importing it keeps producer and consumer from drifting.
_ks_spec = importlib.util.spec_from_file_location(
    "kernel_sweep", pathlib.Path(__file__).with_name("kernel_sweep.py"))
_ks = importlib.util.module_from_spec(_ks_spec)
_ks_spec.loader.exec_module(_ks)
preflight_key = _ks.preflight_key


def pallas_configs(plans: list[pathlib.Path]) -> list[dict]:
    seen, out = set(), []
    for plan in plans:
        for cfg in json.loads(plan.read_text()):
            if cfg.get("kernel") != "pallas":
                continue
            key = preflight_key(cfg)
            if key in seen:
                continue
            seen.add(key)
            out.append({"plan": plan.name, **cfg})
    return out


def compile_one(cfg: dict, device) -> dict:
    """AOT-compile fused/sddmm/spmm tile kernels for one config; tiny
    graph, real (blocks, group, chunk, scatter, batch, R) knobs."""
    # Chunk size is snapshotted at import inside ops.blocked, so configs
    # with a non-default chunk run in a fresh subprocess (see main()).
    from distributed_sddmm_tpu.ops.blocked import CHUNK, build_blocked
    from distributed_sddmm_tpu.ops.pallas_kernels import BlockedTile, PallasKernel
    from distributed_sddmm_tpu.utils.coo import HostCOO

    assert CHUNK == cfg.get("chunk", 128), (CHUNK, cfg)
    bm, bn = (int(x) for x in cfg.get("blocks", "512x512").split("x"))
    R, group = cfg["R"], cfg.get("group", 1)

    S = HostCOO.rmat(log_m=11, edge_factor=8, seed=0)
    meta = build_blocked(1, np.zeros(S.nnz, np.int64), S.rows, S.cols,
                         S.M, S.N, block_rows=bm, block_cols=bn, group=group)
    # A clamped probe would validate a DIFFERENT kernel than the plan's and
    # record a false 'ok' for the unclamped key; fail loudly instead (the
    # probe matrix must be enlarged, or the plan config is one tune_blocks
    # would tombstone anyway).
    if (meta.bm, meta.bn) != (bm, bn):
        raise RuntimeError(
            f"probe clamped blocks {bm}x{bn} -> {meta.bm}x{meta.bn}; "
            f"preflight cannot vouch for this config")
    sharding = jax.sharding.SingleDeviceSharding(device)

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    blk = BlockedTile(
        lr=sds(meta.lr[0].shape, jnp.int32), lc=sds(meta.lc[0].shape, jnp.int32),
        meta=sds(meta.meta[0].shape, jnp.int32), bm=meta.bm, bn=meta.bn,
        gr_blocks=meta.gr_blocks, gc_blocks=meta.gc_blocks, group=meta.group,
    )
    kern = PallasKernel(precision="bf16", interpret=False,
                        scatter_form=cfg.get("scatter", "bt"),
                        batch_step=bool(cfg.get("batch")))
    cvals = sds((meta.n_chunks * CHUNK,))
    A, B = sds((S.M, R)), sds((S.N, R))
    rows_pad = meta.gr_blocks * meta.bm

    report = {}
    # blk is a pytree of ShapeDtypeStructs, so it must flow through lower()
    # as an argument, not a closure constant. All three ops compile
    # regardless of the plan's fused_only flag: the preflight key has no
    # fused_only axis, so a fused-only probe config would otherwise mask
    # the full config sharing its key.
    ops = {
        "fused": lambda: jax.jit(kern.fused_tile).lower(blk, cvals, A, B),
        "sddmm": lambda: jax.jit(kern.sddmm_tile).lower(blk, cvals, A, B),
        "spmm": lambda: jax.jit(
            kern.spmm_tile, static_argnums=3
        ).lower(blk, cvals, B, rows_pad),
    }
    for name, build in ops.items():
        t0 = time.monotonic()
        build().compile()
        report[f"{name}_compile_s"] = round(time.monotonic() - t0, 2)
    return report


# The r_split far corner: the reference sweeps R to 4096
# (`local_kernel_benchmark.cpp:278`); full-R one-hot blocks cannot compile
# there (the configs report proves it), and the DESIGNED escape is the
# 1.5D sparse-shift feature split (`15D_sparse_shift.hpp:139-157` analog):
# per-device kernels see R*c/p columns. This compiles the blocked Mosaic
# programs of that path for the full 8-device v5e topology, proving the
# prescribed grid's far corner is reachable by design.
RSPLIT_CFG = {"R": 4096, "c": 1, "logM": 13, "npr": 8}


def compile_rsplit(cfg: dict) -> dict:
    """AOT-compile the blocked 1.5D sparse-shift sddmm+spmm programs (the
    fused pair chains exactly these two, `distributed_sparse.h:296-312`)
    over the v5e:2x4 topology mesh at per-device R-slices."""
    jax.config.update("jax_platforms", "cpu")
    from distributed_sddmm_tpu.common import MatMode
    from distributed_sddmm_tpu.ops.pallas_kernels import PallasKernel
    from distributed_sddmm_tpu.parallel.mesh import make_grid
    from distributed_sddmm_tpu.parallel.sparse_shift_15d import SparseShift15D
    from distributed_sddmm_tpu.utils.coo import HostCOO

    cpu = jax.devices()
    assert len(cpu) >= 8, "needs XLA_FLAGS=--xla_force_host_platform_device_count=8"
    R, c = cfg["R"], cfg["c"]
    topo = topologies.get_topology_desc(platform="tpu", topology_name=TOPOLOGY)
    S = HostCOO.rmat(log_m=cfg["logM"], edge_factor=cfg["npr"], seed=0)
    # Ingest on the CPU mesh with the interpret kernel (builds the blocked
    # chunk-list metadata), then retarget the topology mesh with the real
    # Mosaic kernel — the run_pallas.py census pattern.
    alg = SparseShift15D(S, R, c=c, devices=cpu[:8],
                         kernel=PallasKernel(precision="f32", interpret=True))
    A = alg.dummy_initialize(MatMode.A)
    B = alg.dummy_initialize(MatMode.B)
    vals = alg.like_s_values(1.0)
    g = alg.grid
    alg.grid = make_grid(g.nr, g.nc, g.nh, adjacency=g.adjacency,
                         devices=list(topo.devices))
    alg.kernel = PallasKernel(precision="bf16", interpret=False)
    alg._programs.clear()
    mesh = alg.grid.mesh

    def sds_like(x):
        return jax.ShapeDtypeStruct(
            x.shape, x.dtype,
            sharding=jax.sharding.NamedSharding(mesh, x.sharding.spec))

    bm, bn, *_ = alg.S_tiles.blk_geom
    rec = {**cfg, "p": 8, "r_local": R * c // 8, "blocks": f"{bm}x{bn}",
           "strategy": "15d_sparse", "kernel": "pallas-bf16 blocked",
           "topology": TOPOLOGY}
    for op, call_args in (
        ("sddmm", (A, B, *alg._tile_args(alg.S_tiles, vals, mask=True))),
        ("spmm", (B, *alg._tile_args(alg.S_tiles, vals))),
    ):
        t0 = time.monotonic()
        prog = alg._program(op, False)
        compiled = prog.lower(*(sds_like(a) for a in call_args)).compile()
        rec[f"{op}_compile_s"] = round(time.monotonic() - t0, 2)
        rec[f"{op}_mosaic_calls"] = compiled.as_text().count(
            'custom_call_target="tpu_custom_call"')
    return rec


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("plans", nargs="*", help="plan JSONs (default: scripts/plans/*)")
    ap.add_argument("-o", "--output", default=str(REPO / "PREFLIGHT.json"))
    ap.add_argument("--config-json", default=None,
                    help="(internal) compile ONE config, passed as JSON")
    ap.add_argument("--rsplit-json", default=None,
                    help="(internal) compile the r_split programs, cfg as JSON")
    args = ap.parse_args(argv)

    if args.rsplit_json:
        print(json.dumps(compile_rsplit(json.loads(args.rsplit_json))))
        return 0

    if args.config_json:
        # Pure AOT work — pin the default backend to CPU so this process
        # never claims a chip.
        jax.config.update("jax_platforms", "cpu")
        cfg = json.loads(args.config_json)
        topo = topologies.get_topology_desc(platform="tpu", topology_name=TOPOLOGY)
        print(json.dumps(compile_one(cfg, topo.devices[0])))
        return 0

    plans = [pathlib.Path(p) for p in args.plans] or sorted(
        (REPO / "scripts" / "plans").glob("*.json"))
    configs = pallas_configs(plans)
    results, failures = [], 0
    out_path = pathlib.Path(args.output)

    # Carry forward prior results for configs this run hasn't reached yet:
    # an outer timeout must not discard the committed report's knowledge
    # (each fresh result replaces its key as the run progresses).
    old_by_key = {}
    rsplit_state = {}
    try:
        old_report = json.loads(out_path.read_text())
        for rec in old_report.get("configs", []):
            old_by_key[preflight_key(rec)] = rec
        rsplit_state = old_report.get("r_split") or {}
    except (OSError, json.JSONDecodeError, KeyError):
        pass

    def flush_report():
        fresh = {preflight_key(r) for r in results}
        merged = results + [r for k, r in old_by_key.items() if k not in fresh]
        out = {"topology": TOPOLOGY,
               "note": "offline Mosaic AOT compile check; a compile-error "
                       "here means the queue would hang/fail on this config",
               "complete": len(results) == len(configs),
               "configs": merged}
        if rsplit_state:
            out["r_split"] = rsplit_state
        # Atomic replace: an outer SIGTERM mid-write must not truncate the
        # report (a broken JSON disables all preflight skipping AND
        # clobbers the committed known-good file).
        tmp = out_path.with_suffix(".tmp")
        tmp.write_text(json.dumps(out, indent=1))
        os.replace(tmp, out_path)

    import subprocess

    def run_config(cfg: dict):
        env = dict(os.environ)
        env["DSDDMM_CHUNK"] = str(cfg.get("chunk", 128))
        env["JAX_PLATFORMS"] = "cpu"
        env["PYTHONPATH"] = f"{REPO}:{env.get('PYTHONPATH', '')}"
        return subprocess.run(
            [sys.executable, __file__, "--config-json", json.dumps(cfg)],
            env=env, capture_output=True, text=True, timeout=600)

    # Canary: the long-measured headline config must compile. If it does
    # not, the AOT environment itself is broken (jax upgrade, missing
    # libtpu AOT, ...) and NO failure this run can be trusted as
    # config-specific — bail without poisoning the report.
    canary = {"logM": 14, "npr": 32, "R": 128, "kernel": "pallas",
              "blocks": "512x512", "group": 4}
    try:
        cp = run_config(canary)
    except subprocess.TimeoutExpired:
        cp = None
    if cp is None or cp.returncode != 0:
        tail = "" if cp is None else "\n".join(
            cp.stderr.strip().splitlines()[-8:])
        print("[preflight] CANARY FAILED — AOT environment broken, "
              f"leaving existing report untouched\n{tail}", file=sys.stderr)
        return 3

    for cfg in configs:
        t0 = time.monotonic()
        rec = {k: cfg.get(k) for k in
               ("plan", "logM", "npr", "R", "blocks", "group", "chunk",
                "scatter", "batch", "fused_only")}
        try:
            proc = run_config(cfg)
        except subprocess.TimeoutExpired:
            # One hanging compile must not lose the whole report — record
            # it and move on. NOTE: a timeout is not proof of
            # uncompilability, so kernel_sweep deliberately does NOT skip
            # these (only status == "compile-error"); the nonzero exit
            # here just flags that preflight could not vouch for
            # everything.
            proc = None
        rec["wall_s"] = round(time.monotonic() - t0, 1)
        if proc is None:
            rec.update(status="timeout")
            failures += 1
        elif proc.returncode == 0:
            try:
                rec.update(status="ok", **json.loads(
                    proc.stdout.strip().splitlines()[-1]))
            except (json.JSONDecodeError, IndexError):
                rec.update(status="bad-output", stderr=proc.stderr[-800:])
                failures += 1
        else:
            stderr_full = proc.stderr or ""
            tail = "\n".join(stderr_full.strip().splitlines()[-12:])
            # A clamped probe means this probe matrix cannot represent the
            # config — NOT that the config can't compile at its real grid
            # size; a libtpu lockfile/busy clash means another local
            # process held the TPU plugin (e.g. a concurrent preflight) —
            # both get statuses failed_preflight_keys ignores, so neither
            # can ever blacklist a measurable config. Classify on the FULL
            # stderr (the signature can scroll above the stored tail).
            if "preflight cannot vouch" in stderr_full:
                status = "probe-invalid"
            elif ("libtpu_lockfile" in stderr_full
                  or "already in use" in stderr_full):
                status = "env-transient"
            else:
                status = "compile-error"
            rec.update(status=status, error=tail)
            failures += 1
        # Transient outcomes (lockfile clash, timeout) are not evidence
        # about the CONFIG — they must not clobber a committed ok record
        # (e.g. a concurrent prewarm holding the libtpu lock would
        # otherwise downgrade the whole report to env-transient). The
        # config then no longer counts as failed: its record IS ok.
        old = old_by_key.get(preflight_key(rec))
        if (rec["status"] in ("env-transient", "timeout")
                and old is not None and old.get("status") == "ok"):
            print(f"[preflight] {rec['status']} for R={cfg['R']} "
                  f"blocks={cfg.get('blocks', '512x512')}; keeping the "
                  "prior ok record", flush=True)
            rec = old
            failures -= 1
        results.append(rec)
        flush_report()
        print(f"[preflight] {rec['status']:13s} "
              f"R={cfg['R']} blocks={cfg.get('blocks', '512x512')} "
              f"g={cfg.get('group', 1)} chunk={cfg.get('chunk', 128)} "
              f"scatter={cfg.get('scatter', 'bt')} batch={bool(cfg.get('batch'))} "
              f"({rec['wall_s']}s)", flush=True)

    # Per-config tally frozen here: the r_split outcome below feeds the
    # exit code but must not misattribute its failure to the config list.
    cfg_failures = failures

    # r_split far-corner proof (resumable: a matching ok record stands).
    rsplit_current = (rsplit_state.get("status") == "ok"
                      and all(rsplit_state.get(k) == v
                              for k, v in RSPLIT_CFG.items()))
    if not rsplit_current:
        t0 = time.monotonic()
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["PYTHONPATH"] = f"{REPO}:{env.get('PYTHONPATH', '')}"
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                            + " --xla_force_host_platform_device_count=8").strip()
        try:
            proc = subprocess.run(
                [sys.executable, __file__, "--rsplit-json",
                 json.dumps(RSPLIT_CFG)],
                env=env, capture_output=True, text=True, timeout=900)
        except subprocess.TimeoutExpired:
            proc = None
        wall = round(time.monotonic() - t0, 1)
        if proc is not None and proc.returncode == 0:
            try:
                rsplit_state = {"status": "ok", "wall_s": wall, **json.loads(
                    proc.stdout.strip().splitlines()[-1])}
            except (json.JSONDecodeError, IndexError):
                rsplit_state = {"status": "bad-output", "wall_s": wall,
                                **RSPLIT_CFG, "stderr": proc.stderr[-800:]}
                failures += 1
        else:
            tail = "timeout" if proc is None else "\n".join(
                (proc.stderr or "").strip().splitlines()[-12:])
            rsplit_state = {"status": "compile-error", "wall_s": wall,
                            **RSPLIT_CFG, "error": tail}
            failures += 1
        flush_report()
        print(f"[preflight] r_split {rsplit_state['status']} "
              f"R={RSPLIT_CFG['R']} c={RSPLIT_CFG['c']} "
              f"r_local={RSPLIT_CFG['R'] * RSPLIT_CFG['c'] // 8} "
              f"({wall}s)", flush=True)

    print(f"[preflight] {len(results) - cfg_failures}/{len(results)} "
          f"configs ok, r_split {rsplit_state.get('status', '?')} "
          f"-> {out_path}")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
