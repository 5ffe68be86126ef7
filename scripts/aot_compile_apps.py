"""Offline AOT compiler for the application benchmarks' strategy programs.

The apps tier (`scripts/tpu_apps.py`) pays an on-device Mosaic compile for
every distinct strategy program it touches: 6 for ALS (sddmm/spmm/fused,
both orientations), 1 per heatmap R value. This script builds those
executables locally against a v5e topology device — the run_pallas.py
retarget pattern — so the TPU process can `inject_program` them and spend
its chip time measuring (GAT is excluded: its per-layer feature widths
retrace, and the injection wrapper's jit fallback covers it anyway).

CPU-pinned. The DenseShift15D arg orders below mirror its public op
methods (`dense_shift_15d.py` sddmm_a/spmm_a/fused_spmm); only the
15d_fusion2 configuration appears in the apps plan.

Usage: python scripts/aot_compile_apps.py APP logM npr R OUT_DIR
(APP in {als, vanilla}; kernel knobs via the usual DSDDMM_* env.)
"""

from __future__ import annotations

import json
import pathlib
import sys
import time

REPO = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))

import jax

jax.config.update("jax_platforms", "cpu")

from jax.experimental import topologies

TOPOLOGY = "v5e:2x4"

from distributed_sddmm_tpu.bench.aot import APP_PROGRAM_KEYS as APP_KEYS  # noqa: E402


def main() -> int:
    app = sys.argv[1]
    log_m, npr, R = (int(x) for x in sys.argv[2:5])
    out_dir = pathlib.Path(sys.argv[5])
    if app not in APP_KEYS:
        print(f"unsupported app {app!r} (want {sorted(APP_KEYS)})",
              file=sys.stderr)
        return 1

    from distributed_sddmm_tpu.bench import aot
    from distributed_sddmm_tpu.common import MatMode
    from distributed_sddmm_tpu.ops.pallas_kernels import PallasKernel
    from distributed_sddmm_tpu.parallel.dense_shift_15d import DenseShift15D
    from distributed_sddmm_tpu.parallel.mesh import make_grid
    from distributed_sddmm_tpu.utils.coo import HostCOO

    S = HostCOO.rmat(log_m=log_m, edge_factor=npr, seed=0)
    kern = PallasKernel(precision="bf16", interpret=False)
    alg = DenseShift15D(S, R=R, c=1, fusion_approach=2, kernel=kern,
                        devices=jax.devices("cpu")[:1])
    A = alg.dummy_initialize(MatMode.A)
    B = alg.dummy_initialize(MatMode.B)
    targs_s = alg._tile_args(alg.S_tiles, alg.like_s_values(1.0))
    targs_st = alg._tile_args(alg.ST_tiles, alg.like_st_values(1.0))
    # Dense-arg order per (op, use_st), mirroring the public methods.
    call_args = {
        ("sddmm", False): (A, B) + targs_s,
        ("sddmm", True): (B, A) + targs_st,
        ("spmm", False): (B,) + targs_s,
        ("spmm", True): (A,) + targs_st,
        ("fused", False): (A, B) + targs_s,
        ("fused", True): (B, A) + targs_st,
    }

    topo = topologies.get_topology_desc(platform="tpu", topology_name=TOPOLOGY)
    g = alg.grid
    alg.grid = make_grid(g.nr, g.nc, g.nh, adjacency=g.adjacency,
                         devices=[topo.devices[0]])
    alg._programs.clear()
    mesh = alg.grid.mesh

    def sds_like(x):
        sharding = jax.sharding.NamedSharding(mesh, x.sharding.spec)
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)

    report = {"ok": True, "app": app, "compile_s": {}}
    for op, use_st in APP_KEYS[app]:
        prog = alg._program(op, use_st)
        arg_sds = tuple(sds_like(x) for x in call_args[(op, use_st)])
        t0 = time.monotonic()
        compiled = prog.lower(*arg_sds).compile()
        name = f"{op}_{'b' if use_st else 'a'}"
        # Target platform (the topology chip), not the CPU-pinned
        # process backend — the on-chip loader's backend gate must match.
        aot.save_executable(compiled, out_dir, name, 0,
                            backend=topo.devices[0].platform)
        report["compile_s"][name] = round(time.monotonic() - t0, 2)
    (out_dir / "meta.json").write_text(json.dumps(report, indent=1))
    print(json.dumps(report))
    return 0


if __name__ == "__main__":
    sys.exit(main())
