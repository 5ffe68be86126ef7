"""Offline AOT compiler for the headline benchmark's chained program.

Companion to bench.py's BENCH_AOT_DIR mode: construct the identical
headline strategy (bench.build_headline, same env knobs), retarget its
mesh at one v5e topology device (the run_pallas.py pattern), and
AOT-compile + serialize `bench.make_headline_chain` for both trip counts,
on a host without a chip. Point the worker at OUT_DIR with BENCH_AOT_DIR.

CPU-pinned.

Usage: python scripts/aot_compile_bench.py OUT_DIR
Env: BENCH_LOG_M/BENCH_NNZ_PER_ROW/BENCH_R/BENCH_TRIALS + DSDDMM_* knobs,
exactly as bench.py's worker reads them.
"""

from __future__ import annotations

import importlib.util
import json
import os
import pathlib
import sys
import time

REPO = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))

import jax

jax.config.update("jax_platforms", "cpu")

from jax.experimental import topologies

TOPOLOGY = "v5e:2x4"


def main() -> int:
    out_dir = pathlib.Path(sys.argv[1])
    trials = int(os.environ.get("BENCH_TRIALS", "5"))

    spec = importlib.util.spec_from_file_location("bench", REPO / "bench.py")
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)

    from distributed_sddmm_tpu.ops.kernels import XlaKernel
    from distributed_sddmm_tpu.ops.pallas_kernels import PallasKernel
    from distributed_sddmm_tpu.parallel.mesh import make_grid

    # Compile exactly what the on-device worker would run: get_kernel
    # ("auto") resolves to the bf16 Mosaic kernel on TPU; BENCH_KERNEL=xla
    # runs the ring on the flat XLA form of the tile seam.
    if os.environ.get("BENCH_KERNEL", "auto") == "xla":
        kernel = XlaKernel()
    else:
        kernel = PallasKernel(precision="bf16", interpret=False)
    t0 = time.monotonic()
    alg, _prog, A, B, targs = bench.build_headline(
        kernel, devices=jax.devices("cpu")[:1])
    build_s = round(time.monotonic() - t0, 1)

    topo = topologies.get_topology_desc(platform="tpu", topology_name=TOPOLOGY)
    g = alg.grid
    alg.grid = make_grid(g.nr, g.nc, g.nh, adjacency=g.adjacency,
                         devices=[topo.devices[0]])
    alg._programs.clear()
    prog = alg._program("fused", use_st=False)
    mesh = alg.grid.mesh

    def sds_like(x):
        sharding = jax.sharding.NamedSharding(mesh, x.sharding.spec)
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)

    arg_sds = tuple(sds_like(x) for x in (A, B, *targs))
    out_dir.mkdir(parents=True, exist_ok=True)
    from distributed_sddmm_tpu.ops.blocked import knob_env_defaults

    key_names = ("BENCH_LOG_M", "BENCH_NNZ_PER_ROW", "BENCH_R",
                 "BENCH_TRIALS", "BENCH_KERNEL") + tuple(
                     sorted(knob_env_defaults()))
    report = {"ok": True, "build_s": build_s, "compile_s": {}, "env": {
        k: os.environ.get(k, "") for k in key_names}}
    from distributed_sddmm_tpu.bench import aot

    for n in (1, 1 + trials):
        t0 = time.monotonic()
        compiled = bench.make_headline_chain(prog, n).lower(*arg_sds).compile()
        # Target platform, not this (CPU-pinned) process's backend: the
        # store's load-side backend gate must accept these on the chip.
        aot.save_executable(compiled, out_dir, "headline", n,
                            backend=topo.devices[0].platform)
        report["compile_s"][n] = round(time.monotonic() - t0, 1)
    (out_dir / "meta.json").write_text(json.dumps(report, indent=1))
    print(json.dumps(report))
    return 0


if __name__ == "__main__":
    sys.exit(main())
