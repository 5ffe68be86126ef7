"""Offline AOT compiler for one kernel-sweep config's chained programs.

Companion to `scripts/tune_blocks.py`'s TUNE_LOAD_DIR mode: builds the SAME
step functions (imported from tune_blocks, so program structure cannot
drift), AOT-compiles their chained-trial pairs against a v5e topology
device, on a host without a chip, and serializes them for the TPU worker to
load. Driven by `scripts/kernel_sweep.py` when AOT_LOAD.json records that
re-homed loads work on this backend.

Runs CPU-pinned; only shapes/dtypes of the operands matter here.

Usage: python scripts/aot_compile_kernels.py logM npr R trials OUT_DIR
Env knobs: identical to tune_blocks (TUNE_BLOCKS single pair, TUNE_GROUP,
TUNE_SCATTER, TUNE_BATCH, TUNE_FUSED_ONLY, DSDDMM_CHUNK).
"""

from __future__ import annotations

import importlib.util
import json
import pathlib
import sys

REPO = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))

import jax

jax.config.update("jax_platforms", "cpu")

from jax.experimental import topologies

TOPOLOGY = "v5e:2x4"


def main() -> int:
    log_m, npr, R, trials = (int(x) for x in sys.argv[1:5])
    out_dir = pathlib.Path(sys.argv[5])

    spec = importlib.util.spec_from_file_location(
        "tune_blocks", pathlib.Path(__file__).with_name("tune_blocks.py"))
    tune = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tune)

    from distributed_sddmm_tpu.bench import aot
    from distributed_sddmm_tpu.ops.pallas_kernels import PallasKernel

    import os

    if os.environ.get("AOTC_KERNEL", "pallas") == "xla":
        # The flat XLA-kernel chains (tune_blocks' non-Pallas branch).
        from distributed_sddmm_tpu.ops.kernels import XlaKernel

        S, A, B, _flops = tune.build_inputs(log_m, npr, R)
        kern = XlaKernel()
        rows, cols, vals = tune.xla_operands(S)
        steps = tune.xla_steps(kern, rows, cols, vals, S, A)
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name=TOPOLOGY)
        report = {"ok": True, "kernel": "xla", "compile_s": {}}
        for name, step in steps.items():
            report["compile_s"][name] = aot.compile_chain_pair(
                step, (B, vals), trials, topo.devices[0], out_dir, name)
        (out_dir / "meta.json").write_text(json.dumps(report, indent=1))
        print(json.dumps(report))
        return 0

    if len(tune.BLOCKS) != 1:
        print("aot_compile_kernels expects exactly one TUNE_BLOCKS pair",
              file=sys.stderr)
        return 1
    bm_pref, bn_pref = tune.BLOCKS[0]
    group = int(os.environ.get("TUNE_GROUP", "1"))
    S, A, B, _flops = tune.build_inputs(log_m, npr, R)
    meta, blk, cvals = tune.build_blk(S, bm_pref, bn_pref, group)
    if blk is None:
        # tune_blocks will emit the tombstone itself; cache the negative so
        # kernel_sweep doesn't re-run this subprocess on every resume.
        out_dir.mkdir(parents=True, exist_ok=True)
        (out_dir / "meta.json").write_text(
            json.dumps({"ok": False, "reason": "clamped"}))
        print(json.dumps({"ok": False, "reason": "clamped"}))
        return 0
    # The on-device worker runs bf16 Mosaic kernels; compile exactly that.
    kernp = PallasKernel(precision="bf16", interpret=False,
                         scatter_form=tune.SCATTER_FORM,
                         batch_step=tune.BATCH_STEP)

    steps = tune.pallas_steps(kernp, blk, cvals, S, A)

    topo = topologies.get_topology_desc(platform="tpu", topology_name=TOPOLOGY)
    dev = topo.devices[0]
    ops = ["fused"] if tune.FUSED_ONLY else ["fused", "sddmm", "spmm"]
    report = {"ok": True, "config": {
        "logM": log_m, "npr": npr, "R": R, "trials": trials,
        "blocks": f"{bm_pref}x{bn_pref}", "group": group,
        "scatter": tune.SCATTER_FORM, "batch": tune.BATCH_STEP,
        "chunk": tune.CHUNK}, "compile_s": {}}
    for op in ops:
        times = aot.compile_chain_pair(
            steps[op], (B, cvals), trials, dev, out_dir, op)
        report["compile_s"][op] = times
    (out_dir / "meta.json").write_text(json.dumps(report, indent=1))
    print(json.dumps(report))
    return 0


if __name__ == "__main__":
    sys.exit(main())
