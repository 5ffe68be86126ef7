"""Readings that the check's limits are set from, on the chip:

    python3 benchmark/calibrate.py --workload <cell> --seeds 1,2,3 \
        [--units N] [--control] [--faults]

For each seed, in one process: the cell's set-up, ``--units`` units of
the window's own call (the answer of the last one kept), then every
number the check compares, for the program (against the plain
reference) and, with ``--control``, for the control: the reference in
the precision below the configuration's, in the program's place. With
``--faults``, faults planted in the reference put in the program's
place: ``fault.half`` (half of the nonzeros left out), ``fault.altered``
(one answer of each output moved by ten times the largest), and a
``fault.<name>`` for each of the app's ``REFERENCE_FAULTS``. One JSON
line per seed. The benchmark's own runs never run these.
"""

import argparse
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import harness  # noqa: E402


def main(argv=None, root: str = harness.ROOT) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--units", type=int, default=1)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--faults", action="store_true")
    ap.add_argument("--cpu", action="store_true",
                    help="allow a run off the chip (tests only)")
    args = ap.parse_args(argv)
    cell = harness.Cell(args.workload, root)
    work = os.path.join(root, "benchmark", "_work")
    harness.prepare_env(work)
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" and not args.cpu:
        print("calibrate: JAX found no TPU", file=sys.stderr)
        return 2
    if devices[0].platform == "tpu":
        harness.enable_compile_cache(work)
    used = devices[: cell.chips]
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        rng, key, _ = harness.seeds(seed)
        mat = harness.make_matrix(cell.cfg["matrix"], rng, root)
        r = cell.app.Run(cell.cfg, mat, key, used, cell.cfg["check"])
        for _ in range(args.units):
            r.unit()
        r.keep()
        r.release()
        gc.collect()
        t1 = time.perf_counter()
        ref = r.reference(harness.quant("reference"))
        t2 = time.perf_counter()
        line = {"workload": args.workload, "seed": seed,
                "program": harness.compare(r.outputs(), ref),
                "setup_and_units_s": t1 - t0, "reference_s": t2 - t1}
        if args.control:
            ctl = r.reference(harness.quant(cell.cfg["check"]["control"]))
            line["control"] = harness.compare(ctl, ref)
            del ctl
        if args.faults:
            import jax.numpy as jnp

            from benchmark.faults import alter

            nnz = mat["rows"].size
            keep = (jnp.arange(nnz) < nnz // 2).astype(jnp.float32)
            half = r.reference(harness.quant("reference"), keep)
            line["fault.half"] = harness.compare(half, ref)
            del half
            line["fault.altered"] = harness.compare(
                {k: tuple(alter(a) for a in v) for k, v in ref.items()}, ref)
            for name in getattr(cell.app, "REFERENCE_FAULTS", ()):
                line["fault." + name] = harness.compare(
                    r.reference(harness.quant("reference"), fault=name), ref)
        line["calibrate_s"] = time.perf_counter() - t0
        print(json.dumps(line), flush=True)
        del r, ref, mat
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
