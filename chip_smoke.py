"""On-chip smoke test of the main path: fused pair, ALS, GAT, serving.

Drives the library through the entry points a user calls
(``bench.harness.make_algorithm``, the models, and ``bench serve`` via
``bench.cli.main``) on one Graph500 R-mat matrix at logM=18, nnz/row=32
(8.39 M nonzeros, 262,144 rows), seed 0, and checks every result:

a. fused SDDMM->SpMM pair (1.5D dense shift, fusion 2, R=128) on the
   kernel ``get_kernel("auto")`` picks — the bf16 Pallas kernel, not in
   interpret mode, with the blocked path in the compiled program —
   against the host oracle's fingerprints;
b. two ALS-CG alternating steps (10 CG iterations each, R=128): the
   residual is finite and falls;
c. one forward pass of the reference's 3-layer GAT (256 features per
   head, 4/4/6 heads): finite output of the implied shape;
d. ``bench serve --app als`` at logM=18: at least 16 replies, every one
   checked against the float64 oracle, none wrong.

``--chips 4`` runs instead only what exists across chips: all five
algorithm configurations on a 4-chip mesh, each at the first legal
replication factor of (2, 4, 1), fused-pair fingerprints against the
oracle, and every sharded operand spread over 4 distinct devices.

Prints the device on its first line and one JSON line per phase (wall
time, compile time = first call minus second call, peak device bytes,
and JAX's own compile accounting: trace, lowering and backend-compile
seconds, persistent-cache hits and misses).
The last line is ``{"ok": true, "device": {...}}`` only when the
platform is ``tpu`` and every phase passed; otherwise the exit code is
non-zero and no such line is printed.

Everything it builds lives under ``artifacts/chip_smoke/`` (git-ignored)
and is made fresh: the program store and the plan cache start empty.
JAX's compilation cache is placed by ``utils.platform.enable_compile_cache``.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import shutil
import sys
import time
import traceback

REPO = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(REPO, "artifacts", "chip_smoke")

LOG_M = 18
EDGE_FACTOR = 32
R = 128
CG_ITERS = 10
#: The reference GAT's features per head (`benchmark_dist.cpp:90-92`).
GAT_FEATURES_PER_HEAD = 256
#: Relative tolerance of the bf16 kernel's oracle tests
#: (tests/test_pallas_kernels.py), applied to the fingerprints.
BF16_RTOL = 3e-2


class PhaseFailed(AssertionError):
    """A phase ran but its result failed its check."""


def _check(cond: bool, what: str) -> None:
    if not cond:
        raise PhaseFailed(what)


def _peak_bytes():
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def _timed(fn):
    from distributed_sddmm_tpu.utils.platform import force_fetch

    t0 = time.perf_counter()
    out = fn()
    force_fetch(out)
    return out, time.perf_counter() - t0


def _close(got: dict, want: dict, rtol: float) -> dict:
    """Per-op relative error of ``got`` against ``want``; raises when any
    exceeds ``rtol``."""
    errs = {op: abs(got[op] - v) / abs(v) for op, v in want.items()}
    _check(all(e <= rtol for e in errs.values()),
           f"fingerprints off the oracle beyond rtol={rtol}: {errs}")
    return errs


def build(log_m: int, kernel, devices):
    """The smoke's matrix and its 1.5D dense-shift fusion-2 strategy."""
    from distributed_sddmm_tpu.bench.harness import make_algorithm
    from distributed_sddmm_tpu.utils.coo import HostCOO

    S = HostCOO.rmat(log_m=log_m, edge_factor=EDGE_FACTOR, seed=0)
    alg = make_algorithm("15d_fusion2", S, R, 1, kernel=kernel,
                         devices=devices)
    return S, alg


def phase_fused(S, alg, rtol: float) -> dict:
    """(a) The fused pair against the oracle's fingerprints."""
    from distributed_sddmm_tpu.common import MatMode
    from distributed_sddmm_tpu.utils.verify import (
        fused_fingerprints, oracle_fused_fingerprints,
    )

    want = oracle_fused_fingerprints(S, alg.R)
    got, t_first = _timed(lambda: fused_fingerprints(alg))
    got, t_second = _timed(lambda: fused_fingerprints(alg))
    errs = _close(got, want, rtol)
    prog = alg._program("fused", use_st=False)
    A = alg.dummy_initialize(MatMode.A)
    B = alg.dummy_initialize(MatMode.B)
    targs = alg._tile_args(alg.S_tiles, alg.like_s_values(1.0))
    text = prog.lower(A, B, *targs).compile().as_text()
    return {
        "compile_s": t_first - t_second,
        "kernel": alg.kernel.name,
        "interpret": getattr(alg.kernel, "interpret", None),
        "blocked": alg._use_blocked(alg.S_tiles),
        "tpu_custom_call": "tpu_custom_call" in text,
        "blk_pad_frac": alg.S_tiles.blk_pad_frac,
        "nnz": S.nnz,
        "rel_err": errs,
    }


def phase_als(S, alg, cg_iters: int = CG_ITERS) -> dict:
    """(b) Two ALS-CG alternating steps; the residual must fall."""
    import numpy as np

    from distributed_sddmm_tpu.models.als import DistributedALS

    als = DistributedALS(alg, S_host=S)
    als.initialize_embeddings()
    residuals, times = [], []
    for _ in range(2):
        _, t = _timed(lambda: (als.run_cg(1, cg_iters=cg_iters),
                               (als.A, als.B))[1])
        times.append(t)
        residuals.append(als.compute_residual())
    _check(all(np.isfinite(residuals)), f"non-finite residual {residuals}")
    _check(residuals[1] < residuals[0],
           f"residual did not fall: {residuals}")
    return {"compile_s": times[0] - times[1], "residuals": residuals,
            "step_s": times, "cg_iters": cg_iters,
            "degraded": als.degraded}


def phase_gat(alg) -> dict:
    """(c) One forward pass of the reference's 3-layer GAT."""
    import jax.numpy as jnp

    from distributed_sddmm_tpu.bench.harness import _gat_layers
    from distributed_sddmm_tpu.common import MatMode
    from distributed_sddmm_tpu.models.gat import GAT

    gat = GAT(_gat_layers(GAT_FEATURES_PER_HEAD), alg)
    _, t_first = _timed(gat.forward)
    out, t_second = _timed(gat.forward)
    last = gat.layers[-1]
    want = alg.dense_shape(MatMode.A)
    _check(tuple(out.shape) == tuple(want)
           and out.shape[-1] == last.features_per_head * last.num_heads,
           f"GAT output shape {out.shape}, expected {want}")
    _check(bool(jnp.isfinite(out).all()), "GAT output is not finite")
    return {"compile_s": t_first - t_second, "shape": list(out.shape),
            "heads": [l.num_heads for l in gat.layers]}


def phase_serve(log_m: int, out_dir: str, duration_s: float = 4.0,
                rate_hz: float = 8.0) -> dict:
    """(d) ``bench serve --app als`` in this process, every reply
    oracle-checked."""
    from distributed_sddmm_tpu.bench import cli

    rec_path = os.path.join(out_dir, "serve.jsonl")
    t0 = time.perf_counter()
    rc = cli.main([
        "serve", "--app", "als", "--log-m", str(log_m),
        "--edge-factor", str(EDGE_FACTOR), "--duration", str(duration_s),
        "--rate", str(rate_hz), "--max-batch", "4", "--oracle-every", "1",
        "--no-runstore", "-o", rec_path,
    ])
    wall = time.perf_counter() - t0
    with open(rec_path) as f:
        rec = json.loads(f.read().splitlines()[-1])
    facts = {
        "rc": rc, "completed": rec["completed"],
        "oracle_checked": rec["oracle_checked"],
        "oracle_failures": rec["oracle_failures"],
        "kernel": rec["kernel"], "algorithm": rec["algorithm"],
        "R": rec["R"], "latency_ms": rec["latency_ms"],
        "live_compiles": rec["engine"]["live_compiles"],
        # No second call to subtract: set-up (build, train, ladder
        # compile) is what the phase spent outside its load window.
        "setup_s": wall - rec["duration_s"],
    }
    _check(rc == 0, f"bench serve exited {rc}")
    _check(rec["completed"] >= 16, f"only {rec['completed']} replies")
    _check(rec["oracle_failures"] == 0
           and rec["oracle_checked"] == rec["completed"],
           f"{rec['oracle_failures']} of {rec['oracle_checked']} checked "
           f"replies wrong ({rec['completed']} completed)")
    return facts


def _spread(x, n: int) -> dict:
    """Devices holding ``x`` and the largest share one of them holds."""
    biggest = max(s.data.size for s in x.addressable_shards)
    return {"devices": len(x.sharding.device_set),
            "max_shard_frac": biggest / x.size}


def phase_multichip(S, devices, kernel, rtol: float) -> dict:
    """All five algorithm configurations on ``len(devices)`` chips."""
    from __graft_entry__ import _construct_legal
    from distributed_sddmm_tpu.bench.harness import ALGORITHM_FACTORIES
    from distributed_sddmm_tpu.common import MatMode
    from distributed_sddmm_tpu.utils.verify import (
        fused_fingerprints, oracle_fused_fingerprints,
    )

    n = len(devices)
    want = oracle_fused_fingerprints(S, R)
    configs = {}
    for name in sorted(ALGORITHM_FACTORIES):
        alg, c = _construct_legal(name, S, R, n, devices, kernel=kernel)
        _check(alg is not None, f"{name}: no legal c at p={n}")
        got, t_first = _timed(lambda: fused_fingerprints(alg))
        got, t_second = _timed(lambda: fused_fingerprints(alg))
        tiles = alg.S_tiles
        # The nonzero values are what no layout replicates whole: the
        # 2.5D sparse-replicating one copies the STRUCTURE to every
        # layer by design and splits the values 1/c per layer.
        operands = {
            "A": alg.dummy_initialize(MatMode.A),
            "B": alg.dummy_initialize(MatMode.B),
            "S.values": alg.like_s_values(1.0),
            "S.rows": tiles.rows,
        }
        spread = {k: _spread(x, n) for k, x in operands.items()}
        for k, s in spread.items():
            _check(s["devices"] == n, f"{name}: {k} on {s['devices']} "
                   f"of {n} devices")
        _check(spread["S.values"]["max_shard_frac"] < 1.0,
               f"{name}: one device holds every nonzero value")
        configs[name] = {
            "c": c, "compile_s": t_first - t_second,
            "blocked": alg._use_blocked(tiles),
            "rel_err": _close(got, want, rtol), "spread": spread,
        }
        del alg, operands
        gc.collect()  # strategies hold reference cycles (program caches)
    return {"configs": configs}


class CompileEvents:
    """What a phase's compiles cost, from JAX's monitoring events: seconds
    tracing, lowering to MLIR and in the backend compile (a persistent
    cache hit included), and the persistent cache's hits and misses."""

    _DURATIONS = {
        "/jax/core/compile/jaxpr_trace_duration": "trace_s",
        "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
        "/jax/core/compile/backend_compile_duration": "backend_compile_s",
    }
    _COUNTS = {
        "/jax/compilation_cache/cache_hits": "cache_hits",
        "/jax/compilation_cache/cache_misses": "cache_misses",
    }

    def __init__(self):
        from jax import monitoring

        self._totals: dict = {}
        monitoring.register_event_listener(self._event)
        monitoring.register_event_duration_secs_listener(self._duration)

    def _add(self, name, value):
        self._totals[name] = self._totals.get(name, 0) + value

    def _event(self, event, **_):
        if event in self._COUNTS:
            self._add(self._COUNTS[event], 1)

    def _duration(self, event, secs, **_):
        if event in self._DURATIONS:
            self._add(self._DURATIONS[event], secs)

    def take(self) -> dict:
        """The totals since the last call."""
        totals, self._totals = self._totals, {}
        return totals


def _run_phase(name: str, fn, failures: list, events: CompileEvents):
    """Run one phase, print its line; a raise or failed check is
    recorded (with its traceback) and the next phase still runs."""
    t0 = time.perf_counter()
    try:
        facts = fn()
    except Exception as e:  # noqa: BLE001 — report every phase
        traceback.print_exc()
        failures.append(name)
        print(json.dumps({"phase": name, "ok": False,
                          "error": f"{type(e).__name__}: {e}",
                          "wall_s": time.perf_counter() - t0,
                          "peak_bytes_in_use": _peak_bytes(),
                          "compiles": events.take()}), flush=True)
        return None
    print(json.dumps({"phase": name, "ok": True,
                      "wall_s": time.perf_counter() - t0,
                      "peak_bytes_in_use": _peak_bytes(),
                      "compiles": events.take(), **facts},
                     default=str), flush=True)
    return facts


def _fresh_state() -> None:
    """Program store and plan cache start empty, under OUT_DIR."""
    shutil.rmtree(OUT_DIR, ignore_errors=True)
    os.makedirs(OUT_DIR)
    os.environ["DSDDMM_PROGRAMS"] = os.path.join(OUT_DIR, "programs")
    os.environ["DSDDMM_PLAN_CACHE"] = os.path.join(OUT_DIR, "plan_cache")


def _native_line() -> dict:
    """Whether the native host library built (``native/`` by make)."""
    from distributed_sddmm_tpu import native

    return {"native": native.available(),
            "native_error": native.build_error()}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: only the multi-chip phase, on 4 chips")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices)}
    print(json.dumps({"device": device}), flush=True)
    if dev.platform != "tpu":
        print(f"chip_smoke: platform is {dev.platform!r}, not 'tpu'; "
              "nothing was run", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 1

    from distributed_sddmm_tpu.ops import get_kernel
    from distributed_sddmm_tpu.utils.platform import (
        enable_compile_cache, tpu_chips_on_host,
    )

    cache = enable_compile_cache()
    _fresh_state()
    print(json.dumps({"compile_cache": cache,
                      "tpu_chips_on_host": tpu_chips_on_host(),
                      "tpu_env": {k: v for k, v in os.environ.items()
                                  if k.startswith("TPU_")},
                      **_native_line()}), flush=True)

    kernel = get_kernel("auto")
    events = CompileEvents()
    failures: list = []
    if args.chips == 4:
        from distributed_sddmm_tpu.utils.coo import HostCOO

        S = HostCOO.rmat(log_m=LOG_M, edge_factor=EDGE_FACTOR, seed=0)
        _run_phase("multichip", lambda: phase_multichip(
            S, devices[:4], kernel, BF16_RTOL), failures, events)
    else:
        t0 = time.perf_counter()
        S, alg = build(LOG_M, kernel, devices[:1])
        print(json.dumps({"phase": "build", "wall_s":
                          time.perf_counter() - t0, "nnz": S.nnz,
                          "compiles": events.take()}), flush=True)
        fused = _run_phase("fused", lambda: phase_fused(S, alg, BF16_RTOL),
                           failures, events)
        if fused is not None and not (
                fused["kernel"] == "pallas-bf16"
                and fused["interpret"] is False and fused["blocked"]
                and fused["tpu_custom_call"]):
            print("chip_smoke: the fused pair did not run the blocked bf16 "
                  f"Pallas kernel on the chip: {fused}", file=sys.stderr)
            failures.append("fused:kernel")
        _run_phase("als", lambda: phase_als(S, alg), failures, events)
        _run_phase("gat", lambda: phase_gat(alg), failures, events)
        del alg, S
        gc.collect()  # free the strategy's device arrays before serving
        _run_phase("serve", lambda: phase_serve(LOG_M, OUT_DIR), failures,
                   events)
    if failures:
        print(f"chip_smoke: failed: {failures}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
