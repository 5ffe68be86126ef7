"""String-keyed algorithm factory + timed trial loop + JSON records.

Mirrors the reference's ``benchmark_algorithm``
(`/root/reference/benchmark_dist.cpp:26-163`):

* the same five algorithm configurations behind the same magic strings
  (`benchmark_dist.cpp:45-82`),
* app selection ``{vanilla, gat, als}`` (`benchmark_dist.cpp:88-100`),
* a fixed-trial loop (default 5, `benchmark_dist.cpp:117-141`),
* throughput ``2*nnz*2*R*trials / elapsed`` GFLOP/s
  (`benchmark_dist.cpp:147-149`),
* one JSON record appended per run to the output file
  (`benchmark_dist.cpp:151-163`).

Deviation by design: one **untimed warmup iteration** precedes the timed
loop so that XLA compilation (which the reference's ahead-of-time C++ build
has no analog of) is excluded from steady-state throughput. Pass
``warmup=0`` to time cold-start instead.
"""

from __future__ import annotations

import json
import time
from typing import Callable, Optional

import jax

from distributed_sddmm_tpu.common import KernelMode, MatMode
from distributed_sddmm_tpu.models.als import DistributedALS
from distributed_sddmm_tpu.models.gat import GAT, GATLayer
from distributed_sddmm_tpu.parallel.base import (
    DistributedSparse, realized_kernel_variant,
)
from distributed_sddmm_tpu.parallel.cannon_dense_25d import CannonDense25D
from distributed_sddmm_tpu.parallel.cannon_sparse_25d import CannonSparse25D
from distributed_sddmm_tpu.parallel.dense_shift_15d import DenseShift15D
from distributed_sddmm_tpu.parallel.sparse_shift_15d import SparseShift15D
from distributed_sddmm_tpu.utils.coo import HostCOO
from distributed_sddmm_tpu.utils.platform import force_fetch

# The five named configurations of `benchmark_dist.cpp:45-82`.
ALGORITHM_FACTORIES: dict[str, Callable[..., DistributedSparse]] = {
    "15d_fusion1": lambda S, R, c, **kw: DenseShift15D(
        S, R=R, c=c, fusion_approach=1, **kw
    ),
    "15d_fusion2": lambda S, R, c, **kw: DenseShift15D(
        S, R=R, c=c, fusion_approach=2, **kw
    ),
    "15d_sparse": lambda S, R, c, **kw: SparseShift15D(S, R=R, c=c, **kw),
    "25d_dense_replicate": lambda S, R, c, **kw: CannonDense25D(S, R=R, c=c, **kw),
    "25d_sparse_replicate": lambda S, R, c, **kw: CannonSparse25D(S, R=R, c=c, **kw),
}

#: Strategies with a double-buffered local-kernel-overlap program
#: variant (``--fusion overlap``): the 1.5D shift family. The 2.5D
#: Cannon strategies have no overlap build — requesting one is a
#: configuration error the sweep driver's skip logic reports.
OVERLAP_CAPABLE = ("15d_fusion1", "15d_fusion2", "15d_sparse")

#: Strategies with a fused block-sparse attention program (``--app
#: attention``): the 1.5D DENSE-shift pair only. The softmax row
#: denominator needs every logit of its row before any SpMM
#: contribution flows, which the dense-shift layout satisfies between
#: its two ring passes; the sparse-shift and Cannon layouts move the
#: values/structure with the ring, so the denominator cannot ride the
#: traveling accumulator — same gating pattern as ``--fusion overlap``.
ATTENTION_CAPABLE = ("15d_fusion1", "15d_fusion2")


def make_algorithm(
    name: str,
    S: HostCOO,
    R: int,
    c: int,
    kernel=None,
    devices=None,
    overlap: bool = False,
    attention: bool = False,
    wire=None,
    **kw,
) -> DistributedSparse:
    """Instantiate one of the five named algorithm configurations.
    ``overlap=True`` selects the double-buffered local-kernel-overlap
    ring programs (shift strategies only); ``attention=True`` asserts
    the strategy can run the fused block-sparse attention pair;
    ``wire`` selects the wire-precision policy (``parallel/wire.py``;
    None = env default, i.e. the f32 identity wire)."""
    if wire is not None:
        kw["wire"] = wire
    if name not in ALGORITHM_FACTORIES:
        raise ValueError(
            f"unknown algorithm {name!r}; available: {sorted(ALGORITHM_FACTORIES)}"
        )
    if overlap:
        if name not in OVERLAP_CAPABLE:
            raise ValueError(
                f"fusion 'overlap' is implemented for the 1.5D shift "
                f"strategies {OVERLAP_CAPABLE}; {name} has no "
                "double-buffered variant"
            )
        kw["overlap"] = True
    if attention and name not in ATTENTION_CAPABLE:
        raise ValueError(
            f"fused attention is implemented for the 1.5D dense-shift "
            f"strategies {ATTENTION_CAPABLE}; {name} cannot carry the "
            "softmax row denominator on its traveling accumulator"
        )
    return ALGORITHM_FACTORIES[name](S, R, c, kernel=kernel, devices=devices, **kw)


def _gat_layers(R: int, num_layers: int = 3) -> list[GATLayer]:
    """GAT spec shaped like the reference's benchmark network but
    parameterized on R (features_per_head) so small test runs work: heads
    (4, 4, 6) as in `benchmark_dist.cpp:90-92`. R=256 is the reference
    network itself: 256 -> (256 x 4) -> (256 x 4) -> (256 x 6)."""
    heads = [4, 4, 6][:num_layers]
    layers = []
    in_feat = R
    for h in heads:
        layers.append(GATLayer(input_features=in_feat, features_per_head=R, num_heads=h))
        in_feat = R * h
    return layers


def _run_vanilla(alg: DistributedSparse, fused: bool, trials: int, warmup: int):
    """The primary measured loop: ``fusedSpMM`` pairs or unfused
    sddmmA-then-spmmA (`benchmark_dist.cpp:117-141`)."""
    A = alg.dummy_initialize(MatMode.A)
    B = alg.dummy_initialize(MatMode.B)
    s_vals = alg.like_s_values(1.0)

    def one_trial():
        if fused:
            out, mid = alg.fused_spmm(A, B, s_vals, MatMode.A)
            return out, mid
        mid = alg.sddmm_a(A, B, s_vals)
        out = alg.spmm_a(A, B, mid)
        return out, mid

    for _ in range(warmup):
        force_fetch(one_trial())
    alg.reset_performance_timers()
    t0 = time.perf_counter()
    out = None
    for _ in range(trials):
        out = one_trial()
    # Execution barrier over every output leaf (utils.platform.force_fetch).
    force_fetch(out)
    elapsed = time.perf_counter() - t0
    return elapsed, {}


def _array_bytes(*arrays) -> int:
    """Total bytes of device arrays (shape x itemsize — the unit one
    HBM read or write of the buffer costs)."""
    total = 0
    for a in arrays:
        total += int(a.size) * int(a.dtype.itemsize)
    return total


def _attention_hbm_bytes(alg, s_vals, A=None, B=None) -> dict:
    """Counted HBM traffic at the program I/O boundary, fused vs
    unfused (PR 9 counted-metric precedent: structural bytes, not
    wall-clock). Every compiled program reads its inputs from HBM and
    writes its outputs back once per dispatch; the unfused
    SDDMM → softmax → SpMM sequence is three programs, so the logits
    and weights round-trip through HBM between stages and the dense
    moving operand plus tile structure are re-read per stage. The fused
    program reads everything once and writes only (out, probs) — the
    strict cut the acceptance gate asserts. Pass the trial loop's
    ``A``/``B`` when they already exist; only shape/itemsize is read."""
    if A is None:
        A = alg.dummy_initialize(MatMode.A)
    if B is None:
        B = alg.dummy_initialize(MatMode.B)
    targs = alg._tile_args(alg.S_tiles, s_vals)
    dense_out = A  # output rides A's sharding/shape
    fused = _array_bytes(A, B, *targs) + _array_bytes(dense_out, s_vals)
    sddmm = _array_bytes(A, B, *targs) + _array_bytes(s_vals)
    softmax = _array_bytes(*targs, s_vals) + _array_bytes(s_vals)
    spmm = _array_bytes(B, *targs) + _array_bytes(dense_out)
    unfused = sddmm + softmax + spmm
    return {
        "fused_bytes": fused,
        "unfused_bytes": unfused,
        "savings_frac": 1.0 - fused / max(unfused, 1),
    }


def _run_attention(alg: DistributedSparse, fused: bool, trials: int,
                   warmup: int):
    """Fused block-sparse attention trials (or the three-program
    unfused baseline with ``fused=False``); the stats carry the counted
    HBM-traffic comparison either way."""
    A = alg.dummy_initialize(MatMode.A)
    B = alg.dummy_initialize(MatMode.B)
    s_vals = alg.like_s_values(1.0)

    def one_trial():
        if fused:
            return alg.fused_attention(A, B, s_vals)
        return alg.attention_unfused(A, B, s_vals)

    for _ in range(warmup):
        force_fetch(one_trial())
    alg.reset_performance_timers()
    t0 = time.perf_counter()
    out = None
    for _ in range(trials):
        out = one_trial()
    force_fetch(out)
    elapsed = time.perf_counter() - t0
    return elapsed, {
        "attention_hbm": _attention_hbm_bytes(alg, s_vals, A=A, B=B)
    }


def _run_gat(alg: DistributedSparse, trials: int, warmup: int, num_layers: int):
    gat = GAT(_gat_layers(alg.R, num_layers), alg)
    for _ in range(warmup):
        force_fetch(gat.forward())
    alg.reset_performance_timers()
    t0 = time.perf_counter()
    out = None
    for _ in range(trials):
        out = gat.forward()
    force_fetch(out)
    return time.perf_counter() - t0, {"gat_heads": [l.num_heads for l in gat.layers]}


def _run_als(
    alg: DistributedSparse,
    trials: int,
    warmup: int,
    cg_iters: int = 10,
    S: Optional[HostCOO] = None,
    checkpoint_dir: Optional[str] = None,
    checkpoint_every: int = 1,
    resume: bool = False,
):
    als = DistributedALS(alg, S_host=S)
    als.initialize_embeddings()
    if warmup:
        als.run_cg(1, cg_iters=cg_iters)  # compiles every program in the loop
        als.initialize_embeddings()
    store = None
    if checkpoint_dir:
        from distributed_sddmm_tpu.resilience import CheckpointStore

        store = CheckpointStore(checkpoint_dir)
    alg.reset_performance_timers()
    t0 = time.perf_counter()
    als.run_cg(
        trials, cg_iters=cg_iters,
        checkpoint=store, checkpoint_every=checkpoint_every, resume=resume,
    )
    force_fetch((als.A, als.B))
    elapsed = time.perf_counter() - t0
    stats = {"als_residual": als.compute_residual(), "cg_iters": cg_iters}
    if als.degraded:
        stats["als_degraded"] = als.degraded
    return elapsed, stats


def pod_record_fields() -> dict:
    """Pod identity for bench/serve records — ONE shape, owned by
    :meth:`dist.init.PodContext.record_fields` (the manifest resolves
    through the same method, so records and manifests cannot drift)."""
    from distributed_sddmm_tpu.dist.init import pod_info

    return pod_info().record_fields()


def benchmark_algorithm(
    S: HostCOO,
    algorithm_name: str,
    output_file: Optional[str],
    fused: bool,
    R: int,
    c: int,
    app: str = "vanilla",
    trials: int = 5,
    warmup: int = 1,
    kernel=None,
    devices=None,
    extra_info: Optional[dict] = None,
    breakdown: bool = False,
    post_build=None,
    checkpoint_dir: Optional[str] = None,
    checkpoint_every: int = 1,
    resume: bool = False,
    overlap: bool = False,
    mask: Optional[str] = None,
    wire=None,
) -> dict:
    """Run one benchmark configuration; append a JSON record to
    ``output_file`` (if given) and return it.

    Record schema follows `benchmark_dist.cpp:151-163`: ``alg_info`` (the
    reference's ``json_algorithm_info``), ``fused``, ``app``,
    ``overall_throughput`` in GFLOP/s, and per-op ``perf_stats`` (kernel
    seconds). Observability additions: ``metrics`` (the full per-op
    attribution — kernel vs retry/fault overhead, retries, comm words,
    FLOPs), and — when tracing is active — ``run_id`` and ``trace_path``
    tying the record to its trace + manifest.
    """
    from distributed_sddmm_tpu.obs import metrics as obs_metrics
    from distributed_sddmm_tpu.obs import trace as obs_trace
    from distributed_sddmm_tpu.obs import watchdog as obs_watchdog
    from distributed_sddmm_tpu.resilience import faults

    if app not in ("vanilla", "gat", "als", "attention"):
        raise ValueError(
            f"unknown app {app!r}; expected vanilla | gat | als | attention"
        )
    # Snapshot the plan's event cursor: the events list is cumulative and
    # process-wide, and a sweep emits many records — each must carry only
    # the faults that fired during ITS run.
    _fault_plan = faults.active()
    _events_before = len(_fault_plan.events) if _fault_plan is not None else 0
    # Same cursor discipline for the anomaly watchdog.
    _watchdog = obs_watchdog.active()
    _anomalies_before = len(_watchdog.events) if _watchdog is not None else 0
    if breakdown and (app != "vanilla" or not fused):
        # Fail before any measurement: the attribution times the fusedSpMM
        # op, so injecting it into unfused or gat/als records would mix ops
        # and units in one JSONL file.
        raise ValueError(
            "--breakdown requires app='vanilla' and fused=True (it "
            "attributes the fusedSpMM op)"
        )

    # Program-store attribution: the record carries how many programs
    # this run compiled live vs recalled from disk (GLOBAL counter
    # deltas — the runstore's cold-start column reads them).
    _prog_before = {
        k: obs_metrics.GLOBAL.get(k)
        for k in ("program_store_hits", "program_store_misses",
                  "live_compiles")
    }
    # Dynamic-structure attribution: rebind/spill/retrace deltas for
    # runs that churn the sparse pattern (dynstruct builds; zero for
    # static runs, and the record section still appears so the
    # ``dynstruct:`` gate axes have a denominator).
    _dyn_before = {
        k: obs_metrics.GLOBAL.get(k)
        for k in ("dynstruct_rebinds", "dynstruct_bucket_spills",
                  "structure_retraces")
    }
    # XLA-cost cursor: only programs THIS run resolved contribute to
    # its analytic-vs-XLA FLOP cross-check (a sweep's earlier cells
    # compiled at other geometries).
    from distributed_sddmm_tpu import programs as program_store_mod

    _cost_cursor = program_store_mod.cost_log_len()

    alg = make_algorithm(algorithm_name, S, R, c, kernel=kernel,
                         devices=devices, overlap=overlap,
                         attention=app == "attention", wire=wire)
    # Bind the strategy (and the app chains built on it) to the active
    # persistent program store under the problem fingerprint — the
    # strategy-config tag in the key keeps sweep cells apart. No active
    # store (tests, --no-store environments): the pre-PR 6 jit path.
    from distributed_sddmm_tpu import programs as program_store_mod

    if program_store_mod.active() is not None:
        from distributed_sddmm_tpu.autotune.fingerprint import (
            Problem, machine_signature, make_fingerprint,
        )

        _p, _backend, _kernels = machine_signature(devices)
        program_store_mod.bind_strategy(
            alg,
            make_fingerprint(Problem.from_coo(S, R), _p, _backend,
                             _kernels).key,
        )
    if post_build is not None:
        # Hook for callers that prepare the strategy before any program
        # runs — e.g. tpu_apps injecting offline-AOT-compiled executables.
        post_build(alg)

    with obs_trace.span(
        "bench", algorithm=algorithm_name, app=app, R=R, c=c,
        fused=bool(fused), trials=trials,
    ):
        if app == "vanilla":
            elapsed, app_stats = _run_vanilla(alg, fused, trials, warmup)
        elif app == "attention":
            elapsed, app_stats = _run_attention(alg, fused, trials, warmup)
        elif app == "gat":
            elapsed, app_stats = _run_gat(alg, trials, warmup, num_layers=3)
        else:
            elapsed, app_stats = _run_als(
                alg, trials, warmup, S=S,
                checkpoint_dir=checkpoint_dir,
                checkpoint_every=checkpoint_every,
                resume=resume,
            )

    # SDDMM+SpMM pair = 2 ops x 2*nnz*R flops each (`benchmark_dist.cpp:147-149`).
    nnz = S.nnz
    throughput = 2.0 * nnz * 2.0 * alg.R * trials / max(elapsed, 1e-12) / 1e9

    perf_stats = alg.json_perf_statistics()
    if breakdown:
        # Region attribution via collective-ablated program variants
        # (reference region timers, `distributed_sparse.h:205-261`). The
        # breakdown REPLACES the whole-call counters: strategies whose
        # fused_spmm delegates to timed sddmm_a/spmm_a would otherwise
        # leave those (collective-inclusive) counters alongside the
        # ablated regions and double-count comm time into Computation.
        A = alg.dummy_initialize(MatMode.A)
        B = alg.dummy_initialize(MatMode.B)
        s_vals = alg.like_s_values(1.0)
        A, B = alg.initial_shift(A, B, KernelMode.SDDMM_A)
        perf_stats = alg.measure_breakdown(
            A, B, s_vals, op="fusedSpMM", trials=trials
        )

    record = {
        "algorithm": algorithm_name,
        "app": app,
        "R": alg.R,
        "c": c,
        "fused": bool(fused),
        "fusion": "overlap" if overlap else "sequential",
        # Attention runs only: the --mask spec (a runstore config axis —
        # mask families must not pool into each other's baselines).
        "mask": mask if app == "attention" else None,
        "num_trials": trials,
        "elapsed": elapsed,
        "overall_throughput": throughput,
        "kernel": getattr(alg.kernel, "name", type(alg.kernel).__name__),
        "kernel_variant": realized_kernel_variant(alg),
        # The REALIZED wire policy (a runstore config axis like
        # kernel_variant: a bf16-wire run must never pool into an f32
        # baseline). The label keeps role overrides distinguishable —
        # bf16 and bf16.reduce=bf16 are different numerics and must
        # not share a baseline. "f32" for default runs; pre-PR-15 docs
        # carry None, which the store's axis matcher normalizes to f32.
        "wire": alg.wire.label,
        # Pod identity: the runstore indexes these and gates on
        # num_processes, so a future multi-host record can never pool
        # into a single-process baseline.
        **pod_record_fields(),
        "alg_info": alg.json_algorithm_info(),
        "perf_stats": perf_stats,
        "metrics": alg.metrics.to_dict(),
        "program_store": {
            k: obs_metrics.GLOBAL.get(k) - v
            for k, v in _prog_before.items()
        },
        "dynstruct": {
            k: obs_metrics.GLOBAL.get(k) - v
            for k, v in _dyn_before.items()
        },
        **app_stats,
        **(extra_info or {}),
    }
    # Analytic-vs-XLA FLOP cross-check: XLA's own cost_analysis numbers
    # for the programs this run resolved, joined per op. The watchdog
    # flags beyond-band disagreement; the run store turns the ratio
    # into a gate axis (xla:<op>_flops).
    _xla_cost = program_store_mod.xla_cost_summary(
        record["metrics"], since=_cost_cursor
    )
    if _xla_cost:
        record["xla_cost"] = _xla_cost
        if _watchdog is not None:
            _watchdog.check_xla_costs(record["metrics"], _xla_cost["ops"])
    if obs_trace.enabled():
        record["run_id"] = obs_trace.run_id()
        record["trace_path"] = obs_trace.trace_path()
        # Refresh the manifest now that the backend is certainly up —
        # the copy written at enable() time may predate backend init and
        # so lack device facts (manifest collection never initializes a
        # backend itself).
        from distributed_sddmm_tpu.obs import manifest as obs_manifest

        obs_manifest.write_for_trace(obs_trace.tracer())
    if _fault_plan is not None:
        # A record produced under fault injection must say so — and which
        # faults actually fired — or sweep files silently mix poisoned and
        # clean measurements.
        record["faults_fired"] = [
            {"site": s, "kind": k, "call": n}
            for s, k, n in _fault_plan.events[_events_before:]
        ]
    if _watchdog is not None:
        # End-of-run anomaly summary — present (possibly empty) whenever
        # the watchdog ran, so a clean record under monitoring is
        # distinguishable from an unmonitored one.
        record["anomalies"] = _watchdog.summary(since=_anomalies_before)
    if output_file:
        # non-atomic-ok: append-only record stream (the -o contract).
        with open(output_file, "a") as f:
            f.write(json.dumps(record) + "\n")

    from distributed_sddmm_tpu.obs import store as obs_store

    run_store = obs_store.active()
    if run_store is not None:
        # Cross-run persistence is best-effort: a full disk or torn
        # index must cost the history entry, never the benchmark.
        try:
            run_store.ingest_record(record)
        except Exception as e:  # noqa: BLE001
            from distributed_sddmm_tpu.obs import log as obs_log

            obs_log.warn("store", "run-store ingest failed",
                         error=f"{type(e).__name__}: {e}")
    return record
