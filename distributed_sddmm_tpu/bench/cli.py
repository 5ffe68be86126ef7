"""Unified benchmark CLI: ``python -m distributed_sddmm_tpu.bench <cmd>``.

Replaces the reference's positional-argv executables with argparse
subcommands:

* ``er``      — R-mat / Erdos-Renyi synthetic benchmark
  (`/root/reference/bench_erdos_renyi.cpp:19-118`)
* ``file``    — matrix-market file benchmark
  (`/root/reference/bench_file.cpp:19-101`)
* ``heatmap`` — R-sweep for the winner heatmap
  (`/root/reference/bench_heatmap.cpp:19-107`)
* ``permute`` — random row/col permutation of a .mtx file
  (`/root/reference/random_permute.cpp:19-59`)
* ``verify``  — fingerprint cross-check of all algorithms
  (`/root/reference/scratch.cpp:26-76`)
* ``kernels`` — single-device local-kernel sweep
  (`/root/reference/local_kernel_benchmark.cpp:109-305`)
* ``overlap`` — comm/compute overlap experiment
  (`/root/reference/test_async_strategies.cpp:14-103`)
* ``baseline`` — external-competitor host SpMM baseline
  (`/root/reference/petsc_baseline/spmm_test.cpp:111-157`)
* ``serve``   — online serving load test (no reference analog): warm
  engine + dynamic micro-batching + open-loop Poisson arrivals with
  SLO-gated latency (``distributed_sddmm_tpu/serve/``)

Cross-run observability subcommands (no reference analog — the obs
layer's store/regress/report half):

* ``history``     — list the persistent run store (``obs/store.py``)
* ``compare``     — per-phase delta table between two stored runs
* ``gate``        — CI regression gate vs a rolling baseline
  (exit 0 pass / 2 regression / 3 insufficient data)
* ``backfill``    — ingest the committed round 1–5 BENCH/MULTICHIP
  records into the store
* ``report-html`` — self-contained HTML dashboard (``obs/report.py``)
* ``report-trace``— per-phase aggregate of one trace file
* ``trace-merge`` — offset-align and merge per-process trace shards
  into one schema-valid trace (``obs/tracemerge.py``)
* ``trace-export``— convert any schema-valid trace (merged multi-shard
  included) to Chrome trace-event JSON openable in Perfetto, request
  chains drawn as cross-thread flows (``obs/traceexport.py``)
* ``top``         — live serving telemetry view over the sampler's
  JSONL stream or a live ``--admin-port`` endpoint; ``--serve``
  re-exports a telemetry stream as /metrics (``obs/telemetry.py``,
  ``obs/httpexp.py``)
* ``lint``        — repo-wide invariant analyzer: the discipline
  checkers over the package with the committed baseline applied
  (``analysis/``; exit 0 clean / 2 new findings / 3 usage error)
* ``env``         — the declared ``DSDDMM_*`` env-knob table
  (``utils/envreg.py``; ``--markdown`` regenerates the README block)

Benchmark-producing subcommands (``er``/``file``/``heatmap``) persist
every record into the run store automatically (``--no-runstore`` opts
out) and accept ``--watchdog warn|strict`` for in-run anomaly
monitoring (``obs/watchdog.py``).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

from distributed_sddmm_tpu.bench import harness
from distributed_sddmm_tpu.bench.harness import (
    ALGORITHM_FACTORIES,
    benchmark_algorithm,
)
from distributed_sddmm_tpu.utils.coo import HostCOO

# `bench_erdos_renyi.cpp:50-115`: "15d" runs both fusion strategies, "25d"
# runs both replication strategies.
ALG_GROUPS = {
    "15d": ["15d_fusion1", "15d_fusion2", "15d_sparse"],
    "25d": ["25d_dense_replicate", "25d_sparse_replicate"],
    "all": list(ALGORITHM_FACTORIES),
}

# `bench_heatmap.cpp:33-35`.
HEATMAP_R_VALUES = [64, 128, 192, 256, 320, 384, 448]


def _resolve_algs(name: str) -> list[str]:
    if name == "auto":
        # Autotuned: the plan (algorithm, c, kernel) is selected per
        # (matrix, R) in _run_configs through the autotune subsystem.
        return ["auto"]
    if name in ALG_GROUPS:
        return ALG_GROUPS[name]
    if name in ALGORITHM_FACTORIES:
        return [name]
    raise SystemExit(
        f"unknown algorithm {name!r}; expected one of "
        f"{sorted(ALGORITHM_FACTORIES) + sorted(ALG_GROUPS) + ['auto']}"
    )


def _get_kernel(name: str, variant: str | None = None):
    from distributed_sddmm_tpu.ops import get_kernel

    if variant:
        from distributed_sddmm_tpu.codegen import make_banked_kernel

        if name not in ("pallas", "auto"):
            raise SystemExit("--kernel-variant requires the pallas kernel")
        return make_banked_kernel(variant)
    return get_kernel(name)


def _run_configs(S, alg_names, args, r_values=None):
    breakdown = getattr(args, "breakdown", False)
    if breakdown and (args.app != "vanilla" or args.fused != "yes"):
        # Raise here, not inside the loop: the per-config ValueError catch
        # below is for divisibility skips and would silently swallow this
        # usage error, "succeeding" with zero records.
        raise SystemExit(
            "--breakdown requires --app vanilla and --fused yes "
            "(it attributes the fusedSpMM op)"
        )
    records = []
    for alg in alg_names:
        for R in r_values or [args.R]:
            plan = None
            if alg == "auto":
                # Autotuned path: fingerprint the problem, recall or select
                # a plan (algorithm + c + kernel); the positional c and
                # --kernel are superseded by the plan's choices.
                from distributed_sddmm_tpu.autotune import Problem, get_plan

                mode = getattr(args, "plan_mode", "model")
                plan = get_plan(
                    Problem.from_coo(S, R),
                    S=S if mode in ("auto", "measure") else None,
                    mode=mode,
                )
                run_alg, run_c, kernel = plan.algorithm, plan.c, plan.make_kernel()
                print(
                    f"plan[{plan.source}] {run_alg} c={run_c} "
                    f"kernel={plan.kernel}"
                    + (f" variant={plan.variant}" if plan.variant else "")
                    + (f" wire={plan.wire}" if plan.wire else "")
                    + (" (chunked)" if plan.gather_budget else ""),
                    file=sys.stderr,
                )
            else:
                run_alg, run_c, kernel = alg, args.c, _get_kernel(
                    args.kernel, getattr(args, "kernel_variant", None)
                )
            for fused in ([True, False] if args.fused == "both" else [args.fused == "yes"]):
                # The plan's Pallas block config applies at strategy BUILD
                # (tile ingest bakes the geometry), so the whole benchmark
                # call runs under the plan's knobs — otherwise the record
                # would claim a block config that never ran.
                if plan is not None:
                    from distributed_sddmm_tpu.autotune.measure import block_knobs

                    knobs = block_knobs(plan.candidate())
                else:
                    import contextlib

                    knobs = contextlib.nullcontext()
                try:
                    with knobs:
                        rec = benchmark_algorithm(
                            S,
                            run_alg,
                            args.output_file,
                            fused=fused,
                            R=R,
                            c=run_c,
                            app=args.app,
                            trials=args.trials,
                            warmup=args.warmup,
                            kernel=kernel,
                            breakdown=getattr(args, "breakdown", False),
                            extra_info={"plan": plan.to_dict()} if plan else None,
                            checkpoint_dir=getattr(args, "checkpoint_dir", None),
                            checkpoint_every=getattr(args, "checkpoint_every", 1),
                            resume=getattr(args, "resume", False),
                            overlap=getattr(args, "fusion", None) == "overlap",
                            mask=(
                                getattr(args, "mask", None)
                                if args.app == "attention" else None
                            ),
                            # Plan-routed runs realize the plan's own
                            # comm_dtype axis; explicit algorithms take
                            # the CLI policy.
                            wire=(plan.wire if plan is not None
                                  else getattr(args, "wire", None)),
                        )
                except ValueError as e:
                    # Divisibility constraints differ per algorithm
                    # (reference exits; the sweep driver skips instead).
                    print(f"skip {run_alg} R={R} c={run_c}: {e}", file=sys.stderr)
                    continue
                records.append(rec)
                print(
                    json.dumps(
                        {
                            "algorithm": run_alg,
                            "R": R,
                            "c": run_c,
                            "fused": fused,
                            "elapsed": round(rec["elapsed"], 4),
                            "GFLOPs": round(rec["overall_throughput"], 3),
                        }
                    )
                )
    return records


def _add_common(p: argparse.ArgumentParser) -> None:
    p.add_argument(
        "--app", default="vanilla",
        choices=["vanilla", "gat", "als", "attention"],
    )
    p.add_argument(
        "--mask", default="window:16", metavar="SPEC",
        help="with --app attention: the block-sparse mask family — "
        "window:<w> (sliding window), bigbird:w=..,g=..,r=.. "
        "(window + global + random), or graph (the generated/loaded "
        "matrix's pattern, the GAT adjacency path); the benchmark "
        "matrix becomes the mask and the spec rides into records as a "
        "gate config axis (distributed_sddmm_tpu/masks.py)",
    )
    p.add_argument("--trials", type=int, default=5)
    p.add_argument("--warmup", type=int, default=1)
    p.add_argument("--kernel", default="auto", help="xla | pallas | auto")
    p.add_argument(
        "--kernel-variant", default=None, metavar="VID",
        help="force a codegen kernel-variant id (e.g. v1.rb32.rm) on the "
        "pallas kernel; plans select one automatically via --algorithm auto",
    )
    p.add_argument(
        "--plan-mode", default="model", choices=["model", "auto", "measure"],
        help="with an 'auto' algorithm: 'model' selects by cost model / "
        "cache only (fast, no trial runs); 'measure' times the top "
        "candidates first; 'auto' measures when possible",
    )
    p.add_argument(
        "--wire", default=None, choices=["f32", "bf16"],
        help="wire-precision policy for the distributed collectives "
        "(parallel/wire.py): 'bf16' halves gather/ring payload bytes "
        "with f32 accumulation everywhere; 'f32' (and the default, "
        "absent DSDDMM_WIRE) is the bit-identical identity wire. With "
        "--algorithm auto the plan's comm_dtype axis supersedes this; "
        "gated structurally by WIRE_HLO.json",
    )
    p.add_argument("--fused", default="yes", choices=["yes", "no", "both"])
    p.add_argument(
        "--fusion", default="sequential", choices=["sequential", "overlap"],
        help="ring-loop build for the 1.5D shift strategies: 'sequential' "
        "(kernel then ppermute per tile) or 'overlap' (double-buffered "
        "local kernel overlap — the next tile's ppermute is issued before "
        "the current tile's kernel, the reference's BufferPair strategy); "
        "bit-identical results, gated structurally by "
        "`bench overlap --fusion-hlo`",
    )
    p.add_argument(
        "--breakdown", action="store_true",
        help="add {Replication, Propagation, Computation} region attribution "
        "to perf_stats (collective-ablation timing; run on a standard "
        "backend, e.g. the CPU test mesh)",
    )
    p.add_argument("-o", "--output-file", default=None, help="append JSON records here")
    p.add_argument(
        "--faults", default=None, metavar="SPEC",
        help="activate a fault-injection plan: inline JSON spec list (or "
        "{'seed','specs'} dict) or @/path/to/plan.json; equivalent to the "
        "DSDDMM_FAULTS env var (see resilience/faults.py)",
    )
    p.add_argument(
        "--checkpoint-dir", default=None, metavar="DIR",
        help="persist app state (ALS factors) under DIR atomically",
    )
    p.add_argument(
        "--checkpoint-every", type=int, default=1, metavar="N",
        help="checkpoint every N alternating steps (with --checkpoint-dir)",
    )
    p.add_argument(
        "--resume", action="store_true",
        help="resume from the newest valid checkpoint in --checkpoint-dir "
        "instead of step 0 (corrupt checkpoints scan back; none = fresh)",
    )
    p.add_argument(
        "--trace", nargs="?", const="1", default=None, metavar="PATH",
        help="emit a structured JSONL trace (spans, comm counters, "
        "resilience events) + run manifest; PATH is a .jsonl file or a "
        "directory, default artifacts/traces/<run_id>.jsonl "
        "(equivalent to DSDDMM_TRACE)",
    )
    p.add_argument(
        "--profile", default=None, metavar="LOGDIR",
        help="capture a jax.profiler trace into LOGDIR "
        "(TensorBoard-readable) with named annotations per compiled "
        "program (equivalent to DSDDMM_PROFILE)",
    )
    p.add_argument(
        "--watchdog", default=None, choices=["warn", "strict"],
        help="in-run anomaly monitor: EWMA step-time spikes/drift, "
        "repair storms, comm-vs-costmodel mismatch; 'warn' reports "
        "(anomaly trace events + an 'anomalies' record field), 'strict' "
        "escalates through the resilience ladder (equivalent to "
        "DSDDMM_WATCHDOG)",
    )
    p.add_argument(
        "--flightrec", nargs="?", const="1", default=None, metavar="DIR",
        help="arm the anomaly flight recorder: the tracer keeps an "
        "in-memory ring of recent spans, and every watchdog anomaly "
        "dumps it (plus metrics/telemetry snapshots, plus a short "
        "jax.profiler window when --profile is also armed) to "
        "artifacts/flightrec/<run_id>/; DIR relocates (equivalent to "
        "DSDDMM_FLIGHTREC)",
    )
    p.add_argument(
        "--no-runstore", action="store_true",
        help="do not persist this run into the run store "
        "(artifacts/runstore); DSDDMM_RUNSTORE relocates or disables it",
    )


def build_parser() -> argparse.ArgumentParser:
    """The CLI's argument parser (importable for parse-only validation,
    e.g. the pod runner's --dry-run)."""
    ap = argparse.ArgumentParser(prog="distributed_sddmm_tpu.bench", description=__doc__)
    sub = ap.add_subparsers(dest="cmd", required=True)

    er = sub.add_parser("er", help="synthetic R-mat benchmark")
    er.add_argument("log_m", type=int, help="log2 of matrix side")
    er.add_argument("edge_factor", type=int, help="average nnz per row")
    er.add_argument("alg", help="algorithm name or group (15d | 25d | all)")
    er.add_argument("R", type=int)
    er.add_argument("c", type=int)
    _add_common(er)

    fi = sub.add_parser("file", help="matrix-market file benchmark")
    fi.add_argument("path", help=".mtx file")
    fi.add_argument("alg")
    fi.add_argument("R", type=int)
    fi.add_argument("c", type=int)
    fi.add_argument("--permute", action="store_true", help="random row/col permutation first")
    _add_common(fi)

    hm = sub.add_parser("heatmap", help="R-value sweep on one synthetic matrix")
    hm.add_argument("log_m", type=int)
    hm.add_argument("edge_factor", type=int)
    hm.add_argument("c", type=int)
    hm.add_argument("--alg", default="all")
    hm.add_argument("--r-values", type=int, nargs="+", default=HEATMAP_R_VALUES)
    _add_common(hm)
    hm.set_defaults(R=None)

    pm = sub.add_parser("permute", help="randomly permute a .mtx file")
    pm.add_argument("path")
    pm.add_argument("--seed", type=int, default=0)
    pm.add_argument("-o", "--output-file", default=None, help="default <in>-permuted.mtx")

    kn = sub.add_parser("kernels", help="single-device local-kernel sweep")
    kn.add_argument("--log-m", type=int, nargs="+", default=None)
    kn.add_argument("--nnz-per-row", type=int, nargs="+", default=None)
    kn.add_argument("--r-values", type=int, nargs="+", default=None)
    kn.add_argument("--kernels", nargs="+", default=["xla", "pallas"])
    kn.add_argument("--trials", type=int, default=5)
    kn.add_argument("-o", "--output-file", default=None)

    ov = sub.add_parser("overlap", help="comm/compute overlap experiment")
    ov.add_argument("--block", type=int, default=1024)
    ov.add_argument("--steps-work", type=int, default=4)
    ov.add_argument("--trials", type=int, default=10)
    ov.add_argument(
        "--hlo-topology", default=None, metavar="NAME",
        help="also AOT-compile for this TPU topology (e.g. v5e:2x4) and "
        "report the structural start/compute/done overlap evidence",
    )
    ov.add_argument(
        "--fusion-hlo", default=None, metavar="TOPOLOGY", nargs="?",
        const="v5e:2x4",
        help="AOT-compile the 1.5D dense-shift fused program (with "
        "--fusion overlap's double-buffered build) for a TPU topology "
        "and report whether collective-permute-start/done bracket the "
        "per-step local kernel — the --fusion overlap structural gate "
        "(set TPU_SKIP_MDS_QUERY=1 on machines without TPU metadata)",
    )
    ov.add_argument(
        "--fusion-mode", default="overlap",
        choices=["overlap", "sequential"],
        help="which ring-loop build --fusion-hlo compiles (default "
        "overlap; 'sequential' probes the baseline build for comparison)",
    )
    ov.add_argument("-o", "--output-file", default=None)

    bl = sub.add_parser("baseline", help="external host-CPU SpMM baseline")
    bl.add_argument("log_m", type=int)
    bl.add_argument("edge_factor", type=int)
    bl.add_argument("R", type=int)
    bl.add_argument("--iters", type=int, default=10)
    bl.add_argument("-o", "--output-file", default=None)

    sv = sub.add_parser(
        "serve",
        help="online serving load test: warm engine (autotune-planned "
        "strategy), dynamic micro-batching, open-loop Poisson arrivals, "
        "SLO-gated latency report (serve/); the record persists to the "
        "run store so `bench gate` regresses p99/shed-rate",
    )
    sv.add_argument("--app", default="als",
                    choices=["als", "gat", "attention"])
    sv.add_argument(
        "--mask", default="window:16", metavar="SPEC",
        help="with --app attention: block-sparse mask family for the "
        "warm context (window:<w> | bigbird:w=..,g=..,r=.. | graph — "
        "graph uses the generated R-mat's pattern)",
    )
    sv.add_argument(
        "--window", type=int, default=None, metavar="W",
        help="with --app attention: per-request sliding-window "
        "half-width (default DSDDMM_ATTN_SERVE_WINDOW)",
    )
    sv.add_argument("--log-m", type=int, default=8, help="log2 matrix side")
    sv.add_argument("--edge-factor", type=int, default=8)
    sv.add_argument("--R", type=int, default=16)
    sv.add_argument("--duration", type=float, default=10.0,
                    metavar="SECONDS", help="load-generation window")
    sv.add_argument("--rate", type=float, default=30.0, metavar="HZ",
                    help="offered Poisson arrival rate (requests/s)")
    sv.add_argument("--max-batch", type=int, default=8)
    sv.add_argument("--max-depth", type=int, default=64,
                    help="admission bound; beyond it requests shed")
    sv.add_argument("--max-wait-ms", type=float, default=5.0,
                    help="micro-batch linger after the first arrival")
    sv.add_argument("--k", type=int, default=10, help="ALS top-k size")
    sv.add_argument("--train-steps", type=int, default=2,
                    help="ALS warm-model alternating steps before serving")
    sv.add_argument("--seed", type=int, default=0)
    sv.add_argument("--oracle-every", type=int, default=8,
                    help="oracle-check every Nth request (0 disables)")
    sv.add_argument(
        "--slo", default=None, metavar="SPEC",
        help="SLO spec 'p99_ms=250,err_rate=0.01' (default DSDDMM_SLO); "
        "violations exit 2",
    )
    sv.add_argument(
        "--plan-mode", default="model", choices=["model", "auto", "measure"],
    )
    sv.add_argument("-o", "--output-file", default=None,
                    help="append the JSON record here")
    sv.add_argument(
        "--faults", default=None, metavar="SPEC",
        help="fault plan: JSON spec, @path, or the comma shorthand "
        "('delay,nan' expands to probabilistic faults at the execute/"
        "output sites); the engine must shed/degrade, never crash",
    )
    sv.add_argument("--trace", nargs="?", const="1", default=None,
                    metavar="PATH")
    sv.add_argument(
        "--telemetry", nargs="?", const="1", default=None, metavar="DIR",
        help="sample live telemetry (queue depth, latency histogram, "
        "shed/degrade, program-store hits, SLO burn rate) to "
        "artifacts/telemetry/<run_id>.jsonl every --telemetry-interval "
        "seconds; DIR relocates (equivalent to DSDDMM_TELEMETRY); "
        "watch it live with `bench top`",
    )
    sv.add_argument("--telemetry-interval", type=float, default=0.5,
                    metavar="SECONDS")
    sv.add_argument("--profile", default=None, metavar="LOGDIR")
    sv.add_argument("--watchdog", default=None, choices=["warn", "strict"])
    sv.add_argument(
        "--admin-port", type=int, default=None, metavar="PORT",
        help="serve the live operational surface on 127.0.0.1:PORT "
        "(0 = ephemeral): Prometheus /metrics, /healthz + /readyz "
        "(503 once the SLO error budget burns past 1x), "
        "/debug/requests recent-timeline ring, /snapshot for "
        "`bench top --admin-port` (obs/httpexp.py); the record gains "
        "an admin_port field",
    )
    sv.add_argument(
        "--flightrec", nargs="?", const="1", default=None, metavar="DIR",
        help="arm the anomaly flight recorder (see the offline "
        "subcommands' --flightrec; equivalent to DSDDMM_FLIGHTREC)",
    )
    sv.add_argument(
        "--tuner", action="store_true",
        help="run the background closed-loop tuner against the live "
        "engine (tuner/): mine trigger gauges, re-measure candidates "
        "off the request path, shadow-validate and hot-swap a winning "
        "kernel variant mid-load; the record gains tuner/"
        "time_to_adapt_s fields and `bench gate` regresses the new "
        "tuner:time_to_adapt axis (equivalent to DSDDMM_TUNER; "
        "DSDDMM_TUNER_* knobs pace it)",
    )
    sv.add_argument("--no-runstore", action="store_true")
    sv.add_argument(
        "--serve-http", action="store_true",
        help="replica mode: instead of generating load, accept requests "
        "over the admin server's POST /submit until SIGTERM, then drain "
        "and print the serving record as the last stdout line (the "
        "fleet manager's replica contract; implies --admin-port 0 when "
        "unset)",
    )
    sv.add_argument(
        "--tenants", default=None, metavar="SPEC",
        help="multi-tenant QoS classes 'name[:weight[:slo]];...' (e.g. "
        "'premium:3:p99_ms=250;batch:1'): weighted-fair dequeue across "
        "classes, per-tenant shed counters and burn-rate gate axes "
        "(default DSDDMM_TENANTS)",
    )

    fl = sub.add_parser(
        "fleet",
        help="serving-fleet harness: spawn N `bench serve --serve-http` "
        "replicas behind the front router (fleet/), drive an open-loop "
        "HTTP load with a multi-tenant mix, optionally inject a seeded "
        "chaos schedule (--chaos 'wedge:r1@0.3/1s;corrupt@0.6;kill@0.8'),"
        " and pin that replies stay bit-identical to a single-engine "
        "oracle while availability holds above --availability-floor and "
        "every gray fault is detected (breaker open / quarantine) within "
        "--detect-deadline; the record lands in the run store with "
        "fleet:availability / fleet:audit_mismatch / per-tenant "
        "serve:burn_rate gate axes",
    )
    fl.add_argument("--replicas", type=int, default=None, metavar="N",
                    help="serve-role replica count (default "
                    "DSDDMM_FLEET_REPLICAS or 2)")
    fl.add_argument("--chaos", default=None, metavar="SCHEDULE",
                    help="seeded deterministic chaos schedule "
                    "(resilience/chaos.py grammar): ';'-separated "
                    "kind[:target]@frac[/duration][:param] actions, e.g. "
                    "'kill@0.5;wedge:r1@0.3/1s;partition:r0@0.6/0.5s;"
                    "slow:r2@0.4:80ms;corrupt:r1@0.7'; 'kill-replica' "
                    "stays as sugar for 'kill@0.5' (default DSDDMM_CHAOS "
                    "or none)")
    fl.add_argument("--audit-frac", type=float, default=None,
                    metavar="FRAC",
                    help="fraction of routed requests re-executed on a "
                    "second replica and compared bit-for-bit before "
                    "delivery (default DSDDMM_FLEET_AUDIT_FRAC or 0; "
                    "chaos schedules with a corrupt action default to "
                    "1.0 so the byzantine replica cannot leak bytes)")
    fl.add_argument("--hedge", default=None, metavar="DELAY",
                    help="hedged requests: after this many seconds "
                    "without a primary reply ('on' = p95-derived), "
                    "re-submit to a second replica and take the first "
                    "answer (default DSDDMM_FLEET_HEDGE or off)")
    fl.add_argument("--detect-deadline", type=float, default=5.0,
                    metavar="SECONDS",
                    help="each injected gray fault must show its "
                    "detection signal (wedge/partition -> breaker open, "
                    "corrupt -> quarantine) within this window or the "
                    "drill exits 1")
    fl.add_argument("--app", default="als", choices=["als", "gat"])
    fl.add_argument("--log-m", type=int, default=6)
    fl.add_argument("--edge-factor", type=int, default=4)
    fl.add_argument("--R", type=int, default=8)
    fl.add_argument("--k", type=int, default=5)
    fl.add_argument("--train-steps", type=int, default=1)
    fl.add_argument("--duration", type=float, default=6.0,
                    metavar="SECONDS")
    fl.add_argument("--rate", type=float, default=20.0, metavar="HZ")
    fl.add_argument("--max-batch", type=int, default=4)
    fl.add_argument("--max-depth", type=int, default=32)
    fl.add_argument("--max-wait-ms", type=float, default=5.0)
    fl.add_argument(
        "--tenants", default="premium:3:p99_ms=2000;batch:1",
        metavar="SPEC",
        help="tenant mix for the generated load (same grammar as serve "
        "--tenants)",
    )
    fl.add_argument("--slo", default=None, metavar="SPEC")
    fl.add_argument("--availability-floor", type=float, default=0.95,
                    help="minimum (answered + shed-with-retry)/offered "
                    "fraction; below it the harness exits 3")
    fl.add_argument("--seed", type=int, default=0)
    fl.add_argument("--ready-timeout", type=float, default=300.0,
                    metavar="SECONDS",
                    help="warmup budget for the replica pool")
    fl.add_argument("-o", "--output-file", default=None)
    fl.add_argument("--no-runstore", action="store_true")

    tn = sub.add_parser(
        "tune",
        help="offline closed-loop re-tune of one problem: mine the "
        "runstore's realized history for the fingerprint, re-rank and "
        "re-measure candidates (full plan space — algorithm and c "
        "included, unlike the live tuner's hot-swappable subset), and "
        "store the winner into the plan cache for the next warmup "
        "(tuner/retune.py)",
    )
    tn.add_argument("log_m", type=int, help="log2 of matrix side")
    tn.add_argument("edge_factor", type=int, help="average nnz per row")
    tn.add_argument("R", type=int)
    tn.add_argument(
        "--trial", default="auto", choices=["auto", "counted", "wall"],
        help="trial mode: wall-clock harness runs, deterministic "
        "counted padded-lane trials, or auto (wall on TPU else counted)",
    )
    tn.add_argument("--trials", type=int, default=1)
    tn.add_argument("--timeout", type=float, default=60.0,
                    help="per-trial wall-clock cap in seconds")
    tn.add_argument("--budget", type=float, default=120.0,
                    help="whole-retune elapsed cap in seconds")
    tn.add_argument("--top-k", type=int, default=3)
    tn.add_argument(
        "--dry-run", action="store_true",
        help="report the challenger without writing the plan cache",
    )
    tn.add_argument("--json", action="store_true")
    tn.add_argument(
        "--store", default=None, metavar="DIR",
        help="run-store root mined for realized history (default "
        "artifacts/runstore, or DSDDMM_RUNSTORE)",
    )

    vf = sub.add_parser("verify", help="fingerprint cross-check of algorithms")
    vf.add_argument("--log-m", type=int, default=8)
    vf.add_argument("--edge-factor", type=int, default=8)
    vf.add_argument("--R", type=int, default=16)
    vf.add_argument("--c", type=int, default=1)
    vf.add_argument("--alg", default="all")
    vf.add_argument("--kernel", default="xla")

    rt = sub.add_parser(
        "report-trace",
        help="aggregate a JSONL trace into a per-phase table + comm-volume"
        " vs cost-model comparison (tools/tracereport.py); exits nonzero "
        "on schema violations unless --no-strict",
    )
    rt.add_argument("trace", help="path to a <run_id>.jsonl trace")
    rt.add_argument("--json", action="store_true")
    rt.add_argument("--no-strict", action="store_true")

    tm = sub.add_parser(
        "trace-merge",
        help="offset-align and merge per-process trace shards into one "
        "schema-valid trace (each shard's begin record carries its "
        "perf_counter<->wall-clock origin; the earliest becomes the "
        "merged timeline's zero); exits 2 on unmergeable/invalid shards",
    )
    tm.add_argument(
        "shards", nargs="+",
        help="shard files, shard directories, or a PATH.jsonl stem "
        "(merged with its sibling PATH.shards/ directory)",
    )
    tm.add_argument("-o", "--output-file", default=None,
                    help="default <first shard dir>/<merged id>.jsonl")
    tm.add_argument("--no-strict", action="store_true",
                    help="tolerate (and drop) malformed shard lines")

    te = sub.add_parser(
        "trace-export",
        help="convert a schema-valid trace (merged multi-shard traces "
        "included) to Chrome trace-event JSON openable in Perfetto / "
        "chrome://tracing: one process lane per shard, one thread lane "
        "per thread, spans as B/E pairs on the calibrated clock, and "
        "flow arrows stitching each request's enqueue->batch->reply "
        "chain across threads; exits 2 on an invalid trace",
    )
    te.add_argument("trace", help="path to a <run_id>.jsonl trace")
    te.add_argument("-o", "--output-file", default=None,
                    help="default <trace stem>.chrome.json")
    te.add_argument("--no-strict", action="store_true",
                    help="tolerate (and drop) malformed trace lines")

    tp = sub.add_parser(
        "top",
        help="live serving telemetry view: queue depth, histogram "
        "percentiles, shed/degrade counters, program-store hit rates, "
        "SLO burn rate — over the sampler stream `bench serve "
        "--telemetry` writes to artifacts/telemetry/, or live off a "
        "`bench serve --admin-port` endpoint",
    )
    tp.add_argument(
        "path", nargs="?", default=None,
        help="telemetry .jsonl stream (default: the newest one under "
        "artifacts/telemetry/ or $DSDDMM_TELEMETRY); a missing "
        "explicit path exits 2",
    )
    tp.add_argument(
        "--watch", type=float, default=0.0, metavar="SECONDS",
        help="refresh every N seconds until interrupted (0 = one shot)",
    )
    tp.add_argument(
        "--admin-port", type=int, default=None, metavar="PORT",
        help="read the live /snapshot endpoint of a `bench serve "
        "--admin-port` engine instead of a telemetry file; falls back "
        "to the telemetry stream when the endpoint is unreachable",
    )
    tp.add_argument("--admin-host", default="127.0.0.1", metavar="HOST")
    tp.add_argument(
        "--serve", type=int, default=None, metavar="PORT", dest="serve_port",
        help="standalone exporter: serve Prometheus /metrics (+ "
        "/snapshot, /healthz, /readyz) rendered from the telemetry "
        "stream on 127.0.0.1:PORT (0 = ephemeral) until interrupted — "
        "the admin surface for runs that only wrote --telemetry",
    )

    from distributed_sddmm_tpu.analysis import cli as analysis_cli

    analysis_cli.build_lint_parser(sub.add_parser(
        "lint",
        help="repo-wide invariant analyzer: the six discipline "
        "checkers over the package (analysis/); exit 0 clean, 2 new "
        "findings, 3 usage error",
    ))
    analysis_cli.build_env_parser(sub.add_parser(
        "env",
        help="the DSDDMM_* env-knob registry table (utils/envreg.py); "
        "--markdown regenerates the README block",
    ))

    def _store_arg(p):
        p.add_argument(
            "--store", default=None, metavar="DIR",
            help="run-store root (default artifacts/runstore, or "
            "DSDDMM_RUNSTORE)",
        )

    hi = sub.add_parser("history", help="list the persistent run store")
    _store_arg(hi)
    hi.add_argument("--key", default=None,
                    help="filter to one fingerprint key")
    hi.add_argument("--backend", default=None)
    hi.add_argument("--limit", type=int, default=None, metavar="N",
                    help="newest N runs")
    hi.add_argument("--json", action="store_true")

    cp = sub.add_parser(
        "compare",
        help="per-phase delta table between two stored runs "
        "(run ids, unique prefixes, or latest / latest~N)",
    )
    _store_arg(cp)
    cp.add_argument("run_a")
    cp.add_argument("run_b")
    cp.add_argument("--threshold", type=float, default=0.15,
                    help="relative noise band (default 0.15)")
    cp.add_argument("--json", action="store_true")

    ga = sub.add_parser(
        "gate",
        help="CI regression gate: compare a run against a rolling "
        "baseline of the last K matching runs; exit 0 pass, 2 "
        "regression, 3 insufficient baseline data",
    )
    _store_arg(ga)
    ga.add_argument("run", help="run id / prefix / latest[~N] to judge")
    ga.add_argument("--against", default=None, metavar="RUN",
                    help="explicit baseline run instead of the rolling "
                    "baseline")
    ga.add_argument("--last", type=int, default=5, metavar="K",
                    help="rolling-baseline population (default 5)")
    ga.add_argument("--min-runs", type=int, default=1,
                    help="fewer matching baseline runs than this exits 3")
    ga.add_argument("--threshold", type=float, default=0.15)
    ga.add_argument("--json", action="store_true")

    bf = sub.add_parser(
        "backfill",
        help="ingest the committed historical records (BENCH_r0*.json, "
        "MULTICHIP_r0*.json) into the run store",
    )
    _store_arg(bf)
    bf.add_argument("--root", default=None, metavar="DIR",
                    help="repo root to scan (default: this checkout)")

    rh = sub.add_parser(
        "report-html",
        help="self-contained HTML dashboard: run history, per-phase "
        "trends, latest compare",
    )
    _store_arg(rh)
    rh.add_argument("-o", "--output-file", default=None,
                    help="default <store>/report.html")
    rh.add_argument("--limit", type=int, default=100)
    rh.add_argument("--key", default=None,
                    help="focus fingerprint key for trends/compare "
                    "(default: the newest run's)")
    rh.add_argument("--threshold", type=float, default=0.15)
    return ap


def _run_store(args):
    from distributed_sddmm_tpu.obs import store as obs_store

    if getattr(args, "store", None):
        return obs_store.RunStore(args.store)
    return obs_store.active() or obs_store.RunStore()


def _resolve_run(store, spec: str):
    try:
        doc = store.resolve(spec)
    except ValueError as e:  # ambiguous prefix — say so, with candidates
        raise SystemExit(str(e))
    if doc is None:
        raise SystemExit(
            f"no stored run matches {spec!r} (try 'history'; specs are "
            "run ids, unique prefixes, or latest / latest~N)"
        )
    return doc


def _dispatch_store(args) -> int:
    """The run-store subcommands (no benchmark execution, no backend)."""
    from distributed_sddmm_tpu.obs import regress

    store = _run_store(args)

    if args.cmd == "history":
        rows = store.history(
            key=args.key, backend=args.backend, limit=args.limit
        )
        if args.json:
            print(json.dumps(rows, indent=1))
        else:
            print(regress.render_history(rows))  # cli-output
        return 0

    if args.cmd == "compare":
        a = _resolve_run(store, args.run_a)
        b = _resolve_run(store, args.run_b)
        report = regress.compare(b, doc_a=a, threshold=args.threshold)
        if args.json:
            print(json.dumps(report, indent=1))
        else:
            print(regress.render_compare(report))  # cli-output
        return 0

    if args.cmd == "gate":
        doc = _resolve_run(store, args.run)
        baseline = (
            _resolve_run(store, args.against) if args.against else None
        )
        code, report = regress.gate(
            store, doc, k=args.last, threshold=args.threshold,
            min_runs=args.min_runs, baseline_doc=baseline,
        )
        if args.json:
            print(json.dumps(report, indent=1))
        else:
            if report.get("phases"):
                print(regress.render_compare(report))  # cli-output
            print(f"gate: {report['verdict']} (exit {code})")  # cli-output
        return code

    if args.cmd == "backfill":
        from distributed_sddmm_tpu.obs.store import backfill_historical

        docs = backfill_historical(store, root=args.root)
        print(  # cli-output
            f"backfilled {len(docs)} historical record(s) into {store.root}"
        )
        for d in docs:
            print(f"  {d['run_id']:<32} <- {d.get('source')}")  # cli-output
        return 0

    if args.cmd == "report-html":
        from distributed_sddmm_tpu.obs import report as obs_report

        path = obs_report.build_html(
            store, out_path=args.output_file, limit=args.limit,
            key=args.key, threshold=args.threshold,
        )
        print(f"wrote {path}")  # cli-output
        return 0

    raise AssertionError(args.cmd)


#: Subcommands that execute benchmarks and therefore feed the run store.
_BENCH_CMDS = ("er", "file", "heatmap", "serve", "fleet")


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)

    if args.cmd in ("lint", "env"):
        from distributed_sddmm_tpu.analysis import cli as analysis_cli

        return (analysis_cli.run_lint(args) if args.cmd == "lint"
                else analysis_cli.run_env(args))

    if args.cmd == "report-trace":
        from distributed_sddmm_tpu.tools import tracereport

        sub_argv = [args.trace]
        if args.json:
            sub_argv.append("--json")
        if args.no_strict:
            sub_argv.append("--no-strict")
        return tracereport.main(sub_argv)

    if args.cmd == "trace-merge":
        return _dispatch_trace_merge(args)

    if args.cmd == "trace-export":
        return _dispatch_trace_export(args)

    if args.cmd == "top":
        return _dispatch_top(args)

    if args.cmd in ("history", "compare", "gate", "backfill", "report-html"):
        return _dispatch_store(args)

    if args.cmd == "tune":
        return _dispatch_tune(args)

    if getattr(args, "watchdog", None):
        from distributed_sddmm_tpu.obs import watchdog as obs_watchdog

        obs_watchdog.enable(args.watchdog)
        print(f"[watchdog] {args.watchdog} mode", file=sys.stderr)

    if args.cmd in _BENCH_CMDS:
        from distributed_sddmm_tpu.obs import store as obs_store
        from distributed_sddmm_tpu.utils.platform import enable_compile_cache

        enable_compile_cache()

        if getattr(args, "no_runstore", False):
            # Explicit opt-out must beat the env var: the harness's
            # store.active() would otherwise self-activate from a
            # non-empty DSDDMM_RUNSTORE despite the flag.
            obs_store.disable()
        else:
            # Records persist into the store automatically;
            # DSDDMM_RUNSTORE can relocate (a path) or veto (0/off)
            # this default — one grammar, shared with store.active().
            enabled, root = obs_store.parse_env_spec(
                os.environ.get("DSDDMM_RUNSTORE")
            )
            if enabled:
                obs_store.enable(root)

    if getattr(args, "faults", None):
        from distributed_sddmm_tpu.resilience import FaultPlan, faults

        faults.install(FaultPlan.from_spec(args.faults))
        print("[faults] plan installed from --faults", file=sys.stderr)

    if getattr(args, "trace", None):
        from distributed_sddmm_tpu.obs import trace as obs_trace

        tr = obs_trace.enable(None if args.trace == "1" else args.trace)
        print(f"[trace] writing {tr.path}", file=sys.stderr)

    flightrec_armed = bool(getattr(args, "flightrec", None))
    if flightrec_armed:
        # AFTER --trace: the ring must tap the file tracer when both
        # are armed, not install a memory-only one first.
        from distributed_sddmm_tpu.obs import flightrec as obs_flightrec

        fr = obs_flightrec.enable(
            None if args.flightrec == "1" else args.flightrec,
            profile_window_s=0.25 if getattr(args, "profile", None) else 0.0,
        )
        print(f"[flightrec] armed -> {fr.out_dir}", file=sys.stderr)

    if getattr(args, "profile", None):
        if flightrec_armed:
            # jax.profiler supports one capture at a time: a whole-run
            # capture would make every anomaly window refuse. With the
            # flight recorder armed, --profile means per-anomaly
            # capture windows (dumped next to each flight record), not
            # a whole-run trace.
            print("[profile] flight recorder armed: capturing short "
                  "per-anomaly windows instead of the whole run",
                  file=sys.stderr)
            return _dispatch(args)
        from distributed_sddmm_tpu.obs import profiler as obs_profiler

        with obs_profiler.capture(args.profile):
            return _dispatch(args)
    return _dispatch(args)


def _dispatch_trace_merge(args) -> int:
    """``bench trace-merge``: discover shards, offset-align, write one
    merged trace, re-validate it. Exit 0 valid, 2 unmergeable."""
    from distributed_sddmm_tpu.obs import tracemerge
    from distributed_sddmm_tpu.tools import tracereport

    strict = not args.no_strict
    paths: list = []
    try:
        for spec in args.shards:
            for p in tracemerge.discover(spec):
                if p not in paths:
                    paths.append(p)
        out, merged = tracemerge.write_merged(
            paths, args.output_file, strict=strict
        )
        # Round-trip: the merged file must satisfy the same reader
        # contract any single-process trace does.
        tracereport.load_trace(out, strict=True)
    except (FileNotFoundError, ValueError) as e:
        print(f"trace-merge failed: {e}", file=sys.stderr)
        return 2
    print(json.dumps({
        "merged": str(out),
        "run_id": merged["begin"]["run_id"],
        "shards": len(merged["begin"]["shards"]),
        "spans": len(merged["spans"]),
        "events": len(merged["events"]),
        "skipped_lines": len(merged["errors"]),
    }))
    return 0


def _dispatch_trace_export(args) -> int:
    """``bench trace-export``: one schema-valid trace -> Chrome
    trace-event JSON. Exit 0 written, 2 invalid/unreadable."""
    from distributed_sddmm_tpu.obs import traceexport

    try:
        out, chrome = traceexport.write_chrome(
            args.trace, args.output_file, strict=not args.no_strict
        )
    except (OSError, ValueError) as e:
        print(f"trace-export failed: {e}", file=sys.stderr)
        return 2
    print(json.dumps({"exported": str(out), **chrome["metadata"]}))
    return 0


def _top_source(args):
    """Resolve the ``bench top`` snapshot source.

    Returns ``(read_fn, label)`` where ``read_fn()`` yields the
    snapshot list to render, or raises SystemExit(2) for an explicitly
    named telemetry file that does not exist (a one-line error, not a
    traceback)."""
    import pathlib as _pathlib

    from distributed_sddmm_tpu.obs import telemetry

    if args.admin_port is not None:
        from distributed_sddmm_tpu.obs import httpexp

        def read_live():
            snap = httpexp.fetch_json(
                args.admin_host, args.admin_port, "/snapshot"
            )
            return [snap] if snap else []

        try:
            read_live()  # probe once; unreachable -> fall back to files
            return read_live, (
                f"admin {args.admin_host}:{args.admin_port}"
            )
        except (OSError, ValueError) as e:  # incl. a non-JSON body
            print(
                f"[top] admin endpoint {args.admin_host}:"
                f"{args.admin_port} unreachable ({e}); falling back to "
                "the telemetry stream", file=sys.stderr,
            )

    path = args.path
    if path is not None:
        if not _pathlib.Path(path).exists():
            # One-line contract: a typo'd path must not scroll a
            # traceback past the operator.
            print(f"bench top: no telemetry file at {path}",
                  file=sys.stderr)
            raise SystemExit(2)
    else:
        _enabled, root = telemetry.parse_env_spec(
            os.environ.get("DSDDMM_TELEMETRY")
        )
        path = telemetry.newest_stream(root)
        if path is None:
            print("no telemetry streams found (run `bench serve "
                  "--telemetry` first)", file=sys.stderr)
            raise SystemExit(1)
    return (lambda: telemetry.read_snapshots(path)), str(path)


def _dispatch_top(args) -> int:
    """``bench top``: render the newest telemetry snapshot(s) from a
    file or a live admin endpoint; --watch refreshes until interrupted;
    --serve re-exports the stream as a /metrics endpoint."""
    import time as _time

    from distributed_sddmm_tpu.obs import telemetry

    try:
        read_fn, label = _top_source(args)
    except SystemExit as e:
        return int(e.code or 0)

    if args.serve_port is not None:
        from distributed_sddmm_tpu.obs import httpexp

        def latest():
            snaps = read_fn()
            return snaps[-1] if snaps else None

        server = httpexp.AdminServer(
            snapshot_fn=latest, port=args.serve_port
        )
        server.start()
        print(f"[top] exporting {label} on "
              f"http://127.0.0.1:{server.port}/metrics", file=sys.stderr)
        try:
            while True:
                _time.sleep(1.0)
        except KeyboardInterrupt:
            return 0
        finally:
            server.stop()

    while True:
        try:
            snaps = read_fn()
        except (OSError, ValueError) as e:
            # A live source can vanish mid-watch (serve exited, file
            # unlinked) or answer mid-shutdown garbage (truncated JSON
            # is a ValueError): one line, never a traceback. A watch
            # loop keeps polling — the endpoint may come back.
            print(f"bench top: snapshot source unavailable ({e})",
                  file=sys.stderr)
            if not args.watch:
                return 1
            snaps = []
        if args.watch:
            print("\x1b[2J\x1b[H", end="")  # clear screen between frames
        print(telemetry.render_top(snaps))
        if not args.watch:
            return 0
        try:
            _time.sleep(args.watch)
        except KeyboardInterrupt:
            return 0


def _dispatch_tune(args) -> int:
    """``bench tune``: one offline closed-loop pass for one problem.

    Loads the incumbent plan (cache hit or cost-model selection — the
    same path a warmup takes), mines the runstore's realized history
    for this fingerprint, re-ranks + re-measures the FULL candidate
    space, and stores a winning challenger back into the plan cache so
    the next replica warms straight onto it. Exit 0 either way — "the
    incumbent stands" is a successful tune."""
    from distributed_sddmm_tpu.autotune import Problem, get_plan
    from distributed_sddmm_tpu.autotune.cache import PlanCache
    from distributed_sddmm_tpu.autotune.plan import Plan
    from distributed_sddmm_tpu.tuner import TunerConfig
    # Import the tuner submodules directly (the package deliberately
    # does not re-export the retune() function — it would shadow the
    # `tuner.retune` submodule attribute).
    import distributed_sddmm_tpu.tuner.retune as tuner_retune
    import distributed_sddmm_tpu.tuner.signals as tuner_signals

    S = HostCOO.rmat(log_m=args.log_m, edge_factor=args.edge_factor, seed=0)
    problem = Problem.from_coo(S, args.R)
    if args.dry_run:
        # get_plan stores its selection on a cache miss; a dry run must
        # leave the real cache byte-untouched — serve a genuine hit,
        # else select against a throwaway cache.
        from distributed_sddmm_tpu.autotune.fingerprint import (
            machine_signature, make_fingerprint,
        )

        p_, backend_, kernels_ = machine_signature()
        hit = PlanCache().load(
            make_fingerprint(problem, p_, backend_, kernels_).key
        )
        if hit is not None:
            incumbent = Plan.from_dict(hit)
        else:
            import tempfile

            with tempfile.TemporaryDirectory() as _td:
                incumbent = get_plan(
                    problem, mode="model", cache=PlanCache(_td)
                )
    else:
        incumbent = get_plan(problem, mode="model")

    # _run_store falls back to the default root (artifacts/runstore /
    # DSDDMM_RUNSTORE) when --store is absent — the documented mining
    # source; an empty or missing store simply yields no signals.
    store = _run_store(args)
    signals = tuner_signals.mine_runstore(
        store, incumbent.fingerprint_key, problem, incumbent.predicted_ms,
    )

    # ONE trial-selection rule (TunerConfig.trial_fn): an explicit
    # --trial wall forces harness trials even off-TPU.
    trial_fn = TunerConfig(trial=args.trial).trial_fn()
    challenger = tuner_retune.retune(
        problem, incumbent, S,
        top_k=args.top_k, trials=args.trials, timeout_s=args.timeout,
        max_elapsed_s=args.budget, trial_fn=trial_fn,
    )
    promoted = False
    if challenger is not None and not args.dry_run:
        PlanCache().store(challenger.fingerprint_key, challenger.to_dict())
        promoted = True
    report = {
        "fingerprint_key": incumbent.fingerprint_key,
        "signals": [s.to_dict() for s in signals],
        "incumbent": incumbent.to_dict(),
        "challenger": challenger.to_dict() if challenger else None,
        "promoted": promoted,
        "dry_run": bool(args.dry_run),
    }
    if args.json:
        print(json.dumps(report, indent=1))
    else:
        inc, ch = incumbent, challenger
        print(  # cli-output
            f"incumbent  {inc.algorithm} c={inc.c} kernel={inc.kernel}"
            + (f" variant={inc.variant}" if inc.variant else "")
            + f" [{inc.source}]"
        )
        if ch is None:
            print("challenger none — incumbent stands")  # cli-output
        else:
            print(  # cli-output
                f"challenger {ch.algorithm} c={ch.c} kernel={ch.kernel}"
                + (f" variant={ch.variant}" if ch.variant else "")
                + f" measured={ch.measured_gflops:.3f} GFLOP/s"
                + (" -> plan cache" if promoted else " (dry run)")
            )
    return 0


def _dispatch_serve(args) -> int:
    """``bench serve``: build a warm engine, drive it open-loop, report
    + persist the serving record. Exit 0 on a clean run, 1 on any
    incorrect (oracle-mismatched) reply, 2 on SLO violation — faults
    and shedding are expected operating conditions, not failures."""
    from distributed_sddmm_tpu.obs import trace as obs_trace
    from distributed_sddmm_tpu.obs import watchdog as obs_watchdog
    from distributed_sddmm_tpu.resilience import faults
    from distributed_sddmm_tpu.serve import (
        SLOSpec, build_als_engine, build_attention_engine,
        build_gat_engine, parse_tenants, run_load, tenants_from_env,
    )

    S = HostCOO.rmat(log_m=args.log_m, edge_factor=args.edge_factor, seed=0)
    if args.app == "attention":
        S = _maybe_mask(S, args)
    slo = SLOSpec.parse(args.slo) if args.slo else SLOSpec.from_env()
    tenants = (parse_tenants(args.tenants) if args.tenants
               else tenants_from_env())
    serve_http = bool(getattr(args, "serve_http", False))
    if serve_http and args.admin_port is None:
        args.admin_port = 0  # replica mode NEEDS the ingestion surface
    engine_kw = dict(
        max_batch=args.max_batch, max_depth=args.max_depth,
        max_wait_ms=args.max_wait_ms, tenants=tenants,
    )
    # XLA-cost cursor: warmup + serving programs resolved from here on
    # feed the record's analytic-vs-XLA cross-check.
    from distributed_sddmm_tpu import programs as programs_mod

    _cost_cursor = programs_mod.cost_log_len()
    print(f"[serve] building warm {args.app} engine "
          f"(2^{args.log_m} matrix, R={args.R})", file=sys.stderr)
    if args.app == "als":
        eng = build_als_engine(
            S, R=args.R, train_steps=args.train_steps, k=args.k,
            plan_mode=args.plan_mode, **engine_kw,
        )
    elif args.app == "attention":
        eng = build_attention_engine(
            S, R=args.R, window=args.window, plan_mode=args.plan_mode,
            seed=args.seed, **engine_kw,
        )
    else:
        eng = build_gat_engine(
            S, R=args.R, plan_mode=args.plan_mode, **engine_kw,
        )
    model = eng.workload.model
    d_ops = model.d_ops
    plan = getattr(model, "plan", None)

    # Same cursor discipline as benchmark_algorithm: the record carries
    # only the faults/anomalies of the SERVING window, not warmup's.
    _fault_plan = faults.active()
    _events_before = len(_fault_plan.events) if _fault_plan else 0
    _watchdog = obs_watchdog.active()
    _anomalies_before = len(_watchdog.events) if _watchdog else 0
    d_ops.reset_performance_timers()

    # Live telemetry: a sampler thread snapshotting the engine to
    # artifacts/telemetry/<run_id>.jsonl for `bench top` and post-hoc
    # burn-rate forensics (--telemetry / DSDDMM_TELEMETRY).
    from distributed_sddmm_tpu.obs import telemetry as obs_telemetry

    sampler = None
    tel_spec = args.telemetry or os.environ.get("DSDDMM_TELEMETRY")
    tel_enabled, tel_root = obs_telemetry.parse_env_spec(tel_spec)
    if tel_enabled:
        sampler = obs_telemetry.TelemetrySampler(
            eng, interval_s=args.telemetry_interval, out_dir=tel_root,
            slo=slo,
        )

    # Live operational surface (obs/httpexp.py): started BEFORE warmup
    # so /readyz honestly reports not-ready while the ladder compiles.
    admin = None
    if args.admin_port is not None:
        from distributed_sddmm_tpu.obs import httpexp

        submit_fn = None
        if serve_http:
            def submit_fn(payload, tenant="default", serial=False,
                          timeout_s=30.0, trace_ctx=None):
                # Wire decode is the workload's own clamp (np.asarray
                # normalizes the JSON lists back to the exact dtypes),
                # so an HTTP-submitted payload takes the IDENTICAL
                # path an in-process one does — bit-identical replies.
                if serial:
                    return eng.workload.serial(eng.workload.clamp(payload))
                req = eng.submit(payload, tenant=tenant,
                                 trace_ctx=trace_ctx)
                # Reply accounting is the CLIENT's job (run_load does it
                # in-process); over HTTP that client is this boundary —
                # without it a replica's drained record reads 0 completed
                # and the fleet's per-tenant burn axes go dark.
                try:
                    reply = req.result(timeout_s=timeout_s)
                except Exception:
                    eng.recorder.record_error(tenant)
                    raise
                eng.recorder.record_reply(req)
                return reply

        chaos_fn = None
        if serve_http:
            def chaos_fn(body):
                # Runtime chaos arming (resilience/chaos.ChaosEngine's
                # corrupt action): install a fault plan in THIS running
                # process — env knobs cannot change after spawn. The
                # drill sets guard_mode=repair so a NaN-corrupted reply
                # is repaired to finite-but-WRONG bytes that only the
                # router's cross-replica audit can catch (raise-mode
                # would degrade to the serial oracle and recompute the
                # right answer, defeating the byzantine scenario).
                from distributed_sddmm_tpu.resilience import (
                    faults as res_faults,
                )

                spec = body.get("faults")
                if not isinstance(spec, (dict, list, str)):
                    raise ValueError("body.faults must be a plan spec")
                plan = res_faults.FaultPlan.from_spec(spec)
                res_faults.install(plan)
                mode = body.get("guard_mode")
                if mode is not None:
                    if mode not in ("raise", "repair"):
                        raise ValueError(f"bad guard_mode: {mode!r}")
                    os.environ["DSDDMM_GUARD_MODE"] = str(mode)
                return {"armed": True, "specs": len(plan.specs),
                        "seed": plan.seed, "guard_mode": mode}

        admin = httpexp.AdminServer(
            engine=eng, op_metrics=d_ops.metrics, slo=slo,
            port=args.admin_port, submit_fn=submit_fn,
            chaos_fn=chaos_fn,
        )
        admin.start()
        print(f"[admin] serving http://127.0.0.1:{admin.port} "
              "(/metrics /healthz /readyz /debug/requests /snapshot"
              + (" POST:/submit" if submit_fn else "") + ")",
              file=sys.stderr)

    # An armed flight recorder gets the engine's telemetry snapshot as
    # a dump source — an anomaly record then carries the queue/latency
    # state of the moment it fired.
    from distributed_sddmm_tpu.obs import flightrec as obs_flightrec

    _fr = obs_flightrec.active()
    if _fr is not None:
        _fr.register_source(
            "engine", lambda: obs_telemetry.engine_snapshot(eng, slo=slo)
        )

    # Closed-loop background tuner (--tuner / DSDDMM_TUNER): started
    # once the ladder is warm, paced by the DSDDMM_TUNER_* knobs.
    tuner = None
    tuner_wanted = args.tuner or os.environ.get(
        "DSDDMM_TUNER", ""
    ).lower() in ("1", "on", "true", "yes")
    try:
        eng.start()  # compile-ahead warmup of the whole bucket ladder
        if tuner_wanted:
            from distributed_sddmm_tpu.tuner import BackgroundTuner

            tuner = BackgroundTuner(eng).start()
            print("[tuner] background tuner armed "
                  f"(interval {tuner.config.interval_s}s)", file=sys.stderr)
        if sampler is not None:
            sampler.start()
            print(f"[telemetry] sampling to {sampler.path}",
                  file=sys.stderr)
        if serve_http:
            summary = _serve_until_signal(eng, slo, tenants)
        else:
            summary = run_load(
                eng, duration_s=args.duration, rate_hz=args.rate,
                seed=args.seed, oracle_every=args.oracle_every, slo=slo,
                tenants=tenants,
            )
    finally:
        if tuner is not None:
            tuner.stop()
        if sampler is not None:
            sampler.stop()
        eng.stop()
        if admin is not None:
            admin.stop()

    record = {
        "app": f"serve-{args.app}",
        "algorithm": plan.algorithm if plan else d_ops.algorithm_name,
        "mask": args.mask if args.app == "attention" else None,
        "R": args.R,
        "c": plan.c if plan else d_ops.c,
        "fused": True,
        "kernel": getattr(d_ops.kernel, "name", type(d_ops.kernel).__name__),
        "kernel_variant": eng.workload.kernel_variant,
        # Pod identity (runstore index + gate config axis) — serving
        # records must split across pod shapes like offline ones.
        **harness.pod_record_fields(),
        "num_trials": summary["completed"],
        "elapsed": summary["duration_s"],
        "overall_throughput": None,
        "alg_info": d_ops.json_algorithm_info(),
        "metrics": d_ops.metrics.to_dict(),
        "engine": eng.stats(),
        "serve_config": {
            "rate_hz": args.rate, "duration_s": args.duration,
            "max_batch": args.max_batch, "max_depth": args.max_depth,
            "max_wait_ms": args.max_wait_ms,
            "batch_buckets": list(eng.batch_buckets),
            "inner_buckets": list(eng.workload.inner_buckets),
            "tenants": args.tenants or os.environ.get("DSDDMM_TENANTS"),
            "serve_http": serve_http,
        },
        **summary,
    }
    if plan is not None:
        record["plan"] = plan.to_dict()
    if tuner is not None:
        # The closed-loop fields (MIGRATING): the tuner summary with
        # its promotions list, and time_to_adapt_s lifted to the top
        # level — the `tuner:time_to_adapt` gate axis reads it there.
        record["tuner"] = tuner.summary()
        record["time_to_adapt_s"] = tuner.time_to_adapt_s
    if sampler is not None:
        record["telemetry_path"] = str(sampler.path)
    if admin is not None:
        record["admin_port"] = admin.port
        record["admin_scrapes"] = admin.scrapes
    if _fr is not None:
        record["flightrec_dir"] = str(_fr.out_dir)
    # Analytic-vs-XLA FLOP cross-check over the engine's resolved
    # programs (strategy ops only — serve fold-in programs have no
    # analytic model to disagree with).
    _xla_cost = programs_mod.xla_cost_summary(
        record["metrics"], since=_cost_cursor
    )
    if _xla_cost:
        record["xla_cost"] = _xla_cost
        if _watchdog is not None:
            _watchdog.check_xla_costs(record["metrics"], _xla_cost["ops"])
    if obs_trace.enabled():
        record["run_id"] = obs_trace.run_id()
        record["trace_path"] = obs_trace.trace_path()
        from distributed_sddmm_tpu.obs import manifest as obs_manifest

        obs_manifest.write_for_trace(obs_trace.tracer())
    if _fault_plan is not None:
        record["faults_fired"] = [
            {"site": s, "kind": k, "call": n}
            for s, k, n in _fault_plan.events[_events_before:]
        ]
    if _watchdog is not None:
        record["anomalies"] = _watchdog.summary(since=_anomalies_before)

    if serve_http:
        # Replica contract (fleet/manager.py): the FULL record is the
        # last stdout JSON line — the manager collects it at reap time.
        print(json.dumps(record))
    else:
        print(json.dumps({
            "app": record["app"], "algorithm": record["algorithm"],
            "requests": summary["requests"],
            "completed": summary["completed"],
            "throughput_rps": summary["throughput_rps"],
            "latency_ms": summary["latency_ms"],
            "batch_occupancy": summary.get("batch_occupancy"),
            "shed_count": summary["shed_count"],
            "degraded_count": summary["degraded_count"],
            "oracle_checked": summary["oracle_checked"],
            "oracle_failures": summary["oracle_failures"],
            "slo_violations": summary["slo_violations"],
            "burn_rate": summary.get("burn_rate"),
            "latency_hist_ms": summary.get("latency_hist_ms"),
            "tenant": summary.get("tenant"),
        }))
    if args.output_file:
        # non-atomic-ok: append-only record stream (the -o contract).
        with open(args.output_file, "a") as f:
            f.write(json.dumps(record) + "\n")

    from distributed_sddmm_tpu.obs import store as obs_store

    run_store = obs_store.active()
    if run_store is not None:
        try:
            doc = run_store.ingest_record(record, source="serve")
            print(f"[serve] runstore doc {doc['run_id']}", file=sys.stderr)
        except Exception as e:  # noqa: BLE001 — never fail the run
            print(f"[serve] runstore ingest failed: {e}", file=sys.stderr)

    if serve_http:
        # Replica mode: the record carries any violations; the fleet
        # harness (not this process's exit code) judges them — a
        # drained replica must read as a clean exit to its manager.
        return 0
    if summary["oracle_failures"]:
        return 1
    if summary["slo_violations"]:
        return 2
    return 0


def _serve_until_signal(eng, slo, tenants) -> dict:
    """Replica mode: park until SIGTERM/SIGINT, then drain the queue and
    summarize — the serving half of the record comes entirely from the
    recorder (there is no local load generator to measure throughput
    against; requests arrived over POST /submit)."""
    import signal
    import threading

    from distributed_sddmm_tpu.obs import clock
    from distributed_sddmm_tpu.serve.slo import attach_tenant_slo

    done = threading.Event()

    def _handler(signum, frame):  # noqa: ARG001 — signal API
        done.set()

    old_term = signal.signal(signal.SIGTERM, _handler)
    old_int = signal.signal(signal.SIGINT, _handler)
    print("[serve] replica mode: accepting POST /submit until SIGTERM",
          file=sys.stderr)
    t0 = clock.now()
    try:
        # Poll rather than block: a process-directed SIGTERM may land on
        # an admin-server thread (as it tends to right after the SIGCONT
        # that ends a wedge), and Python runs the handler only once the main
        # thread is back in the interpreter.
        while not done.wait(0.1):
            pass
    finally:
        signal.signal(signal.SIGTERM, old_term)
        signal.signal(signal.SIGINT, old_int)
    # Drain before summarizing: admission closes, queued requests
    # finish and reach the recorder (the caller's eng.stop() is then a
    # no-op on the already-joined runner).
    eng.stop(drain=True)
    elapsed = clock.now() - t0
    summary = eng.recorder.summary()
    completed = summary["completed"]
    summary.update({
        "duration_s": round(elapsed, 3),
        "offered": eng.queue.submitted_count + summary["shed_count"],
        "submitted": eng.queue.submitted_count,
        "throughput_rps": round(completed / elapsed, 3)
        if elapsed > 0 else 0.0,
        "oracle_checked": 0,
        "oracle_failures": 0,
    })
    summary["slo"] = slo.to_dict()
    summary["slo_violations"] = slo.check(summary)
    summary["burn_rate"] = slo.burn_rate(summary)
    attach_tenant_slo(summary, tenants)
    return summary


def _dispatch_fleet(args) -> int:
    """``bench fleet``: spawn N ``bench serve --serve-http`` replicas
    behind the front router, drive an open-loop multi-tenant HTTP load,
    optionally SIGKILL a replica at the load midpoint, and judge the
    fleet the way the single-engine harness judges one engine:

    * every 200 reply must be bit-identical (post-JSON) to the
      single-engine oracle's ``execute_now`` answer for that payload;
    * a killed replica's in-flight work must be re-admitted (router
      failover) or shed WITH a Retry-After hint — never silently lost;
    * the respawned replacement must warm-start from the shared
      ProgramStore: 0 request-path live compiles;
    * every injected GRAY fault must be *detected* within
      ``--detect-deadline``: a wedge or partition by a breaker-open on
      the victim, a corrupt by a quarantine verdict;
    * availability = (answered + shed-with-retry + client-deferred) /
      offered must hold above ``--availability-floor``.

    Exit 0 clean; 1 on a wrong/lost reply, a cold respawn, or a missed
    gray-fault detection; 3 on an availability-floor breach. Sheds and
    failovers are expected operating conditions, not failures.
    """
    import dataclasses
    import threading
    import time as _time

    import numpy as np

    from distributed_sddmm_tpu import programs as programs_mod
    from distributed_sddmm_tpu.fleet import (
        FleetManager, FleetRouter, ScalerConfig,
    )
    from distributed_sddmm_tpu.obs import trace as obs_trace
    from distributed_sddmm_tpu.obs.httpexp import _json_default, post_json
    from distributed_sddmm_tpu.obs.telemetry import LatencyHistogram
    from distributed_sddmm_tpu.resilience.chaos import ChaosEngine, ChaosSchedule
    from distributed_sddmm_tpu.serve import (
        SLOSpec, build_als_engine, build_gat_engine, parse_tenants,
    )
    from distributed_sddmm_tpu.serve.slo import attach_tenant_slo

    # Fleet-wide tracing (PR 19): the global --trace already armed the
    # tracer in main(); DSDDMM_FLEET_TRACE arms it for fleet runs
    # specifically (1/on, or an explicit trace path). Either way the
    # tracer exports DSDDMM_TRACE to the replicas spawned below, so
    # every replica writes its own shard — harvested by the manager at
    # reap/quarantine time and merged into one causal tree after the
    # load window.
    fleet_trace_spec = (os.environ.get("DSDDMM_FLEET_TRACE") or "").strip()
    if (fleet_trace_spec.lower() not in ("", "0", "off", "false", "no")
            and not obs_trace.enabled()):
        _tr = obs_trace.enable(
            None if fleet_trace_spec.lower() in ("1", "on", "true", "yes")
            else fleet_trace_spec
        )
        print(f"[fleet] tracing -> {_tr.path}", file=sys.stderr)

    n_replicas = (
        args.replicas if args.replicas is not None
        else int(os.environ.get("DSDDMM_FLEET_REPLICAS") or "2")
    )
    chaos_spec = (args.chaos if args.chaos is not None
                  else os.environ.get("DSDDMM_CHAOS") or "")
    schedule = ChaosSchedule.parse(chaos_spec, seed=args.seed)
    # A schedule with a corrupt action defaults the audit on full: the
    # drill's contract is that a byzantine replica cannot leak a single
    # wrong reply, which needs every routed request audited pre-delivery.
    has_corrupt = any(a.kind == "corrupt" for a in schedule.actions)
    audit_frac = (args.audit_frac if args.audit_frac is not None
                  else (1.0 if has_corrupt else None))
    hedge_delay = None
    if args.hedge is not None:
        from distributed_sddmm_tpu.fleet.router import DEFAULT_HEDGE_FLOOR_S

        h = args.hedge.strip().lower()
        if h in ("", "0", "off", "false", "no"):
            hedge_delay = 0.0
        elif h in ("1", "on", "true", "yes"):
            hedge_delay = DEFAULT_HEDGE_FLOOR_S
        else:
            hedge_delay = float(h)
    tenants = parse_tenants(args.tenants)
    slo = SLOSpec.parse(args.slo) if args.slo else SLOSpec.from_env()

    # The warm-start substrate: replicas inherit DSDDMM_PROGRAMS through
    # their environment, so the oracle's warmup below populates the SAME
    # store every replica (and every respawn) resolves its ladder from.
    if programs_mod.active() is None:
        import tempfile

        store_root = tempfile.mkdtemp(prefix="dsddmm-fleet-programs-")
        programs_mod.enable(store_root)
        os.environ["DSDDMM_PROGRAMS"] = store_root
        print(f"[fleet] shared program store at {store_root}",
              file=sys.stderr)

    # -- single-engine oracle (and store pre-warmer) -------------------- #
    S = HostCOO.rmat(log_m=args.log_m, edge_factor=args.edge_factor, seed=0)
    engine_kw = dict(
        max_batch=args.max_batch, max_depth=args.max_depth,
        max_wait_ms=args.max_wait_ms,
    )
    print(f"[fleet] building oracle {args.app} engine "
          f"(2^{args.log_m} matrix, R={args.R})", file=sys.stderr)
    if args.app == "als":
        oracle = build_als_engine(
            S, R=args.R, train_steps=args.train_steps, k=args.k,
            plan_mode="model", **engine_kw,
        )
    else:
        oracle = build_gat_engine(S, R=args.R, plan_mode="model",
                                  **engine_kw)
    oracle.warmup()

    # -- precomputed load plan ------------------------------------------ #
    rng_arr = np.random.default_rng(args.seed)
    gaps = rng_arr.exponential(
        1.0 / max(args.rate, 1e-9),
        size=max(1, int(args.duration * args.rate * 3)),
    )
    t_arrivals = np.cumsum(gaps)
    t_arrivals = [float(t) for t in t_arrivals[t_arrivals < args.duration]]
    rng_pay = np.random.default_rng(args.seed + 1)
    payloads = [oracle.workload.sample_payload(rng_pay) for _ in t_arrivals]
    tenant_names = sorted(tenants) if tenants else ["default"]
    if tenants:
        w = np.array([tenants[t].weight for t in tenant_names], float)
        probs = w / w.sum()
    else:
        probs = np.ones(1)
    rng_t = np.random.default_rng(args.seed + 2)
    assigned = [
        tenant_names[int(rng_t.choice(len(tenant_names), p=probs))]
        for _ in t_arrivals
    ]
    # Oracle answers, JSON-round-tripped the same way an HTTP reply is —
    # the comparison must see both sides through the identical wire
    # encoding. One payload per call: batching-determinism makes the
    # grouping irrelevant, and it sidesteps any batch-bucket clamp.
    oracle_replies = [
        json.loads(json.dumps(oracle.execute_now([p])[0],
                              default=_json_default))
        for p in payloads
    ]
    print(f"[fleet] oracle precomputed {len(oracle_replies)} replies",
          file=sys.stderr)

    # -- the fleet ------------------------------------------------------ #
    def replica_argv(name, port, role):  # noqa: ARG001 — manager contract
        argv = [
            sys.executable, "-m", "distributed_sddmm_tpu.bench", "serve",
            "--serve-http", "--admin-port", str(port), "--no-runstore",
            "--app", args.app, "--log-m", str(args.log_m),
            "--edge-factor", str(args.edge_factor), "--R", str(args.R),
            "--k", str(args.k), "--train-steps", str(args.train_steps),
            "--max-batch", str(args.max_batch),
            "--max-depth", str(args.max_depth),
            "--max-wait-ms", str(args.max_wait_ms),
            "--seed", str(args.seed), "--oracle-every", "0",
        ]
        if args.tenants:
            argv += ["--tenants", args.tenants]
        if args.slo:
            argv += ["--slo", args.slo]
        return argv

    # No live canary here: the chaos harness owns the replica count, and
    # a background tuner's CPU burn would only add latency noise to the
    # availability measurement. fleet/manager tests cover the canary.
    manager = FleetManager(replica_argv, tuner_canary=False)
    for _ in range(n_replicas):
        manager.spawn(role="serve")
    print(f"[fleet] warming {n_replicas} replicas "
          f"(budget {args.ready_timeout:.0f}s)...", file=sys.stderr)

    router = None
    chaos_engine = None
    results: list = [None] * len(t_arrivals)
    router_stats: dict = {}
    topology: dict = {}
    chaos_events: list = []
    breaker_events: list = []
    quarantine_log: list = []
    chaos_t0 = 0.0
    elapsed = 0.0
    try:
        if not manager.wait_ready(args.ready_timeout):
            print("[fleet] replica pool failed to become ready",
                  file=sys.stderr)
            return 1
        router_kw: dict = {"poll_interval_s": 0.2}
        if audit_frac is not None:
            router_kw["audit_frac"] = audit_frac
        if hedge_delay is not None:
            router_kw["hedge_delay_s"] = hedge_delay
        router = FleetRouter(manager, **router_kw).start()
        print(f"[fleet] router at http://127.0.0.1:{router.port}",
              file=sys.stderr)
        from distributed_sddmm_tpu.obs import flightrec as obs_flightrec

        _fr = obs_flightrec.active()
        if _fr is not None:
            # The router as a flight-recorder source: an anomaly dump
            # then carries the fleet topology (breaker states, depths,
            # quarantines) and routing counters of the moment it fired,
            # the same way serve dumps the engine snapshot.
            _fr.register_source("fleet", lambda: {
                "topology": router.topology(),
                "stats": dict(router.stats),
            })
        if schedule:
            chaos_engine = ChaosEngine(
                schedule, manager, router, duration_s=args.duration,
                ready_timeout_s=args.ready_timeout,
            )
            print(f"[fleet] chaos schedule: {schedule.normalized} "
                  f"(seed {schedule.seed})", file=sys.stderr)

        lock = threading.Lock()
        backoff_until = [0.0]

        def _fire(i):
            body = {"payload": payloads[i], "tenant": assigned[i],
                    "timeout_s": 30.0}
            try:
                code, decoded, headers = post_json(
                    "127.0.0.1", router.port, "/submit", body,
                    timeout_s=60.0,
                )
            except OSError as e:
                results[i] = ("error", f"{type(e).__name__}: {e}")
                return
            if code == 200:
                results[i] = ("ok", decoded.get("reply"))
            elif code == 429:
                hint = headers.get("Retry-After")
                if hint is None:
                    hint = decoded.get("retry_after_s")
                try:
                    hint_f = float(hint)
                except (TypeError, ValueError):
                    hint_f = None
                if hint_f:
                    # Honor the hint (satellite of run_load's
                    # honor_retry_after): later arrivals inside the
                    # window defer instead of piling on.
                    with lock:
                        backoff_until[0] = max(
                            backoff_until[0], _time.monotonic() + hint_f,
                        )
                results[i] = ("shed", hint_f)
            else:
                results[i] = (
                    "error", f"HTTP {code}: {decoded.get('error', decoded)}"
                )

        threads = []
        t0 = _time.monotonic()
        if chaos_engine is not None:
            # The engine's clock starts with the load clock: schedule
            # fractions are fractions of THIS load window.
            chaos_engine.start()
            chaos_t0 = chaos_engine._t0
        for i, t_arr in enumerate(t_arrivals):
            delay = t0 + t_arr - _time.monotonic()
            if delay > 0:
                _time.sleep(delay)
            with lock:
                wait = backoff_until[0] - _time.monotonic()
            if wait > 0:
                results[i] = ("deferred", round(wait, 3))
                continue
            th = threading.Thread(target=_fire, args=(i,), daemon=True)
            th.start()
            threads.append(th)
        for th in threads:
            th.join(90.0)
        elapsed = _time.monotonic() - t0
        if chaos_engine is not None:
            # Wait out any in-flight kill heal before reading verdicts:
            # the warm-respawn judgment needs the replacement's record.
            chaos_engine.close(join_timeout_s=args.ready_timeout)
            chaos_events = list(chaos_engine.events)
        router_stats = dict(router.stats)
        topology = router.topology()
        breaker_events = list(router.breaker_events)
        quarantine_log = list(manager.quarantine_log)
    finally:
        if chaos_engine is not None:
            chaos_engine.close()
        if router is not None:
            router.stop()
        manager.stop_all()

    # -- fleet trace collection + chain reconstruction ------------------ #
    # The router's own shard plus every replica shard the manager
    # harvested merge into one causally-connected trace; the chain
    # reconstruction over it is the run's trace-coverage verdict
    # (`fleet:trace_coverage` hard gate axis: every DELIVERED reply
    # must reconstruct a complete router→attempt→replica chain, the
    # winning attempt's span agreeing with the router's recorded
    # latency within 1 ms).
    trace_info = None
    if obs_trace.enabled():
        from distributed_sddmm_tpu.obs import tracemerge
        from distributed_sddmm_tpu.tools import tracereport

        try:
            shard_paths = list(dict.fromkeys(
                [str(obs_trace.trace_path())]
                + [str(s["path"]) for s in manager.trace_shards]
            ))
            # strict=False: a SIGKILLed replica can tear its final
            # shard line mid-write; the merged output is re-serialised
            # from the records that DID validate, so it stays
            # schema-valid for `report-trace`.
            merged_path, merged = tracemerge.write_merged(
                shard_paths, strict=False
            )
            chains = tracereport.fleet_request_chains(merged)
            trace_info = {
                "coverage": chains["coverage"],
                "requests": len(chains["requests"]),
                "delivered": chains["delivered"],
                "complete": chains["complete"],
                "failed": chains["failed"],
                "hedged": chains["hedged"],
                "audited": chains["audited"],
                "shards": len(merged["begin"].get("shards") or ()),
                "fleet_links": merged["begin"].get("fleet_links", 0),
                "merged_path": str(merged_path),
            }
            print(f"[fleet] merged trace {merged_path} "
                  f"({trace_info['shards']} shards, "
                  f"{trace_info['fleet_links']} cross-process links, "
                  f"coverage {trace_info['coverage']:.3f})",
                  file=sys.stderr)
        except Exception as e:  # noqa: BLE001 — tracing never fails the run
            print(f"[fleet] trace merge failed: {e}", file=sys.stderr)
            trace_info = {"error": f"{type(e).__name__}: {e}"}

    # -- judgment ------------------------------------------------------- #
    counts = {"ok": 0, "shed": 0, "deferred": 0, "error": 0, "lost": 0}
    shed_with_retry = 0
    n_mismatch = 0
    mismatch_examples = []
    client_tenant: dict[str, dict] = {}
    for i, res in enumerate(results):
        cell = client_tenant.setdefault(
            assigned[i],
            {"ok": 0, "shed": 0, "deferred": 0, "error": 0, "lost": 0},
        )
        kind = res[0] if res is not None else "lost"
        counts[kind] += 1
        cell[kind] += 1
        if kind == "shed" and res[1]:
            shed_with_retry += 1
        if kind == "ok" and res[1] != oracle_replies[i]:
            n_mismatch += 1
            if len(mismatch_examples) < 5:
                mismatch_examples.append(
                    {"request": i, "tenant": assigned[i]}
                )
    offered = len(t_arrivals)
    availability = (
        (counts["ok"] + shed_with_retry + counts["deferred"]) / offered
        if offered else 1.0
    )

    # Replacement warm-start: the replica living under the killed name
    # at stop time IS the respawn (generation >= 1); its drained record
    # carries the compile attribution.
    killed_names = [ev["target"] for ev in chaos_events
                    if ev["kind"] == "kill" and not ev.get("skipped")]
    killed_name = killed_names[0] if killed_names else None
    replacement = (manager.get(killed_name)
                   if killed_name is not None else None)
    repl_engine = ((replacement.record or {}).get("engine") or {}
                   if replacement is not None and replacement.generation >= 1
                   else {})
    repl_live_compiles = repl_engine.get("live_compiles")

    # -- gray-fault detection judge ------------------------------------- #
    # Every injected gray fault must show its detection signal within
    # --detect-deadline of firing: wedge/partition → a breaker-open on
    # the victim, corrupt → a quarantine verdict on the victim. Kill is
    # a CRASH fault (detected by construction — the connection dies);
    # slow is a latency fault the hedge absorbs rather than detects.
    detection = []
    for ev in chaos_events:
        if ev.get("skipped") or ev["kind"] not in (
                "wedge", "partition", "corrupt"):
            continue
        t_fire_abs = chaos_t0 + ev["t_s"]
        t_limit = t_fire_abs + args.detect_deadline
        if ev["kind"] in ("wedge", "partition"):
            hits = [b for b in breaker_events
                    if b["name"] == ev["target"] and b["state"] == "open"
                    and t_fire_abs <= b["t"] <= t_limit]
            signal_name = "breaker_open"
        else:
            hits = [q for q in quarantine_log
                    if q["name"] == ev["target"]
                    and t_fire_abs <= q["t"] <= t_limit]
            signal_name = "quarantine"
        detection.append({
            "kind": ev["kind"], "target": ev["target"],
            "signal": signal_name, "detected": bool(hits),
            "t_fire_s": ev["t_s"],
            "t_detect_s": (round(hits[0]["t"] - chaos_t0, 3)
                           if hits else None),
        })
    detection_ok = all(d["detected"] for d in detection)

    # -- fleet-wide + per-tenant rollups from the drained records ------- #
    fleet_hist = None
    tot = {"completed": 0, "errors": 0, "shed_count": 0}
    tenant_agg: dict[str, dict] = {}
    for rec in manager.records:
        h = LatencyHistogram.from_dict(rec.get("request_hist"))
        if h is not None:
            fleet_hist = h if fleet_hist is None else fleet_hist.merge(h)
        for k in tot:
            tot[k] += int(rec.get(k) or 0)
        for name, cell in (rec.get("tenant") or {}).items():
            a = tenant_agg.setdefault(name, {
                "requests": 0, "completed": 0, "errors": 0,
                "shed_count": 0, "_hist": None,
            })
            for k in ("requests", "completed", "errors", "shed_count"):
                a[k] += int(cell.get(k) or 0)
            th = LatencyHistogram.from_dict(cell.get("request_hist"))
            if th is not None:
                a["_hist"] = (th if a["_hist"] is None
                              else a["_hist"].merge(th))
    t_req = sum(tot.values())
    fleet_summary = {
        **tot,
        "err_rate": tot["errors"] / t_req if t_req else 0.0,
        "shed_rate": tot["shed_count"] / t_req if t_req else 0.0,
    }
    if fleet_hist is not None and fleet_hist.total:
        fleet_summary["request_hist"] = fleet_hist.to_dict()
        fleet_summary["latency_hist_ms"] = fleet_hist.percentiles_ms()
    tenant_table = {}
    for name, a in sorted(tenant_agg.items()):
        n_req = a["requests"]
        entry = {k: a[k] for k in
                 ("requests", "completed", "errors", "shed_count")}
        entry["err_rate"] = a["errors"] / n_req if n_req else 0.0
        entry["shed_rate"] = a["shed_count"] / n_req if n_req else 0.0
        if a["_hist"] is not None and a["_hist"].total:
            entry["request_hist"] = a["_hist"].to_dict()
            entry["latency_hist_ms"] = a["_hist"].percentiles_ms()
        tenant_table[name] = entry
    tenant_wrap = {"tenant": tenant_table}
    attach_tenant_slo(tenant_wrap, tenants)

    model = oracle.workload.model
    d_ops = model.d_ops
    plan = getattr(model, "plan", None)
    record = {
        "app": f"fleet-{args.app}",
        "algorithm": plan.algorithm if plan else d_ops.algorithm_name,
        "R": args.R,
        "c": plan.c if plan else d_ops.c,
        "fused": True,
        "kernel": getattr(d_ops.kernel, "name",
                          type(d_ops.kernel).__name__),
        "kernel_variant": oracle.workload.kernel_variant,
        **harness.pod_record_fields(),
        "num_trials": counts["ok"],
        "elapsed": round(elapsed, 3),
        "overall_throughput": None,
        "requests": offered,
        "throughput_rps": (round(counts["ok"] / elapsed, 3)
                           if elapsed > 0 else 0.0),
        **fleet_summary,
        "slo": slo.to_dict(),
        "slo_violations": slo.check(fleet_summary),
        "burn_rate": slo.burn_rate(fleet_summary),
        "tenant": tenant_wrap.get("tenant"),
        "fleet": {
            "replicas": n_replicas,
            "chaos": schedule.normalized,
            "chaos_seed": schedule.seed,
            "availability": round(availability, 4),
            "availability_floor": args.availability_floor,
            "offered": offered,
            "ok": counts["ok"],
            "shed_with_retry": shed_with_retry,
            "shed_no_hint": counts["shed"] - shed_with_retry,
            "deferred": counts["deferred"],
            "errors": counts["error"],
            "lost": counts["lost"],
            "oracle_checked": counts["ok"],
            "mismatches": n_mismatch,
            "mismatch_examples": mismatch_examples,
            "killed": killed_name,
            "killed_names": killed_names,
            "spawns": manager.spawns,
            "losses": manager.losses,
            "quarantines": manager.quarantines,
            "records_collected": len(manager.records),
            "replacement_live_compiles": repl_live_compiles,
            "replacement_disk_hits": repl_engine.get("disk_hits"),
            "hedges": router_stats.get("hedges", 0),
            "hedge_wins": router_stats.get("hedge_wins", 0),
            "audits": router_stats.get("audits", 0),
            "audit_mismatches": router_stats.get("audit_mismatches", 0),
            "breaker_opens": router_stats.get("breaker_opens", 0),
            "chaos_events": chaos_events,
            "breaker_events": [
                {**b, "t_s": round(b["t"] - chaos_t0, 3)}
                for b in breaker_events
            ] if chaos_t0 else breaker_events,
            "quarantine_log": [
                {**q, "t_s": round(q["t"] - chaos_t0, 3)}
                for q in quarantine_log
            ] if chaos_t0 else quarantine_log,
            "detection": detection,
            "detection_ok": detection_ok,
            "detect_deadline_s": args.detect_deadline,
            "router": router_stats,
            "topology": topology,
            "scaler_config": dataclasses.asdict(ScalerConfig.from_env()),
            "tenant_client": client_tenant,
        },
        "serve_config": {
            "rate_hz": args.rate, "duration_s": args.duration,
            "max_batch": args.max_batch, "max_depth": args.max_depth,
            "max_wait_ms": args.max_wait_ms,
            "tenants": args.tenants,
        },
    }
    if plan is not None:
        record["plan"] = plan.to_dict()
    if trace_info is not None:
        record["fleet"]["trace"] = trace_info
    if obs_trace.enabled():
        record["run_id"] = obs_trace.run_id()
        record["trace_path"] = obs_trace.trace_path()

    print(json.dumps({
        "app": record["app"],
        "replicas": n_replicas,
        "chaos": schedule.normalized,
        "offered": offered,
        "ok": counts["ok"],
        "shed_with_retry": shed_with_retry,
        "deferred": counts["deferred"],
        "errors": counts["error"],
        "lost": counts["lost"],
        "mismatches": n_mismatch,
        "availability": record["fleet"]["availability"],
        "replacement_live_compiles": repl_live_compiles,
        "quarantines": manager.quarantines,
        "audit_mismatches": router_stats.get("audit_mismatches", 0),
        "breaker_opens": router_stats.get("breaker_opens", 0),
        "hedges": router_stats.get("hedges", 0),
        "detection_ok": detection_ok,
        "burn_rate": record["burn_rate"],
        "trace_coverage": (trace_info or {}).get("coverage"),
        "router": router_stats,
    }))
    if args.output_file:
        # non-atomic-ok: append-only record stream (the -o contract).
        with open(args.output_file, "a") as f:
            f.write(json.dumps(record) + "\n")

    from distributed_sddmm_tpu.obs import store as obs_store

    run_store = obs_store.active()
    if run_store is not None:
        try:
            doc = run_store.ingest_record(record, source="fleet")
            print(f"[fleet] runstore doc {doc['run_id']}", file=sys.stderr)
        except Exception as e:  # noqa: BLE001 — never fail the run
            print(f"[fleet] runstore ingest failed: {e}", file=sys.stderr)

    if n_mismatch or counts["lost"]:
        return 1
    if killed_name is not None and (repl_live_compiles is None
                                    or repl_live_compiles > 0):
        # The respawn either never came back with a record or it
        # compiled on the request path — both break the warm-start
        # contract the fleet's capacity math depends on.
        return 1
    if not detection_ok:
        # An injected gray fault went undetected past its deadline: the
        # detectors (breaker, audit) are the thing under test here.
        print("[fleet] gray-fault detection FAILED: "
              + json.dumps(detection), file=sys.stderr)
        return 1
    if availability < args.availability_floor:
        return 3
    return 0


def _maybe_mask(S, args):
    """With ``--app attention`` the benchmark matrix IS the mask: build
    it from the --mask spec over the generated/loaded matrix's token
    count (``graph`` keeps the matrix's own pattern — the GAT adjacency
    path)."""
    if getattr(args, "app", None) != "attention":
        return S
    from distributed_sddmm_tpu import masks

    return masks.from_spec(args.mask, n=max(S.M, S.N), graph=S)


def _dispatch(args) -> int:
    if args.cmd == "serve":
        return _dispatch_serve(args)

    if args.cmd == "fleet":
        return _dispatch_fleet(args)

    if args.cmd == "er":
        S = HostCOO.rmat(log_m=args.log_m, edge_factor=args.edge_factor, seed=0)
        _run_configs(_maybe_mask(S, args), _resolve_algs(args.alg), args)
        return 0

    if args.cmd == "file":
        S = HostCOO.load_mtx(args.path)
        if args.permute:
            S = S.random_permuted(seed=0)
        _run_configs(_maybe_mask(S, args), _resolve_algs(args.alg), args)
        return 0

    if args.cmd == "heatmap":
        S = HostCOO.rmat(log_m=args.log_m, edge_factor=args.edge_factor, seed=0)
        _run_configs(_maybe_mask(S, args), _resolve_algs(args.alg), args,
                     r_values=args.r_values)
        return 0

    if args.cmd == "permute":
        out = args.output_file or args.path.replace(".mtx", "-permuted.mtx")
        S = HostCOO.load_mtx(args.path).random_permuted(seed=args.seed)
        S.save_mtx(out)
        print(f"wrote {out} ({S.M}x{S.N}, nnz={S.nnz})")
        return 0

    if args.cmd == "kernels":
        from distributed_sddmm_tpu.bench.kernels import run_kernel_benchmark

        run_kernel_benchmark(
            log_m_values=args.log_m,
            nnz_per_row_values=args.nnz_per_row,
            r_values=args.r_values,
            kernels=args.kernels,
            trials=args.trials,
            output_file=args.output_file,
        )
        return 0

    if args.cmd == "overlap":
        from distributed_sddmm_tpu.bench.overlap import (
            fusion_overlap_hlo_report, hlo_overlap_report,
            run_overlap_experiment,
        )

        rec = run_overlap_experiment(
            block=args.block, steps_work=args.steps_work, trials=args.trials,
            output_file=args.output_file,
        )
        print(json.dumps(rec))
        if args.hlo_topology:
            rec = hlo_overlap_report(
                topology_name=args.hlo_topology,
                block=args.block, steps_work=args.steps_work,
                output_file=args.output_file,
            )
            print(json.dumps(rec))
        if args.fusion_hlo:
            rec = fusion_overlap_hlo_report(
                topology_name=args.fusion_hlo,
                overlap=args.fusion_mode == "overlap",
                output_file=args.output_file,
            )
            print(json.dumps(rec))
        return 0

    if args.cmd == "baseline":
        from distributed_sddmm_tpu.bench.baseline import run_baseline

        S = HostCOO.rmat(log_m=args.log_m, edge_factor=args.edge_factor, seed=0)
        rec = run_baseline(
            S, R=args.R, iters=args.iters, output_file=args.output_file
        )
        print(json.dumps(rec))
        return 0

    if args.cmd == "verify":
        from distributed_sddmm_tpu.utils.verify import verify_algorithms

        ok = verify_algorithms(
            log_m=args.log_m,
            edge_factor=args.edge_factor,
            R=args.R,
            c=args.c,
            alg_names=_resolve_algs(args.alg),
            kernel=_get_kernel(args.kernel),
            verbose=True,
        )
        return 0 if ok else 1

    raise AssertionError(args.cmd)


if __name__ == "__main__":
    sys.exit(main())
