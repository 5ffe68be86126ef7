"""The DSDDMM_* env-knob registry: every knob, declared once.

Twenty-six runtime knobs grew across nine PRs, each documented (or not)
wherever it was born; ``bench env`` had no single table to print and
the README drifted ~7 knobs behind. This module is now the source of
truth: the ``env-knob`` checker (``analysis/checkers.py``) fails on any
``os.environ`` access of a ``DSDDMM_*`` name that is not declared here,
on any declared name with no access site left (stale registration), and
on a README table that does not match :func:`render_markdown` — so
registry, code and docs cannot drift apart again.

Declaration fields: name, value type (as the parser treats it), the
effective default, one-line doc, and scope (``runtime`` for package/
script knobs, ``test`` for knobs only the test suite reads — those stay
out of the README's operational table but are registered so the checker
can vouch for them).

``python -m distributed_sddmm_tpu.bench env`` prints the table;
``--markdown`` emits the README block between :data:`README_BEGIN` /
:data:`README_END`; ``--json`` the raw records.

Import discipline: stdlib only (the analyzer and offline tooling import
this in jax-free subprocesses).
"""

from __future__ import annotations

import dataclasses
import pathlib
from typing import Optional

#: Markers delimiting the generated README block (env-knob checker
#: verifies the block equals ``render_markdown()``).
README_BEGIN = "<!-- envreg:begin -->"
README_END = "<!-- envreg:end -->"


@dataclasses.dataclass(frozen=True)
class Knob:
    name: str
    type: str      # how the reader parses it: int/float/flag/spec/path/str
    default: str   # the effective default, human-readable
    doc: str       # one line
    scope: str = "runtime"  # or "test"


_K = Knob
#: Every DSDDMM_* knob, alphabetical. Keep docs to one line — this IS
#: the README table.
KNOBS: dict[str, Knob] = {k.name: k for k in [
    _K("DSDDMM_ATTN_SERVE_WINDOW", "int", "16",
       "attention token-scoring endpoint's sliding-window half-width "
       "(serve/workloads.py)"),
    _K("DSDDMM_ATTN_STREAM_BUDGET", "int", "16777216",
       "element budget past which the masked-softmax row stats switch "
       "to the streaming max/denominator scan (ops/kernels.py)"),
    _K("DSDDMM_BATCH_STEP", "flag", "1",
       "batch grid steps in the blocked Pallas kernels (README: step "
       "batching)"),
    _K("DSDDMM_BLOCK_COLS", "int", "auto",
       "blocked-kernel column tile size; unset, sized to the tile set's "
       "density (ops/blocked.py)"),
    _K("DSDDMM_BLOCK_ROWS", "int", "auto",
       "blocked-kernel row tile size; unset, sized to the tile set's "
       "density (ops/blocked.py)"),
    _K("DSDDMM_CHAOS", "spec", "off",
       "`bench fleet` chaos schedule when --chaos is unset: "
       "kind[:target]@frac[/dur][:param];... (resilience/chaos.py)"),
    _K("DSDDMM_CHECKPOINT_DIR", "path", "artifacts/checkpoints",
       "checkpoint store root (resilience/checkpoint.py)"),
    _K("DSDDMM_CHUNK", "int", "128",
       "one-hot chunk width of the blocked kernels"),
    _K("DSDDMM_CHUNK_GROUP", "int", "4",
       "chunks fused per grid step in the blocked kernels"),
    _K("DSDDMM_DIST_COORDINATOR", "str", "unset (auto-discover)",
       "jax.distributed coordinator host:port a pod launcher exports "
       "to every worker (dist/init.py)"),
    _K("DSDDMM_DIST_INGEST_CHUNK", "int", "4194304",
       "partitioned-loader streaming chunk size in bytes "
       "(dist/ingest.py)"),
    _K("DSDDMM_DIST_INGEST_THREADS", "int", "min(cpus, 8)",
       "parallel parse workers of the partitioned .mtx loader"),
    _K("DSDDMM_DIST_NPROCS", "int", "unset",
       "pod process count label/override (requires the coordinator; "
       "also keys offline pod tooling)"),
    _K("DSDDMM_DIST_PROC_ID", "int", "unset",
       "this worker's pod process index (pairs with "
       "DSDDMM_DIST_NPROCS)"),
    _K("DSDDMM_DONATE", "flag", "1",
       "donate CG/GAT loop buffers to their compiled programs (0 "
       "stands donation down)"),
    _K("DSDDMM_DYNSTRUCT_HEADROOM", "float", "1.0",
       "dynstruct capacity headroom: every raw structure requirement "
       "is multiplied by this before pow2 rung selection "
       "(dynstruct/capacity.py)"),
    _K("DSDDMM_DYNSTRUCT_ROWS", "flag", "1",
       "dynstruct builds reserve a row-growth rung (declared height "
       "pow2_at_least(M+1)); 0 sizes frames to the exact M"),
    _K("DSDDMM_EXEC_RETRIES", "int", "1",
       "dispatch retries at the parallel/base.py resilience choke "
       "point"),
    _K("DSDDMM_EXEC_TIMEOUT", "float", "0 (off)",
       "per-dispatch timeout in seconds (0 disables)"),
    _K("DSDDMM_FAULTS", "spec", "off",
       "fault-injection plan: JSON spec list, @plan.json, or comma "
       "shorthand (nan,delay,...)"),
    _K("DSDDMM_FLEET_AUDIT_FRAC", "float", "0 (off)",
       "front router: fraction of requests re-executed on a second "
       "replica and compared bit-for-bit before delivery"),
    _K("DSDDMM_FLEET_BREAKER_COOLDOWN", "float", "2.0",
       "front router: seconds an open circuit breaker waits before "
       "admitting a half-open probe"),
    _K("DSDDMM_FLEET_BREAKER_ERRS", "int", "3",
       "front router: consecutive strikes (submit/poll/decode "
       "failures) that trip a replica's circuit breaker open"),
    _K("DSDDMM_FLEET_COOLDOWN", "float", "5",
       "fleet autoscaler: seconds between scaling actions "
       "(fleet/scaler.py)"),
    _K("DSDDMM_FLEET_DRAIN_BURN", "float", "1.0",
       "front router: SLO burn rate above which a replica stops "
       "receiving admissions until it recovers (fleet/router.py)"),
    _K("DSDDMM_FLEET_HEDGE", "spec", "off",
       "front router hedged requests: off, on (p95-derived delay), or "
       "a float hedge-delay floor in seconds"),
    _K("DSDDMM_FLEET_HIGH_BURN", "float", "1.0",
       "fleet autoscaler: replica burn rate counting as sustained "
       "pressure (spawn trigger)"),
    _K("DSDDMM_FLEET_HIGH_DEPTH", "float", "0.7",
       "fleet autoscaler: queue-depth fraction counting as sustained "
       "pressure (spawn trigger)"),
    _K("DSDDMM_FLEET_IDLE_S", "float", "10",
       "fleet autoscaler: seconds every replica must sit idle before a "
       "drain-then-reap scale-down"),
    _K("DSDDMM_FLEET_MAX", "int", "4",
       "fleet autoscaler: replica ceiling"),
    _K("DSDDMM_FLEET_MIN", "int", "1",
       "fleet autoscaler: replica floor"),
    _K("DSDDMM_FLEET_REPLICAS", "int", "2",
       "`bench fleet` serve-role replica count when --replicas is "
       "unset (bench/cli.py)"),
    _K("DSDDMM_FLEET_TRACE", "spec", "off",
       "`bench fleet` distributed tracing: 1 (default trace dir) or an "
       "explicit trace path; replicas shard, the run merges one "
       "causal tree and records fleet trace coverage"),
    _K("DSDDMM_FLEET_TRACE_DEBUG", "int", "64",
       "front router: recent fleet request chains kept live for the "
       "/debug/requests surface (fleet/router.py)"),
    _K("DSDDMM_FLIGHTREC", "spec", "off",
       "anomaly-triggered flight recorder: 1 or a dump directory"),
    _K("DSDDMM_GUARD_MODE", "str", "raise",
       "NaN/Inf guard behavior: raise or repair"),
    _K("DSDDMM_GUARDS", "flag", "auto",
       "force output guards on/off (default: on while a fault plan is "
       "active)"),
    _K("DSDDMM_LOG", "str", "info",
       "structured stderr log level: debug|info|warn|error"),
    _K("DSDDMM_PLAN_CACHE", "spec", "artifacts/plan_cache",
       "autotune plan cache: relocate (path) or veto (0)"),
    _K("DSDDMM_POD_ADMIN_BASE", "int", "0 (off)",
       "pod runner: worker k serves its admin /metrics on port "
       "base + k (dist/run.py)"),
    _K("DSDDMM_POD_TRACE_MERGE", "flag", "1",
       "pod runner: worker 0 merges every worker's trace shard into "
       "one pod timeline at run end"),
    _K("DSDDMM_PROFILE", "path", "off",
       "jax.profiler capture logdir (per-anomaly windows when the "
       "flight recorder is armed)"),
    _K("DSDDMM_PROGRAMS", "spec", "artifacts/programs",
       "AOT program store: relocate (path) or veto (0; tests veto via "
       "conftest)"),
    _K("DSDDMM_RUNSTORE", "spec", "artifacts/runstore",
       "persistent run store: relocate (path) or veto (0/off)"),
    _K("DSDDMM_SCATTER_FORM", "str", "bt",
       "scatter formulation of the blocked kernels"),
    _K("DSDDMM_SERVE_RETRIES", "int", "1",
       "serving batch-dispatch retries before degrading to the host "
       "fallback"),
    _K("DSDDMM_SERVE_TIMEOUT", "float", "0 (off)",
       "serving per-batch dispatch timeout in seconds"),
    _K("DSDDMM_SLO", "spec", "none",
       "serving SLO spec (p50_ms=...,p99_ms=...,shed_rate=...; "
       "serve/slo.py validates keys)"),
    _K("DSDDMM_TELEMETRY", "spec", "off",
       "serving telemetry sampler: 1 or the JSONL output path"),
    _K("DSDDMM_TENANTS", "spec", "unset",
       "multi-tenant QoS classes 'name[:weight[:slo]];...' — "
       "weighted-fair dequeue + per-tenant burn-rate gate axes "
       "(serve/slo.py)"),
    _K("DSDDMM_TRACE", "spec", "off",
       "span tracing: 1 (default artifacts/traces), a file, or a "
       "directory; exported as PATH.shards to children"),
    _K("DSDDMM_TUNER", "flag", "0",
       "background closed-loop tuner on `bench serve` (same as "
       "--tuner; tuner/)"),
    _K("DSDDMM_TUNER_BUDGET", "float", "300",
       "per-process wall-clock cap on tuner re-measurement seconds"),
    _K("DSDDMM_TUNER_COOLDOWN", "float", "30",
       "seconds the tuner idles after a promotion or rejection"),
    _K("DSDDMM_TUNER_GAP", "float", "0.5",
       "runstore trigger: realized GFLOP/s below this fraction of the "
       "plan's prediction signals a re-tune"),
    _K("DSDDMM_TUNER_INTERVAL", "float", "2",
       "tuner poll period in seconds (scan/shadow state machine)"),
    _K("DSDDMM_TUNER_LANE_FRAC", "float", "0.25",
       "padded_lane_frac gauge at/above which a generic encoding "
       "triggers a re-tune"),
    _K("DSDDMM_TUNER_SHADOW_N", "int", "4",
       "bit-identical shadow replies required before a challenger "
       "promotes"),
    _K("DSDDMM_TUNER_TRIAL", "str", "auto",
       "tuner trial mode: wall (harness runs), counted (deterministic "
       "padded-lane trials), auto (wall on TPU else counted)"),
    _K("DSDDMM_WATCHDOG", "str", "off",
       "in-run anomaly monitor: warn or strict"),
    _K("DSDDMM_WIRE", "str", "f32",
       "default wire-precision comm dtype (f32|bf16) for strategies "
       "built without an explicit wire= (parallel/wire.py)"),
    _K("DSDDMM_WIRE_OVERRIDES", "spec", "unset",
       "per-role wire-dtype overrides, e.g. reduce=bf16,ring=f32 "
       "(roles: gather|ring|ring_accum|reduce)"),
    _K("DSDDMM_XLA_GATHER_BUDGET", "int", "536870912",
       "HBM gather budget that routes oversize problems onto the "
       "chunked XLA kernel"),
    # -- test-suite knobs (registered so the checker can vouch; not in
    #    the README operational table) --------------------------------
    _K("DSDDMM_MP_INIT_TIMEOUT", "int", "300",
       "jax.distributed init timeout for the two-process test worker",
       scope="test"),
]}


def get(name: str) -> Knob:
    return KNOBS[name]


def declaration_line(name: str) -> Optional[int]:
    """Source line of a knob's declaration (finding anchor for the
    stale-registration check)."""
    src = pathlib.Path(__file__)
    for ln, line in enumerate(src.read_text().splitlines(), 1):
        if f'"{name}"' in line:
            return ln
    return None


def render_table(scope: Optional[str] = None) -> str:
    """Aligned text table (the ``bench env`` default view)."""
    rows = [k for k in KNOBS.values() if scope is None or k.scope == scope]
    w_name = max(len(k.name) for k in rows)
    w_type = max(len(k.type) for k in rows)
    w_dflt = max(len(k.default) for k in rows)
    out = [f"{'knob':<{w_name}}  {'type':<{w_type}}  "
           f"{'default':<{w_dflt}}  doc"]
    for k in rows:
        out.append(f"{k.name:<{w_name}}  {k.type:<{w_type}}  "
                   f"{k.default:<{w_dflt}}  {k.doc}")
    return "\n".join(out)


def render_markdown(scope: Optional[str] = "runtime") -> str:
    """Markdown table. Default ``runtime`` scope IS the README block:
    regenerate with ``bench env --markdown`` whenever a knob is added —
    the env-knob checker fails until README and registry agree
    byte-for-byte. ``scope="test"`` renders the test-suite knobs,
    ``None`` everything."""
    out = ["| knob | type | default | what it does |",
           "| --- | --- | --- | --- |"]
    for k in KNOBS.values():
        if scope is not None and k.scope != scope:
            continue
        out.append(f"| `{k.name}` | {k.type} | `{k.default}` | {k.doc} |")
    return "\n".join(out)


def to_records(scope: Optional[str] = None) -> list[dict]:
    return [dataclasses.asdict(k) for k in KNOBS.values()
            if scope is None or k.scope == scope]
