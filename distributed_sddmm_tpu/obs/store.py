"""Persistent run store: every bench run becomes a queryable document.

PR 3 made one run legible (spans, per-op counters, a manifest); this
module makes runs *comparable*. Each benchmark run is persisted as one
JSON document under ``artifacts/runstore/runs/<run_id>.json``, joined
from four sources the harness already produces:

* the bench **record** (``bench/harness.py`` schema — alg_info,
  elapsed, throughput, per-op ``metrics``, ``anomalies``),
* the **trace aggregate** (``tools/tracereport.aggregate`` per-phase
  table incl. the comm-vs-costmodel column) when tracing was on,
* the run **manifest** (versions/backend/devices/git rev),
* the problem **fingerprint** (``autotune/fingerprint.py``) plus the
  code hash and backend, which together form the index key regression
  comparisons match on: two runs are comparable when problem, machine
  and program-shaping code all agree.

An ``index.json`` summary (one row per run) makes ``bench history``
O(1 file); it is derivative state — :meth:`RunStore.rebuild_index`
regenerates it from the run docs, and a corrupt index is rebuilt on
read rather than trusted. All writes go through ``utils/atomic.py``
(a reader sees old or new content, never a prefix; the resilience
layer's write-fault hook applies).

Activation mirrors the tracer: the bench CLI enables the store for
benchmark-producing subcommands (``--no-runstore`` opts out), the
``DSDDMM_RUNSTORE`` env var enables it programmatically (``1`` → the
default root, a path → that root, ``0``/``off`` → disabled), and
library callers that invoke ``benchmark_algorithm`` directly see no
store unless they ask — tests must not silt up ``artifacts/``.
"""

from __future__ import annotations

import contextlib
import json
import os
import pathlib
import threading

from distributed_sddmm_tpu.obs import clock
from distributed_sddmm_tpu.utils.atomic import atomic_write_json

#: Run-document schema generation; readers skip docs they cannot read.
SCHEMA_VERSION = 1

_REPO = pathlib.Path(__file__).resolve().parents[2]
DEFAULT_ROOT = _REPO / "artifacts" / "runstore"

#: Index-row fields lifted out of each run doc (``bench history`` shows
#: these without opening per-run files).
_INDEX_FIELDS = (
    "run_id", "created_epoch", "key", "backend", "code_hash",
    "algorithm", "app", "R", "c", "fused", "kernel", "kernel_variant",
    # Attention records (`--app attention`) only; None elsewhere. The
    # mask spec is a config axis: a sliding-window run must never pool
    # into a BigBird (or SDDMM) baseline.
    "mask",
    "elapsed", "overall_throughput", "source", "anomaly_count",
    # Serving records (`bench serve`) only; None elsewhere.
    "latency_p99_ms", "shed_count",
    # Program-store cold-start cost: in-process compiles this run paid
    # (0 for a fully disk-warmed run; None for pre-PR 6 records).
    "live_compiles",
    # PR 7 serving telemetry: percentiles from the mergeable fixed-
    # bucket request histogram plus the SLO error-budget burn rate.
    # None on every earlier doc — readers must treat absence as
    # "not measured", never as a verdict.
    "hist_p50_ms", "hist_p95_ms", "hist_p99_ms", "burn_rate",
    # Pod identity (PR 14): controller-process count and this record's
    # process slot. Absent on pre-pod docs; the config-axis matcher
    # normalizes absence to single-process (1) so history stays
    # comparable while future multi-host records never pool into
    # single-process baselines.
    "num_processes", "process_index",
    # Wire precision (PR 15): the realized collective payload policy
    # ("f32"/"bf16") and the run's total counted comm bytes (summed
    # over per-op metrics; None on pre-PR-15 docs and metric-less
    # records — "not measured", never a verdict).
    "wire", "comm_bytes",
    # Dynamic structure (PR 20): zero-retrace structure rebinds this
    # run performed (None on pre-PR-20 docs — "not measured").
    "dynstruct_rebinds",
)

#: Configuration axes (beyond the fingerprint key) two runs must share
#: to be regression-comparable: the fingerprint pins (problem, machine,
#: code) but one problem legitimately runs under many configurations —
#: a heatmap sweep benchmarks every algorithm at every R cell — and
#: pooling a 2.5D Cannon run into a 1.5D-fused baseline would gate on
#: an apples-to-oranges delta.
# ``kernel_variant`` joined in PR 9 — a banked-variant run must not
# pool into the generic kernel's baseline (both directions would poison
# the noise bands); pre-PR-9 docs carry None, which matches every other
# None-variant run, so history stays comparable. ``mask`` joined with
# the attention app (PR 13): the ``app`` axis already keeps attention
# runs out of SDDMM baselines, and the mask spec keeps the mask
# families apart from each other; non-attention docs carry None, which
# matches None.
# ``num_processes`` joined in PR 14: a pod record's timings include DCN
# collectives a single-controller run never pays — pooling either way
# would poison the noise bands. Pre-pod docs carry None, which the
# matcher normalizes to 1 (single-process) so existing history keeps
# comparing.
# ``wire`` joined in PR 15: a bf16-wire run moves half the collective
# bytes of an f32 run of the same problem — pooling either way would
# poison the bands. Pre-PR-15 docs carry None, which the matcher
# normalizes to "f32" (the identity wire every old run realized).
_CONFIG_AXES = (
    "algorithm", "app", "c", "fused", "kernel", "kernel_variant", "mask",
    "num_processes", "wire",
)


def _axis_value(row: dict, axis: str):
    """Config-axis value with absence normalization: ``num_processes``
    None (every pre-PR-14 row) means single-process; ``wire`` None
    (every pre-PR-15 row) means the f32 identity wire."""
    v = row.get(axis)
    if axis == "num_processes" and v is None:
        return 1
    if axis == "wire" and v is None:
        return "f32"
    return v


class RunStore:
    """One directory of run documents plus a derived summary index."""

    def __init__(self, root: str | os.PathLike | None = None):
        self.root = pathlib.Path(root) if root else DEFAULT_ROOT
        self.runs_dir = self.root / "runs"
        self.index_path = self.root / "index.json"
        self._lock = threading.Lock()

    # ------------------------------------------------------------------ #
    # Document I/O
    # ------------------------------------------------------------------ #

    def put(self, doc: dict) -> pathlib.Path:
        """Persist one run document and update the index atomically.

        ``doc`` must carry ``run_id``; ``schema``/``created_epoch`` are
        filled in when absent. Re-putting a run_id overwrites (a rerun
        under the same explicit id is one logical run).
        """
        run_id = doc.get("run_id")
        if not run_id:
            raise ValueError("run doc needs a run_id")
        doc.setdefault("schema", SCHEMA_VERSION)
        doc.setdefault("created_epoch", clock.epoch())
        path = self.runs_dir / f"{_safe_id(run_id)}.json"
        with self._lock, self._flock():
            atomic_write_json(path, doc)
            index = self._read_index()
            if index is _CORRUPT:
                # Recover the other rows from the run docs on disk
                # before appending ours — a torn index must not cost
                # the whole history.
                index = self._rebuild_index_locked()
            index = [r for r in index if r.get("run_id") != run_id]
            index.append(_index_row(doc))
            index.sort(key=lambda r: (r.get("created_epoch") or 0, r["run_id"]))
            atomic_write_json(self.index_path, index)
        return path

    @contextlib.contextmanager
    def _flock(self):
        """Advisory cross-PROCESS lock around the index read-modify-
        write: the threading.Lock covers one process, but two parallel
        bench invocations auto-ingesting into the same store would
        otherwise each read-append-write index.json and drop the
        other's row. Best-effort: no fcntl (non-POSIX) → in-process
        lock only."""
        try:
            import fcntl
        except ImportError:
            yield
            return
        self.root.mkdir(parents=True, exist_ok=True)
        # non-atomic-ok: flock target — the file's CONTENT is never read.
        with open(self.root / ".lock", "w") as fh:
            fcntl.flock(fh, fcntl.LOCK_EX)
            try:
                yield
            finally:
                fcntl.flock(fh, fcntl.LOCK_UN)

    def get(self, run_id: str) -> dict | None:
        """Load one run document (None when absent or unreadable)."""
        path = self.runs_dir / f"{_safe_id(run_id)}.json"
        try:
            doc = json.loads(path.read_text())
        except (OSError, json.JSONDecodeError):
            return None
        return doc if isinstance(doc, dict) else None

    def index(self) -> list[dict]:
        """Summary rows, oldest first; rebuilt from run docs when the
        index file is missing or corrupt (derived state is never load-
        bearing)."""
        with self._lock:
            rows = self._read_index()
            if rows is _CORRUPT:
                return self._rebuild_index_locked()
            return rows

    def rebuild_index(self) -> list[dict]:
        """Regenerate index.json from the run documents on disk."""
        with self._lock:
            return self._rebuild_index_locked()

    def _read_index(self):
        try:
            rows = json.loads(self.index_path.read_text())
        except FileNotFoundError:
            return []
        except (OSError, json.JSONDecodeError):
            return _CORRUPT
        if not isinstance(rows, list):
            return _CORRUPT
        return [r for r in rows if isinstance(r, dict) and r.get("run_id")]

    def _rebuild_index_locked(self) -> list[dict]:
        rows = []
        for f in sorted(self.runs_dir.glob("*.json")):
            try:
                doc = json.loads(f.read_text())
            except (OSError, json.JSONDecodeError):
                continue  # torn write — the doc, not the store, is lost
            if isinstance(doc, dict) and doc.get("run_id"):
                rows.append(_index_row(doc))
        rows.sort(key=lambda r: (r.get("created_epoch") or 0, r["run_id"]))
        atomic_write_json(self.index_path, rows)
        return rows

    # ------------------------------------------------------------------ #
    # Queries the regression gate runs on
    # ------------------------------------------------------------------ #

    def history(
        self, key: str | None = None, backend: str | None = None,
        limit: int | None = None,
    ) -> list[dict]:
        """Index rows, newest LAST, optionally filtered to one
        fingerprint key and/or backend; ``limit`` keeps the newest N."""
        rows = self.index()
        if key:
            rows = [r for r in rows if r.get("key") == key]
        if backend:
            rows = [r for r in rows if r.get("backend") == backend]
        if limit is not None and limit >= 0:
            rows = rows[-limit:] if limit else []
        return rows

    def matching(self, doc: dict, limit: int = 5) -> list[dict]:
        """The newest ``limit`` run DOCUMENTS comparable to ``doc`` —
        same index key (problem fingerprint + code hash + backend) AND
        same configuration axes (algorithm, app, c, fused, kernel) —
        excluding ``doc`` itself: the rolling baseline population for
        ``bench gate``."""
        key = doc.get("key")
        if not key:
            return []
        cfg = _index_row(doc)
        rows = [
            r for r in self.history(key=key, backend=doc.get("backend"))
            if r.get("run_id") != doc.get("run_id")
            and all(_axis_value(r, a) == _axis_value(cfg, a)
                    for a in _CONFIG_AXES)
        ]
        docs = [self.get(r["run_id"]) for r in rows[-limit:]]
        return [d for d in docs if d]

    def resolve(self, spec: str) -> dict | None:
        """Resolve a CLI run spec to a document: an exact run_id, a
        unique run_id prefix, ``latest``, or ``latest~N`` (N runs back).
        Returns None when nothing matches; raises ValueError when a
        prefix is ambiguous (the caller's error message must steer the
        user toward a longer prefix, not claim the run does not exist)."""
        if spec.startswith("latest"):
            back = 0
            if spec != "latest":
                try:
                    back = int(spec.split("~", 1)[1])
                except (IndexError, ValueError):
                    return None
            rows = self.index()
            if back >= len(rows):
                return None
            return self.get(rows[-1 - back]["run_id"])
        doc = self.get(spec)
        if doc is not None:
            return doc
        hits = [r for r in self.index() if r["run_id"].startswith(spec)]
        if len(hits) == 1:
            return self.get(hits[0]["run_id"])
        if len(hits) > 1:
            sample = ", ".join(r["run_id"] for r in hits[:4])
            raise ValueError(
                f"run spec {spec!r} is ambiguous ({len(hits)} matches: "
                f"{sample}{', ...' if len(hits) > 4 else ''}); use a "
                "longer prefix"
            )
        return None

    # ------------------------------------------------------------------ #
    # The join: bench record -> run document
    # ------------------------------------------------------------------ #

    def ingest_record(self, record: dict, source: str = "bench") -> dict:
        """Build + persist the run document for one bench record.

        Joins the record with the trace aggregate and manifest (when the
        record names a trace) and stamps the fingerprint/code-hash/
        backend index key. Every record is its own run: a traced sweep
        stamps ONE tracer run_id into every record it emits, so ids are
        uniquified with a ``-N`` suffix here rather than letting later
        sweep cells overwrite earlier ones. Returns the stored document.
        """
        doc = build_run_doc(record, source=source)
        base = doc["run_id"]
        n = 1
        while self.get(doc["run_id"]) is not None:
            n += 1
            doc["run_id"] = f"{base}-{n}"
        self.put(doc)
        return doc

    def ingest_prebuilt(self, doc: dict) -> dict:
        """Persist an already-joined document (backfill path)."""
        doc.setdefault("created_epoch", clock.epoch())
        self.put(doc)
        return doc


#: Sentinel distinguishing "no index yet" from "index unreadable".
_CORRUPT = object()


def _safe_id(run_id: str) -> str:
    """Run ids become file names; keep them path-safe (no separators,
    no hidden/relative-looking leading dots)."""
    safe = "".join(c if (c.isalnum() or c in "-_.") else "_" for c in run_id)
    return safe.lstrip(".") or "run"


def _total_comm_bytes(rec: dict):
    """Total counted comm bytes across the record's per-op metrics —
    None (not 0) when no op reported the field, so pre-PR-15 docs read
    as "not measured" rather than "moved nothing"."""
    vals = [
        m.get("comm_bytes")
        for m in (rec.get("metrics") or {}).values()
        if isinstance(m, dict) and m.get("comm_bytes") is not None
    ]
    return sum(vals) if vals else None


def _index_row(doc: dict) -> dict:
    rec = doc.get("record") or {}
    anomalies = (doc.get("anomalies") or {}).get("anomalies", [])
    row = {
        "run_id": doc.get("run_id"),
        "created_epoch": doc.get("created_epoch"),
        "key": doc.get("key"),
        "backend": doc.get("backend"),
        "code_hash": doc.get("code_hash"),
        "algorithm": rec.get("algorithm"),
        "app": rec.get("app"),
        "R": rec.get("R"),
        "c": rec.get("c"),
        "fused": rec.get("fused"),
        "kernel": rec.get("kernel"),
        "kernel_variant": rec.get("kernel_variant"),
        "mask": rec.get("mask"),
        "elapsed": rec.get("elapsed"),
        "overall_throughput": rec.get("overall_throughput"),
        "source": doc.get("source"),
        "anomaly_count": sum(a.get("count", 1) for a in anomalies),
        "latency_p99_ms": (rec.get("latency_ms") or {}).get("p99"),
        "shed_count": rec.get("shed_count"),
        "hist_p50_ms": (rec.get("latency_hist_ms") or {}).get("p50"),
        "hist_p95_ms": (rec.get("latency_hist_ms") or {}).get("p95"),
        "hist_p99_ms": (rec.get("latency_hist_ms") or {}).get("p99"),
        "burn_rate": rec.get("burn_rate"),
        "num_processes": rec.get("num_processes"),
        "process_index": rec.get("process_index"),
        "wire": rec.get("wire"),
        "comm_bytes": _total_comm_bytes(rec),
        # Offline records carry the GLOBAL counter delta; serving
        # records the engine's own ladder attribution.
        "live_compiles": (
            (rec.get("program_store") or {}).get("live_compiles")
            if rec.get("program_store") is not None
            else (rec.get("engine") or {}).get("live_compiles")
        ),
        "dynstruct_rebinds": (
            (rec.get("dynstruct") or {}).get("dynstruct_rebinds")
            if rec.get("dynstruct") is not None
            else None
        ),
    }
    return {k: row[k] for k in _INDEX_FIELDS}


def _live_backend() -> str | None:
    """The already-initialized jax backend, never initializing one (the
    same discipline as ``obs/manifest.py``)."""
    import sys

    jax = sys.modules.get("jax")
    if jax is None:
        return None
    try:
        backends = getattr(jax._src.xla_bridge, "_backends", None)
        if backends:
            return jax.default_backend()
    except Exception:  # noqa: BLE001 — best-effort, like the manifest
        pass
    return None


def _fingerprint_for(record: dict, backend: str | None) -> dict:
    """Fingerprint fields + key for a bench record, via the autotune
    fingerprint so plan cache and run store agree on what "same problem
    on same machine under same code" means."""
    from distributed_sddmm_tpu.autotune import fingerprint as fp

    info = record.get("alg_info") or {}
    problem = fp.Problem(
        M=int(info.get("m") or 0), N=int(info.get("n") or 0),
        nnz=int(info.get("nnz") or 0), R=int(record.get("R") or 0),
    )
    backend = backend or "unknown"
    kernels = ("pallas", "xla") if backend == "tpu" else ("xla",)
    made = fp.make_fingerprint(
        problem, p=int(info.get("p") or 0), backend=backend, kernels=kernels,
    )
    return {"fingerprint": made.as_dict(), "key": made.key,
            "code_hash": fp.code_hash(), "backend": backend}


def build_run_doc(record: dict, source: str = "bench") -> dict:
    """The join, without persistence (testable on synthetic records)."""
    doc = {
        "schema": SCHEMA_VERSION,
        "run_id": record.get("run_id") or _fallback_run_id(),
        "created_epoch": clock.epoch(),
        "source": source,
        "record": record,
        "anomalies": record.get("anomalies"),
    }
    backend = None
    trace_path = record.get("trace_path")
    if trace_path:
        from distributed_sddmm_tpu.tools import tracereport

        try:
            # Attach the per-phase aggregate only when this trace holds
            # exactly one bench span: a sweep shares one trace file
            # across its records (spans emit on close, so record k sees
            # k closed bench spans), and aggregating the whole file
            # would charge earlier cells' phases to this record. The
            # record's own `metrics` remain the per-record fallback the
            # regression compare uses. The pre-count streams the raw
            # lines instead of JSON-parsing the whole (growing) file
            # for every sweep cell — only the single-bench case pays
            # for a full parse.
            if _count_bench_spans(trace_path, stop_after=2) <= 1:
                tr = tracereport.load_trace(trace_path, strict=False)
                agg = tracereport.aggregate(tr)
                doc["phases"] = agg.get("phases")
                doc["trace_events"] = agg.get("events")
                doc["strategy"] = agg.get("strategy")
        except (OSError, ValueError):
            pass  # a torn trace must not lose the run record itself
        manifest = tracereport.load_manifest(trace_path)
        if manifest:
            doc["manifest"] = {
                k: manifest.get(k)
                for k in ("jax_version", "jaxlib_version", "backend",
                          "device_count", "device_kind", "git_rev",
                          "git_dirty", "env")
            }
            # The manifest saw the live backend at run time — more
            # authoritative than a post-hoc module probe.
            backend = manifest.get("backend")
    # Fingerprint once, after the backend source is decided.
    doc.update(_fingerprint_for(record, backend or _live_backend()))
    return doc


def _count_bench_spans(trace_path, stop_after: int = 2) -> int:
    """Cheap streaming count of closed ``bench`` spans in a trace file
    (substring match on the raw lines — json.dumps emits the literal
    ``"name": "bench"``), bailing at ``stop_after``. A false positive
    merely skips the optional phase enrichment; it can never corrupt a
    run document."""
    n = 0
    with open(trace_path) as fh:
        for line in fh:
            if '"name": "bench"' in line:
                n += 1
                if n >= stop_after:
                    break
    return n


def _fallback_run_id() -> str:
    """Untraced runs still need a unique id to live in the store — the
    tracer's grammar, so trace files and store docs stay visually and
    prefix-wise interchangeable."""
    from distributed_sddmm_tpu.obs.trace import _make_run_id

    return _make_run_id()


# --------------------------------------------------------------------- #
# Backfill: the committed round 1–5 trajectory becomes store history
# --------------------------------------------------------------------- #

#: ``parsed.metric`` shape of the historical headline records, e.g.
#: "fused SDDMM+SpMM GFLOP/s/chip (R-mat 2^16, nnz/row=32, R=128,
#:  pallas-bf16 kernel, 1 tpu chip(s))".
_METRIC_RE = (
    r"R-mat 2\^(?P<logm>\d+), nnz/row=(?P<npr>\d+), R=(?P<R>\d+), "
    r"(?P<kernel>[\w.-]+) kernel, (?P<p>\d+) (?P<backend>\w+) chip"
)


def _doc_from_headline(run_id: str, parsed: dict, source: str,
                       rc=None, epoch: float = 0.0) -> dict:
    """One run document from a BENCH_r0x ``parsed`` headline. ``epoch`` is a tiny
    deterministic ordinal (round number), NOT the ingest time: history
    sorts by ``created_epoch``, and backfilled rounds must sort *before*
    every live run — `resolve("latest")` returning a years-old record
    because it was ingested a second ago would break compare/gate."""
    import re

    from distributed_sddmm_tpu.autotune import fingerprint as fp

    record = {
        "app": "vanilla",
        "overall_throughput": parsed.get("value"),
        "unit": parsed.get("unit"),
        "metric": parsed.get("metric"),
        "vs_baseline": parsed.get("vs_baseline"),
        "note": parsed.get("note"),
        "rc": rc,
    }
    doc = {
        "schema": SCHEMA_VERSION,
        "run_id": run_id,
        "created_epoch": epoch,
        "source": source,
        "record": record,
        "key": None,
        "backend": parsed.get("backend"),
        # The historical code generation, NOT today's: a backfilled run
        # must never alias a live run's index key — its numbers would
        # poison the rolling baseline the gate compares against.
        "code_hash": parsed.get("code_hash", "historical"),
    }
    m = re.search(_METRIC_RE, str(parsed.get("metric", "")))
    if m:
        M = 1 << int(m.group("logm"))
        backend = parsed.get("backend") or m.group("backend")
        problem = fp.Problem(M=M, N=M, nnz=M * int(m.group("npr")),
                             R=int(m.group("R")))
        made = fp.make_fingerprint(
            problem, p=int(m.group("p")), backend=backend,
            kernels=("pallas", "xla") if backend == "tpu" else ("xla",),
            code=doc["code_hash"],
        )
        doc.update({"fingerprint": made.as_dict(), "key": made.key,
                    "backend": backend})
        record["R"] = problem.R
        record["alg_info"] = {"m": M, "n": M, "nnz": problem.nnz,
                              "p": int(m.group("p"))}
        record["kernel"] = m.group("kernel")
    return doc


def backfill_historical(store: RunStore, root=None) -> list[dict]:
    """Ingest the committed round records — BENCH_r0*.json and
    MULTICHIP_r0*.json under ``root`` — so ``bench history`` opens with
    the repo's real trajectory instead of an empty store. Idempotent: run ids are derived from file names, so
    re-running overwrites in place. Returns the ingested documents."""
    root = pathlib.Path(root) if root else _REPO

    def _round(stem: str) -> float:
        digits = "".join(c for c in stem if c.isdigit())
        return float(digits) if digits else 0.0

    docs = []
    for f in sorted(root.glob("BENCH_r0*.json")):
        try:
            rec = json.loads(f.read_text())
        except (OSError, json.JSONDecodeError):
            continue
        parsed = rec.get("parsed") or {}
        doc = _doc_from_headline(
            f"backfill-{f.stem.lower()}", parsed, source=f.name,
            rc=rec.get("rc"), epoch=_round(f.stem),
        )
        docs.append(store.ingest_prebuilt(doc))
    for f in sorted(root.glob("MULTICHIP_r0*.json")):
        try:
            rec = json.loads(f.read_text())
        except (OSError, json.JSONDecodeError):
            continue
        doc = {
            "schema": SCHEMA_VERSION,
            "run_id": f"backfill-{f.stem.lower()}",
            # Ordinal epoch (round + small offset): sorts with its
            # round, always before live runs (see _doc_from_headline).
            "created_epoch": _round(f.stem) + 0.25,
            "source": f.name,
            "key": None,
            "backend": None,
            "code_hash": "historical",
            "record": {
                "app": "multichip",
                "n_devices": rec.get("n_devices"),
                "ok": rec.get("ok"),
                "skipped": rec.get("skipped"),
                "rc": rec.get("rc"),
            },
        }
        docs.append(store.ingest_prebuilt(doc))
    return docs


# --------------------------------------------------------------------- #
# Module-level activation (the bench harness's auto-write hook)
# --------------------------------------------------------------------- #

_active: RunStore | None = None
_env_checked = False
_registry_lock = threading.Lock()
_suppress_count = 0


@contextlib.contextmanager
def suppressed():
    """Hide the active store for the duration of the block —
    process-wide, not thread-local, because the suppressed work may
    hop to a worker thread (autotune trials run under the thread-based
    timeout). Used by autotune's candidate measurement: those short
    probes flow through ``benchmark_algorithm`` but are not *runs*, and
    persisting them would pollute history and skew the gate's rolling
    baseline with 2-trial compile-heavy records."""
    global _suppress_count
    with _registry_lock:
        _suppress_count += 1
    try:
        yield
    finally:
        with _registry_lock:
            _suppress_count -= 1


def parse_env_spec(spec: str | None) -> tuple[bool, str | None]:
    """One grammar for ``DSDDMM_RUNSTORE``, shared by :func:`active` and
    the bench CLI: returns ``(enabled, root)`` where ``0/off/false/no``
    disables, ``1/on/true/yes``/empty selects the default root, and any
    other value is a root path. Empty/unset counts as *enabled with the
    default root* — the caller decides whether unset means "on by
    default" (CLI bench runs) or "off" (library use, via :func:`active`
    which only enables on a non-empty spec)."""
    spec = spec or ""
    low = spec.lower()
    if low in ("0", "off", "false", "no"):
        return False, None
    if not spec or low in ("1", "on", "true", "yes"):
        return True, None
    return True, spec


def enable(root: str | os.PathLike | None = None) -> RunStore:
    """Activate the process-wide store (idempotent; an active store
    wins, mirroring the tracer's semantics)."""
    global _active, _env_checked
    with _registry_lock:
        _env_checked = True
        if _active is None:
            _active = RunStore(root)
        return _active


def disable() -> None:
    global _active, _env_checked
    with _registry_lock:
        _active = None
        _env_checked = True


def active() -> RunStore | None:
    """The active store, activating from ``DSDDMM_RUNSTORE`` on first
    query (``1``/``on`` → default root, a path → that root, ``0``/
    ``off``/unset → None)."""
    global _active, _env_checked
    if _suppress_count:
        return None
    if _env_checked:
        return _active
    with _registry_lock:
        if not _env_checked:
            _env_checked = True
            spec = os.environ.get("DSDDMM_RUNSTORE", "")
            enabled, root = parse_env_spec(spec)
            if spec and enabled:
                _active = RunStore(root)
    return _active
