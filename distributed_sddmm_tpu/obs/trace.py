"""Process-wide structured tracer: nested spans, thread-safe JSONL.

:func:`span` is the program's one span API, and it has two sinks. Every
span enters a ``jax.profiler.TraceAnnotation`` of its name, always: a
``jax.profiler`` capture, whoever started it, shows the span on the host
line of the thread that opened it, on the trace's clock. With no capture
running that costs the C++ ``TraceMe``'s one check. The JSONL sink below
is the optional second one.

One tracer per process, activated by the ``DSDDMM_TRACE`` environment
variable (``1`` → the default ``artifacts/traces/<run_id>.jsonl``; any
other value is used as the output path, a directory landing the default
file name inside it), by the bench CLI's ``--trace`` flag, or
programmatically via :func:`enable`.

Design constraints, in order:

1. **Near-zero overhead when JSONL is off.** :func:`span` then checks
   one module-level boolean and returns a profiler-only span: no file,
   no lock, no clock read; :func:`event` returns at once. Strategy
   dispatch opens three spans on every compiled-program call; a test
   pins a budget for the JSONL-off path.
2. **Thread-safe emission.** Retry workers, autotune trials and the
   checkpoint writer all emit from non-main threads; records are
   serialized under one lock and written as complete lines, so a trace
   is valid JSONL even under concurrency. Span *nesting* is tracked
   per-thread (thread-local stack) — a worker thread's spans parent to
   that thread's enclosing span, never to another thread's.
3. **Monotonic timestamps.** ``t0``/``t1`` are ``time.perf_counter``
   offsets from the tracer's start; the begin record carries the epoch
   time of that origin so tools can reconstruct wall-clock — and so
   ``bench trace-merge`` can offset-align shards written by different
   processes onto one timeline (each process's ``t0_epoch`` is its
   shard's clock-calibration header).
4. **One process, one file.** A trace file is owned by exactly one
   process. Directory specs embed the run_id (which embeds the pid) in
   the file name, so concurrent processes never collide; an *explicit*
   ``PATH.jsonl`` spec that another live process already owns reroutes
   this process's writes into the sibling shard directory
   ``PATH.shards/<run_id>.jsonl`` instead of truncating or interleaving.
   Enabling with an explicit file also exports ``DSDDMM_TRACE`` =
   ``PATH.shards`` to child processes, so workers a traced run spawns
   (serve smoke, ``tests/_mp_worker.py``) write per-process shards by
   default; ``bench trace-merge PATH.jsonl`` stitches the stem file and
   its shards back into one trace.

Besides the JSONL file there is one optional in-memory sink: the
**span ring** (:func:`arm_ring`), a bounded deque of the most recent
emitted records. The flight recorder (``obs/flightrec.py``) dumps it
when the watchdog fires, and the admin server's ``/debug/requests``
endpoint reconstructs recent request timelines from it. Arming the
ring with no file tracer active installs a *memory-only* tracer
(``path is None``) so spans and events still flow — ``enabled()``
becomes true but ``trace_path()`` stays None, and nothing touches the
filesystem.

Record schema (one JSON object per line, ``schema`` = SCHEMA_VERSION):

* ``{"type": "begin", "schema": 1, "run_id": .., "t0_epoch": ..,
  "pid": ..}`` — first line of every trace; ``t0_epoch`` is the
  wall-clock time of the monotonic origin (the shard-alignment anchor).
* ``{"type": "span", "name": .., "id": .., "parent": .., "tid": ..,
  "t0": .., "t1": .., "dur_s": .., "attrs": {..}}`` — emitted when
  the span *closes* (children therefore appear before their parent;
  readers reconstruct nesting from ``parent``).
* ``{"type": "event", "name": .., "id": .., "parent": .., "tid": ..,
  "t": .., "attrs": {..}}`` — instantaneous (fault fired, retry,
  guard repair, checkpoint, cache hit, log mirror).

``tools/tracereport.py`` is the schema's reader and validator.
"""

from __future__ import annotations

import collections
import errno
import json
import os
import pathlib
import sys
import threading
import time
from typing import Optional

from distributed_sddmm_tpu.obs import clock

#: Trace record schema generation; readers reject records they cannot
#: interpret. Bump on any incompatible change.
SCHEMA_VERSION = 1

_REPO = pathlib.Path(__file__).resolve().parents[2]
DEFAULT_TRACE_DIR = _REPO / "artifacts" / "traces"

# Module-level fast path: `_active is None` means every hook is a no-op.
_active: Optional["Tracer"] = None
_env_checked = False
_registry_lock = threading.Lock()
#: (previous DSDDMM_TRACE value, exported?) — enable() exports the shard
#: directory to children; disable() restores the inherited value.
_env_export: tuple[Optional[str], bool] = (None, False)
#: The directory child processes of this traced run shard into.
_shard_dir: Optional[str] = None
#: Optional bounded in-memory sink of emitted records (flight recorder
#: ring + admin /debug/requests source); None = disarmed.
_ring: Optional["SpanRing"] = None


class SpanRing:
    """Bounded ring of the most recent emitted trace records.

    Thread-safe; holds the record dicts exactly as emitted (spans close
    before they land here, so the ring is the last ``capacity`` *completed*
    spans and events — an in-flight span is not visible until it exits).
    """

    def __init__(self, capacity: int = 512):
        self.capacity = int(capacity)
        self._lock = threading.Lock()
        self._buf: collections.deque = collections.deque(maxlen=self.capacity)
        #: Total records ever appended (rotation-aware: ``appended -
        #: len(records())`` is how many the ring has already forgotten).
        self.appended = 0

    def append(self, rec: dict) -> None:
        with self._lock:
            self._buf.append(rec)
            self.appended += 1

    def records(self) -> list[dict]:
        with self._lock:
            return list(self._buf)

    def clear(self) -> None:
        with self._lock:
            self._buf.clear()


def _make_run_id() -> str:
    return (
        time.strftime("%Y%m%d-%H%M%S")
        + f"-{os.getpid()}-{int.from_bytes(os.urandom(2), 'big'):04x}"
    )


def _annotation(name: str):
    """A ``jax.profiler.TraceAnnotation(name)``, or None in a process
    that has not imported ``jax.profiler`` (no capture can run there, and
    a JAX-free process stays JAX-free)."""
    prof = sys.modules.get("jax.profiler")
    return None if prof is None else prof.TraceAnnotation(name)


class _ProfilerSpan:
    """The span with JSONL off: its profiler annotation alone."""

    __slots__ = ("_me",)

    def __init__(self, name: str):
        self._me = _annotation(name)

    def __enter__(self):
        if self._me is not None:
            self._me.__enter__()
        return self

    def __exit__(self, *exc):
        if self._me is not None:
            self._me.__exit__(*exc)
        return False

    def set(self, **attrs) -> None:
        pass


class Span:
    """A live span; emitted as one JSONL record when it closes, and shown
    in a profiler capture as its annotation."""

    __slots__ = ("tracer", "name", "attrs", "id", "parent", "tid", "_t0",
                 "_me")

    def __init__(self, tracer: "Tracer", name: str, attrs: dict):
        self.tracer = tracer
        self.name = name
        self.attrs = attrs
        self._me = _annotation(name)

    def set(self, **attrs) -> None:
        """Attach attributes mid-span (e.g. kernel vs overhead splits
        known only after the wrapped call returns)."""
        self.attrs.update(attrs)

    def __enter__(self) -> "Span":
        if self._me is not None:
            self._me.__enter__()
        tr = self.tracer
        self.id = tr.next_id()
        self.tid = threading.get_ident()
        stack = tr.stack()
        self.parent = stack[-1] if stack else None
        stack.append(self.id)
        self._t0 = clock.now()
        return self

    def __exit__(self, *exc) -> bool:
        t1 = clock.now()
        tr = self.tracer
        stack = tr.stack()
        if stack and stack[-1] == self.id:
            stack.pop()
        if exc and exc[0] is not None:
            self.attrs.setdefault("error", exc[0].__name__)
        try:
            tr.emit({
                "type": "span",
                "name": self.name,
                "id": self.id,
                "parent": self.parent,
                "tid": self.tid,
                "t0": round(self._t0 - tr.t0, 9),
                "t1": round(t1 - tr.t0, 9),
                "dur_s": round(t1 - self._t0, 9),
                "attrs": self.attrs,
            })
        finally:
            if self._me is not None:
                self._me.__exit__(*exc)
        return False


class Tracer:
    """JSONL-emitting tracer bound to one output file.

    ``path=None`` is the memory-only mode :func:`arm_ring` installs when
    no file tracer is active: spans and events flow (into the ring), but
    nothing touches the filesystem and ``trace_path()`` stays None.
    """

    def __init__(self, path: Optional[pathlib.Path], run_id: str):
        self.path = path
        self.run_id = run_id
        self.t0 = clock.now()
        self._lock = threading.Lock()
        self._ids = 0
        self._local = threading.local()
        if path is None:
            self._fh = None
        else:
            path.parent.mkdir(parents=True, exist_ok=True)
            # Truncate: one trace per file (re-running with the same
            # explicit --trace PATH.jsonl must not merge runs — the
            # reader would double-count). Default/directory specs embed
            # the run_id in the file name, and an explicit file another
            # LIVE process owns was already rerouted into the shard
            # directory by _resolve_path, so two running processes never
            # share a file.
            # non-atomic-ok: streaming JSONL — the tracer appends for
            # the life of the run; readers tolerate a torn tail line.
            self._fh = open(path, "w", buffering=1)  # line-buffered
        # t0_epoch is the wall-clock reading of the monotonic origin —
        # the shard's clock-calibration header trace-merge aligns on.
        self.emit({
            "type": "begin",
            "schema": SCHEMA_VERSION,
            "run_id": run_id,
            "t0_epoch": clock.epoch(),
            "pid": os.getpid(),
        })

    def next_id(self) -> int:
        with self._lock:
            self._ids += 1
            return self._ids

    def stack(self) -> list:
        st = getattr(self._local, "stack", None)
        if st is None:
            st = self._local.stack = []
        return st

    def emit(self, record: dict) -> None:
        ring = _ring
        if ring is not None:
            ring.append(record)
        if self._fh is None:
            return
        line = json.dumps(record, default=str)
        with self._lock:
            if self._fh.closed:
                return
            self._fh.write(line + "\n")

    def current_span_id(self) -> Optional[int]:
        st = self.stack()
        return st[-1] if st else None

    def close(self) -> None:
        if self._fh is None:
            return
        with self._lock:
            if not self._fh.closed:
                self._fh.flush()
                self._fh.close()


# --------------------------------------------------------------------- #
# Module-level API — what the rest of the framework calls.
# --------------------------------------------------------------------- #


#: DSDDMM_TRACE values meaning "on at the default location" (not a
#: path). Shared with dist/run.py's shard-dir resolution so the two
#: can never disagree about what counts as a path spec.
FLAG_VALUES = ("1", "on", "true", "yes")


def _env_activate() -> None:
    global _env_checked
    with _registry_lock:
        if _env_checked:
            return
        _env_checked = True
        spec = os.environ.get("DSDDMM_TRACE")
        if spec:
            _enable_locked(None if spec in FLAG_VALUES else spec)


def _owning_pid(path: pathlib.Path) -> Optional[int]:
    """The pid in an existing trace file's begin record, or None."""
    try:
        with open(path) as fh:
            rec = json.loads(fh.readline())
    except (OSError, ValueError):
        return None
    if isinstance(rec, dict) and rec.get("type") == "begin":
        pid = rec.get("pid")
        return pid if isinstance(pid, int) else None
    return None


def _pid_alive(pid: int) -> bool:
    try:
        os.kill(pid, 0)
    except OSError as e:
        return e.errno == errno.EPERM  # exists, not ours to signal
    return True


def shard_dir_for(path) -> pathlib.Path:
    """The shard directory belonging to an explicit ``PATH.jsonl`` trace
    stem: sibling ``PATH.shards/`` (worker processes of the run write
    their per-process shards there; ``bench trace-merge PATH.jsonl``
    stitches stem + shards)."""
    return pathlib.Path(path).with_suffix(".shards")


def _resolve_path(spec, run_id: str) -> pathlib.Path:
    if spec is None:
        return DEFAULT_TRACE_DIR / f"{run_id}.jsonl"
    p = pathlib.Path(spec)
    if p.suffix != ".jsonl":  # treat as a directory
        return p / f"{run_id}.jsonl"
    # Explicit file: if another LIVE process already owns it (a parent
    # that exported this spec to us, or a sibling launched with the same
    # flag), become a shard instead of truncating/interleaving its file.
    owner = _owning_pid(p)
    if owner is not None and owner != os.getpid() and _pid_alive(owner):
        return shard_dir_for(p) / f"{run_id}.jsonl"
    return p


def _export_child_spec(spec, resolved: pathlib.Path) -> None:
    """Point child processes at the shard directory for this trace.

    Directory/default specs already isolate per process (run_id in the
    file name) — children share the directory. An explicit ``.jsonl``
    file exports its sibling ``.shards`` directory, so workers a traced
    run spawns write shards instead of fighting over one file. The
    inherited ``DSDDMM_TRACE`` value is restored by :func:`disable`.
    """
    global _env_export, _shard_dir
    if spec is None:
        child = str(DEFAULT_TRACE_DIR)
    else:
        p = pathlib.Path(spec)
        child = str(shard_dir_for(p) if p.suffix == ".jsonl" else p)
    if resolved.parent != pathlib.Path(child) and resolved.suffix == ".jsonl" \
            and resolved.parent.name.endswith(".shards"):
        # We ourselves were rerouted into a shard dir: share it.
        child = str(resolved.parent)
    _env_export = (os.environ.get("DSDDMM_TRACE"), True)
    _shard_dir = child
    os.environ["DSDDMM_TRACE"] = child


def _enable_locked(spec=None, run_id: Optional[str] = None) -> "Tracer":
    global _active
    if _active is not None:
        return _active
    rid = run_id or _make_run_id()
    path = _resolve_path(spec, rid)
    _active = Tracer(path, rid)
    _export_child_spec(spec, path)
    return _active


def enable(path=None, run_id: Optional[str] = None) -> "Tracer":
    """Activate tracing (idempotent — an already-active tracer wins).

    ``path``: explicit ``.jsonl`` file, a directory, or None for
    ``artifacts/traces/<run_id>.jsonl``. Also writes the run manifest
    next to the trace (best-effort)."""
    global _env_checked
    with _registry_lock:
        _env_checked = True
        tr = _enable_locked(path, run_id)
    from distributed_sddmm_tpu.obs import manifest

    manifest.write_for_trace(tr)
    return tr


def disable() -> None:
    """Close and deactivate the tracer (tests; end-of-run flush).
    Restores the ``DSDDMM_TRACE`` value :func:`enable` exported for
    child processes, and disarms the span ring — ``disable()`` is the
    full reset the test fixtures rely on."""
    global _active, _env_checked, _env_export, _shard_dir, _ring
    with _registry_lock:
        if _active is not None:
            _active.close()
        _active = None
        _ring = None
        _env_checked = True
        prev, exported = _env_export
        if exported:
            if prev is None:
                os.environ.pop("DSDDMM_TRACE", None)
            else:
                os.environ["DSDDMM_TRACE"] = prev
        _env_export = (None, False)
        _shard_dir = None


def arm_ring(capacity: int = 512) -> SpanRing:
    """Attach (or return) the in-memory span ring.

    With a file tracer already active the ring simply taps its emit
    stream; with no tracer a **memory-only** tracer is installed so
    spans/events flow at all (``enabled()`` turns true, ``trace_path()``
    stays None). Arm AFTER enabling file tracing when you want both —
    ``enable()`` is idempotent and will not upgrade a memory tracer to
    a file one. Idempotent: an armed ring is returned as-is (capacity
    of the first arm wins)."""
    global _ring, _active
    if not _env_checked:
        _env_activate()  # a DSDDMM_TRACE file spec must win over memory
    with _registry_lock:
        if _ring is None:
            _ring = SpanRing(capacity)
        if _active is None:
            _active = Tracer(None, _make_run_id())
        return _ring


def disarm_ring() -> None:
    """Detach the span ring; a memory-only tracer installed by
    :func:`arm_ring` is deactivated too (a file tracer is untouched)."""
    global _ring, _active
    with _registry_lock:
        _ring = None
        if _active is not None and _active.path is None:
            _active = None


def ring() -> Optional[SpanRing]:
    """The armed span ring, or None."""
    return _ring


def shard_dir() -> Optional[str]:
    """The directory child processes of this traced run write shards
    into (the exported ``DSDDMM_TRACE``), or None when not tracing."""
    return _shard_dir if _active is not None else None


def tracer() -> Optional["Tracer"]:
    """The active tracer, activating from ``DSDDMM_TRACE`` on first query."""
    if not _env_checked:
        _env_activate()
    return _active


def enabled() -> bool:
    if not _env_checked:
        _env_activate()
    return _active is not None


def run_id() -> Optional[str]:
    tr = tracer()
    return tr.run_id if tr else None


def rel_time(t_perf: float) -> Optional[float]:
    """A ``clock.now()`` stamp as a trace-relative time (the unit span
    ``t0``/``t1`` and event ``t`` use), or None when not tracing. Lets
    emitters embed *precise* externally-captured stamps in event attrs —
    an event's own ``t`` is its emission time, which can lag the moment
    it describes by a thread-scheduling delay."""
    tr = tracer()
    return round(t_perf - tr.t0, 9) if tr else None


def trace_path() -> Optional[str]:
    tr = tracer()
    return str(tr.path) if tr is not None and tr.path is not None else None


# --------------------------------------------------------------------- #
# Fleet trace context — the cross-process propagation format.
# --------------------------------------------------------------------- #

#: HTTP header carrying fleet trace context on ``POST /submit``.
TRACE_HEADER = "X-DSDDMM-Trace"

#: Header format generation; decoders ignore versions they don't know.
TRACE_HEADER_VERSION = "v1"

#: Context fields, in wire order. ``req`` is the fleet-level request id
#: (always present, minted by the router even when tracing is off so
#: replica logs stay correlatable), ``shard`` the router's trace run_id,
#: ``span`` the router-side attempt span id the replica's records should
#: parent to, ``kind`` the attempt kind (primary/hedge/audit/arbitrate),
#: ``ord`` the failover ordinal of the attempt.
_CTX_FIELDS = ("req", "shard", "span", "kind", "ord")
_CTX_INT_FIELDS = ("span", "ord")


def encode_fleet_ctx(ctx: dict) -> str:
    """Serialize a fleet trace context to the ``X-DSDDMM-Trace`` wire
    value: ``v1;req=..;shard=..;span=..;kind=..;ord=..`` (fields with a
    None value are omitted; unknown keys are dropped)."""
    parts = [TRACE_HEADER_VERSION]
    for key in _CTX_FIELDS:
        val = ctx.get(key)
        if val is None:
            continue
        parts.append(f"{key}={val}")
    return ";".join(parts)


def decode_fleet_ctx(value) -> Optional[dict]:
    """Parse an ``X-DSDDMM-Trace`` header value back into a context
    dict, or None for a missing/garbage/unknown-version value. Integer
    fields (``span``, ``ord``) are coerced; a field that fails to parse
    is dropped rather than poisoning the rest (partial context is still
    useful for correlation)."""
    if not value or not isinstance(value, str):
        return None
    parts = value.strip().split(";")
    if not parts or parts[0] != TRACE_HEADER_VERSION:
        return None
    ctx: dict = {}
    for part in parts[1:]:
        key, sep, raw = part.partition("=")
        if not sep or key not in _CTX_FIELDS or not raw:
            continue
        if key in _CTX_INT_FIELDS:
            try:
                ctx[key] = int(raw)
            except ValueError:
                continue
        else:
            ctx[key] = raw
    return ctx if ctx.get("req") else None


def find_shard(directory, pid: int) -> Optional[str]:
    """The trace shard in ``directory`` whose begin record was written
    by ``pid``, or None. The fleet manager uses this to harvest a
    replica's shard at reap/quarantine time — the shard file name embeds
    the replica's run_id (which embeds its pid), but the begin record is
    the authoritative owner stamp."""
    d = pathlib.Path(directory)
    if not d.is_dir():
        return None
    for path in sorted(d.glob("*.jsonl")):
        if _owning_pid(path) == pid:
            return str(path)
    return None


#: Suffixes of the leaf spans ``DistributedSparse._timed`` opens inside
#: an op's span: ``<op>:dispatch`` around the program call, ``<op>:fetch``
#: around the barrier that waits for its outputs. Host phases of an op,
#: never ops themselves (no ``kernel_s``; absent from pre-split traces).
LEAF_SUFFIXES = (":dispatch", ":fetch")


def is_leaf(name: str) -> bool:
    """True for an ``<op>:dispatch`` / ``<op>:fetch`` leaf span name."""
    return name.endswith(LEAF_SUFFIXES)


def span(name: str, **attrs):
    """A context manager around a nested region: always a profiler
    annotation named ``name``; a JSONL record too when tracing is on.

    Usage::

        with trace.span("fusedSpMM", alg="15d_fusion2", R=128) as sp:
            out = run()
            sp.set(kernel_s=...)   # attrs added before the span closes
    """
    tr = tracer()
    if tr is None:
        return _ProfilerSpan(name)
    return Span(tr, name, attrs)


def event(name: str, **attrs) -> None:
    """Emit an instantaneous event under the current thread's span."""
    tr = tracer()
    if tr is None:
        return
    tr.emit({
        "type": "event",
        "name": name,
        "id": tr.next_id(),
        "parent": tr.current_span_id(),
        "tid": threading.get_ident(),
        "t": round(clock.now() - tr.t0, 9),
        "attrs": attrs,
    })
