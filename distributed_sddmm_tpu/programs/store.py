"""Persistent, fingerprint-keyed AOT compiled-program store.

Three compiled-program caches grew independently — the plan-routed
strategy programs (``autotune/plan.py``), the serve engine's bucket
ladder (``serve/engine.py``), and the bench AOT executables
(``bench/aot.py``) — each recompiling programs a previous run already
built. This module is the single store all three now read and write:
one directory (``artifacts/programs/`` by default) of serialized XLA
executables keyed by the shared grammar in ``programs/keys.py``
(problem shape + machine + code generation + aval signature), so a
serving cold start or a fresh worker process warms from disk instead of
compiling.

Durability discipline (the plan cache's, hardened by its corruption
suite):

* every write goes through ``utils/atomic.py`` (temp file +
  ``os.replace``; the resilience layer's write-fault hook applies),
* the summary ``index.json`` is derivative state behind the same
  advisory ``flock`` as the run store — corrupt or missing, it is
  rebuilt from the entry files, never trusted,
* a corrupt, truncated, schema-mismatched, foreign-key or
  wrong-backend entry reads as a **miss and is evicted**; the caller
  falls through to a live compile. The store is a pure accelerator —
  it can cost a compile, never an error,
* deserialization runs through ``compat.deserialize_and_load`` onto
  the device ids the entry recorded at save time, and any failure there
  also evicts and falls through.

Counters land in ``obs.metrics.GLOBAL``: ``program_store_hits`` (disk),
``program_store_misses`` (absent/evicted), ``live_compiles`` (an
executable was built in-process — the number a warmed cold start must
drive to zero). Since PR 7 the same facts are also trace *events* —
``program_store_hit`` / ``program_store_compile`` with the program key
(and compile seconds on the compile side) — so cold-start cost shows up
as tracereport phases, not just end-of-run counter deltas.

XLA cost cross-check: :meth:`ProgramStore.save` captures the compiled
executable's ``cost_analysis()`` FLOPs/bytes into the entry (and the
index row), and every save/load registers the numbers in a process-wide
cost log. :func:`xla_cost_summary` joins that log against the per-op
analytic counters (token-matching op names inside plan keys), giving
the tracereport/regress layers an *independent* FLOP column: the
analytic cost model and XLA's own accounting are maintained by
different parties, and their ratio drifting is how either one's bugs
surface (``obs/watchdog.py::check_xla_costs`` flags beyond-band
disagreement).

Activation mirrors the run store: ``DSDDMM_PROGRAMS`` = ``0``/``off``
disables, a path relocates, unset/``1`` selects the default root.
Unlike the run store (telemetry), the program store defaults ON — it is
a functional cache — but the test conftest vetoes it so CI cannot silt
``artifacts/``.
"""

from __future__ import annotations

import contextlib
import os
import pathlib
import pickle
import threading

from distributed_sddmm_tpu.programs import keys as keys_mod
from distributed_sddmm_tpu.utils.atomic import atomic_write_bytes, atomic_write_json

#: Entry payload schema generation; readers evict entries they cannot read.
SCHEMA_VERSION = 2  # 2: entries carry the executable's device ids

_REPO = pathlib.Path(__file__).resolve().parents[2]
DEFAULT_ROOT = _REPO / "artifacts" / "programs"


def _global_counters():
    from distributed_sddmm_tpu.obs import metrics as obs_metrics

    return obs_metrics.GLOBAL


# --------------------------------------------------------------------- #
# XLA cost capture (compiled.cost_analysis at compile/load time)
# --------------------------------------------------------------------- #

#: Process-wide append-only log of (key, cost) pairs, in resolution
#: order — callers snapshot ``cost_log_len()`` before a run and summon
#: ``xla_cost_summary(..., since=cursor)`` after, the same cursor
#: discipline the fault plan and watchdog events use.
_cost_log: list[tuple[str, dict]] = []
_cost_lock = threading.Lock()


def _cost_analysis(compiled) -> dict | None:
    """``{"flops", "bytes_accessed"}`` from an executable's own cost
    analysis, or None when this jax generation/backend exposes none.
    The numbers are XLA's accounting of the COMPILED program (padding
    and fusion included) — deliberately not the analytic model's."""
    try:
        cost = compiled.cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0] if cost else None
        if not isinstance(cost, dict):
            return None
        out = {}
        if cost.get("flops") is not None:
            out["flops"] = float(cost["flops"])
        if cost.get("bytes accessed") is not None:
            out["bytes_accessed"] = float(cost["bytes accessed"])
        return out or None
    except Exception:  # noqa: BLE001 — cost capture is best-effort
        return None


def register_cost(key: str, cost: dict | None) -> None:
    if not cost:
        return
    with _cost_lock:
        _cost_log.append((key, cost))


def cost_log_len() -> int:
    with _cost_lock:
        return len(_cost_log)


#: Metric-op → program-cache-key tokens (the strategy names its cached
#: programs "fused"/"sddmm"/"spmm"; app chains embed the metric name).
_OP_KEY_TOKENS = {
    "fusedSpMM": ("fused", "fused_twopass"),
    "fusedSpMMB": ("fused", "fused_twopass"),
    "fusedAttn": ("attn", "attn_v", "attn_sink", "attn_v_sink"),
    "fusedAttnB": ("attn",),
    "attnSoftmax": ("attn_softmax",),
    "sddmmA": ("sddmm",), "sddmmB": ("sddmm",),
    "spmmA": ("spmm",), "spmmB": ("spmm",),
}


def xla_cost_summary(ops, since: int = 0) -> dict | None:
    """Join the cost log against per-op analytic metrics ops.

    ``ops`` is an iterable of op names (typically a bench record's
    ``metrics`` keys). A logged key matches an op when one of the op's
    program-cache tokens appears as a ``-``/``:``-separated token of
    the key (plan keys embed the strategy's program-cache key, e.g.
    ``...-fused-False-none``; chained app keys embed the metric name
    itself, e.g. ``...-cgStep-A-...``). Returns ``{"programs": N,
    "ops": {op: {"flops_per_call", "bytes_per_call", "programs"}}}``
    averaging over matching programs (A/B-mode variants of one op
    legitimately differ), or None when nothing matched — records
    without the field simply lack the gate axis.
    """
    with _cost_lock:
        log = _cost_log[since:]
    if not log:
        return None
    out: dict[str, dict] = {}
    for op in ops:
        tokens = set(_OP_KEY_TOKENS.get(op, (op,)))
        flops, bytes_, n = 0.0, 0.0, 0
        for key, cost in log:
            if tokens & set(key.replace(":", "-").split("-")):
                n += 1
                flops += cost.get("flops", 0.0)
                bytes_ += cost.get("bytes_accessed", 0.0)
        if n and flops:
            out[op] = {
                "flops_per_call": flops / n,
                "bytes_per_call": bytes_ / n if bytes_ else None,
                "programs": n,
            }
    if not out:
        return None
    return {"programs": len(log), "ops": out}


def live_backend() -> str | None:
    """Platform of the default jax backend, initializing it if needed —
    the store's load path runs next to a compile, so a backend is
    already (or about to be) up; this is not the manifest's
    never-initialize context."""
    try:
        import jax

        return jax.default_backend()
    except Exception:  # noqa: BLE001 — no backend, no backend gate
        return None


class ProgramStore:
    """One directory of serialized executables plus a derived index.

    Layout::

        <root>/entries/<safe_stem(key)>.prog   pickled entry dict
        <root>/index.json                      summary rows (derived)

    An entry dict: ``{"schema", "key", "backend", "created_epoch",
    "meta", "payload"}`` where ``payload`` is
    ``jax.experimental.serialize_executable.serialize``'s
    ``(serialized, in_tree, out_tree)`` tuple.
    """

    def __init__(self, root: str | os.PathLike | None = None):
        self.root = pathlib.Path(root) if root else DEFAULT_ROOT
        self.entries_dir = self.root / "entries"
        self.index_path = self.root / "index.json"
        self._lock = threading.Lock()
        # Per-instance counters (tests + engine stats); the GLOBAL
        # counters aggregate across stores process-wide.
        self.hits = 0
        self.misses = 0
        self.live_compiles = 0

    def _path(self, key: str) -> pathlib.Path:
        return self.entries_dir / f"{keys_mod.safe_stem(key)}.prog"

    # ------------------------------------------------------------------ #
    # flock'd index (the run store's cross-process discipline)
    # ------------------------------------------------------------------ #

    @contextlib.contextmanager
    def _flock(self):
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

    def _read_index(self) -> list | None:
        import json

        try:
            rows = json.loads(self.index_path.read_text())
        except FileNotFoundError:
            return []
        except (OSError, ValueError):
            return None  # corrupt — rebuild
        if not isinstance(rows, list):
            return None
        return [r for r in rows if isinstance(r, dict) and r.get("key")]

    def _rebuild_index_locked(self) -> list:
        rows = []
        for f in sorted(self.entries_dir.glob("*.prog")):
            entry = self._read_entry_file(f)
            if entry is not None:
                rows.append(self._index_row(entry))
        atomic_write_json(self.index_path, rows)
        return rows

    @staticmethod
    def _index_row(entry: dict) -> dict:
        return {
            "key": entry.get("key"),
            "backend": entry.get("backend"),
            "created_epoch": entry.get("created_epoch"),
            "meta": entry.get("meta") or {},
            # XLA's own FLOPs/bytes for the executable (None on
            # pre-PR-7 entries and cost-less backends).
            "cost": entry.get("cost"),
        }

    def _update_index(self, entry: dict | None, drop_key: str | None = None):
        with self._flock():
            rows = self._read_index()
            if rows is None:
                rows = self._rebuild_index_locked()
            if drop_key is not None:
                rows = [r for r in rows if r.get("key") != drop_key]
            if entry is not None:
                rows = [r for r in rows if r.get("key") != entry.get("key")]
                rows.append(self._index_row(entry))
            rows.sort(key=lambda r: (r.get("created_epoch") or 0, r["key"]))
            atomic_write_json(self.index_path, rows)

    def index(self) -> list[dict]:
        with self._lock:
            rows = self._read_index()
            if rows is None:
                with self._flock():
                    rows = self._rebuild_index_locked()
            return rows

    # ------------------------------------------------------------------ #
    # Entry I/O
    # ------------------------------------------------------------------ #

    def _read_entry_file(self, path: pathlib.Path) -> dict | None:
        try:
            raw = path.read_bytes()
        except OSError:
            return None
        try:
            entry = pickle.loads(raw)
        except Exception:  # noqa: BLE001 — truncated/garbled pickle
            return None
        if not isinstance(entry, dict):
            return None
        if entry.get("schema") != SCHEMA_VERSION:
            return None
        return entry

    def evict(self, key: str) -> None:
        """Drop one entry (corruption, staleness); never raises."""
        try:
            os.unlink(self._path(key))
        except OSError:
            pass
        try:
            self._update_index(None, drop_key=key)
        except OSError:
            pass

    def load(self, key: str, *, backend: str | None = None, device=None):
        """The deserialized executable for ``key``, or None.

        Misses: absent file, unreadable/truncated pickle, schema or
        embedded-key mismatch (a renamed/copied entry must not answer
        for a foreign key), backend mismatch (an executable serialized
        for another platform cannot run here), or a deserialize failure
        — every non-absent miss also EVICTS the entry so the slot heals
        on the next save (backend mismatch excepted: a store shared
        between backends is legal, the entry is another platform's).
        Never raises for entry-content reasons.

        ``device`` pins deserialization to one device (the bench AOT
        re-homing path); default is the devices the executable was
        compiled for, by the ids the entry recorded.
        """
        from distributed_sddmm_tpu import compat
        from distributed_sddmm_tpu.obs import log as obs_log

        path = self._path(key)
        entry = self._read_entry_file(path)
        if entry is None:
            if path.exists():
                self.evict(key)
            self._miss()
            return None
        if entry.get("key") != key:
            self.evict(key)
            self._miss()
            return None
        if backend is not None:
            want_backend = backend
        elif device is not None:
            want_backend = device.platform
        else:
            want_backend = live_backend()
        if want_backend is not None and entry.get("backend") != want_backend:
            self._miss()
            return None
        try:
            import jax

            serialized, in_tree, out_tree = entry["payload"]
            if device is not None:
                devices = [device]
            else:
                by_id = {d.id: d for d in jax.devices()}
                devices = [by_id[i] for i in entry["device_ids"]]
            loaded = compat.deserialize_and_load(
                serialized, in_tree, out_tree, execution_devices=devices,
            )
        except Exception as e:  # noqa: BLE001 — any failure -> live compile
            obs_log.warn(
                "programs", "deserialize failed; evicting entry",
                key=key, error=f"{type(e).__name__}: {e}",
            )
            self.evict(key)
            self._miss()
            return None
        with self._lock:
            self.hits += 1
        _global_counters().add("program_store_hits")
        cost = entry.get("cost")
        register_cost(key, cost)
        # The counter's trace-event twin: disk warms are visible as
        # events in tracereport, not just end-of-run counter deltas.
        from distributed_sddmm_tpu.obs import trace as obs_trace

        if obs_trace.enabled():
            obs_trace.event(
                "program_store_hit", key=key,
                **({"xla_flops": cost["flops"]}
                   if cost and cost.get("flops") else {}),
            )
        return loaded

    def save(self, key: str, compiled, meta: dict | None = None,
             backend: str | None = None) -> bool:
        """Serialize + persist one compiled executable atomically.

        ``backend`` is the executable's TARGET platform; it defaults to
        the live backend but offline AOT compilers (a CPU-pinned process
        compiling for a TPU topology) must pass the target explicitly or
        the load-side backend gate would reject their own entries.

        Returns False (never raises) when this jax generation or
        executable cannot serialize — the store is an accelerator, and
        the caller already holds a working compiled program.
        """
        from distributed_sddmm_tpu.obs import clock
        from distributed_sddmm_tpu.obs import log as obs_log

        try:
            from jax.experimental import serialize_executable as se

            from distributed_sddmm_tpu import compat

            payload = se.serialize(compiled)
            cost = _cost_analysis(compiled)
            register_cost(key, cost)
            entry = {
                "schema": SCHEMA_VERSION,
                "key": key,
                "backend": backend if backend is not None else live_backend(),
                "created_epoch": clock.epoch(),
                "meta": dict(meta or {}),
                "cost": cost,
                "payload": payload,
                "device_ids": compat.executable_device_ids(compiled),
            }
            atomic_write_bytes(self._path(key), pickle.dumps(entry))
            self._update_index(entry)
            return True
        except Exception as e:  # noqa: BLE001 — persistence is best-effort
            obs_log.warn(
                "programs", "serialize/store failed; entry not persisted",
                key=key, error=f"{type(e).__name__}: {e}",
            )
            return False

    # ------------------------------------------------------------------ #
    # The one call sites use
    # ------------------------------------------------------------------ #

    def get_or_compile(self, key: str, compile_fn, meta: dict | None = None):
        """(program, source): the deserialized entry (``"disk"``) or a
        live ``compile_fn()`` result (``"live"``, persisted for the next
        process). ``compile_fn`` must return a callable compiled
        executable (e.g. ``jit_fn.lower(*args).compile()``). Live
        compiles emit a ``program_store_compile`` trace event carrying
        the key and compile seconds, so cold-start cost shows up in
        tracereport phases rather than only as a counter delta."""
        from distributed_sddmm_tpu.obs import clock
        from distributed_sddmm_tpu.obs import trace as obs_trace

        prog = self.load(key)
        if prog is not None:
            return prog, "disk"
        t0 = clock.now()
        prog = compile_fn()
        compile_s = clock.now() - t0
        self._live()
        self.save(key, prog, meta=meta)
        if obs_trace.enabled():
            with _cost_lock:
                cost = dict(_cost_log[-1][1]) \
                    if _cost_log and _cost_log[-1][0] == key else None
            obs_trace.event(
                "program_store_compile", key=key,
                compile_s=round(compile_s, 6),
                **({"xla_flops": cost["flops"]}
                   if cost and cost.get("flops") else {}),
            )
        return prog, "live"

    def _miss(self) -> None:
        with self._lock:
            self.misses += 1
        _global_counters().add("program_store_misses")

    def _live(self) -> None:
        with self._lock:
            self.live_compiles += 1
        _global_counters().add("live_compiles")

    def stats(self) -> dict:
        with self._lock:
            return {
                "hits": self.hits,
                "misses": self.misses,
                "live_compiles": self.live_compiles,
            }


# --------------------------------------------------------------------- #
# Store-backed jit wrapper (the strategy/app integration point)
# --------------------------------------------------------------------- #


class StoredProgram:
    """Wrap a jitted function with store-backed resolution per aval
    signature.

    On a call with concrete arrays the argument signature selects a
    store key (``key_fn(sig)``); resolution tries the store first
    (disk hit), else AOT-compiles the jit via ``lower(*args).compile()``
    (live compile, persisted). Under a jax trace (the wrapped program is
    being inlined into a larger jitted program — the cgStep/gatLayer
    chains do exactly this) the wrapper steps aside and calls the jit
    directly: tracers have no buffers to load into.

    A disk-loaded executable that rejects a call (shape drift the key
    missed, donation/layout mismatch) permanently falls back to the jit
    for that signature — correctness never depends on the store.
    """

    def __init__(self, jit_fn, key_fn, store: "ProgramStore | None",
                 meta: dict | None = None, on_resolve=None):
        self._jit_fn = jit_fn
        self._key_fn = key_fn
        self._store = store
        self._meta = meta or {}
        self._on_resolve = on_resolve  # callback(source: "disk"|"live")
        self._resolved: dict[str, object] = {}
        self._lock = threading.Lock()

    def __call__(self, *args):
        import jax

        if self._store is None:
            return self._jit_fn(*args)
        # One traversal serves both the tracer check and the dispatch
        # key. The resolved-program cache is keyed on the raw
        # (shape, dtype) tuple — comparable in cost to jit's own cache
        # lookup — and the sha-based store signature is computed only on
        # the resolution miss, not per dispatch.
        shapes = []
        for x in jax.tree_util.tree_leaves(args):
            if isinstance(x, jax.core.Tracer):
                # Being inlined into a larger jitted program: step aside.
                return self._jit_fn(*args)
            shapes.append((getattr(x, "shape", ()),
                           str(getattr(x, "dtype", ""))))
        cache_key = tuple(shapes)
        prog = self._resolved.get(cache_key)
        if prog is None:
            sig = keys_mod.sig_for_args(jax.tree_util.tree_leaves(args))
            prog, src = self._store.get_or_compile(
                self._key_fn(sig),
                lambda: self._jit_fn.lower(*args).compile(),
                meta=self._meta,
            )
            with self._lock:
                self._resolved[cache_key] = prog
            if self._on_resolve is not None:
                self._on_resolve(src)
            if src == "disk":
                # A loaded executable must actually accept this call;
                # reject -> permanent jit fallback for the signature.
                try:
                    return prog(*args)
                except Exception as e:  # noqa: BLE001
                    from distributed_sddmm_tpu.obs import log as obs_log

                    obs_log.warn(
                        "programs",
                        "stored program rejected a call; jit fallback",
                        key=self._key_fn(sig),
                        error=f"{type(e).__name__}: {e}",
                    )
                    with self._lock:
                        self._resolved[cache_key] = self._jit_fn
                    self._store._live()
                    return self._jit_fn(*args)
        return prog(*args)

    # jit-API passthroughs some callers poke at.
    def lower(self, *args, **kw):
        return self._jit_fn.lower(*args, **kw)


def stored(jit_fn, key_fn, store: "ProgramStore | None" = None,
           meta: dict | None = None):
    """``StoredProgram`` over the active store (or ``store``); returns
    the jit unchanged when no store is active — zero overhead when the
    layer is disabled."""
    store = store if store is not None else active()
    if store is None:
        return jit_fn
    return StoredProgram(jit_fn, key_fn, store, meta=meta)


# --------------------------------------------------------------------- #
# Strategy binding (autotune Plan.instantiate's hook)
# --------------------------------------------------------------------- #


def strategy_config_tag(alg) -> str:
    """The strategy-configuration half of a program key.

    The problem fingerprint alone does NOT determine the compiled
    program: one fingerprint legitimately runs under several
    (algorithm, c, kernel) configurations — a heatmap sweep benchmarks
    every algorithm at every cell, and a re-measured plan can change its
    algorithm under an unchanged fingerprint — so the key must carry
    the configuration or entries would alias across them. Tile shapes
    are covered by the aval signature; this tag covers what avals cannot
    see: the strategy class, replication factor, the ring-build knobs
    (overlap fusion, rolled loops — same avals, different traced
    program), the kernel knobs that reshape the traced program without
    changing argument shapes (precision, gather chunking, scatter form,
    batch step), and each blocked tile set's block geometry (the density
    rule sizes it per matrix, and two geometries can share a chunk count).
    """
    kern = alg.kernel
    cls = type(kern).__name__
    if getattr(kern, "variant_id", None):
        # BankedPallasKernel traces the SAME program family as the
        # generic PallasKernel (it falls through on generic tiles); the
        # realized variant in the op segment is what distinguishes
        # banked programs. Tagging the subclass name would fork a
        # guard-fallback build away from the generic entry it is
        # byte-identical to — and pre-PR-9 generic keys must not move.
        cls = "PallasKernel"
    bits = [type(alg).__name__, f"c{alg.c}", cls]
    if getattr(alg, "overlap", False):
        bits.append("ov")
    if not getattr(alg, "unroll", True):
        bits.append("rolled")
    # The codegen kernel variant is deliberately ABSENT here: the
    # per-op segment of the strategy's program-cache key carries the
    # REALIZED variant (base._program_cache_key), so a build that
    # guard-fell to the generic encoding shares the generic entry —
    # tagging the kernel's identity would fork a duplicate.
    for attr in ("precision", "gather_budget", "scatter_form",
                 "batch_step"):
        v = getattr(kern, attr, None)
        if v is not None:
            bits.append(f"{attr[:4]}{v}")
    for tiles in (getattr(alg, "S_tiles", None), getattr(alg, "ST_tiles", None)):
        geom = getattr(tiles, "blk_geom", None)
        if geom is not None:
            bits.append(f"b{geom[0]}x{geom[1]}")
    return "-".join(bits)


def bind_strategy(alg, fingerprint_key: str,
                  store: "ProgramStore | None" = None) -> bool:
    """Install a program binder on a strategy: every shard_map program
    the strategy builds from now on resolves through the store under
    ``plan:<fingerprint_key>:<config>-<op>:<sig>`` keys. Returns False
    (no-op) when no store is active. Already-built programs are dropped
    so they rebuild through the binder — cheap: the jit wrappers
    re-trace only on their next call, which is when they would have
    compiled anyway.

    The binding facts land on ``alg._program_store_meta`` so the
    jit-chained app programs built ON TOP of the strategy (``cgStep``,
    ``gatLayer``) can resolve through the same store under the same
    fingerprint."""
    store = store if store is not None else active()
    if store is None or not fingerprint_key:
        return False
    backend = live_backend() or "unknown"
    cfg = strategy_config_tag(alg)
    # Pod identity is resolved ONCE at bind time (the worker's slot in
    # the pod cannot change mid-run): multi-process workers key their
    # per-process executables under a trailing ``dN.pK`` segment so a
    # worker warm-starts from exactly the entries its own slot wrote;
    # single-process binds append nothing and stay byte-identical to
    # the PR 6-13 grammar.
    dist = keys_mod.dist_segment()

    def binder(op_key: str, jit_fn):
        def key_fn(sig: str) -> str:
            return keys_mod.plan_program_key(
                fingerprint_key, f"{cfg}-{op_key}", sig, backend,
                dist=dist,
            )

        return StoredProgram(
            jit_fn, key_fn, store,
            meta={"fingerprint_key": fingerprint_key, "op": op_key,
                  "config": cfg, **({"dist": dist} if dist else {})},
        )

    alg.bind_program_store(binder)
    alg._program_store_meta = {
        "store": store, "fingerprint_key": fingerprint_key,
        "config": cfg, "backend": backend, "dist": dist,
    }
    return True


def chained_program(alg, op: str, jit_fn):
    """Store-back one jit-chained APP program (cgStep, gatLayer) built
    over a bound strategy: resolves under the strategy's binding
    (fingerprint + config tag) PLUS the ``models/`` code generation —
    the chain bakes the app-side math (CG vector algebra, the GAT layer
    body) into the executable, which the plan-scope ``code_hash`` in
    the fingerprint deliberately does not cover. The tile operands are
    the chain's arguments, as they are the strategy programs', so an
    entry serves any matrix of the same shapes. Returns ``jit_fn``
    unchanged when the strategy is unbound — the pre-store behavior,
    byte for byte."""
    meta = getattr(alg, "_program_store_meta", None)
    if not meta:
        return jit_fn
    from distributed_sddmm_tpu.autotune.fingerprint import models_code_hash

    op = f"{op}-m{models_code_hash()}"

    def key_fn(sig: str) -> str:
        return keys_mod.plan_program_key(
            meta["fingerprint_key"], f"{meta['config']}-{op}", sig,
            meta["backend"], dist=meta.get("dist"),
        )

    return StoredProgram(
        jit_fn, key_fn, meta["store"],
        meta={"fingerprint_key": meta["fingerprint_key"], "op": op,
              "config": meta["config"],
              **({"dist": meta["dist"]} if meta.get("dist") else {})},
    )


# --------------------------------------------------------------------- #
# Module-level activation (env grammar shared with the run store)
# --------------------------------------------------------------------- #

_active: ProgramStore | None = None
_env_checked = False
_registry_lock = threading.Lock()


def default_root() -> pathlib.Path:
    from distributed_sddmm_tpu.obs.store import parse_env_spec

    _enabled, root = parse_env_spec(os.environ.get("DSDDMM_PROGRAMS"))
    return pathlib.Path(root) if root else DEFAULT_ROOT


def enable(root: str | os.PathLike | None = None) -> ProgramStore:
    """Activate the process-wide store (idempotent; an active store
    wins — same semantics as the run store and tracer)."""
    global _active, _env_checked
    with _registry_lock:
        _env_checked = True
        if _active is None:
            _active = ProgramStore(root)
        return _active


def disable() -> None:
    global _active, _env_checked
    with _registry_lock:
        _active = None
        _env_checked = True


def active() -> ProgramStore | None:
    """The active store, resolving ``DSDDMM_PROGRAMS`` on first query.
    Unlike the run store (telemetry, off unless asked), the program
    store is a functional cache and defaults ON at the default root;
    ``DSDDMM_PROGRAMS=0`` (the test conftest) vetoes it."""
    global _active, _env_checked
    if _env_checked:
        return _active
    with _registry_lock:
        if not _env_checked:
            _env_checked = True
            from distributed_sddmm_tpu.obs.store import parse_env_spec

            enabled, root = parse_env_spec(os.environ.get("DSDDMM_PROGRAMS"))
            if enabled:
                _active = ProgramStore(root)
    return _active
