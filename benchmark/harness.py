"""One run of one cell: set-up, a measured window, the check, one line.

Everything a cell is made of is found by name under the checkout's
``benchmark/`` directory, so a later change adds a cell, a configuration,
a traffic mix or a per-layer metric as new files:

- ``BENCHMARK.json`` (checkout root): the cells and the metrics;
- ``configs/<config>.json``: the sizes; its ``app`` names
  ``apps/<app>.py``, which builds the program, drives one unit of work,
  counts the work and holds the plain reference (``apps/<app>_ref.py``),
  and its ``matrix.generator`` names ``matrices/<generator>.py``;
- ``matrices/<generator>.py``: ``make(spec, rng)`` draws the matrix a
  configuration's ``matrix`` describes, as ``{"rows", "cols", "vals",
  "M", "N"}``, from the seed's matrix stream;
- ``traffic/<traffic>.json``: the parameters of a mix, which the one
  generator (``generator.py``) reads to drive the window;
- ``metrics/<metric>.py``: a reader ``read(ctx)`` of one metric: of the
  window for an end-to-end metric (see ``window_metrics.py``), of the
  trace for a per-layer one (see ``layer_metrics.py``). ``setup_s`` is
  the harness's own;
- ``peaks.json``: the chip's peaks, keyed by JAX's ``device_kind``.

An app module defines:

- ``work(cfg, nnz, M, N)``: one unit's ``model_flops``, its
  ``sparse_ops`` as ``(FLOPs, least bytes)`` pairs, and beside them
  ``sparse_kernels``, the name prefix of the Pallas kernel that runs each
  (``fused``, ``sddmm``, ``spmm``: ``ops/pallas_kernels.py`` names a
  launch ``<prefix>_tiles.<n>``);
- ``Run(cfg, mat, key, devices, check)``, whose ``unit()`` runs one unit
  of work and returns when the device has finished it, ``keep()`` keeps
  the last unit's answer for the check, ``release()`` drops the program,
  ``outputs()`` gives the kept answers and ``reference(quant, keep=None)``
  the plain reference's, as ``{name: (array, ...)}``; ``keep`` (0/1 a
  nonzero) leaves nonzeros out;
- ``FAULTS``: the faults of ``faults.py`` that the app can have, each
  with the app's own patch or ``None`` for the generic one;
- optionally ``REFERENCE_FAULTS``: faults of the app's own mathematics
  (a term left out, an operand swapped) that ``reference(quant,
  fault=<name>)`` plants in the plain reference, for ``calibrate.py
  --faults``.

Both an app module and a generator module also define ``tiny``: the
configuration (``tiny(cfg)``) or its ``matrix`` (``tiny(spec)``) with the
keys that module reads cut to a size the CPU tests run. The benchmark's
runs never call it.
"""

from __future__ import annotations

import argparse
import contextlib
import gc
import importlib
import importlib.util
import json
import math
import os
import shutil
import sys
import time
import traceback

import numpy as np

from benchmark import generator

PKG = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(PKG)


def process_start_s() -> float:
    """This process's start on the ``time.time()`` clock (10 ms ticks)."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/stat") as f:
            btime = next(int(l.split()[1]) for l in f if l.startswith("btime"))
        return btime + start_ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, StopIteration, IndexError):
        return time.time()


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """A cell as the registry describes it, with its parts loaded."""

    def __init__(self, name: str, root: str = ROOT):
        self.root = root
        with open(os.path.join(root, "BENCHMARK.json")) as f:
            self.bench = json.load(f)
        cells = {w["name"]: w for w in self.bench["workloads"]}
        if name not in cells:
            raise SystemExit(f"unknown workload {name!r}; known: "
                             f"{sorted(cells)}")
        self.name, self.cell = name, cells[name]
        configs = {c["name"]: c for c in self.bench["configs"]}
        with open(os.path.join(root, configs[self.cell["config"]]["file"])) as f:
            self.cfg = json.load(f)
        bdir = os.path.join(root, "benchmark")
        with open(os.path.join(bdir, "traffic",
                               self.cell["traffic"] + ".json")) as f:
            self.traffic = json.load(f)
        self.app = app_module(self.cfg["app"], root)
        self.chips = int(self.cell["chips"])
        self.end_to_end = [m for m in self.bench["end_to_end"]
                           if name in m.get("workloads", [name])]
        e2e = {m["name"] for m in self.end_to_end}
        self.per_layer = [
            m for m in self.bench["per_layer"]
            if (name in m["workloads"] if "workloads" in m
                else m["moves"] in e2e)]
        self.readers = {
            m["name"]: load_module(os.path.join(bdir, "metrics",
                                                m["name"] + ".py"),
                                   "benchmark.metrics." + m["name"])
            for m in self.end_to_end + self.per_layer
            if m["name"] != "setup_s"}
        with open(os.path.join(bdir, "peaks.json")) as f:
            self.peaks = json.load(f)


class CompileEvents:
    """Compiles as JAX's monitoring events count them: seconds tracing,
    lowering and in the backend (a persistent-cache hit included), and
    the persistent cache's hits and misses. Copied from the program's
    ``chip_smoke.py``."""

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
            if event.endswith("backend_compile_duration"):
                self._add("backend_compiles", 1)

    def take(self) -> dict:
        totals, self._totals = self._totals, {}
        return totals


def prepare_env(work: str) -> None:
    """Clear every inherited program knob; give the program store and the
    plan cache fresh directories under ``work``, and JAX's persistent
    cache its fixed one there, whatever the environment said, before JAX
    reads it."""
    for k in [k for k in os.environ if k.startswith("DSDDMM_")]:
        del os.environ[k]
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(work, "xla_cache")
    for sub in ("programs", "plan_cache"):
        shutil.rmtree(os.path.join(work, sub), ignore_errors=True)
    os.environ["DSDDMM_PROGRAMS"] = os.path.join(work, "programs")
    os.environ["DSDDMM_PLAN_CACHE"] = os.path.join(work, "plan_cache")
    os.environ["DSDDMM_RUNSTORE"] = "0"


def enable_compile_cache(work: str) -> str:
    """JAX's persistent compilation cache at a fixed path in the checkout,
    never an inherited one that another checkout would share."""
    import jax

    path = os.path.join(work, "xla_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def app_module(name: str, root: str = ROOT):
    """``benchmark/apps/<name>.py`` under ``root``."""
    return load_module(os.path.join(root, "benchmark", "apps", name + ".py"),
                       "benchmark.apps." + name)


def generator_module(name: str, root: str = ROOT):
    """``benchmark/matrices/<name>.py`` under ``root``; an unknown name
    exits with the list of the generators there."""
    mdir = os.path.join(root, "benchmark", "matrices")
    path = os.path.join(mdir, name + ".py")
    if not os.path.isfile(path):
        known = sorted(f[:-3] for f in os.listdir(mdir)
                       if f.endswith(".py") and f != "__init__.py")
        raise SystemExit(f"unknown matrix generator {name!r}; known: {known}")
    return load_module(path, "benchmark.matrices." + name)


def make_matrix(spec: dict, rng, root: str = ROOT) -> dict:
    """The configuration's matrix as COO arrays and its shape, by the
    generator that ``spec["generator"]`` names."""
    return generator_module(spec["generator"], root).make(spec, rng)


def compare(got: dict, want: dict) -> dict:
    """Per name, over its arrays, the worst relative Frobenius gap
    (``<name>``) and the worst widest gap of one answer (``<name>.max``),
    see ``sparse_ref.gaps``."""
    from benchmark.sparse_ref import gaps

    out = {}
    for k in want:
        g = [gaps(a, b) for a, b in zip(got[k], want[k])]
        out[k] = max(x for x, _ in g)
        out[k + ".max"] = max(y for _, y in g)
    return out


def seeds(seed: int):
    """Independent streams for the matrix, the device key and the sample."""
    import jax

    ss = np.random.SeedSequence(seed).spawn(3)
    key = jax.random.key(int(np.random.default_rng(ss[1]).integers(2**31)))
    return np.random.default_rng(ss[0]), key, np.random.default_rng(ss[2])


def device_info(devices, count: int) -> dict:
    d = devices[0]
    peaks = [(x.memory_stats() or {}).get("peak_bytes_in_use")
             for x in devices]
    return {"platform": d.platform, "kind": d.device_kind, "count": count,
            "memory_peak_bytes": max((p for p in peaks if p is not None),
                                     default=None)}


def _metric(value, unit):
    return {"value": value, "unit": unit}


def run(args, root: str = ROOT, require_tpu: bool = True) -> int:
    cell = Cell(args.workload, root)
    work = os.path.join(root, "benchmark", "_work")
    prepare_env(work)
    import jax

    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        print(f"benchmark: JAX found no TPU (platform "
              f"{devices[0].platform!r}); nothing is measured off the chip",
              file=sys.stderr)
        return 2
    if len(devices) < cell.chips:
        print(f"benchmark: {args.workload} needs {cell.chips} chips, JAX "
              f"sees {len(devices)}", file=sys.stderr)
        return 2
    kind = devices[0].device_kind
    if require_tpu and kind not in cell.peaks:
        print(f"benchmark: device kind {kind!r} is not in peaks.json",
              file=sys.stderr)
        return 2
    peak = cell.peaks.get(kind)
    used = devices[: cell.chips]
    cache = enable_compile_cache(work) if require_tpu else None
    events = CompileEvents()

    rng, key, sample = seeds(args.seed)
    t0 = time.perf_counter()
    mat = make_matrix(cell.cfg["matrix"], rng, root)
    nnz, M, N = mat["rows"].size, mat["M"], mat["N"]
    t_matrix = time.perf_counter() - t0
    check = cell.cfg["check"]
    r = cell.app.Run(cell.cfg, mat, key, used, check)
    print(json.dumps({"workload": args.workload, "seed": args.seed,
                      **r.kernel, "compile_cache": cache,
                      "matrix_s": t_matrix, "nnz": nnz,
                      "setup_compiles": events.take()}), flush=True)

    span = f"bench:{cell.cfg['app']}:unit"
    tracer = None
    if args.trace:
        tracer = os.path.join(work, "trace", args.workload)
        shutil.rmtree(tracer, ignore_errors=True)
        jax.profiler.start_trace(tracer)
    annotate = ((lambda n: jax.profiler.TraceAnnotation(n)) if args.trace
                else (lambda n: contextlib.nullcontext()))
    host0 = host_load()
    setup_s = time.time() - process_start_s()
    with annotate("bench:window"):
        win = generator.drive(cell.traffic, args.seconds, r.unit, r.keep,
                              sample, annotate, span)
    if tracer:
        jax.profiler.stop_trace()
    host = host_load(host0)
    window_compiles = events.take()
    failed, units = win["failed"], win["units"]
    if not win["kept"] and not failed:
        r.keep()
    device = device_info(used, len(devices))

    limits = check["limits"]
    readings, error = {}, None
    t_check = time.perf_counter()
    if not failed:
        try:
            r.release()
            gc.collect()
            readings = compare(r.outputs(), r.reference(quant("reference")))
        except Exception as e:  # noqa: BLE001 — a check that fails is false
            traceback.print_exc()
            error = f"{type(e).__name__}: {e}"
    check_s = time.perf_counter() - t_check
    checks = {k: {"value": readings.get(k), "limit": v}
              for k, v in limits.items()}
    correct = (not failed and error is None and all(
        c["value"] is not None and math.isfinite(c["value"])
        and c["value"] <= c["limit"] for c in checks.values()))

    result = {"correct": correct, "attempted": units, "failed": failed}
    base = {"window": win, "chips": cell.chips,
            "work": cell.app.work(cell.cfg, nnz, M, N)}
    if args.trace:
        metrics, extra = _layer_metrics(cell, tracer, used, base, peak, span)
        result["metrics"] = metrics
        device.update(extra.pop("device"))
        result["device"] = device
        result["breakdown"] = extra["breakdown"]
    else:
        metrics = {}
        for m in cell.end_to_end:
            v = (setup_s if m["name"] == "setup_s"
                 else cell.readers[m["name"]].read(base))
            if v is not None:
                metrics[m["name"]] = _metric(v, m["unit"])
        result["metrics"] = metrics
        result["device"] = device
    result["checks"] = checks
    ends, lat = win["ends_s"], sorted(win["latency_s"])
    print(json.dumps({"setup_s": setup_s, "window_s": win["window_s"],
                      "units": units,
                      "unit_s": [b - a for a, b in zip([0.0] + ends, ends)],
                      "latency_s_p50_p95_max": (
                          [lat[len(lat) // 2], lat[int(0.95 * (len(lat) - 1))],
                           lat[-1]] if lat else None),
                      "queued": win["queued"], "keep_at": win["keep_at"],
                      "host": host, "window_compiles": window_compiles,
                      "check_s": check_s, "check_error": error}), flush=True)
    if window_compiles.get("backend_compiles"):
        print(f"benchmark: {window_compiles} compiles inside the window",
              file=sys.stderr)
    for k, c in checks.items():
        print(f"check {k}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


def quant(name):
    from benchmark import sparse_ref

    return sparse_ref.QUANT[name]


def _layer_metrics(cell, tracer, used, base, peak, span):
    from benchmark import layer_metrics, trace as tr

    path = tr.find_xplane(tracer)
    t = tr.load(path)
    lo, hi = t.window("bench:window")
    devs = t.devices[: len(used)]
    ctx = {**base, "trace": t, "lo": lo, "hi": hi, "devices": devs,
           "units": len(base["window"]["ends_s"]), "peak": peak}
    metrics = {}
    for m in cell.per_layer:
        v = cell.readers[m["name"]].read(ctx)
        if v is not None:
            metrics[m["name"]] = _metric(v, m["unit"])
    extra = {
        "device": {"busy_s": layer_metrics.busy_s(ctx),
                   "window_s": (hi - lo) / 1e9},
        "breakdown": {
            "device_ops": tr.top_ops(devs, lo, hi),
            "idle_gaps": tr.idle_by_host(t, lo, hi, {"bench:window", span}),
        },
    }
    return metrics, extra


def host_load(before: dict | None = None) -> dict:
    """What the host did to this process over the window: its CPU seconds
    and context switches (``resource.getrusage``), and the machine's load
    average at the window's close. Evidence for a slow run, not a metric."""
    import resource

    ru = resource.getrusage(resource.RUSAGE_SELF)
    now = {"cpu_s": ru.ru_utime + ru.ru_stime, "ctx_vol": ru.ru_nvcsw,
           "ctx_invol": ru.ru_nivcsw}
    if before is None:
        return now
    out = {k: now[k] - before[k] for k in now}
    try:
        with open("/proc/loadavg") as f:
            out["loadavg_1m"] = float(f.read().split()[0])
    except (OSError, ValueError, IndexError):
        pass
    out["cpus"] = len(os.sched_getaffinity(0))
    return out


def parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap


def main(argv=None) -> int:
    return run(parser().parse_args(argv))
