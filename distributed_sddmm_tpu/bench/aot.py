"""Ahead-of-time compile/serialize/load helpers for the kernel benchmarks.

The Mosaic/TPU compiler runs on a host without a chip against a
`jax.experimental.topologies` AOT target (`scripts/preflight_kernels.py`),
and `scripts/aot_load_probe.py` tests whether such executables can be
deserialized onto a live chip. When that answer is yes, the sweep
pipeline uses this module to move its compiles off the chip's clock:

* `compile_chain_pair` (offline, CPU-pinned process): AOT-compile the
  chained-trials program — ``fori_loop(0, n, step)`` for n in
  {1, 1+trials}, the exact shape `bench.kernels._chain_time` jits — for
  one topology device, and serialize both executables to a directory.
* `load_chain_pair` + `chain_time_loaded` (on the TPU process): load the
  pair onto the real device and reproduce `_chain_time`'s timing protocol
  (warm both trip counts, time n=1, time n=1+trials, difference /
  trials).

The reference has no analog (its kernels are prebuilt library calls,
`sparse_kernels.cpp:94-121`); this makes the benchmark breadth of
`local_kernel_benchmark.cpp:276-280` cost chip time for running only.
"""

from __future__ import annotations

import pathlib
import pickle
import time

import jax
import jax.numpy as jnp


# (op, use_st) strategy programs each app touches — shared between the
# offline compiler (scripts/aot_compile_apps.py) and the injecting runner
# (scripts/tpu_apps.py) so the two can't drift. GAT is deliberately absent:
# its per-layer feature widths retrace, and the inject_program wrapper's
# jit fallback covers it.
APP_PROGRAM_KEYS = {
    "als": (("sddmm", False), ("sddmm", True), ("spmm", False),
            ("spmm", True), ("fused", False), ("fused", True)),
    "vanilla": (("fused", False),),
}


def _chain(step_fn, n: int):
    """The chained-trials program — must stay in lockstep with
    `bench.kernels._chain_time`'s jitted chain (same fori_loop shape), or
    AOT timings stop being comparable to on-device ones."""

    @jax.jit
    def chain(state):
        return jax.lax.fori_loop(0, n, lambda _, s: step_fn(s), state)

    return chain


def trip_counts(trials: int) -> tuple[int, int]:
    return (1, 1 + trials)


def _store_for(out_dir: str | pathlib.Path):
    """The program store bench AOT entries live in: the process-wide
    active store (``artifacts/programs/``, the PR 6 unification) when
    enabled, else a store rooted AT ``out_dir`` (tests and explicitly
    relocated caches). ``out_dir`` always contributes the key STEM — its
    basename already encodes the config/code-hash the offline compilers
    derive — so entries from different sweep configs cannot collide."""
    from distributed_sddmm_tpu import programs

    store = programs.active()
    return store if store is not None else programs.ProgramStore(out_dir)


def _aot_key(out_dir: str | pathlib.Path, name: str, n: int,
             backend: str) -> str:
    from distributed_sddmm_tpu.programs import bench_aot_key

    return bench_aot_key(pathlib.Path(out_dir).name, name, n, backend)


def save_executable(compiled, out_dir: str | pathlib.Path, name: str,
                    n: int, backend: str | None = None) -> None:
    """Persist one serialized executable into the program store under a
    ``bench:<dir-stem>:<name>:<n>`` key (the historical ``{name}_{n}.pkl``
    per-directory pickles became store entries in PR 6; `load_executable`
    still reads the legacy files as a fallback). ``backend`` is the
    TARGET platform — offline compilers pass their topology device's
    platform; default is the live backend."""
    if backend is None:
        from distributed_sddmm_tpu.programs.store import live_backend

        backend = live_backend()
    pathlib.Path(out_dir).mkdir(parents=True, exist_ok=True)
    store = _store_for(out_dir)
    if not store.save(_aot_key(out_dir, name, n, backend), compiled,
                      meta={"name": name, "n": n}, backend=backend):
        # This jax generation cannot serialize: keep the legacy pickle
        # format working rather than silently storing nothing.
        from jax.experimental import serialize_executable as se

        from distributed_sddmm_tpu.utils.atomic import atomic_write_bytes

        atomic_write_bytes(pathlib.Path(out_dir) / f"{name}_{n}.pkl",
                           pickle.dumps(se.serialize(compiled)))


def compile_chain_pair(step_fn, state, trials: int, device,
                       out_dir: str | pathlib.Path, name: str) -> dict:
    """AOT-compile ``step_fn``'s chain for both trip counts against
    ``device`` (a topology AOT device) and serialize to
    ``out_dir/{name}_{n}.pkl``. Returns {n: compile_seconds}."""
    sharding = jax.sharding.SingleDeviceSharding(device)

    def sds(x):
        x = jnp.asarray(x)
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)

    sds_state = jax.tree_util.tree_map(sds, state)
    times = {}
    for n in trip_counts(trials):
        t0 = time.monotonic()
        compiled = _chain(step_fn, n).lower(sds_state).compile()
        save_executable(compiled, out_dir, name, n,
                        backend=device.platform)
        times[n] = round(time.monotonic() - t0, 2)
    return times


def load_executable(out_dir: str | pathlib.Path, name: str, n: int, device):
    """Deserialize one saved executable onto ``device``: the program
    store first (PR 6 entries), then the legacy per-directory
    ``{name}_{n}.pkl`` pickle (pre-PR 6 caches stay loadable). Raises on
    any failure — callers fall back to the jitted path."""
    from distributed_sddmm_tpu import compat

    store = _store_for(out_dir)
    loaded = store.load(_aot_key(out_dir, name, n, device.platform),
                        device=device)
    if loaded is not None:
        return loaded
    serialized, in_tree, out_tree = pickle.loads(
        (pathlib.Path(out_dir) / f"{name}_{n}.pkl").read_bytes())
    return compat.deserialize_and_load(
        serialized, in_tree, out_tree, execution_devices=[device])


def load_chain_pair(out_dir: str | pathlib.Path, name: str, trials: int,
                    device) -> dict:
    """Deserialize the chain pair onto ``device``. Returns {n: callable}.
    Raises on any load failure — callers fall back to on-device jit."""
    return {n: load_executable(out_dir, name, n, device)
            for n in trip_counts(trials)}


def timed_difference(run, trials: int) -> float:
    """`_chain_time`'s measurement protocol over an arbitrary ``run(n)``
    callable (which must BLOCK until the n-trip chain executed — end in a
    host fetch): warm both trip counts, time each
    once, per-trial difference, clamped positive. The single home for this
    protocol — bench.py's worker keeps its own only because its negative-
    difference policy differs (uniform-cost estimate, documented there)."""
    run(1)
    run(1 + trials)
    t0 = time.perf_counter()
    run(1)
    t_one = time.perf_counter() - t0
    t0 = time.perf_counter()
    run(1 + trials)
    return max((time.perf_counter() - t0 - t_one) / trials, 1e-9)


def chain_time_loaded(loaded: dict, state, trials: int) -> float:
    """`timed_difference` over pre-loaded chain executables."""

    def run(n):
        out = loaded[n](state)
        # Host fetch: returns only once the chain has run.
        float(jnp.asarray(out[0]).sum())

    return timed_difference(run, trials)
