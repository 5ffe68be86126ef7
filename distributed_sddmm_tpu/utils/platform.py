"""Process-level JAX setup shared by the entry points and the tests.

* :func:`force_cpu_platform` pins a process to the host CPU platform
  (optionally with N virtual devices) before any backend starts: the
  test suite and the multi-chip dry run use it.
* :func:`enable_compile_cache` places JAX's persistent compilation
  cache; the program's entry points call it, the tests never do.
* :func:`tpu_chips_on_host` counts the TPU chips attached to this host
  without starting a JAX backend (which would claim a chip).
* :func:`force_fetch` is the execution barrier timed regions end with.
"""

from __future__ import annotations

import os


def force_cpu_platform(n_devices: int | None = None, replace: bool = False) -> None:
    """Pin this process's JAX to CPU; optionally force a virtual device count.

    Must run before any JAX backend touch (``jax.devices()``, jit execution,
    ``jax.default_backend()``...). Raises if a non-CPU backend already got
    initialized, because then the pin silently cannot take effect.

    ``n_devices``: if given, ensure ``--xla_force_host_platform_device_count``
    is set (kept as-is when already present unless ``replace=True``).
    """
    flags = os.environ.get("XLA_FLAGS", "").split()
    have = any(
        f.startswith("--xla_force_host_platform_device_count") for f in flags
    )
    if n_devices is not None and (replace or not have):
        flags = [
            f
            for f in flags
            if not f.startswith("--xla_force_host_platform_device_count")
        ]
        flags.append(f"--xla_force_host_platform_device_count={n_devices}")
    os.environ["XLA_FLAGS"] = " ".join(flags)
    os.environ["JAX_PLATFORMS"] = "cpu"

    import jax

    jax.config.update("jax_platforms", "cpu")
    backend = jax.default_backend()
    if backend != "cpu":
        raise RuntimeError(
            f"force_cpu_platform ran too late: a {backend!r} backend is "
            "already initialized in this process; call it before any JAX "
            "backend touch (or use a fresh process)"
        )
    if n_devices is not None and jax.device_count() < n_devices:
        raise RuntimeError(
            f"force_cpu_platform ran too late: the CPU backend initialized "
            f"with {jax.device_count()} device(s) before the "
            f"device-count flag could take effect (wanted {n_devices}); "
            "use a fresh process"
        )


def force_fetch(tree) -> float:
    """Execution barrier over a pytree: copies one scalar of every numeric
    leaf to the host and returns their sum.

    A host copy cannot finish before the computation that produced its
    leaf has, so a timed region that ends with ``force_fetch(out)`` times
    the device work for every output, however many leaves ``out`` has.
    Under a trace there is nothing to fetch and tracers are skipped.
    """
    import jax
    import jax.numpy as jnp

    total = 0.0
    for leaf in jax.tree.leaves(tree):
        if (
            hasattr(leaf, "dtype")
            and jnp.issubdtype(leaf.dtype, jnp.number)
            and getattr(leaf, "size", 0)
            # Under a trace (e.g. differentiating through a public op)
            # there is nothing to fetch.
            and not isinstance(leaf, jax.core.Tracer)
        ):
            total += float(jnp.asarray(leaf).reshape(-1)[0])
    return total


def _checkout_root() -> str:
    return os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))


def enable_compile_cache() -> str | None:
    """Turn on JAX's persistent compilation cache on the TPU; returns its
    directory, or None on any other backend (this starts the backend).

    ``JAX_COMPILATION_CACHE_DIR``, when set, is the directory and no other
    is set. Otherwise the cache lives at ``<checkout>/artifacts/xla_cache``
    (git-ignored): a fixed path, because the path is part of the cache's
    key and a moving directory never hits. Call from an entry point before
    the first compile; every program is cached, however short its compile.
    Not on the CPU: XLA:CPU executables read back from the cache can fail
    to run in this installation ("Function convert_convert_fusion not
    found", PR 21), and CPU compiles are cheap.
    """
    import jax

    if jax.default_backend() != "tpu":
        return None
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        _checkout_root(), "artifacts", "xla_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def tpu_chips_on_host(env: dict | None = None) -> int:
    """TPU chips a JAX process started with ``env`` (default: this
    process's) may use, counted without starting a JAX backend — a
    process that started one would hold a chip itself. The chips on the
    PCI bus, or as many as ``TPU_VISIBLE_CHIPS`` lists when it is set."""
    env = os.environ if env is None else env
    visible = env.get("TPU_VISIBLE_CHIPS", "")
    if visible:
        return len([c for c in visible.split(",") if c.strip()])
    from jax._src import hardware_utils

    return hardware_utils.num_available_tpu_chips_and_device_id()[0]


def tpu_chips_held() -> int:
    """TPU chips this process holds: its local devices once a TPU backend
    is up, else 0. Never starts a backend itself."""
    from jax._src import xla_bridge

    if not xla_bridge.backends_are_initialized():
        return 0
    import jax

    return jax.local_device_count() if jax.default_backend() == "tpu" else 0


def uses_tpu(env: dict) -> bool:
    """Whether a JAX process started with ``env`` would take a TPU chip:
    one is attached and ``JAX_PLATFORMS`` does not leave the TPU out."""
    platforms = env.get("JAX_PLATFORMS", "")
    if platforms and "tpu" not in platforms.split(","):
        return False
    return tpu_chips_on_host(env) > 0
