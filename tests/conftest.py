"""Test configuration: run everything on a virtual 8-device CPU mesh.

The reference tested "distributed" behavior by oversubscribing MPI ranks on
one host (SURVEY.md section 4); our equivalent is XLA's forced host platform
device count. Env vars must be set before jax is first imported.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# Tests drive the bench CLI in-process; the run store's CLI default
# (persist every record under artifacts/runstore) must not silt the
# checkout — or a developer's DSDDMM_RUNSTORE-exported real store —
# during CI, so the veto is unconditional. Tests that exercise the
# store pass an explicit --store/root (or monkeypatch the env), which
# bypasses it.
os.environ["DSDDMM_RUNSTORE"] = "0"

# Same veto for the persistent AOT program store (artifacts/programs):
# unlike the run store it defaults ON (it is a functional cache, not
# telemetry), so CI must explicitly opt out or every test run would
# write serialized executables into the checkout. Tests that exercise
# the store construct ProgramStore(tmp_path) or re-enable explicitly.
os.environ["DSDDMM_PROGRAMS"] = "0"

# And for JAX's persistent compilation cache, should the environment name
# one: XLA:CPU executables read back from it can fail to run (see
# utils.platform.enable_compile_cache). The env form reaches the bench
# subprocesses tests start, too.
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

from distributed_sddmm_tpu.utils.platform import force_cpu_platform  # noqa: E402

force_cpu_platform(n_devices=8, replace=True)

import jax  # noqa: E402

assert jax.device_count() == 8, jax.devices()
