"""The few jax entry points the package reaches through one name.

Everything in the package imports these from here, so the keyword
surface the package relies on lives in one place.

* :func:`shard_map` — ``jax.shard_map`` with the varying-mesh-axes
  checker (``check_vma``).
* :func:`pvary` — marks an array device-varying over mesh axes
  (``lax.pcast(..., to="varying")``), for loop-carried inits of rolled
  ring loops.
* :func:`pallas_tpu_compiler_params` — the Mosaic compiler-params struct.
* :func:`deserialize_and_load` — loads a serialized executable onto the
  devices it was compiled for.
"""

from __future__ import annotations

import jax
from jax import lax


def shard_map(f, *, mesh, in_specs, out_specs, check_vma: bool = True):
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=check_vma,
    )


def pvary(x, axes):
    return lax.pcast(x, axes, to="varying")


def pallas_tpu_compiler_params(**kwargs):
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(**kwargs)


def deserialize_and_load(serialized, in_tree, out_tree, *, execution_devices):
    """``serialize_executable.deserialize_and_load`` onto
    ``execution_devices`` — the devices the executable was compiled for,
    in its device-assignment order. jax's own default (every device of
    the client) is wrong for any program compiled for fewer devices: its
    launch then expects one shard per client device."""
    from jax.experimental import serialize_executable as se

    execution_devices = list(execution_devices)
    return se.deserialize_and_load(
        serialized, in_tree, out_tree,
        backend=execution_devices[0].client,
        execution_devices=execution_devices,
    )


def executable_device_ids(compiled) -> list[int]:
    """Ids of the devices ``compiled`` runs on, in assignment order — what
    :func:`deserialize_and_load` needs back at load time."""
    try:
        devices = compiled.runtime_executable().local_devices()
    except AttributeError:  # compiled for a described, unattached topology
        devices = jax.tree.leaves(compiled.input_shardings)[0]._device_assignment
    return [d.id for d in devices]
