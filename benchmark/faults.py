"""Faults planted under the timed path, to show that the check catches
them: the tests plant them at a tiny size on the CPU, ``calibrate.py
--faults`` in the reference at a cell's own size on the chip. Each is a
context manager that patches the program and undoes the patch on exit.

- ``unchanged``: the unit returns its state or its inputs unchanged;
- ``half``: half of the matrix's nonzeros are left out of every sparse
  op (their values read as zero);
- ``no_exchange``: every collective between chips is left out (the
  program's own ``"local"`` ablation of ``parallel/loops.py``);
- ``altered``: one answer is altered where it is produced.

Which of them an app can have is the app's to say: its module's
``FAULTS`` maps each that applies to the app's own patch, a callable
with no arguments that returns the context manager, or to ``None`` where
the generic patch here (``half``, ``no_exchange``) serves. Only the app
knows where its state or its answers are made, so ``unchanged`` and
``altered`` always come with the app's patch.
"""

from __future__ import annotations

import contextlib

import jax.numpy as jnp


@contextlib.contextmanager
def patched(obj, name, value):
    """``obj.name`` is ``value`` inside the context, as before outside."""
    old = getattr(obj, name)
    setattr(obj, name, value)
    try:
        yield
    finally:
        setattr(obj, name, old)


def alter(x):
    """``x`` with its first element moved by ten times its largest."""
    flat = x.reshape(-1)
    return flat.at[0].add(10 * jnp.max(jnp.abs(flat)) + 1).reshape(x.shape)


def _half(fn):
    def wrapped(self, *a, **k):
        v = fn(self, *a, **k)
        flat = v.reshape(-1)
        keep = jnp.arange(flat.size) < flat.size // 2
        return jnp.where(keep, flat, 0).reshape(v.shape).astype(v.dtype)
    return wrapped


@contextlib.contextmanager
def half():
    """Every tile set's values with the second half read as zero."""
    from distributed_sddmm_tpu.parallel.sharding import TileSet

    with patched(TileSet, "like_values", _half(TileSet.like_values)), \
            patched(TileSet, "scatter_values", _half(TileSet.scatter_values)):
        yield


def no_exchange():
    """The program's loops with every collective left out."""
    from distributed_sddmm_tpu.parallel import loops

    return patched(loops, "_ABLATION", "local")


#: The faults whose patch is the same for every app.
GENERIC = {"half": half, "no_exchange": no_exchange}


def plant(name: str, app):
    """The context in which fault ``name`` is planted for the app module
    ``app``, by the patch its ``FAULTS`` gives or the generic one."""
    if name not in app.FAULTS:
        raise ValueError(f"fault {name!r} does not apply to {app.__name__!r}")
    patch = app.FAULTS[name] or GENERIC.get(name)
    if patch is None:
        raise ValueError(f"fault {name!r} has no generic patch: "
                         f"{app.__name__!r} has to give its own")
    return patch()
