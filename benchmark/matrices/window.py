"""A causal sliding-window attention mask: ``tokens`` queries, each
attending to itself and the ``window - 1`` tokens before it (fewer at
the start of the sequence), values ones.

A configuration's ``matrix`` with ``"generator": "window"`` gives
``log_m`` (``2**log_m`` tokens, the matrix's rows and columns) and
``window``. The benchmark's own copy of the program's
``masks.causal_window``, kept here so that the matrix a cell runs on
cannot change when the program changes. The mask has no randomness:
every seed gives the same one.
"""

from __future__ import annotations

import numpy as np


def causal_window(n: int, window: int) -> dict:
    """Row ``i`` attends to columns ``[max(i - window + 1, 0), i]``, rows
    in order, columns ascending within a row."""
    offs = np.arange(-(window - 1), 1, dtype=np.int64)
    rows = np.repeat(np.arange(n, dtype=np.int64), window)
    cols = rows + np.tile(offs, n)
    keep = cols >= 0
    rows, cols = rows[keep], cols[keep]
    return {"rows": rows, "cols": cols, "vals": np.ones(rows.size),
            "M": n, "N": n}


def make(spec: dict, rng) -> dict:
    """The mask ``spec`` describes (``rng`` is not drawn from)."""
    return causal_window(1 << spec["log_m"], spec["window"])


def tiny(spec: dict) -> dict:
    """``spec`` cut to a size the CPU tests run: 256 tokens, window 8."""
    return dict(spec, log_m=8, window=8)
