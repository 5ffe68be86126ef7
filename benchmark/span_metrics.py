"""Per-layer metrics from the program's own names in the trace, shared by
the readers under ``metrics/`` (context as in ``layer_metrics.py``).

The program opens a span ``<op>`` around each compiled-program call of
its host loop and, inside it, the leaves ``<op>:dispatch`` around the
call and ``<op>:fetch`` around the host fetch that waits for its outputs
(``parallel/base.py``, ``_timed``). Each span is a
``jax.profiler.TraceAnnotation``, so it is a host event of the trace.
The host's and the device's events agree to about a millisecond on a v5e
(PR 23: each ``cgStep`` program's first operation starts 1.15 ms before
to 0.05 ms after the span that launched it), so a split of idle time by
span is exact for long spans and unreliable for spans shorter than
that. Each Pallas launch is named by its kernel
(``fused_tiles.2``, ``sddmm_tiles.10``, ``spmm_tiles.4``). A program
without these names gives every reader here nothing to read: ``None``.
"""

from __future__ import annotations

import bisect

from benchmark import trace as tr

DISPATCH, FETCH = ":dispatch", ":fetch"
LEAVES = (DISPATCH, FETCH)


def leaf_spans(trace) -> list:
    """``[(start, end, name)]`` of the host's leaf spans, by start."""
    return sorted((s, e, n) for n, s, e in trace.host if n.endswith(LEAVES))


def _leaf_at(leaves, starts, longest, t):
    """The name of the leaf span open at ``t`` (the latest started of
    those open, as ``trace.idle_by_host`` picks), or None."""
    for j in range(bisect.bisect_right(starts, t) - 1, -1, -1):
        s, e, name = leaves[j]
        if s < t - longest:
            break
        if e > t:
            return name
    return None


def idle_by_leaf(ctx) -> dict | None:
    """``{":dispatch": ns, ":fetch": ns, None: ns}``: each device's idle
    time in the window, every idle interval given whole to the leaf span
    open at its middle (None: no leaf), averaged over the devices. None
    without leaf spans or device operations in the window."""
    lo, hi = ctx["lo"], ctx["hi"]
    leaves = [x for x in leaf_spans(ctx["trace"]) if x[1] > lo and x[0] < hi]
    devices = ctx["devices"]
    if not leaves or not any(d.ops for d in devices):
        return None
    starts = [s for s, _, _ in leaves]
    longest = max(e - s for s, e, _ in leaves)
    tot = {DISPATCH: 0, FETCH: 0, None: 0}
    for dev in devices:
        for s, e in tr.gaps([(o.start, o.end) for o in dev.ops], lo, hi):
            name = _leaf_at(leaves, starts, longest, (s + e) // 2)
            tot[None if name is None else
                (DISPATCH if name.endswith(DISPATCH) else FETCH)] += e - s
    return {k: v / len(devices) for k, v in tot.items()}


def idle_under(ctx, suffix: str):
    """% of the window in which a device is idle under a ``suffix`` leaf
    span (``:dispatch`` or ``:fetch``), mean over the devices."""
    idle = idle_by_leaf(ctx)
    if idle is None:
        return None
    return 100.0 * idle[suffix] / (ctx["hi"] - ctx["lo"])


def fetches_per_step(ctx):
    """``:fetch`` spans that start in the window, per unit of work."""
    lo, hi = ctx["lo"], ctx["hi"]
    n = sum(1 for name, s, _ in ctx["trace"].host
            if name.endswith(FETCH) and lo <= s < hi)
    if not ctx["units"] or not n:
        return None
    return n / ctx["units"]


def kernel_s(ctx, kind: str):
    """Device seconds a unit of the operations whose name starts with
    ``kind`` (``sddmm``, ``spmm``, ``fused``): their union in the window,
    mean over the devices, over the units."""
    lo, hi = ctx["lo"], ctx["hi"]
    per = [tr.length(tr.clip([(o.start, o.end) for o in d.ops
                              if o.name.startswith(kind)], lo, hi))
           for d in ctx["devices"]]
    if not ctx["units"] or not any(per):
        return None
    return sum(per) / len(per) / 1e9 / ctx["units"]


#: How far before the host's span a device event may appear to start
#: (the two clocks differ by up to about a millisecond, see above).
SKEW_NS = 5 * 10**6


def launch_offsets(trace, lo: int, hi: int, span: str, module: str,
                   dev) -> list:
    """Clock agreement, in ns: for each ``span`` (e.g. ``cgStep:dispatch``)
    starting in the window, its start to the start of the first device
    operation of the program execution it launched. The ``k``-th such
    span launched the ``k``-th execution, from ``SKEW_NS`` before the
    first span on, of a module whose name holds ``module``; an empty
    list where the two counts differ."""
    spans = sorted(s for n, s, _ in trace.host if n == span and lo <= s < hi)
    if not spans:
        return []
    runs = sorted((s, e) for n, s, e in dev.modules
                  if module in n and s >= spans[0] - SKEW_NS)
    runs = runs[:len(spans)]
    if len(runs) != len(spans):
        return []
    ops = sorted(o.start for o in dev.ops)
    out = []
    for t, (s, e) in zip(spans, runs):
        i = bisect.bisect_left(ops, s)
        first = ops[i] if i < len(ops) and ops[i] < e else s
        out.append(first - t)
    return out

