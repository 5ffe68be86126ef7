"""The per-layer metrics' arithmetic, shared by the readers under
``metrics/``. A reader gets the context of one traced run:

- ``trace``: ``trace.Trace``; ``lo``, ``hi``: the traced window (ns);
- ``devices``: the traced devices the cell uses;
- ``units``: units of work completed in the window; ``chips``;
- ``work``: the app's work function for one unit (``model_flops``,
  ``sparse_ops``, a list of ``(flops, least bytes)``, and
  ``sparse_kernels``, the kernel that runs each);
- ``peak``: the device's row of ``peaks.json``.

A reader that finds nothing to read returns ``None``.
"""

from __future__ import annotations

from benchmark import span_metrics
from benchmark import trace as tr


def _mean(xs):
    xs = list(xs)
    return sum(xs) / len(xs) if xs else None


def window_ns(ctx) -> int:
    return ctx["hi"] - ctx["lo"]


def busy_s(ctx):
    """Seconds in which an operation ran, averaged over the devices."""
    ns = _mean(tr.busy_ns(d, ctx["lo"], ctx["hi"]) for d in ctx["devices"])
    return None if ns is None else ns / 1e9


def idle_share(ctx):
    """% of the window in which no operation runs on a device (mean over
    the devices)."""
    if not any(d.ops for d in ctx["devices"]):
        return None
    return 100.0 * (1.0 - busy_s(ctx) * 1e9 / window_ns(ctx))


def roofline_s(ctx, kind: str | None = None) -> float:
    """Least time of the window's sparse work on ``chips`` chips: per op,
    the larger of FLOPs over peak and least bytes over bandwidth. With
    ``kind``, only the ops that kernel runs (``sparse_kernels``)."""
    peak, chips = ctx["peak"], ctx["chips"]
    ops = ctx["work"]["sparse_ops"]
    if kind is not None:
        ops = [op for op, k in zip(ops, ctx["work"].get("sparse_kernels", ()))
               if k == kind]
    one = sum(max(f / (peak["flops_bf16"] * chips),
                  b / (peak["hbm_bytes_per_s"] * chips))
              for f, b in ops)
    return one * ctx["units"]


def sparse_roofline(ctx):
    """% of its roofline: the sparse work's least time over the device
    time of the Mosaic custom calls (the Pallas kernels), mean over the
    devices."""
    kernel = _mean(tr.kind_ns(d, "custom_call", ctx["lo"], ctx["hi"])
                   for d in ctx["devices"])
    if not kernel:
        return None
    return 100.0 * roofline_s(ctx) / (kernel / 1e9)


def kernel_roofline(ctx, kind: str):
    """% of its roofline of the kernel ``kind`` (``fused``, ``sddmm``,
    ``spmm``): the least time of the window's ops that it runs over the
    device time of the operations named ``kind*`` (their union in the
    window, mean over the devices, as ``span_metrics.kernel_s``)."""
    least = roofline_s(ctx, kind)
    kernel = span_metrics.kernel_s(ctx, kind)
    if not least or not kernel:
        return None
    return 100.0 * least / (kernel * ctx["units"])


def mfu(ctx):
    """% of the chips' bf16 peak: the window's model FLOPs over the
    window, the chips and the peak."""
    if not ctx["units"]:
        return None
    flops = ctx["work"]["model_flops"] * ctx["units"]
    return 100.0 * flops / (window_ns(ctx) / 1e9 * ctx["chips"]
                            * ctx["peak"]["flops_bf16"])


def dispatches_per_step(ctx):
    """Program executions per unit of work, from the trace's module
    events, mean over the devices."""
    if not ctx["units"] or not any(d.modules for d in ctx["devices"]):
        return None
    return _mean(tr.modules_in(d, ctx["lo"], ctx["hi"])
                 for d in ctx["devices"]) / ctx["units"]


def collective_exposed(ctx):
    """% of the window in which a collective runs on a device and no
    other operation does, mean over the devices."""
    if not any(o.kind == "collective" for d in ctx["devices"] for o in d.ops):
        return None
    return 100.0 * _mean(tr.exposed_collective_ns(d, ctx["lo"], ctx["hi"])
                         for d in ctx["devices"]) / window_ns(ctx)
