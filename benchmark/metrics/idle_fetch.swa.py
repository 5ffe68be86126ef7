"""idle_fetch.swa: % of the traced window in which a device is idle under a program `<op>:fetch` span (the host waiting for the `swaLayer` program's outputs), mean over devices."""

from benchmark.span_metrics import FETCH, idle_under


def read(ctx):
    return idle_under(ctx, FETCH)
