"""idle_fetch.als: % of the traced window in which the device is idle under a program `<op>:fetch` span (the host waiting for outputs), mean over devices."""

from benchmark.span_metrics import FETCH, idle_under


def read(ctx):
    return idle_under(ctx, FETCH)
