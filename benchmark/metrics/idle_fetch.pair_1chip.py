"""idle_fetch.pair_1chip (the one-chip pair, whose end-to-end rate has a bound of its own): % of the traced window in which a device is idle under a program `<op>:fetch` span (the host waiting for outputs), mean over devices."""

from benchmark.span_metrics import FETCH, idle_under


def read(ctx):
    return idle_under(ctx, FETCH)
