"""idle_dispatch.pair_1chip (the one-chip pair, whose end-to-end rate has a bound of its own): % of the traced window in which a device is idle under a program `<op>:dispatch` span (the host launching a program), mean over devices."""

from benchmark.span_metrics import DISPATCH, idle_under


def read(ctx):
    return idle_under(ctx, DISPATCH)
