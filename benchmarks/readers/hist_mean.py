"""Mean of a ``/metrics`` histogram over the window, in milliseconds:
(sum after - sum before) / (count after - count before), over every label
set or over those that carry ``labels``.  A histogram that saw nothing in
the window gives nothing."""

import prom


def read(ctx, histogram, labels=None, **_args):
    before, after = ctx["before"], ctx["after"]
    n = prom.total(after, histogram + "_count", labels) - prom.total(before, histogram + "_count", labels)
    if n <= 0:
        return None
    s = prom.total(after, histogram + "_sum", labels) - prom.total(before, histogram + "_sum", labels)
    return 1e3 * s / n
