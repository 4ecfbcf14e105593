"""One ``/metrics`` counter's increase over the window for each increase of
another: node rows re-encoded a wave, say.  A counter the program has not
registered yet (it registers one at its first increment) counts 0; a
window in which ``per`` did not move gives nothing."""

import prom


def read(ctx, counter, per, **_args):
    before, after = ctx["before"], ctx["after"]
    n = prom.total(after, per) - prom.total(before, per)
    if n <= 0:
        return None
    return (prom.total(after, counter) - prom.total(before, counter)) / n
