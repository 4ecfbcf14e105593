"""What the summed seconds of some ``/metrics`` histograms over the window
come to, in percent: of the window's own seconds (``over`` not given: a
span's CPU seconds then read as a share of one core), or of another
histogram's summed seconds (``over``).  ``complement`` gives 100 less that
share: with a span's CPU histogram over its wall histogram, the part of
the span's time in which its thread did not run.

A histogram the program does not have (the parent commit) gives nothing;
one that is registered and saw nothing in the window counts 0, which is why
the program registers these from boot."""

import prom


def _seconds(ctx, histogram):
    """Sum delta over the window, or None where the series does not exist."""
    if not any(name == histogram + "_count" for name, _labels, _v in ctx["after"]):
        return None
    return prom.total(ctx["after"], histogram + "_sum") - prom.total(ctx["before"], histogram + "_sum")


def read(ctx, histograms, over=None, complement=False, **_args):
    parts = [_seconds(ctx, h) for h in histograms]
    base = _seconds(ctx, over) if over else ctx.get("window_s")
    if None in parts or not base or base <= 0:
        return None
    share = 100.0 * sum(parts) / base
    return 100.0 - share if complement else share
