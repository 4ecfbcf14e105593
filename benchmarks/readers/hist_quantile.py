"""A quantile of a ``/metrics`` histogram over the window, in milliseconds,
from the differences of its cumulative buckets, interpolated inside the
bucket it falls in (the buckets double, so it is coarse: read it beside the
client's own percentile, not in its place)."""

import prom


def read(ctx, histogram, q, **_args):
    before = prom.buckets(ctx["before"], histogram)
    after = prom.buckets(ctx["after"], histogram)
    bounds = sorted(after)
    counts = [after[b] - before.get(b, 0.0) for b in bounds]
    if not bounds or counts[-1] <= 0:
        return None
    rank = q / 100.0 * counts[-1]
    lower, below = 0.0, 0.0
    for bound, cum in zip(bounds, counts):
        if cum >= rank:
            if bound == float("inf"):
                return 1e3 * lower
            inside = cum - below
            frac = (rank - below) / inside if inside > 0 else 1.0
            return 1e3 * (lower + frac * (bound - lower))
        lower, below = bound, cum
    return None
