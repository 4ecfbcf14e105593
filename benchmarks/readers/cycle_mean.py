"""Mean of one of the engine's ``CycleMetrics`` phases over the window, in
milliseconds: the scan lanes' host clocks are not on ``/metrics`` yet, so
``run.py`` reads the engine's own aggregate before and after the window.
A phase that did not run in the window gives nothing."""


def read(ctx, phase, **_args):
    before = (ctx.get("cycle_before") or {}).get(phase, {"count": 0, "total_s": 0.0})
    after = (ctx.get("cycle_after") or {}).get(phase)
    if after is None or after["count"] <= before["count"]:
        return None
    return 1e3 * (after["total_s"] - before["total_s"]) / (after["count"] - before["count"])
