"""Share of the traced window in which no operation ran on the device.

1 - (union of the device operations' intervals / traced window), averaged
over the devices used.  Nothing to read (no device plane in the trace)
gives nothing, never 0.
"""

import tracefile


def read(ctx, **_args):
    trace = ctx.get("trace")
    if trace is None or not trace.devices or trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - tracefile.busy_s(trace) / trace.window_s)
