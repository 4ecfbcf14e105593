"""Device time of one named program over the traced window for each
thousand of what its calls carried, in milliseconds.  Where calls of one
program carry different numbers of pods (the blocked scan: 32 rows a step
or a pod a step, a few pods or thousands, under one name), the time a call
says little and the time a pod says what the lane costs.

What a call carried is read off the host span that dispatched it and
waited for it: every event of the program on the ``XLA Modules`` line
starts inside one span named ``span`` (the planes share a clock,
``hosttrace.started_inside``), and that span's ``stat`` is the number of
live pods the program was handed.  Events that start inside no such span
(the span was open when the trace began) are left out on both sides.  No
trace, no such program or no such span gives nothing."""

import bisect


def read(ctx, module, span, stat="n", **_args):
    trace = ctx.get("trace")
    if trace is None:
        return None
    spans = sorted(
        (start, start + dur, stats.get(stat))
        for name, start, dur, _line, stats in trace.host
        if name == span and stats.get(stat) is not None
    )
    starts = [s[0] for s in spans]
    total_ns = carried = 0
    for d in trace.devices:
        for name, start, dur in d.modules:
            if name.split("(")[0] != module:
                continue
            i = bisect.bisect_right(starts, start) - 1
            if i >= 0 and start <= spans[i][1]:
                total_ns += dur
                carried += int(spans[i][2])
    if carried <= 0:
        return None
    return total_ns / 1e6 / (carried / 1e3)
