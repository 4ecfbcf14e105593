"""Mean device time of one named program over the traced window, in
milliseconds: the sum of the durations of its events on the ``XLA Modules``
line / their number, over every device.  The program is found by the name
its lane gave it (``jit_wave``, ``jit_scan_blocked``, ``jit_scan_exact``;
the trace appends a fingerprint in brackets).  A trace without that
program (the lane did not run; the parent commit, where every packed
program is ``jit_run``) gives nothing."""


def read(ctx, module, **_args):
    trace = ctx.get("trace")
    if trace is None:
        return None
    durations = [
        dur for d in trace.devices for name, _start, dur in d.modules if name.split("(")[0] == module
    ]
    if not durations:
        return None
    return sum(durations) / len(durations) / 1e6
