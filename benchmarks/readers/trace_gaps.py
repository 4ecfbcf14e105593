"""What the host was doing while the device was idle.

Every interval of the traced window in which no operation ran on the
device is cut by the spans of the program's loop thread (the thread whose
line holds ``sched.wave_dispatch`` or ``sched.scan_dispatch``: a line is
found by the spans on it, every line is named after the process), and each
piece goes to the deepest span that covers it.  A piece under
``sched.loop_handoff_wait`` (the loop waiting for its next wave) goes
instead to the deepest span of the build worker's thread at that instant,
written ``handoff>sched.wave_build_tables``, or ``handoff>sched.queue_pop_wait``
when no work was offered.  A piece under no span is ``unattributed``.

Works on plain intervals, so that a test can hand it a trace written by
hand: ``attribute`` takes device events and host events as tuples.  With
several devices each is attributed alone and the seconds are averaged,
as ``trace_idle`` averages the busy time.

``read`` gives the share of the idle seconds that no span covers, in
percent; ``table`` the ``[[what, seconds], ...]`` list of the result line's
``breakdown.idle_gaps``.  A trace with no host spans (the parent commit)
gives nothing.  The host events are ``trace.host``, which ``hosttrace.load``
reads from the same file as ``tracefile.load`` reads the device planes.
"""

from __future__ import annotations

from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[int, int]  # start_ns, end_ns
Segment = Tuple[int, int, str]  # start_ns, end_ns, deepest span
HostEvent = Tuple[str, int, int, int, dict]  # name, start_ns, duration_ns, line, stats

DISPATCH = ("sched.wave_dispatch", "sched.scan_dispatch")
WORKER = ("sched.wave_build", "sched.queue_pop_wait")
HANDOFF = "sched.loop_handoff_wait"
NONE = "unattributed"


def idle_intervals(events: Iterable[Tuple[str, int, int]], window: Interval) -> List[Interval]:
    """The window less the union of the events' intervals."""
    out: List[Interval] = []
    cursor, stop = window
    for _name, start, dur in sorted(events, key=lambda e: e[1]):
        if start > cursor:
            out.append((cursor, min(start, stop)))
        cursor = max(cursor, start + dur)
        if cursor >= stop:
            break
    if cursor < stop:
        out.append((cursor, stop))
    return [(a, b) for a, b in out if b > a]


def deepest_segments(spans: Iterable[Tuple[str, int, int]]) -> List[Segment]:
    """One thread's spans (they nest) as disjoint, ordered segments, each
    named after the innermost span open in it."""
    out: List[Segment] = []
    stack: List[Tuple[int, str]] = []  # (end, name), outermost first
    cursor = 0

    def close_until(t: int) -> None:
        nonlocal cursor
        while stack and stack[-1][0] <= t:
            end, name = stack.pop()
            if end > cursor:
                out.append((cursor, end, name))
                cursor = end

    for name, start, dur in sorted(spans, key=lambda s: (s[1], -s[2])):
        close_until(start)
        if stack and start > cursor:
            out.append((cursor, start, stack[-1][1]))
        cursor = max(cursor, start) if stack else start
        stack.append((start + dur, name))
    close_until(max((end for end, _n in stack), default=0))
    return out


def cut(intervals: Sequence[Interval], segments: Sequence[Segment]) -> List[Segment]:
    """Each interval in pieces named after the segment that covers the
    piece (``NONE`` where none does).  Both inputs ordered and disjoint."""
    out: List[Segment] = []
    i = 0
    for a, b in intervals:
        while i < len(segments) and segments[i][1] <= a:
            i += 1
        j, cursor = i, a
        while cursor < b:
            if j >= len(segments) or segments[j][0] >= b:
                out.append((cursor, b, NONE))
                break
            s, e, name = segments[j]
            if s > cursor:
                out.append((cursor, s, NONE))
                cursor = s
            stop = min(e, b)
            out.append((cursor, stop, name))
            cursor = stop
            j += 1
    return out


def line_with(host: Iterable[HostEvent], names: Sequence[str]) -> Optional[int]:
    """The line (thread) that holds most events of ``names``."""
    count: Dict[int, int] = {}
    for name, _s, _d, line, _stats in host:
        if name in names:
            count[line] = count.get(line, 0) + 1
    return max(count, key=count.get) if count else None


def attribute(
    devices: Sequence[Sequence[Tuple[str, int, int]]], host: Sequence[HostEvent], window: Interval
) -> Dict[str, float]:
    """what -> idle seconds, averaged over the devices."""
    loop, worker = line_with(host, DISPATCH), line_with(host, WORKER)
    on = lambda line: deepest_segments([e[:3] for e in host if e[3] == line])  # noqa: E731
    loop_segments = on(loop) if loop is not None else []
    worker_segments = on(worker) if worker is not None else []
    total: Dict[str, float] = {}
    for events in devices:
        pieces = cut(idle_intervals(events, window), loop_segments)
        waiting = [(a, b) for a, b, what in pieces if what == HANDOFF]
        pieces = [p for p in pieces if p[2] != HANDOFF] + [
            (a, b, "handoff>" + what) for a, b, what in cut(waiting, worker_segments)
        ]
        for a, b, what in pieces:
            total[what] = total.get(what, 0.0) + (b - a) / 1e9 / len(devices)
    return total


def of_trace(trace) -> Optional[Dict[str, float]]:
    """``attribute`` on a loaded trace; nothing without host spans or a
    device plane.  The window is cut to what the loop thread's spans span:
    a span that was open when the trace started or stopped is not in the
    trace, and the idle time under it would read as under none."""
    host = getattr(trace, "host", None)
    if trace is None or not host or not trace.devices:
        return None
    loop = line_with(host, DISPATCH)
    known = [e for e in host if e[3] == loop] or host
    window = (min(e[1] for e in known), max(e[1] + e[2] for e in known))
    return attribute([d.ops or d.modules for d in trace.devices], host, window)


def table(trace, n: int = 10) -> List[List]:
    gaps = of_trace(trace) or {}
    return [[what, s] for what, s in sorted(gaps.items(), key=lambda kv: -kv[1])[:n]]


def read(ctx, **_args):
    gaps = of_trace(ctx.get("trace"))
    if not gaps:
        return None
    idle = sum(gaps.values())
    lost = sum(s for what, s in gaps.items() if what.endswith(NONE))
    return 100.0 * lost / idle if idle > 0 else None
