"""The host half of a ``jax.profiler`` trace: the program's spans.

``tracefile.load`` keeps the device planes and, through ``spans`` here,
these beside them as ``Trace.host``.  The program opens a
``jax.profiler.TraceAnnotation`` at every layer boundary
(``minisched_tpu/observability/profiling.span``); with the options
``run.py`` sets they land on the plane ``/host:CPU``, one line a thread,
every line named after the process.  ``spans`` keeps those events as
``(name, start_ns, duration_ns, line, stats)``: ``line`` is the line's
index in the plane (a thread is found by the spans on it), ``stats`` the
span's ids (``wave``, ``n``, ...).  Device and host planes of one file
share one clock; ``started_inside`` shows it instead of assuming it.
"""

from __future__ import annotations

from typing import Any, Dict, Iterable, List, Sequence, Tuple

HOST_PLANE = "/host:CPU"
#: a span's name starts with its layer (the benchmark imports nothing of
#: the program, so it knows the layers' prefixes and not the registry)
PREFIXES = ("sched.", "http.", "watch.", "informer.")

HostEvent = Tuple[str, int, int, int, Dict[str, object]]


def spans(data: Any) -> List[HostEvent]:
    """The spans of a trace that ``jax.profiler.ProfileData`` has read."""
    out: List[HostEvent] = []
    for plane in data.planes:
        if plane.name != HOST_PLANE:
            continue
        for index, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith(PREFIXES):
                    out.append((e.name, int(e.start_ns), int(e.duration_ns), index, dict(e.stats)))
    return out


def started_inside(events: Iterable[Tuple[str, int, int]], spans: Sequence[Tuple[str, int, int]]) -> List[int]:
    """[events that start inside one of ``spans``, events]: a device
    program has to start while the host span that dispatched it and waits
    for it is open, if the two planes share a clock.  Only events between
    the first span's start and the last span's end count: a span that was
    open when the trace started or stopped is not in the trace."""
    ordered = sorted((s[1], s[1] + s[2]) for s in spans)
    if not ordered:
        return [0, 0]
    first, last = ordered[0][0], max(stop for _start, stop in ordered)
    inside = total = 0
    i = 0
    for _name, start, _dur in sorted(events, key=lambda e: e[1]):
        if not first <= start <= last:
            continue
        total += 1
        while i < len(ordered) and ordered[i][1] < start:
            i += 1
        inside += i < len(ordered) and ordered[i][0] <= start
    return [inside, total]


def top_spans(host: Iterable[HostEvent], n: int = 40) -> List[List]:
    """The spans by total time: [name, events, seconds]."""
    total: Dict[str, List[int]] = {}
    for name, _start, dur, _line, _stats in host:
        t = total.setdefault(name, [0, 0])
        t[0] += 1
        t[1] += dur
    ranked = sorted(total.items(), key=lambda kv: -kv[1][1])[:n]
    return [[name, c, ns / 1e9] for name, (c, ns) in ranked]
