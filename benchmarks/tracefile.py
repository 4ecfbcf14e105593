"""From a ``jax.profiler`` trace to intervals: the reduction's first half.

``load`` reads the newest ``.xplane.pb`` under a directory with
``jax.profiler.ProfileData`` and keeps, for every device plane
(``/device:TPU:n``), two lists of ``(name, start_ns, duration_ns)``: the
device's operations (the line ``XLA Ops``) and its programs (the line
``XLA Modules``), and beside them the program's spans from the host plane
of the same file (``hosttrace.spans``, as ``Trace.host``).  The readers
under ``readers/`` work on that plain structure, so that a test can hand
them intervals it wrote by hand.
"""

from __future__ import annotations

import glob
import os
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Tuple

import hosttrace

Event = Tuple[str, int, int]  # name, start_ns, duration_ns

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclass
class DevicePlane:
    name: str
    ops: List[Event] = field(default_factory=list)
    modules: List[Event] = field(default_factory=list)


@dataclass
class Trace:
    devices: List[DevicePlane]
    window_s: float
    #: plane -> line -> number of events, for a look by hand
    layout: Dict[str, Dict[str, int]] = field(default_factory=dict)
    #: the program's spans on the host plane (``hosttrace.HostEvent``)
    host: List[hosttrace.HostEvent] = field(default_factory=list)


def newest_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def load(trace_dir: str, window_s: float) -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(newest_xplane(trace_dir))
    devices: List[DevicePlane] = []
    layout: Dict[str, Dict[str, int]] = {}
    for plane in data.planes:
        is_device = plane.name.startswith("/device:") and "CUSTOM" not in plane.name
        dev = DevicePlane(plane.name) if is_device else None
        lines = layout.setdefault(plane.name, {})
        for line in plane.lines:
            if dev is None or line.name not in (OPS_LINE, MODULES_LINE):
                lines[line.name] = lines.get(line.name, 0) + sum(1 for _ in line.events)
                continue
            events = [(e.name, int(e.start_ns), int(e.duration_ns)) for e in line.events]
            lines[line.name] = len(events)
            if line.name == OPS_LINE:
                dev.ops += events
            else:
                dev.modules += events
        if dev is not None and (dev.ops or dev.modules):
            devices.append(dev)
    return Trace(devices, window_s, layout, hosttrace.spans(data))


def union_ns(events: Iterable[Event]) -> int:
    """Length of the union of the events' intervals: two operations that
    overlap are busy time once."""
    total, end = 0, -1
    for _name, start, dur in sorted(events, key=lambda e: e[1]):
        stop = start + dur
        if start > end:
            total += dur
            end = stop
        elif stop > end:
            total += stop - end
            end = stop
    return total


def busy_s(trace: Trace) -> float:
    """Seconds in which an operation ran, averaged over the devices."""
    if not trace.devices:
        return 0.0
    return sum(union_ns(d.ops or d.modules) for d in trace.devices) / len(trace.devices) / 1e9


def leaves(events: List[Event]) -> List[Event]:
    """The events that hold no other: a ``while`` or a ``conditional`` is
    on the line beside the operations of its body, and counting both would
    count the body twice."""
    ordered = sorted(events, key=lambda e: (e[1], -e[2]))
    return [
        e for e, nxt in zip(ordered, ordered[1:] + [None])
        if nxt is None or nxt[1] + nxt[2] > e[1] + e[2]
    ]


def top_ops(trace: Trace, n: int = 10) -> List[List]:
    """The device operations that took most time: [name, seconds]."""
    total: Dict[str, int] = {}
    for d in trace.devices:
        for name, _start, dur in leaves(d.ops):
            total[name] = total.get(name, 0) + dur
    ranked = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[name[:120], ns / 1e9 / max(1, len(trace.devices))] for name, ns in ranked]


def top_modules(trace: Trace, n: int = 8) -> List[List]:
    """The device programs by total time: [name, events, seconds]."""
    total: Dict[str, List[int]] = {}
    for d in trace.devices:
        for name, _start, dur in d.modules:
            t = total.setdefault(name, [0, 0])
            t[0] += 1
            t[1] += dur
    ranked = sorted(total.items(), key=lambda kv: -kv[1][1])[:n]
    return [[name, c, ns / 1e9] for name, (c, ns) in ranked]
