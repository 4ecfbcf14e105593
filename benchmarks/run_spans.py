#!/usr/bin/env python3
"""One traced run of a cell, with the tables that have no place in the
contract's result line.

    python3 benchmarks/run_spans.py --workload <name> --seed <n> --seconds <s>

This is ``run.py --trace 1`` and nothing else of its own: the same set-up,
window, read-back, comparison, per-layer metrics and ``idle_gaps``.  The
result line's ``breakdown`` gains, for a builder's look at one run:
``started_inside``, the device programs that start inside the host span
that waits for them, to show that the two planes share a clock;
``programs`` and ``host_spans``, both by total time in the trace;
``span_clocks``, every span's wall and CPU seconds a second of window from
the two scrapes; ``thread_cpu``, the process's threads by CPU seconds a
second of window from ``/proc/self/task``, so that CPU no span covers still
has a thread's name.  It looks over ``run.py``'s shoulder (the trace it
loads, the context it hands the readers, the instants it scrapes) and
changes nothing of the run.

The rehearsal is reached only as a Python argument, as in ``run.py``.
"""

from __future__ import annotations

import io
import json
import os
import re
import shutil
import sys
import tempfile
import threading
import time
from contextlib import redirect_stdout
from typing import Any, Dict, List, Optional
from unittest import mock

HERE = os.path.dirname(os.path.abspath(__file__))
if HERE not in sys.path:
    sys.path.insert(0, HERE)

import hosttrace  # noqa: E402
import prom  # noqa: E402
import run  # noqa: E402
import tracefile  # noqa: E402

ALL_THREADS = "(the process)"
ENDED = "(threads that ended in the window)"

#: device program -> the host span that dispatches it and waits for it
WAITED_FOR_IN = {
    "jit_wave": "sched.wave_device",
    "jit_scan_blocked": "sched.scan_evaluate",
    "jit_scan_exact": "sched.scan_evaluate",
}


def started_inside(trace: Any) -> Dict[str, List[int]]:
    """program -> [events that start inside its host span, events]."""
    out: Dict[str, List[int]] = {}
    for program, span in WAITED_FOR_IN.items():
        events = [e for d in trace.devices for e in d.modules if e[0].split("(")[0] == program]
        if events:
            out[program] = hosttrace.started_inside(events, [e for e in trace.host if e[0] == span])
    return out


def thread_cpu_s() -> Dict[str, float]:
    """CPU seconds so far of every thread of this process, by name: the
    Python thread's where there is one (numbers struck out, so a server's
    handler threads add up), else the name the runtime gave it; and of
    the process as a whole."""
    python = {t.native_id: t.name for t in threading.enumerate()}
    tick = os.sysconf("SC_CLK_TCK")
    # a thread that ends takes its clock with it (a connection's handler):
    # the process's own clock less the threads still here is theirs
    out: Dict[str, float] = {ALL_THREADS: time.process_time()}
    try:
        tasks = os.listdir("/proc/self/task")
    except OSError:
        return out
    for tid in tasks:
        try:
            with open(f"/proc/self/task/{tid}/stat") as f:
                stat = f.read()
        except OSError:
            continue  # the thread ended meanwhile
        comm = stat[stat.index("(") + 1 : stat.rindex(")")]
        fields = stat[stat.rindex(")") + 2 :].split()
        name = re.sub(r"\d+", "#", python.get(int(tid)) or comm)
        out[name] = out.get(name, 0.0) + (int(fields[11]) + int(fields[12])) / tick  # utime + stime
    return out


def span_clocks(ctx: Dict[str, Any]) -> List[List]:
    """[span, wall s / window s, CPU s / window s] for every span histogram
    on ``/metrics`` that saw anything in the window, most CPU first."""
    window_s = ctx["window_s"]
    stems = sorted({n[: -len("_cpu_seconds_sum")] for n, _l, _v in ctx["after"] if n.endswith("_cpu_seconds_sum")})
    rows = []
    for stem in stems:
        delta = lambda series: prom.total(ctx["after"], series) - prom.total(ctx["before"], series)  # noqa: E731
        if delta(stem + "_seconds_count") > 0:
            rows.append([stem, delta(stem + "_seconds_sum") / window_s, delta(stem + "_cpu_seconds_sum") / window_s])
    return sorted(rows, key=lambda r: -r[2])


def main(argv: Optional[List[str]] = None, rehearsal: bool = False) -> int:
    argv = list(sys.argv[1:] if argv is None else argv) + ["--trace", "1"]
    load_trace, per_layer, scrape = tracefile.load, run.per_layer, prom.scrape
    seen: Dict[str, Any] = {"threads": []}

    # a directory of this run's own: run.py's is one fixed path, which two
    # traced runs at once (the tests' workers) would empty under each other
    trace_dir = tempfile.mkdtemp(prefix="run_spans.")

    def trace_seen(where: str, window_s: float) -> Any:
        trace = seen["trace"] = load_trace(where, window_s)
        return trace

    def per_layer_seen(metrics: List[Dict[str, Any]], ctx: Dict[str, Any]) -> Dict[str, Any]:
        seen["ctx"] = ctx
        return per_layer(metrics, ctx)

    def scrape_and_threads(base: str) -> Any:
        # run.py scrapes as the window opens and as it closes
        seen["threads"].append(thread_cpu_s())
        return scrape(base)

    out = io.StringIO()
    try:
        with mock.patch.object(run, "TRACE_DIR", trace_dir), mock.patch.object(
            tracefile, "load", trace_seen
        ), mock.patch.object(run, "per_layer", per_layer_seen), mock.patch.object(
            prom, "scrape", scrape_and_threads
        ), redirect_stdout(out):
            rc = run.main(argv, rehearsal=rehearsal)
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    result = json.loads(out.getvalue().strip().splitlines()[-1])
    trace, ctx = seen["trace"], seen["ctx"]
    before, after = seen["threads"][:2]
    threads = {name: (s - before.get(name, 0.0)) / ctx["window_s"] for name, s in after.items()}
    threads[ENDED] = 2 * threads[ALL_THREADS] - sum(threads.values())
    result["breakdown"].update(
        started_inside=started_inside(trace),
        programs=tracefile.top_modules(trace),
        host_spans=hosttrace.top_spans(trace.host),
        span_clocks=span_clocks(ctx),
        thread_cpu=[[n, s] for n, s in sorted(threads.items(), key=lambda kv: -kv[1])[:16] if s > 0],
    )
    print(json.dumps(result), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
