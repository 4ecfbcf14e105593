"""ISSUE 26: one span primitive at every layer boundary.

A span feeds two histograms (wall and the thread's CPU), lands in a
``jax.profiler`` trace under the same name with its ids as the event's
stats, and is on ``/metrics`` from boot; the device programs carry their
lane's name; none of it touches a backend before the engine does.
"""

from __future__ import annotations

import glob
import json
import os
import subprocess
import sys
import time

import pytest

from minisched_tpu.observability import hist, profiling

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: ISSUE 26 §2's table, as Prometheus series stems
TABLE_SPANS = [
    "http_create", "http_create_read", "http_create_decode",
    "http_create_store", "http_create_respond",
    "watch_deliver", "informer_dispatch",
    "sched_queue_pop_wait",
    "sched_wave_build", "sched_wave_snapshot", "sched_wave_build_tables",
    "sched_wave_build_constraints",
    "sched_loop_handoff_wait",
    "sched_wave", "sched_wave_device", "sched_wave_dispatch",
    "sched_wave_fetch",
    "sched_wave_commit", "sched_permit", "sched_bind",
    "sched_scan_flush", "sched_scan_grouping", "sched_scan_build",
    "sched_scan_evaluate", "sched_scan_dispatch", "sched_scan_fetch",
    "sched_loop_gc",
]
#: spans of threads that wait for work: they may have closed once by the
#: time a scrape right after start() is answered
WAITERS = {"sched_queue_pop_wait", "sched_loop_handoff_wait", "sched_loop_gc"}


def _count(name: str) -> int:
    return hist.GLOBAL.merged(name)[3]


def test_a_span_feeds_wall_and_cpu_histograms():
    n_wall, n_cpu = _count("t.span.one_s"), _count("t.span.one_cpu_s")
    with profiling.span("t.span.one", n=3) as sp:
        time.sleep(0.02)
    assert _count("t.span.one_s") == n_wall + 1
    assert _count("t.span.one_cpu_s") == n_cpu + 1
    assert sp.wall_s >= 0.02 and 0.0 <= sp.cpu_s < sp.wall_s


def test_spans_nest_and_the_parent_covers_the_child():
    with profiling.span("t.span.parent") as parent:
        with profiling.span("t.span.child") as child:
            time.sleep(0.01)
        time.sleep(0.01)
    assert parent.wall_s >= child.wall_s + 0.01 > 0.02
    assert _count("t.span.parent_s") >= 1 and _count("t.span.child_s") >= 1


def test_a_span_survives_an_exception():
    n = _count("t.span.raises_s")
    with pytest.raises(KeyError):
        with profiling.span("t.span.raises"):
            raise KeyError("through the span")
    assert _count("t.span.raises_s") == n + 1
    assert _count("t.span.raises_cpu_s") == n + 1


@pytest.mark.parametrize("how", ["sleeps", "spins"])
def test_wall_less_cpu_is_the_time_the_thread_did_not_run(how):
    """A sleeping span reads wall >> CPU; a spinning one wall ~ CPU."""
    with profiling.span("t.span." + how) as sp:
        if how == "sleeps":
            time.sleep(0.2)
        else:
            t_end = time.thread_time() + 0.2
            while time.thread_time() < t_end:
                pass
    if how == "sleeps":
        assert sp.wall_s >= 0.2 and sp.cpu_s < 0.05
    else:
        assert sp.cpu_s >= 0.2 and sp.cpu_s >= 0.5 * sp.wall_s


def test_a_young_cpu_reading_is_used_again(monkeypatch):
    """The thread CPU clock is a system call: spans that open and close
    within microseconds of each other share one reading of it."""
    reads = []
    real = time.thread_time
    monkeypatch.setattr(profiling.time, "thread_time", lambda: reads.append(1) or real())
    time.sleep(0.001)  # whatever was read before is old by now
    with profiling.span("t.span.outer"):
        with profiling.span("t.span.inner"):
            pass
    assert 1 <= len(reads) < 4  # four boundaries
    time.sleep(0.001)
    with profiling.span("t.span.outer"):
        pass
    assert len(reads) >= 2  # an old reading is not used again


def test_spans_find_their_histograms_again_after_a_reset():
    with profiling.span("t.span.reset"):
        pass
    hist.reset()
    with profiling.span("t.span.reset"):
        pass
    assert _count("t.span.reset_s") == 1 and _count("t.span.reset_cpu_s") == 1


def test_cycle_metrics_timed_is_the_span_plus_the_aggregate():
    """The per-engine aggregate keeps the phase key; the histograms carry
    the span's name (a mapped phase and an unmapped one)."""
    m = profiling.CycleMetrics()
    before = {n: _count(n) for n in (
        "sched.wave_commit_s", "sched.wave_commit_cpu_s",
        "sched.wave_winners_s", "sched.wave_stall_s", "sched.wave_stall_cpu_s",
    )}
    with m.timed("commit", wave=7, n=2) as sp:
        time.sleep(0.005)
    with m.timed("wave_winners"):
        pass
    m.observe("wave_pipeline_stall", 0.25)
    snap = m.snapshot()
    assert snap["commit"]["count"] == 1
    assert snap["commit"]["total_s"] == pytest.approx(sp.wall_s)
    assert snap["wave_winners"]["count"] == 1
    assert snap["wave_pipeline_stall"]["total_s"] == 0.25
    assert _count("sched.wave_commit_s") == before["sched.wave_commit_s"] + 1
    assert _count("sched.wave_commit_cpu_s") == before["sched.wave_commit_cpu_s"] + 1
    assert _count("sched.wave_winners_s") == before["sched.wave_winners_s"] + 1
    # an observed duration has no CPU clock: the stall stays one histogram
    assert _count("sched.wave_stall_s") == before["sched.wave_stall_s"] + 1
    assert _count("sched.wave_stall_cpu_s") == before["sched.wave_stall_cpu_s"]


def test_null_metrics_opens_no_span():
    n = _count("sched.wave_commit_s")
    with profiling.NULL_METRICS.timed("commit", wave=1) as sp:
        pass
    assert sp.wall_s == 0.0 and _count("sched.wave_commit_s") == n


def test_a_registered_name_outlives_a_reset():
    h = hist.Histograms()
    h.register("t.reg_s")
    h.observe("t.reg_s", 0.5)
    h.observe("t.unreg_s", 0.5)
    h.reset()
    assert h.names() == ["t.reg_s"] and h.merged("t.reg_s")[3] == 0
    assert "t_reg_seconds_count 0" in hist.render_prometheus(hists=h)


_BOOT_PROBE = """
import json, sys, urllib.request
from jax._src import xla_bridge
from minisched_tpu.observability import profiling
with profiling.span("probe.span", n=1):
    pass
first_span_clean = not xla_bridge.backends_are_initialized()
from minisched_tpu.__main__ import start
from minisched_tpu.service.config import ProcessConfig
_c, base, stop = start(ProcessConfig(port=0, frontend_url="http://localhost:3000"), device_mode=True)
try:
    with urllib.request.urlopen(base + "/metrics", timeout=30) as r:
        text = r.read().decode()
finally:
    stop()
counts = {}
for line in text.splitlines():
    if line.startswith("#") or "_count" not in line:
        continue
    name, value = line.rsplit(" ", 1)
    counts[name] = float(value)
print("PROBE " + json.dumps({"first_span_clean": first_span_clean, "counts": counts}))
"""


@pytest.fixture(scope="module")
def boot_probe():
    r = subprocess.run(
        [sys.executable, "-c", _BOOT_PROBE], cwd=REPO, capture_output=True,
        text=True, timeout=300,
        env={**os.environ, "PYTHONPATH": REPO, "JAX_PLATFORMS": "cpu"},
    )
    lines = [l for l in r.stdout.splitlines() if l.startswith("PROBE ")]
    assert r.returncode == 0 and lines, r.stderr[-3000:]
    return json.loads(lines[-1][len("PROBE "):])


def test_the_first_span_initialises_no_backend(boot_probe):
    """profiling imports no JAX; the first span imports ``jax.profiler``
    and still leaves the chip to whoever needs it."""
    assert boot_probe["first_span_clean"] is True


@pytest.mark.parametrize("stem", TABLE_SPANS)
def test_every_span_is_on_metrics_from_boot(boot_probe, stem):
    """Both histograms of every span of the table, before any pod: a
    reader that differences two scrapes never meets a series that was
    born in between."""
    counts = boot_probe["counts"]
    for series in (stem + "_seconds_count", stem + "_cpu_seconds_count"):
        assert series in counts, sorted(counts)
        if stem not in WAITERS:
            assert counts[series] == 0, (series, counts[series])


@pytest.mark.parametrize(
    "series", ["sched_wave_stall_seconds_count", "sched_queue_wait_seconds_count"]
)
def test_the_two_plain_histograms_are_on_metrics_from_boot(boot_probe, series):
    assert boot_probe["counts"].get(series) == 0


# -- a tiny served wave under the profiler ------------------------------------


def _spread_pod(name, app="s"):
    from minisched_tpu.api.objects import (
        LabelSelector,
        TopologySpreadConstraint,
        make_pod,
    )

    pod = make_pod(name, requests={"cpu": "100m"}, labels={"app": app})
    pod.spec.topology_spread_constraints = [
        TopologySpreadConstraint(
            max_skew=1, topology_key="zone",
            when_unsatisfiable="DoNotSchedule",
            label_selector=LabelSelector(match_labels={"app": app}),
        )
    ]
    return pod


def _wait_bound(client, n, seconds=300.0):
    t_end = time.monotonic() + seconds
    while time.monotonic() < t_end:
        if sum(1 for p in client.pods().list() if p.spec.node_name) >= n:
            return
        time.sleep(0.05)
    raise AssertionError(f"fewer than {n} pods bound in {seconds}s")


@pytest.fixture(scope="module")
def traced_wave(tmp_path_factory):
    """The served stack (``__main__.start``, device engine), all three
    lanes warmed, then ONE more wave of 24 pods under ``jax.profiler`` with
    the options the benchmark sets.  Returns the host plane's lines as
    lists of (name, start_ns, duration_ns, stats) and the lanes' programs."""
    import jax
    from jax.profiler import ProfileData

    import chip_smoke
    from minisched_tpu.api.objects import make_node, make_pod
    from minisched_tpu.controlplane.remote import RemoteClient

    trace_dir = str(tmp_path_factory.mktemp("trace"))
    with chip_smoke.booted_stack() as (base, service):
        client = RemoteClient(base)
        client.nodes().create_many(
            [
                make_node(
                    f"n{i:02d}", labels={"zone": f"z{i % 3}"},
                    capacity={"cpu": "8", "memory": "16Gi", "pods": 110},
                )
                for i in range(16)
            ],
            return_objects=False,
        )
        lister = service.informer_factory.informer_for("Node").lister
        while len(lister()) < 16:
            time.sleep(0.05)
        plain = [make_pod(f"a{i}", requests={"cpu": "100m"}) for i in range(24)]
        client.pods().create_many(plain, return_objects=False)
        _wait_bound(client, 24)
        # 40 > SCAN_BLOCK_SIZE: the blocked lane, whose first two blocks
        # hold two pods (wide) and whose other 36 hold one (narrow); 8: the
        # exact lane
        client.pods().create_many(
            [_spread_pod(f"r{i}", "r") for i in range(2)]
            + [_spread_pod(f"s{i}") for i in range(38)],
            return_objects=False,
        )
        _wait_bound(client, 64)
        client.pods().create_many(
            [_spread_pod(f"t{i}") for i in range(8)], return_objects=False
        )
        _wait_bound(client, 72)

        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 1
        options.enable_hlo_proto = False
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        try:
            client.pods().create_many(
                [make_pod(f"b{i}", requests={"cpu": "100m"}) for i in range(24)],
                return_objects=False,
            )
            _wait_bound(client, 96)
        finally:
            jax.profiler.stop_trace()
        sched = service.scheduler
        programs = sched.dispatched_programs()
        with_locations = {
            "wave": sched._evaluator._packed_caller.lowered_texts(debug_info=True),
            "blocked_scan": sched._blocked_scheduler._packed_caller.lowered_texts(
                debug_info=True
            ),
            "narrow_scan": sched._narrow_scheduler._packed_caller.lowered_texts(
                debug_info=True
            ),
        }
        ring = [s for s in _ring(base) if s.get("stage") == "wave_build"]

    path = max(
        glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True),
        key=os.path.getmtime,
    )
    lines = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            lines.append(
                [(e.name, e.start_ns, e.duration_ns, dict(e.stats)) for e in line.events]
            )
    return {"lines": lines, "programs": programs, "with_locations": with_locations,
            "ring": ring}


def _ring(base):
    import urllib.request

    with urllib.request.urlopen(base + "/debug/trace", timeout=30) as r:
        return [json.loads(line) for line in r.read().decode().splitlines()]


def _events(traced, name):
    return [e for line in traced["lines"] for e in line if e[0] == name]


def _line_of(traced, name):
    """The one thread (line) that holds ``name``: a reader finds a thread
    by the spans on it, never by the line's name (every line is named
    after the process)."""
    holding = [line for line in traced["lines"] if any(e[0] == name for e in line)]
    assert len(holding) == 1, (name, len(holding))
    return holding[0]


def test_one_wave_id_across_the_threads(traced_wave):
    """The build worker's span, the loop thread's spans and the trace
    ring's ``wave_build`` carry the same number for one wave."""
    builds = [e for e in _events(traced_wave, "sched.wave_build") if e[3].get("n") == 24]
    assert len(builds) == 1, _events(traced_wave, "sched.wave_build")
    wave_id = builds[0][3]["wave"]
    for name in ("sched.wave", "sched.wave_device", "sched.wave_commit"):
        assert wave_id in [e[3].get("wave") for e in _events(traced_wave, name)], name
    assert any(
        s.get("wave") == wave_id and s.get("size") == 24 for s in traced_wave["ring"]
    ), traced_wave["ring"]
    # the build ran on the worker's thread, the device call on the loop's
    assert _line_of(traced_wave, "sched.wave_build") is not _line_of(
        traced_wave, "sched.wave_dispatch"
    )


@pytest.mark.parametrize(
    "parent,children",
    [
        ("sched.wave_device", ["sched.wave_dispatch", "sched.wave_fetch"]),
        ("sched.wave", ["sched.wave_device", "sched.wave_commit"]),
        ("sched.wave_commit", ["sched.bind"]),
        ("sched.wave_build", ["sched.wave_snapshot", "sched.wave_build_tables"]),
        ("http.create", ["http.create_read", "http.create_decode",
                         "http.create_store", "http.create_respond"]),
    ],
)
def test_children_lie_inside_their_parent_on_its_thread(traced_wave, parent, children):
    line = _line_of(traced_wave, parent)
    parents = [e for e in line if e[0] == parent]
    for child in children:
        found = [e for e in line if e[0] == child]
        assert found, f"{child} is not on {parent}'s thread"
        for _n, start, dur, _s in found:
            assert any(
                p[1] <= start and start + dur <= p[1] + p[2] for p in parents
            ), (child, parent)


def test_the_create_span_carries_its_item_count(traced_wave):
    assert [e[3].get("n") for e in _events(traced_wave, "http.create")] == [24]
    assert _events(traced_wave, "informer.dispatch")


@pytest.mark.parametrize(
    "lane,module",
    [("wave", "jit_wave"), ("blocked_scan", "jit_scan_blocked"),
     ("narrow_scan", "jit_scan_blocked"), ("exact_scan", "jit_scan_exact")],
)
def test_the_device_programs_carry_their_lane(traced_wave, lane, module):
    texts = traced_wave["programs"][lane]
    assert texts, f"the {lane} lane dispatched nothing"
    for text in texts:
        assert f"module @{module} " in text
        assert "jit_run" not in text


@pytest.mark.parametrize("lane", ["wave", "blocked_scan", "narrow_scan"])
def test_the_selection_tail_has_a_name_in_the_program(traced_wave, lane):
    """Off the TPU the XLA tail runs, under its named scope (on the chip
    the Mosaic kernel's ``name=`` says ``select_hosts`` there instead)."""
    for text in traced_wave["with_locations"][lane]:
        assert "select_hosts" in text
