"""The readers of the program's spans, on inputs with known answers, and the
traced rehearsal with ``run_spans.py``'s tables beside it."""

import importlib
import json
import os

import pytest

import hosttrace
import manifest
import prom
import run
import run_spans
from readers import trace_gaps
import tracefile
from tracefile import DevicePlane, Trace

MS = 1_000_000  # ns
LOOP, WORKER, HANDLER = 0, 1, 2


def reader(name):
    return importlib.import_module("readers." + name)


def span(name, start_ms, stop_ms, line, **stats):
    return (name, start_ms * MS, (stop_ms - start_ms) * MS, line, stats)


def hand_trace():
    """100 ms on three threads and one device.

    Device busy 10-20 (inside the loop's wave_fetch) and 60-70.  Idle:
    0-10 under sched.wave_dispatch; 20-30 under wave_fetch, 30-40 under
    sched.wave_commit's child sched.bind; 40-60 under loop_handoff_wait,
    of it 40-50 while the worker is in wave_build > wave_build_tables and
    50-55 while it waits for a pop, 55-60 under nothing on the worker;
    70-90 under no loop span at all; 90-100 under sched.loop_gc.
    """
    host = [
        span("sched.wave", 0, 40, LOOP, wave=1, n=4),
        span("sched.wave_device", 0, 30, LOOP, wave=1, n=4),
        span("sched.wave_dispatch", 0, 10, LOOP),
        span("sched.wave_fetch", 10, 30, LOOP),
        span("sched.wave_commit", 30, 40, LOOP, wave=1, n=4),
        span("sched.bind", 30, 40, LOOP, n=4),
        span("sched.loop_handoff_wait", 40, 60, LOOP),
        span("sched.loop_gc", 90, 100, LOOP),
        span("sched.wave_build", 35, 50, WORKER, wave=2, n=4),
        span("sched.wave_build_tables", 38, 50, WORKER),
        span("sched.queue_pop_wait", 50, 55, WORKER),
        span("http.create", 0, 100, HANDLER, n=4),
    ]
    modules = [("jit_wave(7)", 10 * MS, 10 * MS), ("jit_wave(7)", 60 * MS, 10 * MS), ("jit_other(1)", 61 * MS, 2 * MS)]
    ops = [("fusion.1", 10 * MS, 10 * MS), ("fusion.2", 60 * MS, 10 * MS)]
    trace = Trace([DevicePlane("/device:TPU:0", ops, modules)], window_s=0.1)
    trace.host = host
    return trace


def test_deepest_segments_of_nested_spans():
    spans = [("a", 0, 100), ("b", 10, 30), ("c", 20, 10), ("d", 60, 10), ("e", 200, 10)]
    assert trace_gaps.deepest_segments(spans) == [
        (0, 10, "a"), (10, 20, "b"), (20, 30, "c"), (30, 40, "b"), (40, 60, "a"),
        (60, 70, "d"), (70, 100, "a"), (200, 210, "e"),
    ]


def test_idle_intervals_are_the_window_less_the_union():
    events = [("x", 10, 10), ("y", 15, 10), ("z", 40, 5)]
    assert trace_gaps.idle_intervals(events, (0, 50)) == [(0, 10), (25, 40), (45, 50)]
    assert trace_gaps.idle_intervals([], (5, 9)) == [(5, 9)]
    assert trace_gaps.idle_intervals([("x", 0, 100)], (10, 20)) == []


def test_gaps_go_to_the_deepest_span_of_the_loop_thread():
    gaps = trace_gaps.of_trace(hand_trace())
    assert gaps["sched.wave_dispatch"] == pytest.approx(0.010)
    assert gaps["sched.wave_fetch"] == pytest.approx(0.010)  # 20-30; 10-20 was busy
    assert gaps["sched.bind"] == pytest.approx(0.010)  # the child, not sched.wave_commit
    assert "sched.wave_commit" not in gaps and "sched.wave" not in gaps
    assert gaps["sched.loop_gc"] == pytest.approx(0.010)


def test_a_gap_under_the_handoff_wait_goes_to_the_workers_span():
    gaps = trace_gaps.of_trace(hand_trace())
    assert "sched.loop_handoff_wait" not in gaps
    assert gaps["handoff>sched.wave_build_tables"] == pytest.approx(0.010)
    assert gaps["handoff>sched.queue_pop_wait"] == pytest.approx(0.005)
    assert gaps["handoff>unattributed"] == pytest.approx(0.005)


def test_a_gap_under_no_span_is_unattributed_and_the_share_counts_it():
    trace = hand_trace()
    gaps = trace_gaps.of_trace(trace)
    assert gaps["unattributed"] == pytest.approx(0.020)  # 70-90
    assert sum(gaps.values()) == pytest.approx(0.080)  # the window less 20 ms busy
    # 20 ms under none and 5 ms handed to a worker that was under none, of 80 ms idle
    assert reader("trace_gaps").read({"trace": trace}) == pytest.approx(100 * 25 / 80)
    table = trace_gaps.table(trace, n=3)
    assert table[0] == ["unattributed", pytest.approx(0.020)] and len(table) == 3
    assert [s for _w, s in table] == sorted((s for _w, s in table), reverse=True)


def test_gaps_average_over_two_devices():
    trace = hand_trace()
    trace.devices.append(DevicePlane("/device:TPU:1", [("busy", 0, 100 * MS)], []))
    gaps = trace_gaps.of_trace(trace)
    assert sum(gaps.values()) == pytest.approx(0.040)  # (80 ms + 0 ms) / 2
    assert gaps["unattributed"] == pytest.approx(0.010)


def test_the_window_is_what_the_loop_threads_spans_span():
    """A span open when the trace started is not in it: the idle time before
    the loop's first span is outside the window, not under no span."""
    trace = hand_trace()
    trace.host = [e for e in trace.host if e[3] != LOOP or e[1] >= 30 * MS]
    trace.host.append(span("sched.scan_dispatch", 92, 94, LOOP))  # the loop is still found by its dispatch
    gaps = trace_gaps.of_trace(trace)
    assert "sched.wave_fetch" not in gaps and gaps["sched.scan_dispatch"] == pytest.approx(0.002)
    assert sum(gaps.values()) == pytest.approx(0.060)  # 30-100 less 60-70


@pytest.mark.parametrize("strip", ["host", "devices", "trace"])
def test_gap_readers_return_nothing_without_spans_or_a_device(strip):
    trace = hand_trace()
    if strip == "host":
        del trace.host  # a trace loaded by tracefile alone, as on the parent commit
    elif strip == "devices":
        trace.devices = []
    ctx = {"trace": None if strip == "trace" else trace}
    assert reader("trace_gaps").read(ctx) is None
    assert trace_gaps.table(ctx["trace"]) == []


def test_trace_module_mean():
    read = reader("trace_module_mean").read
    ctx = {"trace": hand_trace()}
    assert read(ctx, module="jit_wave") == pytest.approx(10.0)
    assert read(ctx, module="jit_other") == pytest.approx(2.0)
    assert read(ctx, module="jit_run") is None
    assert read({"trace": None}, module="jit_wave") is None


def test_device_programs_start_inside_the_span_that_waits_for_them():
    trace = hand_trace()
    # the program at 60 ms starts after the last wave_device span the trace holds: not counted
    assert run_spans.started_inside(trace) == {"jit_wave": [1, 1]}
    spans = [("s", 0, 10), ("s", 20, 10)]
    assert hosttrace.started_inside([("m", 5, 1), ("m", 15, 1), ("m", 50, 1)], spans) == [1, 2]
    assert hosttrace.started_inside([("m", 5, 1)], []) == [0, 0]
    assert hosttrace.top_spans(trace.host, n=1) == [["http.create", 1, pytest.approx(0.1)]]


BEFORE = """
sched_wave_build_seconds_sum 1.0
sched_wave_build_seconds_count 4
sched_wave_build_cpu_seconds_sum 0.5
sched_wave_build_cpu_seconds_count 4
sched_scan_build_cpu_seconds_sum 0.0
sched_scan_build_cpu_seconds_count 0
sched_wave_stall_seconds_sum 0.0
sched_wave_stall_seconds_count 0
"""
AFTER = """
sched_wave_build_seconds_sum 3.0
sched_wave_build_seconds_count 8
sched_wave_build_cpu_seconds_sum 1.0
sched_wave_build_cpu_seconds_count 8
sched_scan_build_cpu_seconds_sum 0.3
sched_scan_build_cpu_seconds_count 2
sched_wave_stall_seconds_sum 0.0
sched_wave_stall_seconds_count 0
"""


@pytest.fixture
def scrapes():
    return {"before": prom.parse(BEFORE), "after": prom.parse(AFTER), "window_s": 4.0}


def test_hist_sum_share_of_the_window(scrapes):
    read = reader("hist_sum_share").read
    # (0.5 + 0.3) CPU seconds in a window of 4 s: 20 % of one core
    both = ["sched_wave_build_cpu_seconds", "sched_scan_build_cpu_seconds"]
    assert read(scrapes, histograms=both) == pytest.approx(20.0)
    # registered and silent is 0, not nothing: the stall share of a run with no stall
    assert read(scrapes, histograms=["sched_wave_stall_seconds"]) == 0.0
    # a histogram the program does not have gives nothing (the parent commit)
    assert read(scrapes, histograms=both + ["sched_scan_grouping_cpu_seconds"]) is None
    assert read(dict(scrapes, window_s=0.0), histograms=both) is None


def test_hist_sum_share_of_another_histogram_and_its_complement(scrapes):
    read = reader("hist_sum_share").read
    cpu, wall = ["sched_wave_build_cpu_seconds"], "sched_wave_build_seconds"
    assert read(scrapes, histograms=cpu, over=wall) == pytest.approx(25.0)  # 0.5 of 2.0 s
    assert read(scrapes, histograms=cpu, over=wall, complement=True) == pytest.approx(75.0)
    assert read(scrapes, histograms=cpu, over="sched_wave_commit_seconds") is None
    assert read(scrapes, histograms=cpu, over="sched_wave_stall_seconds") is None  # nothing to divide by


def test_hosttrace_reads_the_spans_of_a_recorded_trace(tmp_path):
    """A trace recorded here, with the options ``run.py`` sets: the spans are
    on the host plane under their names, with their ids as stats, one line a
    thread; other host events are left out."""
    import threading

    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)

    def other_thread():
        with jax.profiler.TraceAnnotation("sched.wave_build", wave=3, n=2):
            pass

    with jax.profiler.TraceAnnotation("sched.wave_device", wave=3, n=2):
        with jax.profiler.TraceAnnotation("sched.wave_dispatch"):
            jax.jit(lambda x: x + 1)(1.0).block_until_ready()
    with jax.profiler.TraceAnnotation("not_a_span"):
        pass
    t = threading.Thread(target=other_thread)
    t.start()
    t.join()
    jax.profiler.stop_trace()
    host = tracefile.load(str(tmp_path), window_s=1.0).host
    by_name = {e[0]: e for e in host}
    assert set(by_name) == {"sched.wave_device", "sched.wave_dispatch", "sched.wave_build"}
    assert by_name["sched.wave_device"][4] == {"wave": 3, "n": 2}
    assert by_name["sched.wave_device"][3] == by_name["sched.wave_dispatch"][3] != by_name["sched.wave_build"][3]
    device, dispatch = by_name["sched.wave_device"], by_name["sched.wave_dispatch"]
    assert device[1] <= dispatch[1] and dispatch[1] + dispatch[2] <= device[1] + device[2]


# -- every new metric is a file the harness can run -------------------------


#: the span metrics, by the cell whose own file lists them (``run.py`` reports them since PR 28)
ADDED = {
    "basic-5000n.drain": [
        "device.idle_unattributed_share", "evaluate.device_ms_per_wave", "evaluate.dispatch_ms_per_wave",
        "evaluate.fetch_ms_per_wave", "rest.cpu_share", "rest.watch_cpu_share", "queue.admit_cpu_share",
        "wave_build.cpu_share", "evaluate.cpu_share", "commit.cpu_share", "wave_build.offcpu_share",
        "commit.offcpu_share", "pipeline.stall_share",
    ],
    "spread-5000n.drain": [
        "device.idle_unattributed_share", "evaluate.scan_device_ms_per_call", "rest.cpu_share",
        "rest.watch_cpu_share", "queue.admit_cpu_share", "wave_build.cpu_share", "evaluate.cpu_share",
        "commit.cpu_share", "scan.grouping_ms_per_call", "scan.build_ms_per_call",
    ],
    "basic-5000n.trickle": ["queue.wait_p99_ms"],
}
#: what a CPU has no plane for: these read a device plane, as device.idle_share does
NEEDS_A_DEVICE_PLANE = {"device.idle_unattributed_share", "evaluate.device_ms_per_wave", "evaluate.scan_device_ms_per_call"}
#: the rehearsal's flushes hold 32 pods or fewer and ride the exact lane, which groups nothing
NEEDS_THE_BLOCKED_LANE = {"scan.grouping_ms_per_call"}


def test_span_metrics_names_cells_and_metrics_that_exist():
    cells = {w["name"]: w for w in manifest.build()["workloads"]}
    assert set(ADDED) <= set(cells)
    for cell, names in ADDED.items():
        reports = set(run.load_cell(cell)["traffic_data"]["end_to_end"])
        listed = run.load_cell(cell)["per_layer"]
        assert len(listed) == len(set(listed)) and set(names) <= set(listed)
        for name in names:
            m = run.load_json("metrics", name + ".json")
            assert m["name"] == name and m["source"] in manifest.SOURCES
            assert m["moves"] in reports, (cell, name)
            assert callable(reader(m["reader"]).read)


def test_the_manifest_with_the_new_metrics_listed_stands():
    """The cells' own files list them, and the manifest built from the
    files is one the driver's limits accept."""
    assert manifest.main() == 0
    built = manifest.build()
    assert manifest.check(built) == []
    listed = {m["name"]: m["workloads"] for m in built["per_layer"]}
    assert listed["commit.cpu_share"] == ["basic-5000n.drain", "spread-5000n.drain"]
    assert listed["queue.wait_p99_ms"] == ["basic-5000n.trickle"]
    assert len(built["per_layer"]) == 9 + 17


@pytest.mark.parametrize("cell", sorted(ADDED))
def test_the_traced_rehearsal_reads_every_new_metric(capfd, cell):
    argv = ["--workload", cell, "--seed", "3000000019", "--seconds", "2"]
    assert run_spans.main(argv, rehearsal=True) == 0
    out, _err = capfd.readouterr()
    result = json.loads(out.strip().splitlines()[-1])
    assert result["correct"] is True and list(result)[-1] == "compared"
    assert result["device"]["platform"] == "cpu"
    for name in set(ADDED[cell]) - NEEDS_A_DEVICE_PLANE - NEEDS_THE_BLOCKED_LANE:
        assert name in result["metrics"], (name, sorted(result["metrics"]))
    spans = {name: events for name, events, _seconds in result["breakdown"]["host_spans"]}
    assert {"http.create", "http.create_decode", "informer.dispatch", "watch.deliver"} <= set(spans)
    lane = "sched.scan_dispatch" if cell.startswith("spread") else "sched.wave_dispatch"
    assert spans.get(lane, 0) >= 1, spans
    # the six CPU shares are shares of one core each, and no host layer is idle
    for name, m in result["metrics"].items():
        if name.endswith(".offcpu_share"):
            # two clocks of different grain: a span that never left the CPU can read a hair under 0
            assert -5.0 <= m["value"] <= 100.0, (name, m)
        elif name.endswith(".cpu_share"):
            assert 0.0 < m["value"] < 100.0 * 16, (name, m)
    clocks = {stem: (wall, cpu) for stem, wall, cpu in result["breakdown"]["span_clocks"]}
    assert {"http_create", "informer_dispatch", "sched_loop_handoff_wait"} <= set(clocks)
    assert all(wall >= 0 and cpu >= 0 for wall, cpu in clocks.values())
    if os.path.isdir("/proc/self/task"):
        assert sum(s for _name, s in result["breakdown"]["thread_cpu"]) > 0
    # a CPU has no device plane: nothing to cut, so no gaps and no clock check
    assert result["breakdown"]["idle_gaps"] == [] and result["breakdown"]["started_inside"] == {}
