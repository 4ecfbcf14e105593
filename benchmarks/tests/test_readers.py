"""The reduction from traces and scrapes to metrics, on inputs with known
answers: intervals written by hand, a small trace recorded here, and two
Prometheus scrapes with known differences."""

import importlib

import pytest

import prom
import tracefile
from tracefile import DevicePlane, Trace

MS = 1_000_000  # ns


def reader(name):
    return importlib.import_module("readers." + name)


def hand_trace():
    """Window of 100 ms.  Ops: 0-10, 5-20 (overlap: union 20), a gap,
    50-60, 55-58 (inside), 90-100 -> busy 40 ms.  Two programs: A twice
    (10 ms and 20 ms), B once (30 ms)."""
    ops = [("fusion.1", 0, 10 * MS), ("fusion.2", 5 * MS, 15 * MS), ("copy", 50 * MS, 10 * MS),
           ("fusion.1", 55 * MS, 3 * MS), ("tail", 90 * MS, 10 * MS)]
    modules = [("jit_run(1)", 0, 10 * MS), ("jit_run(1)", 50 * MS, 20 * MS), ("jit_other(2)", 20 * MS, 30 * MS)]
    return Trace([DevicePlane("/device:TPU:0", ops, modules)], window_s=0.1)


def test_union_counts_overlap_once():
    assert tracefile.union_ns(hand_trace().devices[0].ops) == 40 * MS
    assert tracefile.busy_s(hand_trace()) == pytest.approx(0.040)


def test_trace_idle_share():
    assert reader("trace_idle").read({"trace": hand_trace()}) == pytest.approx(60.0)


def test_trace_idle_averages_over_devices():
    t = hand_trace()
    t.devices.append(DevicePlane("/device:TPU:1", [("x", 0, 80 * MS)], []))
    assert reader("trace_idle").read({"trace": t}) == pytest.approx(40.0)


@pytest.mark.parametrize("ctx", [{}, {"trace": None}, {"trace": Trace([], 1.0)}])
def test_trace_readers_return_nothing_without_a_device_plane(ctx):
    assert reader("trace_idle").read(ctx) is None


def test_top_ops_and_modules():
    t = hand_trace()
    # "copy" 50-60 holds "fusion.1" 55-58: a parent, left out
    assert tracefile.top_ops(t, 2) == [["fusion.2", pytest.approx(0.015)], ["fusion.1", pytest.approx(0.013)]]
    assert "copy" not in [name for name, _s in tracefile.top_ops(t)]
    assert tracefile.top_modules(t)[0] == ["jit_run(1)", 2, pytest.approx(0.030)]


def test_load_reads_a_recorded_trace(tmp_path):
    """A small trace recorded here (the CPU has no device plane: the loader
    must say so by finding none, and still list what the file holds)."""
    import jax
    import jax.numpy as jnp

    with jax.profiler.trace(str(tmp_path)):
        jax.jit(lambda x: (x @ x).sum())(jnp.ones((64, 64))).block_until_ready()
    t = tracefile.load(str(tmp_path), window_s=1.0)
    assert t.devices == [] or all(d.name.startswith("/device:") for d in t.devices)
    assert any(sum(lines.values()) > 0 for lines in t.layout.values())
    assert reader("trace_idle").read({"trace": t}) is None or jax.default_backend() != "cpu"


BEFORE = """
# TYPE sched_wave_build_seconds histogram
sched_wave_build_seconds_bucket{le="0.01"} 2
sched_wave_build_seconds_bucket{le="0.02"} 4
sched_wave_build_seconds_bucket{le="+Inf"} 4
sched_wave_build_seconds_sum 0.05
sched_wave_build_seconds_count 4
# TYPE sched_time_to_bind_seconds histogram
sched_time_to_bind_seconds_bucket{priority="0",le="0.1"} 10
sched_time_to_bind_seconds_bucket{priority="0",le="0.2"} 10
sched_time_to_bind_seconds_bucket{priority="0",le="0.4"} 10
sched_time_to_bind_seconds_bucket{priority="0",le="+Inf"} 10
sched_time_to_bind_seconds_sum{priority="0"} 0.5
sched_time_to_bind_seconds_count{priority="0"} 10
wave_parked 1
"""
AFTER = """
sched_wave_build_seconds_bucket{le="0.01"} 2
sched_wave_build_seconds_bucket{le="0.02"} 10
sched_wave_build_seconds_bucket{le="+Inf"} 14
sched_wave_build_seconds_sum 0.35
sched_wave_build_seconds_count 14
sched_time_to_bind_seconds_bucket{priority="0",le="0.1"} 60
sched_time_to_bind_seconds_bucket{priority="0",le="0.2"} 100
sched_time_to_bind_seconds_bucket{priority="0",le="0.4"} 110
sched_time_to_bind_seconds_bucket{priority="0",le="+Inf"} 110
sched_time_to_bind_seconds_bucket{priority="5",le="0.1"} 0
sched_time_to_bind_seconds_bucket{priority="5",le="0.2"} 0
sched_time_to_bind_seconds_bucket{priority="5",le="0.4"} 0
sched_time_to_bind_seconds_bucket{priority="5",le="+Inf"} 0
sched_time_to_bind_seconds_sum{priority="0"} 12.5
sched_time_to_bind_seconds_count{priority="0"} 110
sched_wave_stall_seconds_sum 0.5
sched_wave_stall_seconds_count 3
wave_parked 3
"""


@pytest.fixture
def scrapes():
    return {"before": prom.parse(BEFORE), "after": prom.parse(AFTER), "window_s": 2.0}


def test_prom_totals_and_buckets(scrapes):
    assert prom.total(scrapes["after"], "wave_parked") - prom.total(scrapes["before"], "wave_parked") == 2
    assert prom.total(scrapes["before"], "never_registered") == 0
    assert prom.buckets(scrapes["after"], "sched_time_to_bind_seconds")[0.2] == 100


def test_prom_reads_a_label_value_that_holds_a_brace_and_stops_before_an_exemplar():
    """The façade labels a request by the shape of its path."""
    text = 'http_request_seconds_count{route="pod/{name}",verb="DELETE"} 7\nhttp_request_seconds_sum{route="pod/{name}",verb="DELETE"} 0.014\n'
    text += 'sched_time_to_bind_seconds_bucket{priority="0",le="0.0256"} 182 # {key="default/pod-{1}"} 0.0005\n'
    samples = prom.parse(text)
    assert prom.total(samples, "http_request_seconds_count", {"verb": "DELETE", "route": "pod/{name}"}) == 7
    assert prom.buckets(samples, "sched_time_to_bind_seconds") == {0.0256: 182.0}  # the value, not the exemplar's
    assert reader("hist_mean").read(
        {"before": [], "after": samples}, histogram="http_request_seconds", labels={"route": "pod/{name}"}
    ) == pytest.approx(2.0)


def test_hist_mean(scrapes):
    # (0.35 - 0.05) s over 10 waves = 30 ms
    assert reader("hist_mean").read(scrapes, histogram="sched_wave_build_seconds") == pytest.approx(30.0)
    assert reader("hist_mean").read(scrapes, histogram="sched_wave_commit_seconds") is None
    same = dict(scrapes, before=scrapes["after"])
    assert reader("hist_mean").read(same, histogram="sched_wave_build_seconds") is None
    # by label: priority 0 took (12.5 - 0.5) s over 100 binds; priority 5 saw none
    read = reader("hist_mean").read
    assert read(scrapes, histogram="sched_time_to_bind_seconds", labels={"priority": "0"}) == pytest.approx(120.0)
    assert read(scrapes, histogram="sched_time_to_bind_seconds", labels={"priority": "5"}) is None


def test_hist_quantile(scrapes):
    read = reader("hist_quantile").read
    # window: 50 in (0, 0.1], 40 in (0.1, 0.2], 10 in (0.2, 0.4]; 100 in all
    assert read(scrapes, histogram="sched_time_to_bind_seconds", q=50) == pytest.approx(100.0)
    assert read(scrapes, histogram="sched_time_to_bind_seconds", q=70) == pytest.approx(150.0)
    assert read(scrapes, histogram="sched_time_to_bind_seconds", q=99) == pytest.approx(380.0)
    assert read(scrapes, histogram="sched_wave_commit_seconds", q=99) is None


def test_client_record_and_cycle_mean():
    ctx = {"client": {"late_ms": {"p99": 1.25}}}
    assert reader("client_record").read(ctx, record="late_ms", field="p99") == 1.25
    assert reader("client_record").read(ctx, record="bind_ms", field="p99") is None
    ctx = {
        "cycle_before": {"scan_evaluate": {"count": 2, "total_s": 1.0}},
        "cycle_after": {"scan_evaluate": {"count": 6, "total_s": 3.0}},
    }
    assert reader("cycle_mean").read(ctx, phase="scan_evaluate") == pytest.approx(500.0)
    assert reader("cycle_mean").read(ctx, phase="scan_grouping") is None
    assert reader("cycle_mean").read({"cycle_before": ctx["cycle_after"], "cycle_after": ctx["cycle_after"]}, phase="scan_evaluate") is None
