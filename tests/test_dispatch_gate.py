"""The informers' dispatch gate under the wave engine's closing rule.

A bind (``DeviceScheduler._bind_batch``) closes the gate only where a next
host stretch is there for the hold to protect: ready pods in the queue, a
batch the build worker holds, or scan work (a backlog, or a lane running).
Every close is followed by a device call that reopens it.  With none of
the three the bind leaves the gate as it found it, so a pod that arrives
after a wave emptied the queue reaches it at once instead of waiting at a
closed gate for the build worker's empty 0.5 s pop.  The bind never opens
the gate.  ``sched.gate_closed`` / ``sched.gate_left_open`` count the two
outcomes and ``informer.gate_wait_expired`` the timed waits that ran out.
"""

from __future__ import annotations

import time

import pytest

from minisched_tpu.api.objects import make_node, make_pod
from minisched_tpu.controlplane.client import Client
from minisched_tpu.observability import counters
from minisched_tpu.service.config import default_full_roster_config
from minisched_tpu.service.service import SchedulerService

from test_pod_path import _values, spied
from test_spans import _spread_pod

GATE = ("sched.gate_closed", "sched.gate_left_open", "informer.gate_wait_expired")


def _wait(pred, timeout=120.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(0.005)
    return pred()


def _nodes(client, n=4):
    client.nodes().create_many(
        [
            make_node(
                f"n{i}", labels={"zone": f"z{i % 3}"},
                capacity={"cpu": "32", "memory": "64Gi", "pods": 110},
            )
            for i in range(n)
        ],
        return_objects=False,
    )


def _gate_open(sched) -> bool:
    return sched.informer_factory.informer_for("Pod")._gate.is_set()


def _engine(max_wave=16):
    """A loop-less wave engine over 6 roomy nodes: (client, factory, sched)."""
    from minisched_tpu.controlplane.informer import SharedInformerFactory
    from minisched_tpu.engine.device_scheduler import new_device_scheduler

    client = Client()
    factory = SharedInformerFactory(client.store)
    sched = new_device_scheduler(
        client, factory, default_full_roster_config(time_scale=0.01),
        max_wave=max_wave,
    )
    factory.start()
    assert factory.wait_for_cache_sync()
    _nodes(client, 6)
    assert _wait(lambda: len(sched.cache.snapshot()) == 6)
    return client, factory, sched


def _delta(before):
    return {k: counters.get(k) - before[k] for k in GATE}


def test_a_trickle_pod_never_waits_at_a_gate_the_last_bind_closed():
    """The trickle's shape: each pod is created just after the bind of the
    one before left the queue empty, while the build worker waits on it.
    No bind closes the gate, so every pod reaches the handlers at once:
    ``informer.pod_add_lag_s`` and every ``informer.gate_wait`` under
    0.1 s (a closed gate held each such pod until the worker's 0.5 s pop
    ran out)."""
    client = Client()
    svc = SchedulerService(client)
    svc.start_scheduler(
        default_full_roster_config(time_scale=0.01), device_mode=True,
        max_wave=16,
    )
    pods = client.pods()

    def bound(name):
        return _wait(lambda: pods.get(name).spec.node_name != "")

    try:
        _nodes(client)
        pods.create(make_pod("warm", requests={"cpu": "100m"}))
        assert bound("warm")  # the wave program compiled
        time.sleep(0.6)  # the worker is back on the empty queue
        before = {k: counters.get(k) for k in GATE}
        with spied() as spy:
            for i in range(6):
                pods.create(make_pod(f"t{i}", requests={"cpu": "100m"}))
                assert bound(f"t{i}")
        delta = _delta(before)
    finally:
        svc.shutdown_scheduler()
    lags = _values(spy, "informer.pod_add_lag_s")
    waits = [w for name, w in spy["spans"] if name == "informer.gate_wait"]
    assert len(lags) == 6 and max(lags) < 0.1, lags
    assert not waits or max(waits) < 0.1, waits
    assert delta == {
        "sched.gate_closed": 0, "sched.gate_left_open": 6,
        "informer.gate_wait_expired": 0,
    }


@pytest.mark.parametrize("pending", ["queue", "worker", "backlog"])
def test_a_bind_closes_the_gate_for_a_next_stretch(pending):
    """The drain's shape: two waves of 4 from 8 queued pods.  At the first
    wave's bind the next stretch is the 4 pods still ready in the queue,
    or the build worker that popped and built them, or (with only 4 pods)
    a constrained pod the wave deferred to the scan backlog: the bind
    closes the gate and counts it.  The next wave's device call reopens
    it; that wave's bind finds nothing next and leaves it open."""
    from minisched_tpu.engine.pipeline import WavePipeline

    client, factory, sched = _engine()
    at_device = []
    evaluate = sched._eval_packed_wave

    def spy(*args):
        at_device.append(_gate_open(sched))
        return evaluate(*args)

    sched._eval_packed_wave = spy
    n = 5 if pending == "backlog" else 8
    plain = [make_pod(f"p{i}", requests={"cpu": "100m"}) for i in range(n - 1)]
    last = _spread_pod("c0") if pending == "backlog" else make_pod("p7")
    client.pods().create_many(plain + [last], return_objects=False)
    assert _wait(lambda: sched.queue.ready_count() == n)
    first = sched.queue.pop_batch(4 if pending != "backlog" else n, timeout=1.0)
    pipe = None
    try:
        if pending == "worker":
            pipe = sched._pipeline = WavePipeline(sched)
            pipe.start()
            assert _wait(lambda: pipe.holds_batch and sched.queue.ready_count() == 0)
        before = {k: counters.get(k) for k in GATE}
        sched.schedule_wave(first)
        assert not _gate_open(sched)
        assert _delta(before)["sched.gate_closed"] == 1
        if pending == "backlog":
            assert [q.pod.metadata.name for q in sched._scan_backlog] == ["c0"]
            assert at_device == [True]
            return
        if pending == "worker":
            item = pipe.get(timeout=120.0)
            assert item[0] == "wave" and not pipe.holds_batch
            sched._run_prepared_wave(item[1])
        else:
            sched.schedule_wave(sched.queue.pop_batch(4, timeout=1.0))
        assert at_device == [True, True]
        assert _gate_open(sched)
        assert _delta(before) == {
            "sched.gate_closed": 1, "sched.gate_left_open": 1,
            "informer.gate_wait_expired": 0,
        }
        assert _wait(lambda: sum(1 for p in client.pods().list() if p.spec.node_name) == 8)
    finally:
        if pipe is not None:
            pipe.stop()
        sched._pipeline = None
        sched._scan_backlog = []
        sched.stop()
        factory.shutdown()


def test_a_bind_inside_a_scan_flush_leaves_the_gate_closed():
    """A blocked flush of 40 pods of one zone-spread service (narrow calls):
    with the queue empty and no worker, every bind of the flush still
    closes the gate (a lane is running) and none opens it; the flush
    reopens it on its way out."""
    client, factory, sched = _engine(max_wave=64)
    after_bind = []
    bind = sched._bind_batch

    def spy(ready, lane=None):
        bind(ready, lane)
        after_bind.append((lane, _gate_open(sched)))

    sched._bind_batch = spy
    try:
        client.pods().create_many(
            [_spread_pod(f"s{i}") for i in range(40)], return_objects=False
        )
        assert _wait(lambda: sched.queue.ready_count() == 40)
        sched.schedule_wave(sched.queue.pop_batch(64, timeout=1.0))
        assert len(sched._scan_backlog) == 40 and not after_bind
        before = {k: counters.get(k) for k in GATE}
        sched._flush_scan_backlog_timed()
        assert after_bind and all(not is_open for _lane, is_open in after_bind)
        assert {lane for lane, _ in after_bind} <= {"narrow", "wide", "exact"}
        assert _delta(before)["sched.gate_closed"] == len(after_bind)
        assert _delta(before)["sched.gate_left_open"] == 0
        assert _gate_open(sched)
        assert _wait(lambda: sum(1 for p in client.pods().list() if p.spec.node_name) == 40)
    finally:
        sched.stop()
        factory.shutdown()


def test_the_gate_counters_are_at_zero_from_construction():
    """A reader of /metrics tells a rule that never engaged from a program
    that has no such counter."""
    from minisched_tpu.controlplane.informer import SharedInformerFactory
    from minisched_tpu.engine.device_scheduler import new_device_scheduler
    from minisched_tpu.observability.hist import render_prometheus

    counters.reset()
    client = Client()
    factory = SharedInformerFactory(client.store)
    new_device_scheduler(client, factory, default_full_roster_config(), max_wave=8)
    assert {k: counters.snapshot().get(k) for k in GATE} == dict.fromkeys(GATE, 0)
    text = render_prometheus()
    for k in GATE:
        assert f"\n{k.replace('.', '_')} 0\n" in text
