"""The events broadcaster (controlplane/client.EventRecorder): every
decision lands as one ``Event`` object, a batch at a time.

The writer thread takes whatever has piled up and lands it as one store
transaction (one ``delete_many`` of what passes the cap, then one
``create_many``).  What a reader of the store or a watcher of the kind can
see is held here to what a ``create`` and a ``delete`` an event gave: the
names, the fields, rvs that rise in emit order, every ``ADDED`` and every
``DELETED``, never more than the cap — and the work is counted, not timed:
at most two swaps of the read plane a batch.
"""

from __future__ import annotations

import threading
import time

import pytest

from minisched_tpu.api.objects import Event, ObjectMeta, make_pod
from minisched_tpu.controlplane.client import KIND_EVENT, EventRecorder
from minisched_tpu.controlplane.store import (
    EventType,
    ObjectStore,
    StorageDegraded,
)
from minisched_tpu.observability import counters, hist


class GatedStore:
    """An ``ObjectStore`` behind a gate: while the gate is shut the
    writer's first transaction waits, so what is emitted meanwhile piles
    up and is taken as ONE batch when the gate opens.  It also notes what
    the kind holds after every transaction — each is atomic under the
    store's lock, so these are all the states a reader can see — and lets
    a test answer a call or an item with an exception."""

    def __init__(self, **kwargs):
        self.inner = ObjectStore(**kwargs)
        self.gate = threading.Event()
        self.gate.set()
        self.sizes = []  # Events in the store after each transaction
        self.calls = []  # (op, number of items)
        self.create_raises = []  # exceptions for the next create_many calls
        self.delete_raises = []  # ... and the next delete_many calls
        self.refuse = {}  # event name -> exception returned for that item

    def _done(self, op, n):
        self.calls.append((op, n))
        self.sizes.append(len(self.inner._objects.get(KIND_EVENT, {})))

    def create_many(self, kind, objs, return_objects=True):
        self.gate.wait()
        if self.create_raises:
            raise self.create_raises.pop(0)
        ok = [o for o in objs if o.metadata.name not in self.refuse]
        results = iter(self.inner.create_many(kind, ok, return_objects))
        out = [
            self.refuse[o.metadata.name] if o.metadata.name in self.refuse
            else next(results)
            for o in objs
        ]
        self._done("create_many", len(objs))
        return out

    def delete_many(self, kind, keys):
        self.gate.wait()
        if self.delete_raises:
            raise self.delete_raises.pop(0)
        out = self.inner.delete_many(kind, keys)
        self._done("delete_many", len(keys))
        return out

    def names(self):
        evs = self.inner.list(KIND_EVENT)
        evs.sort(key=lambda e: e.metadata.resource_version)
        return [e.metadata.name for e in evs]


def _emit(rec, lo, hi):
    for i in range(lo, hi):
        rec.eventf(
            make_pod(f"pod-{i}"),
            "Normal" if i % 3 else "Warning",
            "Scheduled" if i % 3 else "FailedScheduling",
            f"decision {i}",
        )


def _pile_up(rec, store, n):
    """``n`` decisions enqueued while the writer is held at the gate
    behind one primer decision: the primer is a batch of 1, the ``n`` are
    taken together when the gate opens."""
    store.gate.clear()
    rec.eventf(None, "Normal", "SchedulerStarted", "scheduler starting")
    deadline = time.monotonic() + 5.0
    while rec._q.qsize() and time.monotonic() < deadline:
        time.sleep(0.001)  # the writer takes the primer, then waits
    assert rec._q.qsize() == 0
    _emit(rec, 0, n)
    assert rec._q.qsize() == n


def _counts():
    return {k: counters.get(k) for k in
            ("events.written", "events.trimmed", "events.batches")}


@pytest.mark.parametrize("cap", [4, 2048])
@pytest.mark.parametrize("n", [1, 7, 2048, 5000])
def test_every_decision_lands_as_one_event_in_emit_order(n, cap):
    store = GatedStore(watch_queue_events=1 << 20)
    watch, initial = store.inner.watch(KIND_EVENT)
    assert initial == []
    before = _counts()
    rec = EventRecorder(store=store, max_events=cap)
    try:
        _pile_up(rec, store, n)
        store.gate.set()
        rec.flush(timeout=60.0)
        total = n + 1  # the primer
        assert rec._q.empty() and rec._landed == total

        # the watcher opened before: every ADDED in emit order, every
        # DELETED oldest first, the whole stream in rv order
        seen = []
        while len(seen) < total + max(total - cap, 0):
            got = watch.next_batch(timeout=5.0)
            assert got, f"watch ran dry at {len(seen)} events"
            seen.extend(got)
        assert watch.next_batch(timeout=0.05) == []
        rvs = [ev.rv for ev in seen]
        assert rvs == sorted(rvs) and len(set(rvs)) == len(rvs)
        assert all(
            ev.obj.metadata.resource_version == ev.rv
            for ev in seen if ev.type is EventType.ADDED
        )
        added = [ev.obj for ev in seen if ev.type is EventType.ADDED]
        deleted = [ev.obj for ev in seen if ev.type is EventType.DELETED]
        want_names = ["scheduler.1"] + [
            f"pod-{i}.{i + 2:x}" for i in range(n)
        ]
        assert [e.metadata.name for e in added] == want_names
        assert [e.metadata.name for e in deleted] == \
            want_names[: max(total - cap, 0)]
        for i, evt in enumerate(added[1:]):
            assert evt.metadata.namespace == "default"
            assert evt.metadata.uid and evt.metadata.creation_timestamp > 0
            assert evt.type == ("Normal" if i % 3 else "Warning")
            assert evt.reason == (
                "Scheduled" if i % 3 else "FailedScheduling")
            assert evt.message == f"decision {i}"
            assert evt.regarding == f"default/pod-{i}"
            assert evt.reporting_controller == "minisched-tpu"
        assert len({e.metadata.uid for e in added}) == total

        # the store: the newest ``cap`` of them, under their names, and
        # never more than the cap after any transaction
        assert store.names() == want_names[-cap:]
        assert max(store.sizes) <= cap
        # one trim and one create a batch, no batch longer than the cap
        creates = [c for c in store.calls if c[0] == "create_many"]
        assert [c[1] for c in creates[:2]] == [1, min(n, cap)]
        assert all(c[1] <= cap for c in creates)
        assert sum(c[1] for c in creates) == total
        pieces = 1 + -(-n // cap)
        assert len(creates) == pieces
        after = _counts()
        assert after["events.written"] - before["events.written"] == total
        assert after["events.trimmed"] - before["events.trimmed"] == \
            max(total - cap, 0)
        assert after["events.batches"] - before["events.batches"] == pieces
        # the in-process record, filled before eventf returned
        assert len(rec.events) == min(total, cap)
        assert rec.events[-1]["message"] == f"decision {n - 1}"
        assert rec.events[-1]["object"] == f"default/pod-{n - 1}"
    finally:
        store.gate.set()
        rec.close()
        watch.stop()


def test_a_batch_costs_at_most_two_publishes_of_the_read_plane():
    """Work, not time: 3,000 decisions behind a cap of 2,048 are two
    batches and at most four swaps of the kind's read plane (a create
    and a delete an event swapped it 5,904 times)."""
    store = GatedStore()
    published = []
    real = store.inner._cow_publish

    def counting(kinds):
        published.append(tuple(kinds))
        real(kinds)

    store.inner._cow_publish = counting
    rec = EventRecorder(store=store, max_events=2048)
    try:
        _pile_up(rec, store, 3000)
        store.gate.set()
        rec.flush(timeout=60.0)
        batches = [c for c in store.calls if c[0] == "create_many"]
        assert [c[1] for c in batches] == [1, 2048, 952]
        assert all(k == (KIND_EVENT,) for k in published)
        assert len(published) <= 2 * len(batches)
        assert len(store.inner.list(KIND_EVENT)) == 2048
    finally:
        store.gate.set()
        rec.close()


def test_counters_and_span_are_on_metrics_from_construction():
    counters.reset()
    hist.reset()
    rec = EventRecorder(store=ObjectStore())
    try:
        text = hist.render_prometheus()
        for line in (
            "events_written 0", "events_trimmed 0", "events_batches 0",
            "events_write_seconds_count 0",
            "events_write_cpu_seconds_count 0",
        ):
            assert line in text.splitlines(), line
        _emit(rec, 0, 3)
        rec.flush()
        assert counters.get("events.written") == 3
        assert 1 <= counters.get("events.batches") <= 3
        wall = hist.GLOBAL.get("events.write_s")
        cpu = hist.GLOBAL.get("events.write_cpu_s")
        assert wall.count == cpu.count == counters.get("events.batches")
    finally:
        rec.close()


def test_flush_waits_for_what_was_enqueued_before_it():
    store = GatedStore()
    rec = EventRecorder(store=store)
    try:
        _pile_up(rec, store, 5)
        t0 = time.monotonic()
        rec.flush(timeout=0.2)  # bounded: the gate is shut
        assert 0.15 < time.monotonic() - t0 < 2.0
        assert store.names() == []
        threading.Timer(0.1, store.gate.set).start()
        rec.flush(timeout=10.0)
        assert len(store.names()) == 6
    finally:
        store.gate.set()
        rec.close()


def test_close_drains_what_is_pending_and_ends_the_thread():
    """close() while decisions are still queued: its sentinel arrives
    inside their batch, everything lands, the thread ends."""
    store = GatedStore()
    rec = EventRecorder(store=store)
    writer = rec._writer
    _pile_up(rec, store, 10)
    closer = threading.Thread(target=rec.close, kwargs={"timeout": 10.0})
    closer.start()
    time.sleep(0.05)
    assert writer.is_alive()  # held at the gate, 10 still pending
    store.gate.set()
    closer.join(timeout=10.0)
    assert not closer.is_alive() and not writer.is_alive()
    assert rec._writer is None
    assert len(store.names()) == 11
    rec.close()  # idempotent

    # after close: the in-process record still fills, the store does not
    n = len(rec.events)
    rec.eventf(make_pod("late"), "Normal", "Scheduled", "after close")
    assert len(rec.events) == n + 1
    assert rec.events[-1]["object"] == "default/late"
    t0 = time.monotonic()
    rec.flush(timeout=5.0)
    assert time.monotonic() - t0 < 1.0
    assert len(store.names()) == 11


def test_recorder_without_a_store_keeps_the_record_alone():
    rec = EventRecorder(max_events=3)
    _emit(rec, 0, 5)
    assert [e["message"] for e in rec.events] == [
        "decision 2", "decision 3", "decision 4"]
    rec.flush()
    rec.close()


def test_an_item_the_store_refuses_is_that_items_loss_alone():
    store = GatedStore()
    # a name that is taken (KeyError) and an item shed by a degraded disk
    store.inner.create(
        KIND_EVENT, Event(metadata=ObjectMeta(name="pod-2.4")))
    store.refuse["pod-5.7"] = StorageDegraded("disk full")
    dropped = counters.get("storage.event_dropped_degraded")
    written = counters.get("events.written")
    rec = EventRecorder(store=store)
    try:
        _pile_up(rec, store, 8)
        store.gate.set()
        rec.flush(timeout=10.0)
        names = store.names()
        assert names == ["pod-2.4", "scheduler.1"] + [
            f"pod-{i}.{i + 2:x}" for i in range(8) if i not in (2, 5)]
        assert store.inner.get(KIND_EVENT, "default", "pod-2.4").message == ""
        assert counters.get("storage.event_dropped_degraded") == dropped + 1
        assert counters.get("events.written") == written + 7
        assert rec._writer.is_alive()
    finally:
        store.gate.set()
        rec.close()


def test_a_call_the_store_refuses_loses_that_batch_and_not_the_writer():
    store = GatedStore()
    dropped = counters.get("storage.event_dropped_degraded")
    rec = EventRecorder(store=store, max_events=4)
    try:
        _emit(rec, 0, 4)
        rec.flush()
        assert len(store.names()) == 4
        # the trim of the next batch is refused whole, then its create
        store.delete_raises.append(StorageDegraded("read-only"))
        store.create_raises.append(StorageDegraded("read-only"))
        _pile_up(rec, store, 0)  # one decision: the primer
        store.gate.set()
        rec.flush()
        assert counters.get("storage.event_dropped_degraded") == dropped + 1
        assert len(store.names()) == 4  # nothing went, nothing came
        assert rec._writer.is_alive()
        # the Event the refused trim left is first in line again
        _emit(rec, 4, 6)
        rec.flush()
        assert store.names() == [f"pod-{i}.{i + 1 if i < 4 else i + 2:x}"
                                 for i in range(2, 6)]
        # a store that raises anything at all: the writer never dies
        store.create_raises.append(RuntimeError("store closed"))
        _emit(rec, 6, 7)
        rec.flush()
        _emit(rec, 7, 8)
        rec.flush()
        assert rec._writer.is_alive()
        assert store.names()[-1] == "pod-7.9"
        assert len(store.names()) <= 4
    finally:
        store.gate.set()
        rec.close()


def test_a_degraded_durable_store_sheds_events_and_recovers(tmp_path):
    """The same through the real thing: a WAL whose appends fail (Events
    stage an rv watermark a piece) refuses the batch typed; the writer
    counts what it shed and writes again once the disk answers."""
    from minisched_tpu.controlplane.durable import DurableObjectStore
    from minisched_tpu.faults import FaultFabric

    store = DurableObjectStore(
        str(tmp_path / "store.wal"), probe_interval_s=0.01)
    rec = EventRecorder(store=store)
    try:
        _emit(rec, 0, 3)
        rec.flush()
        assert len(store.list(KIND_EVENT)) == 3
        dropped = counters.get("storage.event_dropped_degraded")
        store.faults = FaultFabric(7).on(
            "disk.enospc", rate=1.0, after=0, max_fires=1)
        _emit(rec, 3, 5)
        rec.flush()
        shed = counters.get("storage.event_dropped_degraded") - dropped
        assert 1 <= shed <= 2
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline:
            time.sleep(0.02)
            rec.eventf(None, "Normal", "Probe", "is the disk back")
            rec.flush()
            if any(e.reason == "Probe" for e in store.list(KIND_EVENT)):
                break
        assert any(e.reason == "Probe" for e in store.list(KIND_EVENT))
        assert rec._writer.is_alive()
    finally:
        rec.close()
        store.close()


def test_a_reader_never_sees_more_than_the_cap_while_the_writer_is_slow():
    """Lists taken while batches land (a writer slowed by a reader that
    holds the store's lock between its transactions) hold at most
    ``max_events`` + 1 Events."""
    store = ObjectStore()
    cap = 64
    rec = EventRecorder(store=store, max_events=cap)
    seen = []
    stop = threading.Event()

    def reader():
        while not stop.is_set():
            with store.locked():
                seen.append(len(store.list(KIND_EVENT)))
                time.sleep(0.0005)

    t = threading.Thread(target=reader)
    t.start()
    try:
        for lo in range(0, 2000, 100):
            _emit(rec, lo, lo + 100)
            time.sleep(0.002)
        rec.flush(timeout=30.0)
    finally:
        stop.set()
        t.join()
        rec.close()
    assert len(seen) > 20 and max(seen) <= cap + 1
    assert len(store.list(KIND_EVENT)) == cap


def test_many_emitters_lose_and_reorder_nothing():
    """Eight threads emit at once under a switch interval of 10 us: every
    decision gets one sequence number, lands once, and rvs rise with the
    sequence (the enqueue is under the lock that numbers it)."""
    import sys

    store = ObjectStore(watch_queue_events=1 << 20)
    watch, _ = store.watch(KIND_EVENT)
    cap, threads, each = 256, 8, 1500
    rec = EventRecorder(store=store, max_events=cap)
    before = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        workers = [
            threading.Thread(target=_emit, args=(rec, t * each, (t + 1) * each))
            for t in range(threads)
        ]
        for w in workers:
            w.start()
        for w in workers:
            w.join(timeout=60.0)
        assert not any(w.is_alive() for w in workers)
        rec.flush(timeout=60.0)
    finally:
        sys.setswitchinterval(before)
        rec.close()
    total = threads * each
    assert rec._landed == rec._seq == total
    seen = []
    while len(seen) < 2 * total - cap:
        got = watch.next_batch(timeout=5.0)
        assert got, f"watch ran dry at {len(seen)} events"
        seen.extend(got)
    watch.stop()
    added = [ev for ev in seen if ev.type is EventType.ADDED]
    seqs = [int(ev.obj.metadata.name.rsplit(".", 1)[1], 16) for ev in added]
    assert seqs == list(range(1, total + 1))  # none lost, none twice, in order
    assert [ev.rv for ev in seen] == sorted(ev.rv for ev in seen)
    assert {ev.obj.regarding for ev in added} == {
        f"default/pod-{i}" for i in range(total)}
    assert len(store.list(KIND_EVENT)) == cap
