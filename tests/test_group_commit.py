"""Group-commit WAL pipeline (ISSUE 13): the off-lock durability path.

The tentpole moved WAL IO out from under the store lock: a mutation
validates and reserves its rv under a short hold, stages its framed
record, and parks on a commit barrier; a leader-elected caller drains
the stage under the IO lock, writes every pending frame in ONE buffered
write (+ one fsync when armed), then publishes the group — in-memory
apply and watch fanout in strict rv order — before any waiter is acked.

This file owns the pipeline's direct contracts; the chaos suites
(test_disk_chaos / test_proc_chaos) own its failure atomicity under
injected ENOSPC and SIGKILL.  The throughput claim has no owner until
a durable cell exists (ROADMAP R9).
"""

from __future__ import annotations

import os
import threading

import pytest

from minisched_tpu.api.objects import make_node, make_pod
from minisched_tpu.controlplane.durable import DurableObjectStore
from minisched_tpu.controlplane.store import Conflict
from minisched_tpu.observability import counters, hist

N_WRITERS = 8
PER_WRITER = 25


def _concurrent_creates(store, n_writers=N_WRITERS, per=PER_WRITER):
    gate = threading.Barrier(n_writers)
    errs: list = []

    def worker(w: int) -> None:
        try:
            gate.wait()
            for i in range(per):
                store.create("Pod", make_pod(f"p{w:02d}-{i:03d}"))
        except BaseException as e:  # pragma: no cover - surfaced below
            errs.append(e)

    threads = [
        threading.Thread(target=worker, args=(w,)) for w in range(n_writers)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errs, errs
    return n_writers * per


def test_concurrent_creates_coalesce_and_replay(tmp_path):
    """The core claim: concurrent singleton mutations share barriers
    (groups < records, fsyncs saved), every ack is durable (reopen
    agrees exactly), and the rv sequence is dense — the WAL byte order
    IS the rv order."""
    path = str(tmp_path / "gc.wal")
    store = DurableObjectStore(path, fsync=True)
    counters.reset()
    n = _concurrent_creates(store)
    assert counters.get("storage.group_commit.records") == n
    groups = counters.get("storage.group_commit.groups")
    assert 0 < groups < n, f"no coalescing: {groups} groups for {n}"
    assert counters.get("storage.group_commit.fsyncs_saved") == n - groups
    rvs = sorted(p.metadata.resource_version for p in store.list("Pod"))
    assert rvs == list(range(1, n + 1))
    store.close()
    re = DurableObjectStore(path)
    assert len(re.list("Pod")) == n
    assert re.resource_version == n
    re.close()


def test_kill_switch_restores_per_mutation_path(tmp_path, monkeypatch):
    """MINISCHED_GROUP_COMMIT=0 is the exact pre-pipeline path: no
    group counters move, no staging structures fill, and the same
    workload produces the same replayable state."""
    monkeypatch.setenv("MINISCHED_GROUP_COMMIT", "0")
    path = str(tmp_path / "off.wal")
    store = DurableObjectStore(path, fsync=True)
    assert not store._gc_enabled
    counters.reset()
    n = _concurrent_creates(store)
    assert counters.get("storage.group_commit.groups") == 0
    assert counters.get("storage.group_commit.records") == 0
    assert not store._gc_stage and not store._gc_pending
    rvs = sorted(p.metadata.resource_version for p in store.list("Pod"))
    assert rvs == list(range(1, n + 1))
    store.close()
    re = DurableObjectStore(path)
    assert len(re.list("Pod")) == n
    re.close()


def test_watch_fanout_order_matches_rv_order(tmp_path):
    """Fanout happens at group PUBLISH, in strict rv order — a watcher
    opened before a concurrent burst sees every event exactly once,
    rvs strictly ascending, nothing delivered before its barrier."""
    store = DurableObjectStore(str(tmp_path / "w.wal"))
    w, _snap = store.watch("Pod", send_initial=False)
    n = _concurrent_creates(store, n_writers=6, per=20)
    got: list = []
    while len(got) < n:
        ev = w.next(timeout=5.0)
        assert ev is not None, f"watch starved at {len(got)}/{n}"
        got.append(ev.rv)
    assert got == sorted(got)
    assert got == list(range(1, n + 1))
    w.stop()
    store.close()


def test_visible_rv_lags_reservations(tmp_path):
    """list_with_rv and watch snapshots stamp the PUBLISHED rv, never a
    reserved-but-unwritten one — after quiesce the two agree."""
    store = DurableObjectStore(str(tmp_path / "v.wal"))
    _concurrent_creates(store, n_writers=4, per=10)
    objs, rv = store.list_with_rv("Pod")
    assert rv == store.resource_version == 40
    assert len(objs) == 40
    w, snap = store.watch("Pod")
    assert len(snap) == 40
    assert w.start_rv == rv  # nothing promised that was not delivered
    w.stop()
    store.close()


def test_expected_rv_cas_decided_at_reservation(tmp_path):
    """CAS conflicts are decided under the reservation lock, not at the
    barrier: of N concurrent updates against the same expected_rv,
    exactly one wins — the rest get a typed Conflict, not a phantom
    double-apply."""
    store = DurableObjectStore(str(tmp_path / "cas.wal"))
    pod = store.create("Pod", make_pod("contested"))
    n_w = 8
    results: list = [None] * n_w
    gate = threading.Barrier(n_w)

    def worker(i: int) -> None:
        work = pod.clone()
        work.metadata.labels = {"winner": str(i)}
        try:
            gate.wait()
            results[i] = store.update(
                "Pod", work, expected_rv=pod.metadata.resource_version
            )
        except Conflict as e:
            results[i] = e

    threads = [
        threading.Thread(target=worker, args=(i,)) for i in range(n_w)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    winners = [r for r in results if not isinstance(r, Conflict)]
    assert len(winners) == 1, results
    final = store.get("Pod", "default", "contested")
    assert final.metadata.labels == winners[0].metadata.labels
    assert final.metadata.resource_version == 2
    store.close()


def test_mixed_ops_one_store_stay_ordered(tmp_path):
    """Creates, RMW mutates, and deletes interleaved across threads all
    ride the same barrier machinery and replay to the same state."""
    path = str(tmp_path / "mix.wal")
    store = DurableObjectStore(path, fsync=True)
    store.create("Node", make_node("n1"))
    for i in range(8):
        store.create("Pod", make_pod(f"base-{i}"))
    gate = threading.Barrier(3)
    errs: list = []

    def creates() -> None:
        gate.wait()
        for i in range(20):
            store.create("Pod", make_pod(f"extra-{i}"))

    def mutates() -> None:
        gate.wait()
        # base-4..7 only: base-0..3 are the delete thread's victims
        for i in range(20):
            def fn(p, i=i):
                p.metadata.labels = {"round": str(i)}
                return p
            store.mutate("Pod", "default", f"base-{4 + i % 4}", fn)

    def deletes() -> None:
        gate.wait()
        for i in range(4):
            store.delete("Pod", "default", f"base-{i}")

    def run(f) -> None:
        try:
            f()
        except BaseException as e:  # pragma: no cover - surfaced below
            errs.append(e)

    threads = [
        threading.Thread(target=run, args=(f,))
        for f in (creates, mutates, deletes)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errs, errs
    live = {p.metadata.name for p in store.list("Pod")}
    state = {
        p.metadata.name: (
            p.metadata.resource_version,
            dict(p.metadata.labels or {}),
        )
        for p in store.list("Pod")
    }
    store.close()
    re = DurableObjectStore(path)
    assert {p.metadata.name for p in re.list("Pod")} == live
    assert {
        p.metadata.name: (
            p.metadata.resource_version,
            dict(p.metadata.labels or {}),
        )
        for p in re.list("Pod")
    } == state
    re.close()


def test_group_wait_histogram_carries_exemplar(tmp_path):
    """Every waiter observes storage.group_wait_s with its object key as
    the exemplar — the p99 bucket names a pod, straight off /metrics."""
    hist.reset()
    store = DurableObjectStore(str(tmp_path / "h.wal"), fsync=True)
    n = _concurrent_creates(store, n_writers=4, per=5)
    store.close()
    child = hist.GLOBAL.get("storage.group_wait_s")
    assert child is not None and child.count == n
    assert child.exemplars, "no exemplar stamped on any bucket"
    keys = {key for key, _v in child.exemplars.values()}
    assert any(k.startswith("default/p") for k in keys), keys
    text = hist.render_prometheus(counters.Counters(), hist.GLOBAL)
    exs = hist.parse_exemplars(text)
    assert any(
        name == "storage_group_wait_seconds_bucket"
        and ex.get("key", "").startswith("default/p")
        for name, _labels, ex, _v in exs
    ), text
    hist.reset()


def test_single_threaded_caller_self_elects(tmp_path):
    """No concurrency → every mutation leads its own group of one; the
    sequential semantics (and errors) are exactly the old path's."""
    store = DurableObjectStore(str(tmp_path / "s.wal"))
    counters.reset()
    store.create("Pod", make_pod("solo"))
    with pytest.raises(KeyError):
        store.get("Pod", "default", "missing")
    with pytest.raises(KeyError):
        store.delete("Pod", "default", "missing")
    with pytest.raises(Conflict):
        obj = store.get("Pod", "default", "solo").clone()
        store.update("Pod", obj, expected_rv=99)
    assert counters.get("storage.group_commit.groups") == 1
    assert counters.get("storage.group_commit.records") == 1
    assert counters.get("storage.group_commit.fsyncs_saved") == 0
    store.close()
