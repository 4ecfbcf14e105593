"""The façade's ``_DeleteCombiner``: single-object ``DELETE``s that arrive
together land as one ``store.delete_many`` a kind, each answered with the
outcome ``store.delete`` would have given it (PERF.md section 6, PR 35)."""

from __future__ import annotations

import threading
import time

import pytest

from minisched_tpu.api.objects import make_node, make_pod
from minisched_tpu.controlplane.httpserver import _DeleteCombiner, start_api_server
from minisched_tpu.controlplane.remote import RemoteClient
from minisched_tpu.controlplane.store import ObjectStore, StorageDegraded


def _store(n=24):
    store = ObjectStore()
    store.create_many("Pod", [make_pod(f"p{i:02d}") for i in range(n)])
    return store


class _Gate:
    """``delete_many`` held at a gate: what arrives meanwhile queues."""

    def __init__(self, store):
        self.real, self.batches = store.delete_many, []
        self.entered, self.open = threading.Event(), threading.Event()
        store.delete_many = self

    def __call__(self, kind, keys):
        self.batches.append((kind, list(keys)))
        self.entered.set()
        assert self.open.wait(10)
        return self.real(kind, keys)


def _run(combiner, requests):
    """Each request on a thread of its own; returns name -> outcome."""
    out = {}

    def one(kind, name):
        try:
            combiner.delete(kind, "default" if kind == "Pod" else "", name)
            out[name] = None
        except Exception as err:  # noqa: BLE001 — the outcome under test
            out[name] = err

    threads = [threading.Thread(target=one, args=r) for r in requests]
    for t in threads:
        t.start()
    return threads, out


def test_requests_that_queue_behind_a_leader_land_as_one_transaction():
    store = _store()
    gate = _Gate(store)
    combiner = _DeleteCombiner(store)
    first, out = _run(combiner, [("Pod", "p00")])
    assert gate.entered.wait(10)  # p00 leads and is inside the store
    later, out2 = _run(combiner, [("Pod", f"p{i:02d}") for i in range(1, 9)] + [("Pod", "nope")])
    deadline = time.monotonic() + 10
    while len(combiner._queued) < 9 and time.monotonic() < deadline:
        time.sleep(0.01)
    gate.open.set()
    for t in first + later:
        t.join(10)
    assert [len(keys) for _kind, keys in gate.batches] == [1, 9]  # the leader's own, then all that queued
    assert out == {"p00": None}
    assert {n: type(o) for n, o in out2.items()} == {**{f"p{i:02d}": type(None) for i in range(1, 9)}, "nope": KeyError}
    assert sorted(p.metadata.name for p in store.list("Pod")) == [f"p{i:02d}" for i in range(9, 24)]
    assert combiner._queued == [] and combiner._led is False  # nobody leads an empty queue


def test_kinds_are_transactions_of_their_own_and_a_refused_one_answers_every_request_of_it():
    store = _store(4)
    store.create("Node", make_node("n0"))
    gate = _Gate(store)
    combiner = _DeleteCombiner(store)
    first, _ = _run(combiner, [("Pod", "p00")])
    assert gate.entered.wait(10)
    real = gate.real

    def refusing(kind, keys):
        if ("default", "p01") in keys:
            raise StorageDegraded("the log cannot append")
        return real(kind, keys)

    gate.real = refusing
    later, out = _run(combiner, [("Pod", "p01"), ("Pod", "p02"), ("Node", "n0")])
    deadline = time.monotonic() + 10
    while len(combiner._queued) < 3 and time.monotonic() < deadline:
        time.sleep(0.01)
    gate.open.set()
    for t in first + later:
        t.join(10)
    assert {n: type(o) for n, o in out.items()} == {"p01": StorageDegraded, "p02": StorageDegraded, "n0": type(None)}
    assert store.list("Node") == [] and len(store.list("Pod")) == 3  # p00's own transaction went through


@pytest.mark.parametrize("deleters", [1, 8])
def test_over_the_wire_every_delete_is_answered_once_the_pod_is_gone(deleters):
    """Through the façade as the benchmark's deleters send them: 200 and
    gone, a second ``DELETE`` of the same pod 404 (``KeyError``)."""
    store = _store(40)
    _srv, base, stop = start_api_server(store)
    try:
        names = [f"p{i:02d}" for i in range(40)]
        todo, mu, errors = iter(names), threading.Lock(), []

        def deleter():
            api = RemoteClient(base).pods()
            while True:
                with mu:
                    name = next(todo, None)
                if name is None:
                    return
                try:
                    api.delete(name)
                    assert all(p.metadata.name != name for p in store.list("Pod"))
                except Exception as err:  # noqa: BLE001
                    errors.append(err)

        threads = [threading.Thread(target=deleter) for _ in range(deleters)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(30)
        assert errors == [] and store.list("Pod") == []
        with pytest.raises(KeyError):
            RemoteClient(base).pods().delete("p00")
    finally:
        stop()
