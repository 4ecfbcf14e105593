"""``delete_many`` — the batch twin of ``delete`` — held to N calls of
``delete`` on a twin store: the same objects left, the same watch events
under the same rvs, the same history ring and its byte count, the same
per-node aggregates, a missing key answered as that item's ``KeyError``.
For the in-memory store, the durable one in both of its commit modes (and
after a replay of its log), the sharded router and ``RemoteStore``."""

from __future__ import annotations

import pytest

from minisched_tpu.api.objects import Event, ObjectMeta, make_node, make_pod
from minisched_tpu.controlplane.durable import DurableObjectStore
from minisched_tpu.controlplane.httpserver import start_api_server
from minisched_tpu.controlplane.remote import RemoteStore
from minisched_tpu.controlplane.shards import ShardedStore
from minisched_tpu.controlplane.store import ObjectStore
from tests.test_shards import NAMESPACES, TwoGroups

#: keys in the order they are deleted: bound and unbound pods, a key that
#: never was, one named twice; then Events (a volatile kind: the durable
#: store logs a bare rv for each)
POD_DELETES = [("default", name) for name in (
    "p0", "p3", "p4", "never-was", "p3", "p9")]
EVENT_DELETES = [("default", "e1"), ("default", "nope"), ("default", "e0")]


def _seed(store) -> None:
    """The same objects into any store, uids and creation times pinned
    so that twins agree byte for byte."""
    for n in range(3):
        node = make_node(f"n{n}")
        node.metadata.uid = f"node-{n}"
        node.metadata.creation_timestamp = 1.0
        store.create("Node", node)
    pods = []
    for i in range(10):
        pod = make_pod(f"p{i}")
        pod.metadata.uid = f"pod-{i}"
        pod.metadata.creation_timestamp = 2.0
        if i % 2 == 0:
            pod.spec.node_name = f"n{i % 3}"
        pods.append(pod)
    assert not any(
        isinstance(r, BaseException) for r in store.create_many("Pod", pods))
    for i in range(3):
        store.create("Event", Event(metadata=ObjectMeta(
            name=f"e{i}", uid=f"event-{i}", creation_timestamp=3.0)))


def _watch_all(store):
    return {k: store.watch(k, send_initial=False)[0] for k in ("Pod", "Event")}


def _drained(watches):
    out = {}
    for kind, w in watches.items():
        got = []
        while True:
            batch = w.next_batch(timeout=0.05)
            if not batch:
                break
            got.extend(batch)
        out[kind] = [(e.type, e.obj.metadata.key, e.rv) for e in got]
    return out


def _one_by_one(store, kind, keys):
    out = []
    for namespace, name in keys:
        try:
            out.append(store.delete(kind, namespace, name))
        except KeyError as err:
            out.append(err)
    return out


def _state(store):
    return {
        "rv": store.resource_version,
        "objects": {
            kind: sorted(
                (o.metadata.key, o.metadata.resource_version, o.metadata.uid)
                for o in store.list(kind)
            )
            for kind in ("Node", "Pod", "Event")
        },
        "ring": {
            kind: [(ev.type, ev.obj.metadata.key, ev.rv, cost)
                   for ev, cost in store._history.get(kind, ())]
            for kind in ("Pod", "Event")
        },
        "history_stats": {k: store.history_stats(k) for k in ("Pod", "Event")},
        "node_agg": {k: tuple(v) for k, v in store._pod_node_agg.items()},
        "plane": {
            kind: sorted(store.read_plane().maps.get(kind, {}))
            for kind in ("Pod", "Event")
        },
    }


def _shape(results):
    return [type(r) if isinstance(r, BaseException) else r for r in results]


def _make(flavour, tmp_path, name, monkeypatch):
    if flavour == "memory":
        return ObjectStore()
    monkeypatch.setenv(
        "MINISCHED_GROUP_COMMIT", "0" if flavour == "durable-inline" else "1")
    return DurableObjectStore(str(tmp_path / f"{name}.wal"))


@pytest.mark.parametrize(
    "flavour", ["memory", "durable-group-commit", "durable-inline"])
def test_delete_many_is_n_deletes(flavour, tmp_path, monkeypatch):
    batch = _make(flavour, tmp_path, "batch", monkeypatch)
    twin = _make(flavour, tmp_path, "twin", monkeypatch)
    if flavour != "memory":
        assert batch._gc_enabled == (flavour == "durable-group-commit")
    for s in (batch, twin):
        _seed(s)
    wb, wt = _watch_all(batch), _watch_all(twin)

    got = batch.delete_many("Pod", POD_DELETES)
    want = _one_by_one(twin, "Pod", POD_DELETES)
    assert _shape(got) == _shape(want) == [
        None, None, None, KeyError, KeyError, None]
    got = batch.delete_many("Event", EVENT_DELETES)
    want = _one_by_one(twin, "Event", EVENT_DELETES)
    assert _shape(got) == _shape(want) == [None, KeyError, None]
    assert batch.delete_many("Pod", []) == []

    events = _drained(wb)
    assert events == _drained(wt)
    assert [e[1] for e in events["Pod"]] == [
        "default/p0", "default/p3", "default/p4", "default/p9"]
    rvs = [e[2] for e in events["Pod"]]
    assert rvs == list(range(rvs[0], rvs[0] + 4))  # rising, in the order given
    state = _state(batch)
    assert state == _state(twin)
    # p2 and p8 on n2, p6 on n0; n1 lost its only pod and its row with it
    assert {n: a[2] for n, a in state["node_agg"].items()} == {"n0": 1, "n2": 2}
    assert "default/p0" not in state["plane"]["Pod"]  # the plane followed

    if flavour == "memory":
        return
    # ... and the log replays to the same store either way
    for s in (batch, twin):
        s.close()
    rb = DurableObjectStore(str(tmp_path / "batch.wal"))
    rt = DurableObjectStore(str(tmp_path / "twin.wal"))
    try:
        sb, st = _state(rb), _state(rt)
        assert sb == st
        assert sb["rv"] == state["rv"]
        assert sb["objects"]["Pod"] == state["objects"]["Pod"]
        assert sb["objects"]["Event"] == []  # volatile
        assert sb["node_agg"] == state["node_agg"]
        assert [r[:3] for r in sb["ring"]["Pod"]] == \
            [r[:3] for r in state["ring"]["Pod"]]
    finally:
        rb.close()
        rt.close()


def test_durable_delete_many_is_refused_before_memory_moves(tmp_path):
    """Record before visibility: a batch whose append the disk refuses
    never happened — nothing deleted, no event, aggregates as they were."""
    from minisched_tpu.controlplane.store import StorageDegraded
    from minisched_tpu.faults import FaultFabric

    store = DurableObjectStore(
        str(tmp_path / "s.wal"), probe_interval_s=3600.0)
    _seed(store)
    watches = _watch_all(store)
    before = _state(store)
    store.faults = FaultFabric(3).on(
        "disk.enospc", rate=1.0, after=0, max_fires=1)
    with pytest.raises(StorageDegraded):
        store.delete_many("Pod", [("default", "p0"), ("default", "p1")])
    after = _state(store)
    for key in ("objects", "ring", "node_agg", "plane"):
        assert after[key] == before[key], key
    assert _drained(watches) == {"Pod": [], "Event": []}
    store.close()


def test_sharded_and_remote_stores_answer_the_same_call():
    """The router deletes a key at a time at its namespace's owner; over
    either, the result list is the in-process store's."""
    by_owner: dict = {}
    planes = [TwoGroups(), TwoGroups()]
    routers = [
        ShardedStore(topology=p.topology.copy(), retries=2) for p in planes
    ]
    try:
        for ns in NAMESPACES:
            by_owner.setdefault(
                planes[0].topology.owner(ns or "default"), ns or "default")
        assert set(by_owner) == {"g0", "g1"}
        spaces = [by_owner["g0"], by_owner["g1"]]
        for router in routers:
            for i in range(6):
                pod = make_pod(f"p{i}", namespace=spaces[i % 2])
                pod.metadata.uid = f"pod-{i}"
                pod.metadata.creation_timestamp = 2.0
                router.create("Pod", pod)
        keys = [(spaces[i % 2], f"p{i}") for i in (4, 1, 0)]
        keys.insert(2, (spaces[0], "never-was"))
        watches = [
            {g: s.watch("Pod", send_initial=False)[0]
             for g, s in p.stores.items()}
            for p in planes
        ]
        got = routers[0].delete_many("Pod", keys)
        want = _one_by_one(routers[1], "Pod", keys)
        assert _shape(got) == _shape(want) == [None, None, KeyError, None]
        for g in ("g0", "g1"):
            a, b = planes[0].stores[g], planes[1].stores[g]
            assert _drained({"Pod": watches[0][g]}) == \
                _drained({"Pod": watches[1][g]})
            for s in (a, b):
                assert not any(k in s._objects["Pod"] for k in (
                    f"{ns}/{name}" for ns, name in keys))
            assert sorted(
                (o.metadata.key, o.metadata.resource_version)
                for o in a.list("Pod")
            ) == sorted(
                (o.metadata.key, o.metadata.resource_version)
                for o in b.list("Pod")
            )
            assert a.history_stats("Pod") == b.history_stats("Pod")
        left = {o.metadata.key for o in routers[0].list("Pod")}
        assert left == {f"{spaces[i % 2]}/p{i}" for i in (2, 3, 5)}
    finally:
        for r in routers:
            r.close()
        for p in planes:
            p.close()


def test_remote_store_delete_many_over_the_facade():
    store = ObjectStore()
    _seed(store)
    _server, base, shutdown = start_api_server(store)
    remote = RemoteStore(base, retries=0)
    try:
        got = remote.delete_many(
            "Pod", [("default", "p1"), ("default", "gone"), ("default", "p2")])
        assert _shape(got) == [None, KeyError, None]
        got = remote.delete_many("Event", [("default", "e2")])
        assert got == [None]
        assert {p.metadata.name for p in store.list("Pod")} == {
            f"p{i}" for i in range(10) if i not in (1, 2)}
        assert [e.metadata.name for e in store.list("Event")] == ["e0", "e1"]
        # the same list the in-process store gives for the same call
        assert _shape(store.delete_many(
            "Pod", [("default", "p1"), ("default", "p3")])) == [KeyError, None]
    finally:
        remote.close()
        shutdown()
