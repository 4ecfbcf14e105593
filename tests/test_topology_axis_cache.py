"""The node side of the constraint build kept while the node roster stands
(models/constraints ``TopoAxisCache``): the node index and each topology
key's planes, derived once a roster version
(``CachedNodeTableBuilder.roster_epoch``) and read by every build after.

Held to the from-scratch build bit for bit, over a sequence of rosters and
key sets; to the loop that derived the axis before it was cached; and
through the device engine's scan lane, where a relabelled node has to move
what the next call may place.  Everything here is on the CPU.
"""

from __future__ import annotations

import copy
import time
from typing import Any, Dict, List, Optional

import numpy as np
import pytest

from minisched_tpu.api.objects import (
    Affinity,
    LabelSelector,
    LabelSelectorRequirement,
    PodAffinity,
    PodAffinityTerm,
    PodAntiAffinity,
    TopologySpreadConstraint,
    make_node,
    make_pod,
)
from minisched_tpu.framework.nodeinfo import build_node_infos
from minisched_tpu.models.constraint_index import ConstraintIndex
from minisched_tpu.models.constraints import (
    MAX_DOMAINS,
    SCAN_ELIDE_GROUPS,
    TopoAxisCache,
    _axis_planes,
    _key_axis,
    build_constraint_tables,
)
from minisched_tpu.models.tables import CachedNodeTableBuilder, pad_to
from minisched_tpu.observability import counters

HOST = "kubernetes.io/hostname"
ZONE = "zone"
N_CAP = 128


def _node(i: int, zone: Optional[str] = "auto"):
    labels = {HOST: f"n{i:03d}"}
    if zone == "auto":
        zone = None if i % 7 == 6 else f"z{i % 3}"  # a few nodes lack the zone
    if zone is not None:
        labels[ZONE] = zone
    node = make_node(f"n{i:03d}", labels=labels)
    node.metadata.resource_version = 1
    return node


def _term(app: str, key: str) -> PodAffinityTerm:
    return PodAffinityTerm(
        label_selector=LabelSelector(
            match_expressions=[LabelSelectorRequirement("app", "In", [app])]
        ),
        topology_key=key,
    )


def _pod(name: str, kind: str, node: str = ""):
    """``green``: one a host (required anti-affinity under the hostname);
    ``spread``: a DoNotSchedule zone spread; ``cache`` / ``web``: the
    colocate deployment's tiers; ``plain``: no cross-pod term."""
    app = {"green": "green", "spread": "blue", "cache": "store", "web": "web-store"}.get(kind, "plain")
    pod = make_pod(name, labels={"app": app})
    if kind in ("green", "cache"):
        pod.spec.affinity = Affinity(pod_anti_affinity=PodAntiAffinity(required=[_term(app, HOST)]))
    elif kind == "web":
        pod.spec.affinity = Affinity(
            pod_affinity=PodAffinity(required=[_term("store", HOST)]),
            pod_anti_affinity=PodAntiAffinity(required=[_term("web-store", HOST)]),
        )
    elif kind == "spread":
        pod.spec.topology_spread_constraints = [
            TopologySpreadConstraint(
                max_skew=1, topology_key=ZONE, when_unsatisfiable="DoNotSchedule",
                label_selector=LabelSelector(match_labels={"app": "blue"}),
            )
        ]
    pod.spec.node_name = node
    pod.metadata.uid = f"default/{name}"
    return pod


def _columns(packed) -> Dict[str, np.ndarray]:
    """A PackedTable's columns, sliced on the host (its metas say how)."""
    out, off = {}, 0
    for name, kind, shape in packed.metas:
        size = int(np.prod(shape))
        out[name] = np.asarray(packed.flat[off : off + size]).reshape(shape)
        off += size
    return out


def _assert_same(a, b) -> None:
    assert a.metas == b.metas and a.zero_metas == b.zero_metas
    ca, cb = _columns(a), _columns(b)
    for name in ca:  # key by key, plane by plane
        assert ca[name].shape == cb[name].shape, name
        for k in range(ca[name].shape[0] if ca[name].ndim > 1 else 1):
            pa = ca[name][k] if ca[name].ndim > 1 else ca[name]
            pb = cb[name][k] if cb[name].ndim > 1 else cb[name]
            assert np.array_equal(pa, pb), (name, k)


SCAN_KW = dict(scan_planes=True, device=False, elide_zeros=False, elide_groups=SCAN_ELIDE_GROUPS)
WAVE_KW = dict(scan_planes=False, device=False)


class _Roster:
    """Nodes, the placed pods' index and a table builder, as the engine
    holds them: each build reads the roster version off the builder."""

    def __init__(self, nodes: List[Any], placed: List[Any]):
        self.placed = placed
        self.builder = CachedNodeTableBuilder(device_static=True)
        self.cache = TopoAxisCache()
        self.set_nodes(nodes)

    def set_nodes(self, nodes: List[Any]) -> None:
        self.nodes = sorted(nodes, key=lambda n: n.metadata.name)
        self.index = ConstraintIndex()
        by_name = {n.metadata.name: n for n in self.nodes}
        self.index._node_get = by_name.get
        for p in self.placed:
            if p.spec.node_name in by_name:
                self.index.add_pod(p)

    def build_both(self, pending: List[Any], owners: bool = True, **kw):
        """(through the cache, from scratch) for one build; ``owners``
        False builds as if nothing were placed, so that pods with no term
        make a build with no key."""
        infos = build_node_infos(self.nodes, [])
        _static, agg, _names = self.builder.build_packed(infos, capacity=N_CAP)
        kw = dict(
            kw, pod_capacity=32, node_capacity=agg.capacity,
            index=self.index if owners else ConstraintIndex(),
        )
        cached = build_constraint_tables(
            pending, self.nodes, (), axis_cache=self.cache,
            roster=self.builder.roster_epoch(), **kw,
        )
        scratch = build_constraint_tables(pending, self.nodes, (), **kw)
        return cached, scratch


def _moved(before: Dict[str, int]) -> Dict[str, int]:
    now = counters.snapshot()
    return {
        k: now.get(k, 0) - before.get(k, 0)
        for k in counters.AXIS_COUNTERS
        if now.get(k, 0) != before.get(k, 0)
    }


def test_the_cached_build_is_the_from_scratch_build_over_a_sequence_of_rosters():
    """The same roster twice; a node relabelled (its resource_version
    bumped); a node added and one removed; a zone key and a hostname key;
    a build with no keys between two with one, as the mixed drain's wave
    and scan lanes alternate.  Every build is the from-scratch one bit for
    bit, and the counters say when the axis was derived."""
    nodes = [_node(i) for i in range(40)]
    placed = [_pod(f"own{i}", "green", node=f"n{i:03d}") for i in range(0, 40, 3)]
    placed += [_pod(f"sp{i}", "spread", node=f"n{i:03d}") for i in range(1, 40, 5)]
    roster = _Roster(nodes, placed)
    greens = [_pod(f"g{i}", "green") for i in range(5)]
    mixed = greens[:3] + [_pod(f"s{i}", "spread") for i in range(4)]
    plain = [_pod(f"p{i}", "plain") for i in range(6)]

    def step(pending, kw, expect, owners=True):
        before = counters.snapshot()
        cached, scratch = roster.build_both(pending, owners, **kw)
        _assert_same(cached, scratch)
        assert _moved(before) == expect

    built, reused = "constraints.axis_built", "constraints.axis_reused"
    step(greens, SCAN_KW, {built: 1})
    step(greens, SCAN_KW, {reused: 1})  # the same roster
    step(greens, WAVE_KW, {reused: 1})
    # one node relabelled: a new resource_version, a new roster
    moved = copy.deepcopy(nodes[4])
    moved.metadata.labels[ZONE] = "z9"
    moved.metadata.labels[HOST] = "n000"  # joins an owner's host domain
    moved.metadata.resource_version = 2
    nodes[4] = moved
    roster.set_nodes(nodes)
    step(greens, SCAN_KW, {built: 1})
    step(mixed, SCAN_KW, {built: 1, reused: 1})  # the zone key is new
    # a build with no key, between two with both: nothing derived
    step(plain, WAVE_KW, {}, owners=False)
    step(plain, SCAN_KW, {}, owners=False)
    step(mixed, SCAN_KW, {reused: 2})
    # a node added, one removed
    nodes = [n for n in nodes if n.metadata.name != "n007"] + [_node(57, zone="z1")]
    roster.set_nodes(nodes)
    step(mixed, SCAN_KW, {built: 2})
    step(greens, WAVE_KW, {reused: 1})
    step(mixed, SCAN_KW, {reused: 2})


def _old_topo_key_axis(keys, nodes, N):
    """The loop that derived the axis on every build before it was
    cached: the reference for what the cache holds."""
    K = pad_to(max(len(keys), 1), 4)
    topo_domain = np.full((K, N), MAX_DOMAINS, np.int32)
    topo_onehot = np.zeros((K, MAX_DOMAINS, N), bool)
    unique = np.zeros(K, bool)
    val_id = np.full((K, N), -1, np.int32)
    values: List[Dict[str, int]] = []
    for k, key in enumerate(keys):
        vals: Dict[str, int] = {}
        per_node: List[Optional[int]] = []
        for node in nodes:
            v = node.metadata.labels.get(key)
            if v is not None and v not in vals:
                vals[v] = len(vals)
            per_node.append(None if v is None else vals[v])
        n_keyed = sum(1 for v in per_node if v is not None)
        unique[k] = len(vals) == n_keyed and len(vals) > 0
        for i, dom in enumerate(per_node):
            if dom is None:
                continue
            val_id[k, i] = dom
            topo_domain[k, i] = 0 if unique[k] else dom
            if not unique[k]:
                topo_onehot[k, dom, i] = True
        values.append(vals)
    return topo_domain, topo_onehot, unique, val_id, values


@pytest.mark.parametrize("keys", [(), (ZONE,), (HOST,), (HOST, ZONE), ("absent", ZONE)])
def test_the_axis_is_the_loop_it_replaced(keys):
    nodes = [_node(i) for i in range(50)] + [_node(50, zone=None)]
    domain, onehot, unique, val_id, values = _old_topo_key_axis(keys, nodes, N_CAP)
    axes = [_key_axis(k, nodes, N_CAP) for k in keys]
    got = _axis_planes(axes, N_CAP)
    assert np.array_equal(got[0], domain) and np.array_equal(got[1], onehot)
    assert np.array_equal(got[2], unique)
    for k, ax in enumerate(axes):
        assert np.array_equal(ax.val_id, val_id[k]) and ax.values == values[k]


def test_a_cached_plane_refuses_a_write():
    cache = TopoAxisCache()
    nodes = [_node(i) for i in range(20)]
    axes, planes = cache.axes(1, nodes, (HOST, ZONE), N_CAP)
    for arr in (*planes, axes[0].val_id, axes[0].domain, axes[1].onehot):
        with pytest.raises(ValueError, match="read-only"):
            arr[0] = 1
    # a build over the same roster and keys reads those very planes
    tables = build_constraint_tables(
        [_pod("s", "spread"), _pod("g", "green")], nodes, (),
        node_capacity=N_CAP, axis_cache=cache, roster=1,
    )
    assert np.array_equal(np.asarray(tables.topo_onehot), planes[1])
    assert cache.axes(1, nodes, (HOST, ZONE), N_CAP)[1] is planes


def test_the_domain_refusal_raises_on_every_cached_build():
    """A key of more than MAX_DOMAINS values that is not one a node
    raises on the from-scratch path, and on every build through the
    cache: a refused axis is never kept."""
    nodes = [_node(i, zone=f"z{i // 2}") for i in range(2 * (MAX_DOMAINS + 1))]
    pending = [_pod("s", "spread")]
    cache = TopoAxisCache()
    with pytest.raises(ValueError, match="MAX_DOMAINS"):
        build_constraint_tables(pending, nodes, (), node_capacity=256)
    before = counters.snapshot()
    for _ in range(2):
        with pytest.raises(ValueError, match="MAX_DOMAINS"):
            build_constraint_tables(
                pending, nodes, (), node_capacity=256, axis_cache=cache, roster=7
            )
    assert _moved(before) == {}
    # the same nodes under the hostname key are fine (unique a node)
    build_constraint_tables(
        [_pod("g", "green")], nodes, (), node_capacity=256, axis_cache=cache, roster=7
    )
    assert _moved(before) == {"constraints.axis_built": 1}


# -- through the device engine ---------------------------------------------


def _wait(cond, seconds: float = 180.0) -> None:
    t_end = time.monotonic() + seconds
    while not cond():
        assert time.monotonic() < t_end, "the engine never got there"
        time.sleep(0.05)


def _bound(client, names) -> Dict[str, str]:
    return {n: client.pods().get(n).spec.node_name for n in names}


@pytest.mark.parametrize("shape", ["antiaffinity", "colocate"])
def test_the_scan_lane_reuses_the_axis_and_follows_a_relabelled_node(shape):
    """64 hosts, one required anti-affinity term under the hostname key
    (``antiaffinity``: green pods; ``colocate``: caches, then webs that may
    go only beside one).  Two scan calls on one roster: the second reuses
    the axis.  Then every free host but one is relabelled into an occupied
    host's domain: of the next two pods of the first tier, one binds, on
    that host, and one finds none; an axis kept from before the relabel
    would have placed both."""
    from minisched_tpu.controlplane.client import Client
    from minisched_tpu.service.config import default_full_roster_config
    from minisched_tpu.service.service import SchedulerService

    first, second = ("green", "green") if shape == "antiaffinity" else ("cache", "web")
    fresh = counters.Counters()
    real, counters.GLOBAL = counters.GLOBAL, fresh
    try:
        client = Client()
        svc = SchedulerService(client)
        svc.start_scheduler(default_full_roster_config(), device_mode=True, max_wave=128)
        try:
            snap = fresh.snapshot()
            assert snap["constraints.axis_built"] == 0 and snap["constraints.axis_reused"] == 0
            for i in range(64):
                client.nodes().create(_node(i, zone=None))
            one = [f"a{i}" for i in range(8)]
            for n in one:
                client.pods().create(_pod(n, first))
            _wait(lambda: all(_bound(client, one).values()))
            built = fresh.get("constraints.axis_built")
            assert built >= 1
            reused = fresh.get("constraints.axis_reused")
            two = [f"b{i}" for i in range(8)]
            for n in two:
                client.pods().create(_pod(n, second))
            _wait(lambda: all(_bound(client, two).values()))
            assert fresh.get("constraints.axis_built") == built  # one roster
            assert fresh.get("constraints.axis_reused") > reused
            hosts = _bound(client, one + two)
            if shape == "colocate":  # a web beside each cache
                assert sorted(hosts[n] for n in two) == sorted(hosts[n] for n in one)
            else:
                assert len(set(hosts.values())) == 16
            free = [f"n{i:03d}" for i in range(64) if f"n{i:03d}" not in set(hosts.values())]
            keep, into = free[0], hosts[one[0]]
            for name in free[1:]:
                node = copy.deepcopy(client.nodes().get(name))
                node.metadata.labels[HOST] = into
                client.nodes().update(node)
            engine = svc.scheduler
            _wait(lambda: sum(
                ni.node.metadata.labels[HOST] == into for ni in engine.cache.snapshot()
            ) == len(free))
            three = ["c0", "c1"]
            for n in three:
                client.pods().create(_pod(n, first))
            _wait(lambda: fresh.get("sched.unschedulable_pods") >= 1 and any(_bound(client, three).values()))
            got = _bound(client, three)
            assert sorted(got.values()) == ["", keep], got
            assert fresh.get("constraints.axis_built") > built
        finally:
            svc.shutdown_scheduler()
    finally:
        counters.GLOBAL = real
