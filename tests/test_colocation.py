"""Required pod affinity in the forward direction (a pending pod may go only
where a pod matching its term already is) and the symmetric score of placed
pods' required affinity terms, on the shape of the deployment
``benchmarks/configs/colocate-host-5000n.json``: a cache tier (``app=store``,
one a host by required anti-affinity) and a web tier (``app=web-store``, one
a host, and only on a host that holds a cache), every term a set-based
selector (``app In (...)``) under the hostname key.

The scalar ``InterPodAffinity`` filter and score and the sequential oracle
(``schedule_pods_sequentially``) are the statement of the semantics; the
exact scan and the narrow blocked scan (a pod a step) have to place pod for
pod what they place.  Everything here is seeded and on the CPU.
"""

from __future__ import annotations

import random
import time

import numpy as np
import pytest

from minisched_tpu.api.objects import (
    Affinity,
    LabelSelector,
    LabelSelectorRequirement,
    Pod,
    PodAffinity,
    PodAffinityTerm,
    PodAntiAffinity,
    make_node,
    make_pod,
)
from minisched_tpu.controlplane.checkpoint import _decode, _encode
from minisched_tpu.controlplane.client import Client
from minisched_tpu.engine.scheduler import schedule_pods_sequentially
from minisched_tpu.framework.nodeinfo import build_node_infos
from minisched_tpu.framework.types import CycleState
from minisched_tpu.models.constraint_index import ConstraintIndex
from minisched_tpu.models.constraints import HARD_POD_AFFINITY_WEIGHT, build_constraint_tables
from minisched_tpu.models.tables import build_node_table, build_pod_table
from minisched_tpu.observability import counters
from minisched_tpu.ops.fused import BatchContext
from minisched_tpu.ops.sequential import BlockedSequentialScheduler, SequentialScheduler
from minisched_tpu.plugins.interpodaffinity import InterPodAffinity
from minisched_tpu.plugins.noderesources import (
    NodeResourcesBalancedAllocation,
    NodeResourcesFit,
    NodeResourcesLeastAllocated,
)
from minisched_tpu.plugins.nodeunschedulable import NodeUnschedulable

HOST = "kubernetes.io/hostname"
N_NODES = 48
CPUS = ("100m", "250m", "500m")
MEMS = ("256Mi", "500Mi", "1Gi")


def _nodes(n=N_NODES):
    return [
        make_node(f"n{i:03d}", capacity={"cpu": "4", "memory": "32Gi", "pods": 110}, labels={HOST: f"n{i:03d}"})
        for i in range(n)
    ]


def _app_in(app):
    return PodAffinityTerm(
        label_selector=LabelSelector(match_expressions=[LabelSelectorRequirement("app", "In", [app])]),
        topology_key=HOST,
    )


def _pod(name, tier, node="", cpu="100m", mem="500Mi", terms=True):
    """A cache or a web as the deployment sends it; ``terms=False`` is the
    same pod with no affinity at all."""
    pod = make_pod(name, requests={"cpu": cpu, "memory": mem}, labels={"app": "store" if tier == "cache" else "web-store"})
    if terms and tier == "cache":
        pod.spec.affinity = Affinity(pod_anti_affinity=PodAntiAffinity(required=[_app_in("store")]))
    elif terms:
        pod.spec.affinity = Affinity(
            pod_affinity=PodAffinity(required=[_app_in("store")]),
            pod_anti_affinity=PodAntiAffinity(required=[_app_in("web-store")]),
        )
    pod.spec.node_name = node
    pod.metadata.uid = f"default/{name}"
    return pod


def _cluster(seed):
    """(nodes, placed pods, pending pods) of one seeded case: caches on a
    quarter of the hosts, webs beside most of them, two orphan webs (their
    cache deleted), and a backlog of both tiers in a random order with
    random requests."""
    rng = random.Random(seed)
    nodes = _nodes()
    names = [n.metadata.name for n in nodes]
    rng.shuffle(names)
    cache_hosts, orphan_hosts = names[:12], names[12:14]
    placed = [_pod(f"c{i:02d}", "cache", node=h) for i, h in enumerate(cache_hosts)]
    placed += [_pod(f"w{i:02d}", "web", node=h) for i, h in enumerate(cache_hosts[:8] + orphan_hosts)]
    pending = [
        _pod(f"p{i:02d}", rng.choice(["cache", "web", "web"]), cpu=rng.choice(CPUS), mem=rng.choice(MEMS))
        for i in range(28)
    ]
    return nodes, placed, pending


def _chains():
    ipa = InterPodAffinity()
    return (
        [NodeUnschedulable(), NodeResourcesFit(), ipa],
        [ipa],
        [NodeResourcesLeastAllocated(), NodeResourcesBalancedAllocation(), ipa],
    )


def _by_node(placed):
    by_node = {}
    for p in placed:
        by_node.setdefault(p.spec.node_name, []).append(p)
    return by_node


def _indexed(nodes, placed):
    """A ConstraintIndex that has seen ``placed`` through its handlers."""
    index = ConstraintIndex()
    index._node_get = {n.metadata.name: n for n in nodes}.get
    for p in placed:
        index.add_pod(p)
    return index


def _scan(lane, nodes, placed, pending, through_index=False):
    """Node names the exact scan, or the narrow blocked scan (a pod a
    step), gives the pending pods over the placed ones."""
    chains = _chains()
    node_table, names = build_node_table(nodes, _by_node(placed))
    pod_table, _ = build_pod_table(pending, capacity=128)
    extra = build_constraint_tables(
        pending, nodes, () if through_index else placed, pod_capacity=128, node_capacity=node_table.capacity,
        index=_indexed(nodes, placed) if through_index else None,
    )
    if lane == "exact":
        _, choice, _ = SequentialScheduler(*chains)(pod_table, node_table, extra)
    else:
        _, choice, _, accepted = BlockedSequentialScheduler(*chains, block_size=1)(pod_table, node_table, extra)
        # a pod a block: no capacity race to refuse a placement
        assert all(ok or c < 0 for ok, c in zip(accepted.tolist(), choice.tolist()[: len(pending)]))
    return [names[c] if c >= 0 else "" for c in choice.tolist()[: len(pending)]]


def _oracle(nodes, placed, pending):
    return schedule_pods_sequentially(*_chains(), {}, pending, build_node_infos(nodes, placed))


def _tiers_hold(nodes, placed, pending, got):
    """The deployment's guarantees over the placements: one cache and one
    web a host at most, and every web on a host with a cache."""
    tier = {p.metadata.name: p.metadata.labels["app"] for p in placed + pending}
    on = [(p.metadata.name, p.spec.node_name) for p in placed]
    on += [(p.metadata.name, node) for p, node in zip(pending, got) if node]
    for app in ("store", "web-store"):
        hosts = [node for name, node in on if tier[name] == app]
        assert len(hosts) == len(set(hosts)), app
    caches = {node for name, node in on if tier[name] == "store"}
    return all(node in caches for p, node in zip(pending, got) if node and tier[p.metadata.name] == "web-store")


@pytest.mark.parametrize("through_index", [False, True])
@pytest.mark.parametrize("lane", ["exact", "narrow"])
@pytest.mark.parametrize("seed", [4100, 4101, 4102])
def test_the_scan_lanes_place_like_the_sequential_oracle(seed, lane, through_index):
    """Seeded orders and requests over both tiers: pod for pod what the
    scalar plugin chain places with every bind applied in between, from the
    walk over the placed pods and from the index's aggregates."""
    nodes, placed, pending = _cluster(seed)
    want = _oracle(nodes, placed, pending)
    assert _scan(lane, nodes, placed, pending, through_index) == want
    assert _tiers_hold(nodes, placed, pending, want)
    webs = [node for p, node in zip(pending, want) if p.metadata.labels["app"] == "web-store"]
    assert sum(bool(n) for n in webs) >= 4  # the forward term admits some hosts


@pytest.mark.parametrize("lane", ["exact", "narrow"])
def test_a_web_is_admitted_by_a_cache_committed_earlier_in_the_same_call(lane):
    """No cache is placed: a web alone finds no host, and a cache ahead of
    it in the same call admits it to the cache's host, through the combo
    planes the scan carries from step to step."""
    nodes = _nodes(8)
    assert _oracle(nodes, [], [_pod("w", "web")]) == [""] == _scan(lane, nodes, [], [_pod("w", "web")])
    pending = [_pod("c", "cache"), _pod("w", "web")]
    want = _oracle(nodes, [], pending)
    assert want[0] and want[1] == want[0]
    assert _scan(lane, nodes, [], pending) == want


@pytest.mark.parametrize("lane", ["exact", "narrow"])
def test_an_orphan_webs_host_draws_the_next_cache(lane):
    """A web left alone on its host (its cache deleted) scores that host up
    for an incoming cache through its required term
    (``HARD_POD_AFFINITY_WEIGHT`` on ``rev_weight``), against the resource
    scores that favour the empty hosts: the cache goes there.  The same web
    without the term leaves the cache to the resource scores."""
    nodes = _nodes(8)
    orphan = [_pod("w", "web", node="n005", cpu="500m", mem="1Gi")]
    extra = build_constraint_tables([_pod("c", "cache")], nodes, orphan)
    rev = np.asarray(extra.rev_weight)
    assert rev.any(axis=1).sum() == 1 and rev[:, 5].max() == HARD_POD_AFFINITY_WEIGHT
    want = _oracle(nodes, orphan, [_pod("c", "cache")])
    assert want == ["n005"] == _scan(lane, nodes, orphan, [_pod("c", "cache")])
    plain = [_pod("w", "web", node="n005", cpu="500m", mem="1Gi", terms=False)]
    elsewhere = _oracle(nodes, plain, [_pod("c", "cache")])
    assert elsewhere != ["n005"] and elsewhere == _scan(lane, nodes, plain, [_pod("c", "cache")])


def _scalar_feasible(pods, nodes, placed):
    ipa = InterPodAffinity()
    infos = build_node_infos(nodes, placed)
    out = np.zeros((len(pods), len(nodes)), bool)
    for i, pod in enumerate(pods):
        state = CycleState()
        assert ipa.pre_filter(state, pod, infos).is_success()
        out[i] = [ipa.filter(state, pod, ni).is_success() for ni in infos]
    return out


@pytest.mark.parametrize("through_index", [False, True])
@pytest.mark.parametrize("seed", [4110, 4111])
def test_the_wave_lane_batch_filter_is_the_scalar_filter(seed, through_index):
    """A wave build (``scan_planes=False``) over ``match_expressions``
    selectors: node for node what the scalar filter gives, from the walk
    and from the index."""
    nodes, placed, pending = _cluster(seed)
    node_table, _ = build_node_table(nodes, _by_node(placed))
    pod_table, _ = build_pod_table(pending)
    extra = build_constraint_tables(
        pending, nodes, () if through_index else placed,
        pod_capacity=pod_table.capacity, node_capacity=node_table.capacity,
        scan_planes=False, index=_indexed(nodes, placed) if through_index else None,
    )
    got = np.asarray(InterPodAffinity().batch_filter(BatchContext(), pod_table, node_table, extra))
    want = _scalar_feasible(pending, nodes, placed)
    assert np.array_equal(got[: len(pending), : len(nodes)], want)
    webs = [i for i, p in enumerate(pending) if p.metadata.labels["app"] == "web-store"]
    assert 0 < want[webs].sum() < want[webs].size  # the forward term bites, and leaves room


def test_set_based_terms_survive_the_wire():
    """What the façade writes and reads back is the term that was sent."""
    web = _pod("w", "web")
    back = _decode(Pod, _encode(web))
    assert back.spec.affinity == web.spec.affinity
    assert back.spec.affinity.pod_affinity.required[0].label_selector.match_expressions[0].values == ["store"]


def _moved(before):
    return {
        k: v - before.get(k, 0)
        for k, v in counters.snapshot().items()
        if v != before.get(k, 0) and k.startswith("scan.aff_")
    }


def test_the_affinity_counters_equal_hand_counts():
    """A scan build counts the nodes where its one distinct forward
    required term already matches (three cache hosts, whatever the webs
    placed) and that term x real nodes; a wave build counts nothing."""
    nodes = _nodes(10)
    placed = [_pod(f"c{i}", "cache", node=f"n00{i}") for i in range(3)]
    placed += [_pod("w0", "web", node="n000"), _pod("w9", "web", node="n009")]
    pending = [_pod("p0", "web"), _pod("p1", "web"), _pod("p2", "cache")]
    before = counters.snapshot()
    build_constraint_tables(pending, nodes, placed, scan_planes=False)
    assert _moved(before) == {}
    build_constraint_tables(pending, nodes, placed)
    assert _moved(before) == {"scan.aff_nodes": 3, "scan.aff_capacity": 10}
    # caches alone carry no forward term
    before = counters.snapshot()
    build_constraint_tables([_pod("p2", "cache")], nodes, placed[:3])
    assert _moved(before) == {}
    assert {"scan.aff_nodes", "scan.aff_capacity"} <= set(counters.LANE_COUNTERS)


def _wait(cond, seconds=120.0):
    t_end = time.monotonic() + seconds
    while not cond():
        assert time.monotonic() < t_end, "the engine never got there"
        time.sleep(0.05)


def test_a_web_with_no_cache_host_waits_and_the_next_caches_bind_moves_it_back():
    """Through a device engine: a web sent before any cache finds no host,
    is counted in ``sched.unschedulable_pods`` and parks; a cache's bind
    moves it back and it binds beside that cache."""
    from minisched_tpu.service.config import default_full_roster_config
    from minisched_tpu.service.service import SchedulerService

    fresh = counters.Counters()
    real, counters.GLOBAL = counters.GLOBAL, fresh
    try:
        client = Client()
        svc = SchedulerService(client)
        svc.start_scheduler(default_full_roster_config(), device_mode=True, max_wave=128)
        try:
            for n in _nodes(6):
                client.nodes().create(n)
            client.pods().create(_pod("w", "web"))
            _wait(lambda: fresh.get("sched.unschedulable_pods") >= 1)
            assert not client.pods().get("w").spec.node_name
            client.pods().create(_pod("c", "cache"))
            _wait(lambda: client.pods().get("w").spec.node_name != "")
            cache = client.pods().get("c")
            assert cache.spec.node_name and client.pods().get("w").spec.node_name == cache.spec.node_name
            assert fresh.get("sched.evaluated_pods") >= 3
        finally:
            svc.shutdown_scheduler()
    finally:
        counters.GLOBAL = real
