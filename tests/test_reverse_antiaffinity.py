"""Required pod anti-affinity in the reverse direction (a placed pod's term
bans the pending pods it matches from the owner's topology domain), on the
combo axis: ``combo_excl[C, N]`` holds the domains the owners occupy, one
row a distinct term, and ``pod_matches_combo @ combo_excl`` is the check,
for owners placed before the build and owners the scan committed a step
ago alike (PERF.md section 6, PR 35).

The scalar ``InterPodAffinity.filter`` and the sequential oracle are the
statement of the semantics; everything here is seeded and on the CPU.
"""

from __future__ import annotations

import random

import numpy as np
import pytest

from minisched_tpu.api.objects import (
    Affinity,
    LabelSelector,
    PodAffinityTerm,
    PodAntiAffinity,
    make_node,
    make_pod,
)
from minisched_tpu.controlplane.client import Client
from minisched_tpu.controlplane.informer import SharedInformerFactory
from minisched_tpu.engine.scheduler import schedule_pods_sequentially
from minisched_tpu.framework.nodeinfo import build_node_infos
from minisched_tpu.framework.types import CycleState
from minisched_tpu.models.constraint_index import ConstraintIndex
from minisched_tpu.models.constraints import build_constraint_tables
from minisched_tpu.models.tables import build_node_table, build_pod_table
from minisched_tpu.observability import counters
from minisched_tpu.ops.fused import BatchContext
from minisched_tpu.ops.sequential import (
    BlockedSequentialScheduler,
    SequentialScheduler,
)
from minisched_tpu.plugins.interpodaffinity import InterPodAffinity
from minisched_tpu.plugins.nodeunschedulable import NodeUnschedulable

HOST = "kubernetes.io/hostname"
ZONE = "zone"
N_NODES = 64


def _nodes(n=N_NODES):
    """Every node a hostname of its own; three in four also in one of four
    zones (a node without the key is in nobody's zone domain)."""
    return [
        make_node(
            f"n{i:03d}",
            labels={HOST: f"n{i:03d}", **({ZONE: f"z{i % 4}"} if i % 4 != 3 or i % 8 == 3 else {})},
        )
        for i in range(n)
    ]


def _term(color, key, namespaces=()):
    return PodAffinityTerm(
        label_selector=LabelSelector(match_labels={"color": color}),
        topology_key=key,
        namespaces=list(namespaces),
    )


def _pod(name, color, terms=(), namespace="default", node=""):
    pod = make_pod(name, namespace=namespace, labels={"color": color})
    if terms:
        pod.spec.affinity = Affinity(pod_anti_affinity=PodAntiAffinity(required=list(terms)))
    pod.spec.node_name = node
    pod.metadata.uid = pod.metadata.uid or f"{namespace}/{name}"
    return pod


def _cluster(case, seed):
    """(nodes, placed owners, pending pods) of one seeded case."""
    rng = random.Random(seed)
    nodes = _nodes()
    names = [n.metadata.name for n in nodes]
    if case == "hostname":
        own = lambda: [_term("green", HOST)]
        colors, spaces = ["green"], ["default"]
    elif case == "zone":
        own = lambda: [_term(rng.choice(["green", "blue"]), ZONE)]
        colors, spaces = ["green", "blue", "red"], ["default"]
    elif case == "two_terms":
        own = lambda: [_term("green", HOST), _term(rng.choice(["blue", "red"]), ZONE)]
        colors, spaces = ["green", "blue", "red"], ["default"]
    else:
        assert case == "two_namespaces"
        # a term names its namespaces, or falls to its owner's own
        own = lambda: [_term("green", HOST, rng.choice([(), ("default", "other"), ("other",)]))]
        colors, spaces = ["green", "blue"], ["default", "other"]
    placed = [
        _pod(f"own{i:02d}", rng.choice(colors), own(), rng.choice(spaces), node=node)
        for i, node in enumerate(rng.sample(names, 20))
    ]
    pending = [
        _pod(f"p{i:02d}", rng.choice(colors), own() if i % 3 else (), rng.choice(spaces))
        for i in range(24)
    ]
    return nodes, placed, pending


def _scalar_feasible(pods, nodes, placed):
    """bool[len(pods), len(nodes)] by the scalar plugin, a pod at a time."""
    ipa = InterPodAffinity()
    infos = build_node_infos(nodes, placed)
    out = np.zeros((len(pods), len(nodes)), bool)
    for i, pod in enumerate(pods):
        state = CycleState()
        assert ipa.pre_filter(state, pod, infos).is_success()
        out[i] = [ipa.filter(state, pod, ni).is_success() for ni in infos]
    return out


def _indexed(nodes, placed):
    """A ConstraintIndex that has seen ``placed`` through its handlers."""
    index = ConstraintIndex()
    by_name = {n.metadata.name: n for n in nodes}
    index._node_get = by_name.get
    for p in placed:
        index.add_pod(p)
    return index


CASES = ["hostname", "zone", "two_terms", "two_namespaces"]


@pytest.mark.parametrize("through_index", [False, True])
@pytest.mark.parametrize("case", CASES)
def test_wave_lane_feasible_sets_are_the_scalar_plugins(case, through_index):
    """Owners placed before the build: the batch filter of a wave build
    (``scan_planes=False``: only the reverse-active combos are matched)
    gives node for node what the scalar filter gives, from the walk over
    the assigned pods and from the index's aggregates."""
    nodes, placed, pending = _cluster(case, seed=3500 + CASES.index(case))
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
    assert 0 < want.sum() < want.size  # the case bites, and leaves room


def _by_node(placed):
    by_node = {}
    for p in placed:
        by_node.setdefault(p.spec.node_name, []).append(p)
    return by_node


@pytest.mark.parametrize("case", CASES)
def test_scan_lane_places_like_the_sequential_oracle(case):
    """Owners placed before the build AND owners committed earlier in the
    same scan ban through the one plane: the exact scan places pod for pod
    what the scalar loop places with every bind applied in between."""
    nodes, placed, pending = _cluster(case, seed=3600 + CASES.index(case))
    ipa = InterPodAffinity()
    chains = ([NodeUnschedulable(), ipa], [ipa], [ipa])
    want = schedule_pods_sequentially(*chains, {}, pending, build_node_infos(nodes, placed))
    node_table, names = build_node_table(nodes, _by_node(placed))
    pod_table, _ = build_pod_table(pending)
    extra = build_constraint_tables(
        pending, nodes, placed, pod_capacity=pod_table.capacity, node_capacity=node_table.capacity,
    )
    _, choice, _ = SequentialScheduler(*chains)(pod_table, node_table, extra)
    got = [names[c] if c >= 0 else "" for c in choice.tolist()[: len(pending)]]
    assert got == want
    assert len(set(got) - {""}) > 3


def test_one_pod_a_host_through_the_narrow_layout_with_owners_placed_before():
    """The deployment's own shape: every pod green under the hostname key,
    40 hosts taken before the build, 30 pending for the 24 left.  A pod a
    step through the blocked kernel: no pod on a taken host, no two on
    one, the last six find none; the exact scan says the same."""
    nodes = _nodes()
    names = [n.metadata.name for n in nodes]
    taken = random.Random(35).sample(names, 40)
    placed = [_pod(f"own{i:02d}", "green", [_term("green", HOST)], node=n) for i, n in enumerate(taken)]
    pending = [_pod(f"p{i:02d}", "green", [_term("green", HOST)]) for i in range(30)]
    ipa = InterPodAffinity()
    chains = ([NodeUnschedulable(), ipa], [ipa], [ipa])
    node_table, order = build_node_table(nodes, _by_node(placed))
    pod_table, _ = build_pod_table(pending, capacity=128)
    extra = build_constraint_tables(
        pending, nodes, placed, pod_capacity=128, node_capacity=node_table.capacity,
    )
    assert int(np.asarray(extra.combo_excl).sum()) == 40  # one row, forty hosts
    _, exact, _ = SequentialScheduler(*chains)(pod_table, node_table, extra)
    _, choice, _, accepted = BlockedSequentialScheduler(*chains, block_size=1)(pod_table, node_table, extra)
    choice, accepted = choice.tolist()[:30], accepted.tolist()[:30]
    assert choice == exact.tolist()[:30]
    got = [order[c] for c in choice if c >= 0]
    assert len(got) == 24 == len(set(got)) and not set(got) & set(taken)
    assert choice[24:] == [-1] * 6 and all(accepted[:24])


# -- shape discipline ----------------------------------------------------------


def _packed_shapes(occupied):
    nodes = [make_node(f"n{i:04d}", labels={HOST: f"n{i:04d}"}) for i in range(1100)]
    placed = [
        _pod(f"own{i:04d}", "green", [_term("green", HOST)], node=nodes[i].metadata.name)
        for i in range(occupied)
    ]
    pending = [_pod(f"p{i}", "green", [_term("green", HOST)]) for i in range(5)]
    from minisched_tpu.models.constraints import SCAN_ELIDE_GROUPS

    packed = build_constraint_tables(
        pending, nodes, placed, pod_capacity=128, node_capacity=1152,
        device=False, elide_zeros=False, elide_groups=SCAN_ELIDE_GROUPS,
    )
    built = build_constraint_tables(pending, nodes, placed, pod_capacity=128, node_capacity=1152)
    shapes = {f: np.asarray(getattr(built, f)).shape for f in type(built).__dataclass_fields__}
    return (packed.metas, packed.zero_metas), shapes, int(np.asarray(built.combo_excl).sum())


def test_no_shape_follows_the_occupied_hostnames():
    """1, 40 and 1,000 occupied hostnames: the same arrays at the same
    shapes and one packed schema, so one executable a lane.  (The axis
    that followed them, ``T`` of ``ex_domain``/``pod_matches_ex``, went
    16 -> 32 -> 2,048 rows over these three and was a program each.)"""
    built = [_packed_shapes(k) for k in (1, 40, 1000)]
    assert [banned for _schema, _shapes, banned in built] == [1, 40, 1000]
    assert built[0][0] == built[1][0] == built[2][0]
    assert built[0][1] == built[1][1] == built[2][1]
    fields = built[0][1]
    assert "ex_domain" not in fields and "pod_matches_ex" not in fields
    assert fields["combo_excl"] == fields["combo_dsum"] == (32, 1152)


# -- the delete path -----------------------------------------------------------


def test_a_deleted_owner_frees_its_host_at_the_next_build():
    nodes = _nodes(8)
    placed = [_pod(f"own{i}", "green", [_term("green", HOST)], node=f"n{i:03d}") for i in range(5)]
    index = _indexed(nodes, placed)
    pending = [_pod("p0", "green", [_term("green", HOST)])]

    def banned():
        extra = build_constraint_tables(pending, nodes, (), index=index)
        mask = np.asarray(InterPodAffinity().batch_filter(BatchContext(), None, None, extra))
        return [n.metadata.name for n, ok in zip(nodes, mask[0]) if not ok]

    assert banned() == ["n000", "n001", "n002", "n003", "n004"]
    index.delete_pod(placed[2])
    assert banned() == ["n000", "n001", "n003", "n004"]
    [(_key, _sel, vals)] = index.rev_excl_list()
    assert vals == {"n000": 1, "n001": 1, "n003": 1, "n004": 1}  # no entry for the freed host
    for p in placed:
        index.delete_pod(p)
    assert banned() == [] and index.rev_excl_list() == []
    assert index._rev_excl == {} and index._excl_sel == {}


def _index_size(index):
    """Entries the index holds, over every container it keeps."""
    total = 0
    for value in vars(index).values():
        if isinstance(value, dict):
            total += len(value) + sum(len(v) for v in value.values() if isinstance(v, (dict, set, list)))
        elif isinstance(value, list):
            total += sum(len(v) if isinstance(v, (dict, list)) else 1 for v in value if v is not None)
    return total


def test_a_thousand_add_remove_rounds_leave_the_index_the_size_it_was():
    """A host of its own every round (1,000 distinct owner values), an
    owner bound and deleted: nothing is left behind for any of them."""
    nodes = [make_node(f"n{i:04d}", labels={HOST: f"n{i:04d}", ZONE: f"z{i % 3}"}) for i in range(1000)]
    index = _indexed(nodes, [_pod("stays", "green", [_term("green", HOST), _term("green", ZONE)], node="n0000")])
    build_constraint_tables([_pod("p", "green", [_term("green", HOST)])], nodes[:4], (), index=index)
    size = _index_size(index)
    for i in range(1000):
        pod = _pod(f"job{i}", "green", [_term("green", HOST), _term("blue", ZONE)], node=f"n{i:04d}")
        index.add_pod(pod)
        if i % 100 == 0:
            assert _index_size(index) > size
        index.delete_pod(pod)
    assert _index_size(index) == size
    assert {key[2]: vals for key, _sel, vals in index.rev_excl_list()} == {HOST: {"n0000": 1}, ZONE: {"z0": 1}}


def test_the_informers_deleted_event_reaches_the_index():
    """Over the store and the informer, as a served ``DELETE`` arrives."""
    client = Client()
    factory = SharedInformerFactory(client.store)
    index = ConstraintIndex()
    index.wire(factory)
    factory.start()
    assert factory.wait_for_cache_sync()
    try:
        for n in _nodes(4):
            client.nodes().create(n)
        for i in range(3):
            client.pods().create(_pod(f"own{i}", "green", [_term("green", HOST)], node=f"n{i:03d}"))
        before = counters.get("constraint_index.pods_removed")
        _wait(lambda: index.rev_excl_list() and len(index.rev_excl_list()[0][2]) == 3)
        client.pods().delete("own1")
        _wait(lambda: len(index.rev_excl_list()[0][2]) == 2)
        assert index.rev_excl_list()[0][2] == {"n000": 1, "n002": 1}
        assert counters.get("constraint_index.pods_removed") == before + 1
    finally:
        factory.shutdown()


def _wait(cond, seconds=5.0):
    import time

    t_end = time.monotonic() + seconds
    while not cond():
        assert time.monotonic() < t_end, "the informer never delivered"
        time.sleep(0.02)


# -- the counters --------------------------------------------------------------


def test_the_excl_counters_say_how_much_of_the_cluster_is_banned():
    """A scan build counts its distinct reverse terms (1 here, whatever
    the cluster holds), the nodes they ban and terms x real nodes; a wave
    build (``scan_planes=False``) counts nothing."""
    nodes = _nodes()
    pending = [_pod("p0", "green", [_term("green", HOST)])]
    for occupied in (1, 20, 50):
        placed = [_pod(f"own{i}", "green", [_term("green", HOST)], node=f"n{i:03d}") for i in range(occupied)]
        before = counters.snapshot()
        build_constraint_tables(pending, nodes, placed, scan_planes=False)
        assert _moved(before) == {}
        build_constraint_tables(pending, nodes, placed)
        assert _moved(before) == {
            "scan.excl_terms": 1, "scan.excl_nodes": occupied, "scan.excl_capacity": N_NODES,
            "scan.combos_live": 1, "scan.combos_total": 32,
        }
    # a zone term bans its owners' whole zones; a second distinct term is a second row
    placed = [_pod("a", "green", [_term("green", ZONE)], node="n000"),
              _pod("b", "blue", [_term("green", ZONE)], node="n004"),
              _pod("c", "blue", [_term("blue", ZONE)], node="n001")]
    before = counters.snapshot()
    build_constraint_tables(pending, nodes, placed)
    moved = _moved(before)
    zone_size = sum(1 for n in nodes if n.metadata.labels.get(ZONE) == "z0")
    assert (moved["scan.excl_terms"], moved["scan.excl_capacity"]) == (2, 2 * N_NODES)
    assert moved["scan.excl_nodes"] == zone_size + sum(1 for n in nodes if n.metadata.labels.get(ZONE) == "z1")


def _moved(before):
    return {
        k: v - before.get(k, 0)
        for k, v in counters.snapshot().items()
        if v != before.get(k, 0) and k.startswith(("scan.", "sched.", "constraint_index."))
    }


def test_a_full_cluster_counts_its_pods_unschedulable():
    """One pod a host on 6 hosts, 8 green pods through a device engine:
    6 bind, and the two that no host is left for are counted as evaluated
    and returned without a node (the benchmark's
    ``queue.unschedulable_share``); every new counter stands at 0 from
    the engine's construction."""
    import time

    from minisched_tpu.service.config import default_full_roster_config
    from minisched_tpu.service.service import SchedulerService

    fresh = counters.Counters()
    real, counters.GLOBAL = counters.GLOBAL, fresh
    try:
        client = Client()
        svc = SchedulerService(client)
        svc.start_scheduler(default_full_roster_config(), device_mode=True, max_wave=128)
        new = ("scan.excl_terms", "scan.excl_nodes", "scan.excl_capacity", "sched.evaluated_pods",
               "sched.unschedulable_pods", "constraint_index.pods_removed")
        assert {name: fresh.snapshot().get(name) for name in new} == dict.fromkeys(new, 0)
        try:
            for n in _nodes(6):
                client.nodes().create(n)
            for i in range(8):
                client.pods().create(_pod(f"g{i}", "green", [_term("green", HOST)]))
            t_end = time.monotonic() + 120
            while fresh.get("sched.evaluated_pods") < 8 and time.monotonic() < t_end:
                time.sleep(0.05)
            bound = [p for p in client.pods().list() if p.spec.node_name]
            assert len(bound) == 6 == len({p.spec.node_name for p in bound})
            assert fresh.get("sched.unschedulable_pods") >= 2
            assert fresh.get("sched.evaluated_pods") >= 8
            # a bound pod deleted: the index drops it, and its host takes a waiting pod
            client.pods().delete(bound[0].metadata.name)
            t_end = time.monotonic() + 120
            while time.monotonic() < t_end and (
                fresh.get("constraint_index.pods_removed") < 1
                or sum(1 for p in client.pods().list() if p.spec.node_name) < 6
            ):
                time.sleep(0.05)
            assert fresh.get("constraint_index.pods_removed") == 1
            assert fresh.get("scan.excl_terms") >= 1  # that build met the five owners left
            now = [p for p in client.pods().list() if p.spec.node_name]
            assert len(now) == 6 == len({p.spec.node_name for p in now})
        finally:
            svc.shutdown_scheduler()
    finally:
        counters.GLOBAL = real
