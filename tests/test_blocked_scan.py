"""The blocked scan lane (ops/sequential.blocked_scan_schedule +
engine/scan_groups.py) — VERDICT r3 item 4: cross-pod throughput without
giving up within-group sequential semantics."""

from __future__ import annotations

import random
import time
from collections import Counter

import pytest

from minisched_tpu.api.objects import (
    Affinity,
    LabelSelector,
    PodAffinity,
    PodAffinityTerm,
    TopologySpreadConstraint,
    WeightedPodAffinityTerm,
    make_node,
    make_pod,
)
from minisched_tpu.engine.scan_groups import interaction_sets, order_into_blocks
from minisched_tpu.models.constraints import build_constraint_tables
from minisched_tpu.models.tables import build_node_table, build_pod_table
from minisched_tpu.ops.sequential import (
    BlockedSequentialScheduler,
    SequentialScheduler,
)
from minisched_tpu.plugins.noderesources import NodeResourcesFit
from minisched_tpu.plugins.nodeunschedulable import NodeUnschedulable
from minisched_tpu.plugins.podtopologyspread import PodTopologySpread


def _spread_pod(name, app, skew=1, mode="DoNotSchedule"):
    p = make_pod(name, labels={"app": app}, requests={"cpu": "100m"})
    p.spec.topology_spread_constraints = [
        TopologySpreadConstraint(
            max_skew=skew,
            topology_key="zone",
            when_unsatisfiable=mode,
            label_selector=LabelSelector(match_labels={"app": app}),
        )
    ]
    return p


# -- grouping ---------------------------------------------------------------


def test_same_group_pods_never_share_a_block_and_keep_fifo():
    pods = [_spread_pod(f"p{i}", f"app{i % 3}") for i in range(12)]
    sets = interaction_sets(pods)
    blocks = order_into_blocks(pods, sets, block_size=4)
    # one member per app per block
    for blk in blocks:
        apps = [m.metadata.labels["app"] for m in blk if m is not None]
        assert len(apps) == len(set(apps)), apps
    # FIFO within each app across blocks
    order = {
        app: [
            m.metadata.name
            for blk in blocks
            for m in blk
            if m is not None and m.metadata.labels["app"] == app
        ]
        for app in ("app0", "app1", "app2")
    }
    for app, names in order.items():
        want = [p.metadata.name for p in pods if p.metadata.labels["app"] == app]
        assert names == want, (app, names)


def test_matching_direction_counts_as_interaction():
    """A pod whose LABELS match another pod's selector interacts with it
    even if it carries no constraint of its own referencing that group."""
    chaser = make_pod("chaser", labels={"app": "x"})
    chaser.spec.affinity = Affinity(
        pod_affinity=PodAffinity(
            preferred=[
                WeightedPodAffinityTerm(
                    weight=5,
                    term=PodAffinityTerm(
                        label_selector=LabelSelector(match_labels={"app": "y"}),
                        topology_key="zone",
                    ),
                )
            ]
        )
    )
    target = _spread_pod("target", "y")  # labels app=y — matched by chaser
    sets = interaction_sets([chaser, target])
    assert sets[0] & sets[1], (sets[0], sets[1])
    blocks = order_into_blocks([chaser, target], sets, block_size=4)
    assert len(blocks) == 2  # forced into separate blocks


def _first_fit_oracle(items, sets, block_size):
    """The plain first fit ``order_into_blocks`` was until PR 30, kept as
    the reference: every item walks every block from the first."""
    blocks = []
    for item, s in zip(items, sets):
        for members, union in blocks:
            if len(members) < block_size and not (union & s):
                members.append(item)
                union |= s
                break
        else:
            blocks.append(([item], set(s)))
    return [
        members + [None] * (block_size - len(members))
        for members, _ in blocks
    ]


def _shape_sets(shape, rng, n):
    if shape == "one_group":
        return [{0} for _ in range(n)]
    if shape == "all_disjoint":
        return [{i} for i in range(n)]
    if shape == "mixed":  # 0-3 identities of 12, so blocks fill and refuse
        return [set(rng.sample(range(12), rng.randrange(4))) for _ in range(n)]
    if shape == "empty_sets":
        return [set() for _ in range(n)]
    assert shape == "no_items"
    return []


@pytest.mark.parametrize("block_size", [1, 2, 4, 32])
@pytest.mark.parametrize(
    "shape", ["one_group", "all_disjoint", "mixed", "empty_sets", "no_items"]
)
@pytest.mark.parametrize("seed", [11, 12, 13])
def test_order_into_blocks_is_first_fit(seed, shape, block_size):
    """List for list what the plain first fit returns: same blocks, same
    order within a block, same None padding."""
    rng = random.Random(seed)
    for _ in range(20):
        sets = _shape_sets(shape, rng, rng.randrange(1, 160))
        items = [f"i{k}" for k in range(len(sets))]
        want = _first_fit_oracle(items, [set(s) for s in sets], block_size)
        got = order_into_blocks(items, [set(s) for s in sets], block_size)
        assert got == want, (seed, shape, block_size, sets)


class _Probes:
    """Counts block probes without a clock: every hash of an identity (a
    membership test against a block's union, a lookup of the identity's
    pointer) and every intersection of an item's set with a union."""

    def __init__(self):
        self.n = 0

    def sets(self, identities):
        probes = self

        class Ident:
            def __init__(self, g):
                self.g = g

            def __hash__(self):
                probes.n += 1
                return hash(self.g)

            def __eq__(self, other):
                return self.g == other.g

        class CountingSet(set):
            def __rand__(self, other):  # plain_union & self lands here
                probes.n += 1
                return set.__and__(self, other)

            __and__ = __rand__

        idents = {}
        out = [
            CountingSet(idents.setdefault(g, Ident(g)) for g in s)
            for s in identities
        ]
        self.n = 0  # building the sets hashed every identity once
        return out


@pytest.mark.parametrize(
    "shape, oracle_probes",
    [
        ("one_group", lambda m, B: m * (m - 1) // 2),  # item i walks i blocks
        ("all_disjoint", lambda m, B: m - m // B),  # full blocks cost no `&`
    ],
)
def test_order_into_blocks_probes_linear_in_backlog(shape, oracle_probes):
    """Work, not time: a backlog of 8,192 costs at most 4 probes an item,
    where walking every block from the first costs n² ÷ 2 with one group
    — read off the oracle, at a size that takes no time, to show that the
    count sees the walk."""
    n, m, B = 8192, 512, 32
    identities = _shape_sets(shape, None, n)
    probes = _Probes()
    order_into_blocks(list(range(n)), probes.sets(identities), B)
    assert probes.n <= 4 * n, probes.n
    _first_fit_oracle(list(range(m)), probes.sets(identities[:m]), B)
    assert probes.n == oracle_probes(m, B)


# -- kernel -----------------------------------------------------------------


def _zone_cluster(n_nodes=24):
    zones = ["za", "zb", "zc"]
    return sorted(
        (
            make_node(
                f"n{i:03d}",
                labels={"zone": zones[i % 3]},
                capacity={"cpu": "16", "memory": "32Gi", "pods": 64},
            )
            for i in range(n_nodes)
        ),
        key=lambda n: n.metadata.name,
    )


def test_blocked_kernel_matches_exact_scan_on_disjoint_groups():
    """With disjoint groups and no capacity-coupled scorer, the blocked
    kernel must reproduce the exact per-pod scan bit-for-bit (one member
    per group per block ⇒ every pod sees exactly the sequential state)."""
    nodes = _zone_cluster()
    pods = [_spread_pod(f"p{i:03d}", f"app{i % 8}") for i in range(64)]
    ts = PodTopologySpread()
    filters = (NodeUnschedulable(), NodeResourcesFit(), ts)
    pres, scores = (ts,), (ts,)

    node_table, names = build_node_table(nodes)
    pod_table, _ = build_pod_table(pods)
    extra = build_constraint_tables(
        pods, nodes, [], pod_capacity=pod_table.capacity,
        node_capacity=node_table.capacity,
    )
    seq = SequentialScheduler(filters, pres, scores)
    _, want, _ = seq(pod_table, node_table, extra)
    want = [names[c] if c >= 0 else "" for c in want.tolist()[: len(pods)]]

    sets = interaction_sets(pods)
    blocks = order_into_blocks(pods, sets, 8)
    flat = [m for b in blocks for m in b]
    pad_rows = [i for i, m in enumerate(flat) if m is None]
    dummy = make_pod("scan-pad")
    flat_pods = [m if m is not None else dummy for m in flat]
    node_table, names = build_node_table(nodes)
    pod_table, _ = build_pod_table(flat_pods, invalid_rows=pad_rows)
    extra = build_constraint_tables(
        flat_pods, nodes, [], pod_capacity=pod_table.capacity,
        node_capacity=node_table.capacity,
    )
    blk = BlockedSequentialScheduler(filters, pres, scores, block_size=8)
    _, choice, _, accepted = blk(pod_table, node_table, extra)
    choice, accepted = choice.tolist(), accepted.tolist()

    got = {}
    for i, m in enumerate(flat):
        if m is None:
            continue
        assert choice[i] >= 0 and accepted[i], (m.metadata.name, choice[i])
        got[m.metadata.name] = names[choice[i]]
    assert [got[p.metadata.name] for p in pods] == want


def test_blocked_kernel_capacity_race_is_flagged_not_lost():
    """Two independent pods racing for the LAST slot of the only feasible
    node: acceptance commits one; the other comes back feasible-but-
    unaccepted (retry), never silently failed or double-booked."""
    nodes = [
        make_node("only", labels={"zone": "za"}, capacity={"cpu": "1", "pods": 10})
    ]
    a = _spread_pod("a", "appA")
    b = _spread_pod("b", "appB")
    for p in (a, b):
        p.spec.containers[0].requests.milli_cpu = 1000
    pods = [a, b]
    ts = PodTopologySpread()
    filters = (NodeUnschedulable(), NodeResourcesFit(), ts)

    node_table, names = build_node_table(nodes)
    pod_table, _ = build_pod_table(pods)
    extra = build_constraint_tables(
        pods, nodes, [], pod_capacity=pod_table.capacity,
        node_capacity=node_table.capacity,
    )
    blk = BlockedSequentialScheduler(filters, (), (), block_size=2)
    _, choice, _, accepted = blk(pod_table, node_table, extra)
    choice, accepted = choice.tolist(), accepted.tolist()
    assert choice[0] == 0 and accepted[0]  # index order wins
    assert choice[1] == 0 and not accepted[1]  # flagged for retry


# -- live engine ------------------------------------------------------------


def test_live_engine_blocked_lane_places_spread_burst(monkeypatch):
    """End to end: a burst of DoNotSchedule spread pods through the live
    device engine's blocked lane — all bind, max-skew holds per app, and
    the lane's block fill is counted once a grouping."""
    from minisched_tpu.controlplane.client import Client
    from minisched_tpu.engine import scan_groups
    from minisched_tpu.observability import counters, hist
    from minisched_tpu.service.config import default_full_roster_config
    from minisched_tpu.service.service import SchedulerService

    handed = []  # (pods in, blocks out) of every grouping the lane made

    def recording(items, sets, block_size):
        blocks = order_into_blocks(items, sets, block_size)
        handed.append((len(items), len(blocks)))
        return blocks

    monkeypatch.setattr(scan_groups, "order_into_blocks", recording)
    before = counters.snapshot()
    client = Client()
    zones = ["za", "zb", "zc", "zd"]
    for i in range(32):
        client.nodes().create(
            make_node(
                f"node{i:03d}",
                labels={"zone": zones[i % 4]},
                capacity={"cpu": "16", "memory": "32Gi", "pods": 64},
            )
        )
    for i in range(192):
        client.pods().create(_spread_pod(f"sp{i:04d}", f"app{i % 12}", skew=1))
    svc = SchedulerService(client)
    sched = svc.start_scheduler(
        default_full_roster_config(), device_mode=True, max_wave=256
    )
    assert sched.SCAN_BLOCK_SIZE > 1  # the lane under test
    deadline = time.monotonic() + 120
    while time.monotonic() < deadline:
        if all(p.spec.node_name for p in client.pods().list()):
            break
        time.sleep(0.2)
    svc.shutdown_scheduler()
    pods = client.pods().list()
    assert all(p.spec.node_name for p in pods), (
        sum(1 for p in pods if not p.spec.node_name),
        "unbound",
    )
    assert handed, "no burst reached the blocked lane"
    live = counters.get("scan.rows_live") - before.get("scan.rows_live", 0)
    total = counters.get("scan.rows_total") - before.get("scan.rows_total", 0)
    assert live == sum(n for n, _ in handed)
    assert total == sched.SCAN_BLOCK_SIZE * sum(b for _, b in handed)
    metrics = hist.render_prometheus()
    assert f"scan_rows_live {counters.get('scan.rows_live')}\n" in metrics
    assert f"scan_rows_total {counters.get('scan.rows_total')}\n" in metrics
    zone_of = {
        n.metadata.name: n.metadata.labels["zone"] for n in client.nodes().list()
    }
    for app in {p.metadata.labels["app"] for p in pods}:
        c = Counter(
            zone_of[p.spec.node_name]
            for p in pods
            if p.metadata.labels["app"] == app
        )
        counts = [c.get(z, 0) for z in zones]
        assert max(counts) - min(counts) <= 1, (app, counts)
