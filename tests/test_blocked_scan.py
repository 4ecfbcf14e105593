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


def _one_group_backlog(case):
    """(nodes, pods, plugin chains, volume objects) of a backlog whose pods
    all interact with each other: one group, so one live pod a block."""
    from minisched_tpu.api.objects import (
        ObjectMeta,
        PersistentVolume,
        PersistentVolumeClaim,
        PodAntiAffinity,
        PVCSpec,
        PVSpec,
    )
    from minisched_tpu.plugins.interpodaffinity import InterPodAffinity
    from minisched_tpu.plugins.volumerestrictions import VolumeRestrictions

    base = (NodeUnschedulable(), NodeResourcesFit())
    volumes = {}
    if case in ("spread", "capacity_tight"):
        # capacity_tight: 6 nodes of 2 such pods for 16 pods, so the node a
        # pod may take depends on which nodes earlier pods filled, and the
        # last four find none
        tight = case == "capacity_tight"
        nodes = [
            make_node(
                f"n{i:03d}", labels={"zone": "zabc"[1 + i % 3]},
                capacity={"cpu": "1" if tight else "16", "pods": 64},
            )
            for i in range(6 if tight else 24)
        ]
        pods = [_spread_pod(f"p{i:03d}", "one") for i in range(16 if tight else 40)]
        for p in pods:
            p.spec.containers[0].requests.milli_cpu = 500 if tight else 100
        ts = PodTopologySpread()
        chains = ((*base, ts), (ts,), (ts,))
    elif case == "antiaffinity_hostname":
        # one pod a node: 24 pods for 20 nodes, the last four find none
        nodes = [
            make_node(f"n{i:03d}", labels={"kubernetes.io/hostname": f"n{i:03d}"})
            for i in range(20)
        ]
        pods = [make_pod(f"p{i:03d}", labels={"color": "green"}) for i in range(24)]
        for p in pods:
            p.spec.affinity = Affinity(
                pod_anti_affinity=PodAntiAffinity(
                    required=[
                        PodAffinityTerm(
                            label_selector=LabelSelector(
                                match_labels={"color": "green"}
                            ),
                            topology_key="kubernetes.io/hostname",
                        )
                    ]
                )
            )
        ipa = InterPodAffinity()
        chains = ((*base, ipa), (ipa,), (ipa,))
    else:
        assert case == "shared_volume"
        # every pod mounts the one writable claim: single attach, one a node
        nodes = _zone_cluster(12)
        pods = [make_pod(f"p{i:03d}", volumes=["shared"]) for i in range(16)]
        volumes = dict(
            pvcs=[
                PersistentVolumeClaim(
                    metadata=ObjectMeta(name="shared"),
                    spec=PVCSpec(request=1 << 30, volume_name="pv-shared"),
                )
            ],
            pvs=[
                PersistentVolume(
                    metadata=ObjectMeta(name="pv-shared", namespace=""),
                    spec=PVSpec(capacity=1 << 30, claim_ref="default/shared"),
                )
            ],
        )
        chains = ((*base, VolumeRestrictions()), (), ())
    return sorted(nodes, key=lambda n: n.metadata.name), pods, chains, volumes


def _blocked_choices(nodes, rows, chains, volumes, block_size):
    """Node name ('' for none) of every live row of ``rows`` (None = a
    padding row), in row order, through the blocked kernel."""
    dummy = make_pod("scan-pad")
    row_pods = [m if m is not None else dummy for m in rows]
    node_table, names = build_node_table(nodes)
    pod_table, _ = build_pod_table(
        row_pods,
        invalid_rows=[i for i, m in enumerate(rows) if m is None],
        capacity=-(-len(rows) // 128) * 128,
    )
    extra = build_constraint_tables(
        row_pods, nodes, [],
        pod_capacity=pod_table.capacity, node_capacity=node_table.capacity,
        **volumes,
    )
    blk = BlockedSequentialScheduler(*chains, block_size=block_size)
    _, choice, _, accepted = blk(pod_table, node_table, extra)
    choice, accepted = choice.tolist(), accepted.tolist()
    out = []
    for i, m in enumerate(rows):
        if m is not None:
            assert choice[i] < 0 or accepted[i], m.metadata.name  # no races
            out.append(names[choice[i]] if choice[i] >= 0 else "")
    return out


@pytest.mark.parametrize("width", [1, 8])
@pytest.mark.parametrize(
    "case",
    ["spread", "capacity_tight", "antiaffinity_hostname", "shared_volume"],
)
def test_narrow_layout_places_a_one_group_backlog_like_the_wide_one(case, width):
    """The kernel at a narrow block size, one live pod every ``width``
    rows, gives pod for pod the choices of the 32-wide layout (one live
    pod a block of 32) and of the exact per-pod scan: a padding row never
    changed a live row's answer, and every pod still sees the state every
    earlier pod of its group left."""
    nodes, pods, chains, volumes = _one_group_backlog(case)
    sets = interaction_sets(pods)
    wide = [m for blk in order_into_blocks(pods, sets, 32) for m in blk]
    assert len(wide) == 32 * len(pods)  # one group: a block each
    node_table, names = build_node_table(nodes)
    pod_table, _ = build_pod_table(pods)
    extra = build_constraint_tables(
        pods, nodes, [], pod_capacity=pod_table.capacity,
        node_capacity=node_table.capacity, **volumes,
    )
    _, exact, _ = SequentialScheduler(*chains)(pod_table, node_table, extra)
    exact = [names[c] if c >= 0 else "" for c in exact.tolist()[: len(pods)]]
    narrow = [m for p in pods for m in (p, *[None] * (width - 1))]
    assert (
        _blocked_choices(nodes, narrow, chains, volumes, width)
        == _blocked_choices(nodes, wide, chains, volumes, 32)
        == exact
    )
    # the backlog bites: pods land on several nodes, and where the case
    # runs out of room the late pods find none
    assert len(set(exact) - {""}) > 3
    if case != "spread":
        assert exact[-1] == "" and exact[0] != ""


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


def _burst(case):
    """Pods of a burst, in the order they are created, by (service, how
    many): each service's pods carry its one selector."""
    services = {
        # one selector shared by all: a block each, all of them narrow
        "one_selector": [("big", 40)],
        # 40 services of one pod: a full block and one of 8, nothing narrow
        "disjoint_services": [(f"svc{i:02d}", 1) for i in range(40)],
        # three small services ahead of one large: the small ones share
        # the first blocks with it, the rest of the large one stands alone
        "mixed": [("a", 2), ("b", 3), ("c", 2), ("big", 60)],
    }["mixed" if case == "mixed_folded" else case]
    pods, left = [], dict(services)
    while left:  # round robin, as replicas of several services arrive
        for app in list(left):
            n = sum(1 for p in pods if p.metadata.labels["app"] == app)
            pods.append(_spread_pod(f"{app}-{n:03d}", app))
            left[app] -= 1
            if not left[app]:
                del left[app]
    return pods


@pytest.mark.parametrize(
    "case", ["one_selector", "disjoint_services", "mixed", "mixed_folded"]
)
def test_live_engine_lays_rows_out_by_the_fill_it_found(case, monkeypatch):
    """The engine's half: blocks of one live pod at the end of a grouping
    go down the narrow layout and are counted by ``scan.rows_narrow``,
    every other block keeps 32 rows, wide calls run first; every pod
    binds, a group's pods are handed to the kernel in the order the
    grouping got them, and ``maxSkew`` holds.  ``mixed`` drives that
    layout directly (``_calls_by_fill``): under the engine's own plan
    (``mixed_folded``) the same burst, whose live pods fit one narrow
    call, runs as that one call."""
    from minisched_tpu.controlplane.client import Client
    from minisched_tpu.engine.device_scheduler import DeviceScheduler
    from minisched_tpu.observability import counters, hist
    from minisched_tpu.service.config import default_full_roster_config
    from minisched_tpu.service.service import SchedulerService

    groupings = []  # [pods in, in order], then (narrow, cap, rows) a call
    plan = (
        DeviceScheduler._calls_by_fill
        if case == "mixed"
        else DeviceScheduler._plan_blocked_calls
    ).__func__

    def recording_plan(cls, blocks):
        calls = plan(cls, blocks)
        groupings.append(
            (
                [m.pod.metadata.name for blk in blocks for m in blk if m],
                [
                    (narrow, cap, [m and m.pod.metadata.name for m in part])
                    for narrow, part, cap in calls
                ],
            )
        )
        return calls

    monkeypatch.setattr(
        DeviceScheduler, "_plan_blocked_calls", classmethod(recording_plan)
    )
    before = counters.snapshot()
    client = Client()
    zones = ["za", "zb", "zc", "zd"]
    for i in range(32):
        client.nodes().create(
            make_node(
                f"node{i:03d}", labels={"zone": zones[i % 4]},
                capacity={"cpu": "16", "memory": "32Gi", "pods": 64},
            )
        )
    burst = _burst(case)
    for pod in burst:
        client.pods().create(pod)
    svc = SchedulerService(client)
    sched = svc.start_scheduler(
        default_full_roster_config(), device_mode=True, max_wave=256
    )
    deadline = time.monotonic() + 180
    while time.monotonic() < deadline:
        if all(p.spec.node_name for p in client.pods().list()):
            break
        time.sleep(0.2)
    svc.shutdown_scheduler()
    pods = client.pods().list()
    assert all(p.spec.node_name for p in pods), [
        p.metadata.name for p in pods if not p.spec.node_name
    ]

    def moved(name):
        return counters.get(name) - before.get(name, 0)

    B, W = sched.SCAN_BLOCK_SIZE, sched.SCAN_NARROW_WIDTH
    calls = [c for _, cs in groupings for c in cs]
    narrow_pods = sum(
        1 for narrow, _, rows in calls if narrow for m in rows if m
    )
    wide_rows = sum(len(rows) for narrow, _, rows in calls if not narrow)
    assert moved("scan.rows_live") == sum(len(g) for g, _ in groupings)
    assert moved("scan.rows_narrow") == narrow_pods
    assert moved("scan.rows_total") == wide_rows + W * narrow_pods
    assert wide_rows % B == 0
    # one capacity for every narrow call, and the narrow program is the
    # lane's own: the wide scheduler never saw those rows
    assert {cap for narrow, cap, _ in calls if narrow} <= {
        sched.SCAN_MAX_CHUNK * W
    }
    programs = sched.dispatched_programs()
    assert bool(programs["narrow_scan"]) == bool(narrow_pods)
    assert bool(programs["blocked_scan"]) == bool(wide_rows)
    for text in programs["narrow_scan"]:
        assert "module @jit_scan_blocked " in text
    if case == "one_selector":
        assert moved("scan.rows_narrow") == len(burst) and not wide_rows
        assert moved("scan.rows_total") <= W * len(burst)
    elif case == "disjoint_services":
        assert moved("scan.rows_narrow") == 0
        assert moved("scan.rows_total") == B * 2  # 32 + 8 pods: two blocks
    elif case == "mixed":
        assert narrow_pods and wide_rows
        assert moved("scan.rows_narrow") < len(burst)
        assert moved("scan.calls_folded") == 0
    else:
        assert moved("scan.rows_narrow") == len(burst) and not wide_rows
        assert moved("scan.calls_folded") >= 1 and moved("scan.calls_wide") == 0
    assert f"scan_rows_narrow {counters.get('scan.rows_narrow')}\n" in (
        hist.render_prometheus()
    )
    app_of = {p.metadata.name: p.metadata.labels["app"] for p in pods}
    for handed, its_calls in groupings:
        # the head runs before the suffix, and a group keeps its order
        kinds = [narrow for narrow, _, _ in its_calls]
        assert kinds == sorted(kinds), kinds
        ran = [m for _, _, rows in its_calls for m in rows if m]
        assert sorted(ran) == sorted(handed)
        for app in set(app_of.values()):
            assert [m for m in ran if app_of[m] == app] == [
                m for m in handed if app_of[m] == app
            ], app
    zone_of = {
        n.metadata.name: n.metadata.labels["zone"] for n in client.nodes().list()
    }
    for app in set(app_of.values()):
        c = Counter(
            zone_of[p.spec.node_name] for p in pods if app_of[p.metadata.name] == app
        )
        counts = [c.get(z, 0) for z in zones]
        assert max(counts) - min(counts) <= 1, (app, counts)


def test_a_round_of_race_losers_runs_narrow_and_binds_them_all():
    """34 services of one pod each: one wide call of two blocks, whose
    first block all chooses the one empty node, which holds only eight of
    them.  The losers' round (24 pods, one block by their fill: a lone
    wide call) runs as one narrow call, a pod a step, so none of them
    loses again and the wide program runs once; every pod binds and no
    node goes over its cpu."""
    from minisched_tpu.controlplane.client import Client
    from minisched_tpu.observability import counters
    from minisched_tpu.service.config import default_full_roster_config
    from minisched_tpu.service.service import SchedulerService

    client = Client()
    for i in range(8):
        client.nodes().create(
            make_node(
                f"node{i}", labels={"zone": f"z{i % 2}"},
                capacity={"cpu": "4", "memory": "32Gi", "pods": 110},
            )
        )
    for i in range(1, 8):  # every node but node0 already a quarter full
        filler = make_pod(f"filler{i}", requests={"cpu": "1"})
        filler.spec.node_name = f"node{i}"
        client.pods().create(filler)
    burst = []
    for i in range(34):
        pod = make_pod(f"r{i:02d}", labels={"app": f"svc{i:02d}"}, requests={"cpu": "500m"})
        pod.spec.topology_spread_constraints = _spread_pod("x", f"svc{i:02d}").spec.topology_spread_constraints
        burst.append(pod)
    for pod in burst:
        client.pods().create(pod)
    before = counters.snapshot()
    svc = SchedulerService(client)
    svc.start_scheduler(default_full_roster_config(), device_mode=True, max_wave=256)
    try:
        deadline = time.monotonic() + 180
        while time.monotonic() < deadline:
            if all(p.spec.node_name for p in client.pods().list()):
                break
            time.sleep(0.2)
    finally:
        svc.shutdown_scheduler()

    def moved(name):
        return counters.get(name) - before.get(name, 0)

    pods = client.pods().list()
    assert all(p.spec.node_name for p in pods), [p.metadata.name for p in pods if not p.spec.node_name]
    cpu = Counter()
    for p in pods:
        cpu[p.spec.node_name] += 1000 if p.metadata.name.startswith("filler") else 500
    assert max(cpu.values()) <= 4000, cpu
    assert moved("scan.rows_live") > len(burst)  # a round of losers ran
    assert moved("scan.calls_wide") == 1 and moved("scan.calls_narrow") >= 1
    assert moved("sched.lane_pods.wide") + moved("sched.lane_pods.narrow") == len(burst)
