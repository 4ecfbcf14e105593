"""PostFilter extension point + DefaultPreemption (the reference's config
machinery carries DefaultPreemption args through conversion,
scheduler/scheduler_test.go:164,205; plugin/plugins.go:77-141)."""

from __future__ import annotations

import time

from minisched_tpu.api.objects import make_node, make_pod
from minisched_tpu.controlplane.client import Client
from minisched_tpu.framework.nodeinfo import build_node_infos
from minisched_tpu.framework.types import CycleState, Diagnosis, Status
from minisched_tpu.plugins.defaultpreemption import DefaultPreemption
from minisched_tpu.plugins.noderesources import NodeResourcesFit


class _Handle:
    """Minimal engine handle: filter chain + client."""

    def __init__(self, client, filter_plugins):
        self.client = client
        self.filter_plugins = filter_plugins


def _assigned(name, node, cpu, priority=0):
    p = make_pod(name, requests={"cpu": cpu}, priority=priority)
    p.metadata.uid = name
    p.spec.node_name = node
    return p


def _cluster(client, assigned):
    nodes = [
        make_node("n1", capacity={"cpu": "2", "memory": "8Gi", "pods": 10}),
        make_node("n2", capacity={"cpu": "2", "memory": "8Gi", "pods": 10}),
    ]
    for n in nodes:
        client.nodes().create(n)
    for p in assigned:
        client.pods().create(p)
    return build_node_infos(nodes, assigned)


def test_preemption_picks_fewest_victims():
    client = Client()
    assigned = [
        _assigned("small-a", "n1", "1"),
        _assigned("small-b", "n1", "1"),
        _assigned("big", "n2", "2"),
    ]
    infos = _cluster(client, assigned)
    dp = DefaultPreemption()
    dp.h = _Handle(client, [NodeResourcesFit()])
    pod = make_pod("wants-2cpu", requests={"cpu": "2"}, priority=10)
    nominated, status = dp.post_filter(CycleState(), pod, infos, Diagnosis())
    assert status.is_success()
    # evicting 1 pod (big on n2) beats evicting 2 (n1's smalls)
    assert nominated == "n2"
    names = {p.metadata.name for p in client.pods().list()}
    assert "big" not in names
    assert {"small-a", "small-b"} <= names


def test_preemption_requires_lower_priority_victims():
    client = Client()
    assigned = [
        _assigned("peer-a", "n1", "2", priority=10),
        _assigned("peer-b", "n2", "2", priority=10),
    ]
    infos = _cluster(client, assigned)
    dp = DefaultPreemption()
    dp.h = _Handle(client, [NodeResourcesFit()])
    pod = make_pod("same-prio", requests={"cpu": "2"}, priority=10)
    nominated, status = dp.post_filter(CycleState(), pod, infos, Diagnosis())
    assert nominated is None and not status.is_success()
    assert len(client.pods().list()) == 2  # nothing evicted


def test_preemption_evicts_lowest_priority_first():
    client = Client()
    assigned = [
        _assigned("low", "n1", "1", priority=1),
        _assigned("mid", "n1", "1", priority=5),
        _assigned("blocker", "n2", "2", priority=9),
    ]
    infos = _cluster(client, assigned)
    dp = DefaultPreemption()
    dp.h = _Handle(client, [NodeResourcesFit()])
    # needs 1 cpu: evicting just "low" on n1 suffices; n2 would also work
    # with one victim ("blocker", prio 9) — the lower max-victim-priority
    # candidate (n1, prio 1) must win the tie on victim count
    pod = make_pod("wants-1cpu", requests={"cpu": "1"}, priority=10)
    nominated, status = dp.post_filter(CycleState(), pod, infos, Diagnosis())
    assert status.is_success() and nominated == "n1"
    names = {p.metadata.name for p in client.pods().list()}
    assert "low" not in names and "mid" in names and "blocker" in names


def test_preemption_never_strands_a_partial_gang():
    """A bound gang member is never a victim: evicting one would leave its
    siblings a partial gang.  The gang's node is passed over, the
    singleton's is chosen, and every member is still there afterwards;
    with nothing but gang members below the preemptor, nothing is
    evicted at all (the old bench's churn role audited this at scale)."""
    from minisched_tpu.api.objects import GangSpec
    from minisched_tpu.observability import counters

    def member(name, node):
        p = _assigned(name, node, "1")
        p.spec.gang = GangSpec("g", 3)
        return p

    client = Client()
    gang = [member("g-a", "n1"), member("g-b", "n1"), member("g-c", "n2")]
    infos = _cluster(client, gang + [_assigned("single", "n2", "1")])
    dp = DefaultPreemption()
    dp.h = _Handle(client, [NodeResourcesFit()])
    before = counters.get("gang.preempt_shielded")
    pod = make_pod("wants-1cpu", requests={"cpu": "1"}, priority=10)
    nominated, status = dp.post_filter(CycleState(), pod, infos, Diagnosis())
    assert status.is_success() and nominated == "n2"
    names = {p.metadata.name for p in client.pods().list()}
    assert names == {"g-a", "g-b", "g-c"}
    assert counters.get("gang.preempt_shielded") > before

    # only gang members left below the preemptor: no candidate, no victim
    infos = build_node_infos(client.nodes().list(), gang)
    nominated, status = dp.post_filter(
        CycleState(), make_pod("wants-2cpu", requests={"cpu": "2"}, priority=10),
        infos, Diagnosis(),
    )
    assert not nominated and not status.is_success()
    assert {p.metadata.name for p in client.pods().list()} == names


def test_preemption_skips_unresolvable_nodes():
    client = Client()
    assigned = [_assigned("small", "n1", "2", priority=0)]
    infos = _cluster(client, assigned)
    dp = DefaultPreemption()
    dp.h = _Handle(client, [NodeResourcesFit()])
    diagnosis = Diagnosis()
    diagnosis.node_to_status["n1"] = Status.unresolvable("volume gone")
    pod = make_pod("p", requests={"cpu": "1"}, priority=10)
    nominated, status = dp.post_filter(CycleState(), pod, infos, diagnosis)
    # n1 is unresolvable; n2 is empty (no victims) → no candidates
    assert nominated is None and not status.is_success()
    assert len(client.pods().list()) == 1


def test_candidate_cap_math():
    dp = DefaultPreemption(
        min_candidate_nodes_percentage=10, min_candidate_nodes_absolute=2
    )
    assert dp._max_candidates(1000) == 100  # pct wins
    assert dp._max_candidates(10) == 2  # absolute floor wins
    assert dp._max_candidates(1) == 1  # capped at n


def test_preemption_reprieve_keeps_high_priority_blockers():
    """Upstream selectVictimsOnNode semantics: remove ALL lower-priority
    pods, then reprieve most-important first.  With varied pod sizes the
    greedy lowest-first form diverges: it would evict small `low` (1cpu
    frees exactly the 1cpu needed... but here the blocker is mid-sized).
    Cluster: n1 cap 4cpu holds hi(prio 8, 1cpu), mid(prio 3, 2cpu),
    low(prio 1, 1cpu); incoming needs 2cpu.  Greedy lowest-first evicts
    low (frees 1cpu, still short) then mid → victims {low, mid}.
    Reprieve removes all three lower... (hi has prio 8 < 10, also
    removable) → frees 4; re-adds hi (ok), mid (2cpu, leaves 1 < 2 →
    victim), low (ok) → victims exactly {mid}."""
    client = Client()
    nodes = [make_node("n1", capacity={"cpu": "4", "memory": "8Gi", "pods": 10})]
    client.nodes().create(nodes[0])
    assigned = [
        _assigned("hi", "n1", "1", priority=8),
        _assigned("mid", "n1", "2", priority=3),
        _assigned("low", "n1", "1", priority=1),
    ]
    for p in assigned:
        client.pods().create(p)
    infos = build_node_infos(nodes, assigned)
    dp = DefaultPreemption()
    dp.h = _Handle(client, [NodeResourcesFit()])
    pod = make_pod("wants-2cpu", requests={"cpu": "2"}, priority=10)
    nominated, status = dp.post_filter(CycleState(), pod, infos, Diagnosis())
    assert status.is_success() and nominated == "n1"
    names = {p.metadata.name for p in client.pods().list()}
    assert names == {"hi", "low"}  # only the blocking mid-priority pod


def test_preemption_no_candidate_when_all_lower_removed_insufficient():
    """Upstream's first check: if the pod is infeasible even with every
    lower-priority pod evicted, the node is not a candidate and nothing
    is probed further (no partial evictions)."""
    client = Client()
    nodes = [make_node("n1", capacity={"cpu": "2", "memory": "8Gi", "pods": 10})]
    client.nodes().create(nodes[0])
    assigned = [
        _assigned("low", "n1", "1", priority=1),
        _assigned("peer", "n1", "1", priority=10),
    ]
    for p in assigned:
        client.pods().create(p)
    infos = build_node_infos(nodes, assigned)
    dp = DefaultPreemption()
    dp.h = _Handle(client, [NodeResourcesFit()])
    pod = make_pod("wants-2cpu", requests={"cpu": "2"}, priority=10)
    nominated, status = dp.post_filter(CycleState(), pod, infos, Diagnosis())
    assert nominated is None and not status.is_success()
    assert len(client.pods().list()) == 2


def test_pick_one_node_upstream_order():
    """pickOneNodeForPreemption: minimum highest victim priority
    dominates victim COUNT — a node sacrificing two prio-1 pods beats a
    node sacrificing one prio-5 pod."""
    client = Client()
    nodes = [
        make_node("n1", capacity={"cpu": "2", "memory": "8Gi", "pods": 10}),
        make_node("n2", capacity={"cpu": "2", "memory": "8Gi", "pods": 10}),
    ]
    for n in nodes:
        client.nodes().create(n)
    assigned = [
        _assigned("tiny-a", "n1", "1", priority=1),
        _assigned("tiny-b", "n1", "1", priority=1),
        _assigned("mid", "n2", "2", priority=5),
    ]
    for p in assigned:
        client.pods().create(p)
    infos = build_node_infos(nodes, assigned)
    dp = DefaultPreemption()
    dp.h = _Handle(client, [NodeResourcesFit()])
    pod = make_pod("wants-2cpu", requests={"cpu": "2"}, priority=10)
    nominated, status = dp.post_filter(CycleState(), pod, infos, Diagnosis())
    assert status.is_success() and nominated == "n1"
    names = {p.metadata.name for p in client.pods().list()}
    assert names == {"mid"}


def test_preemption_zero_victim_candidate_nominates_without_eviction():
    """Snapshot drift can leave a loser that now fits a node outright
    (an earlier loser's big victim was evicted and replaced by a smaller
    phantom).  Every reprieve then succeeds — upstream returns the
    zero-victim node immediately; nothing must be deleted."""
    client = Client()
    node = make_node("n1", capacity={"cpu": "4", "memory": "8Gi", "pods": 10})
    client.nodes().create(node)
    occupant = _assigned("low", "n1", "1", priority=1)
    client.pods().create(occupant)
    infos = build_node_infos([node], [occupant])
    dp = DefaultPreemption()
    dp.h = _Handle(client, [NodeResourcesFit()])
    pod = make_pod("fits", requests={"cpu": "1"}, priority=10)
    nominated, status = dp.post_filter(CycleState(), pod, infos, Diagnosis())
    assert status.is_success() and nominated == "n1"
    assert dp.last_victims == []
    assert {p.metadata.name for p in client.pods().list()} == {"low"}


def test_store_stamps_creation_timestamp():
    """The reprieve order and the pick-node start-time criterion read
    metadata.creation_timestamp — the store must stamp it on create and
    carry it through updates (like uid)."""
    client = Client()
    client.nodes().create(make_node("n1"))
    p = make_pod("p1")
    created = client.pods().create(p)
    assert created.metadata.creation_timestamp > 0
    created.metadata.labels["x"] = "y"
    updated = client.pods().update(created)
    assert (
        updated.metadata.creation_timestamp
        == created.metadata.creation_timestamp
    )


def test_resource_gate_matches_full_probes():
    """The arithmetic probe gate (victims marked without running the
    filter chain when NodeResourcesFit must reject) must select exactly
    the victims full probing selects, across randomized clusters."""
    import random

    from minisched_tpu.framework.plugin import Plugin
    from minisched_tpu.framework.types import Status

    class _HiddenFit(Plugin):
        """NodeResourcesFit behavior without the isinstance identity —
        disables the gate so the comparison runs full probes."""

        def __init__(self):
            self._inner = NodeResourcesFit()

        def name(self):
            return self._inner.name()

        def filter(self, state, pod, node_info):
            return self._inner.filter(state, pod, node_info)

    def _sized(name, cpu, mem_gi, prio):
        p = make_pod(
            name,
            requests={"cpu": cpu, "memory": f"{mem_gi}Gi"},
            priority=prio,
        )
        p.metadata.uid = name
        p.spec.node_name = "n1"
        return p

    rng = random.Random(20260731)
    for trial in range(40):
        n_pods = rng.randint(1, 8)
        nodes = [
            make_node(
                "n1",
                capacity={
                    # make every gate branch load-bearing across trials:
                    # cpu, memory, and the pod-count headroom all bind
                    "cpu": str(rng.randint(2, 8)),
                    "memory": f"{rng.randint(2, 10)}Gi",
                    "pods": rng.randint(1, 9),
                },
            )
        ]
        assigned = [
            _sized(
                f"p{i}",
                str(rng.randint(1, 3)),
                rng.randint(1, 3),
                # priorities straddle the incoming pod's (3): `remaining`
                # starts non-empty when higher-priority pods are assigned
                rng.randint(0, 6),
            )
            for i in range(n_pods)
        ]
        pod = make_pod(
            "incoming",
            requests={
                "cpu": str(rng.randint(1, 4)),
                "memory": f"{rng.randint(1, 4)}Gi",
            },
            priority=3,
        )
        results = []
        for chain in ([NodeResourcesFit()], [_HiddenFit()]):
            client = Client()
            client.nodes().create(nodes[0])
            for p in assigned:
                client.pods().create(p)
            infos = build_node_infos(nodes, assigned)
            dp = DefaultPreemption()
            dp.h = _Handle(client, chain)
            nominated, status = dp.post_filter(
                CycleState(), pod, infos, Diagnosis()
            )
            survivors = sorted(p.metadata.name for p in client.pods().list())
            results.append((nominated, status.is_success(), survivors))
        assert results[0] == results[1], f"trial {trial}: {results}"


def test_default_preemption_args_flow_through_config():
    """The reference's conversion carries DefaultPreemption plugin args
    (scheduler_test.go:164,205); ours must too — through customization,
    build, AND simulator conversion."""
    from minisched_tpu.plugins.registry import build_plugins
    from minisched_tpu.plugins.simulator import convert_configuration_for_simulator
    from minisched_tpu.service.config import (
        SchedulerConfig,
        apply_plugin_customization,
        default_full_roster_config,
    )

    custom = SchedulerConfig(
        plugin_args={"DefaultPreemption": {"min_candidate_nodes_absolute": 7}}
    )
    cfg = apply_plugin_customization(default_full_roster_config(), custom)
    assert [p.name for p in cfg.post_filter.enabled] == ["DefaultPreemption"]
    chains = build_plugins(cfg)
    [dp] = chains.post_filter
    assert dp.min_candidate_nodes_absolute == 7
    # simulator conversion wraps filter/score only; PostFilter passes through
    conv = convert_configuration_for_simulator(cfg)
    assert [p.name for p in conv.post_filter.enabled] == ["DefaultPreemption"]
    assert conv.plugin_args["DefaultPreemption"] == {
        "min_candidate_nodes_absolute": 7
    }


def _wait(cond, timeout=15.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(0.02)
    return False


def test_live_preemption_scalar_engine():
    """Full loop: cluster full of low-priority pods; a high-priority pod
    arrives, preemption evicts a victim, the DELETE event requeues the
    pod, and it binds to the nominated node."""
    from minisched_tpu.service.config import default_full_roster_config
    from minisched_tpu.service.service import SchedulerService

    client = Client()
    svc = SchedulerService(client)
    cfg = default_full_roster_config(time_scale=0.01)
    cfg.queue_opts = {"initial_backoff_s": 0.05, "max_backoff_s": 0.2}
    svc.start_scheduler(cfg)
    try:
        client.nodes().create(
            make_node("n1", capacity={"cpu": "2", "memory": "8Gi", "pods": 10})
        )
        client.pods().create(make_pod("low", requests={"cpu": "2"}, priority=1))
        assert _wait(lambda: client.pods().get("low").spec.node_name == "n1")
        client.pods().create(make_pod("high", requests={"cpu": "2"}, priority=100))
        # nomination surfaces on the API while the pod waits for its victim
        assert _wait(
            lambda: client.pods().get("high").status.nominated_node_name == "n1"
            or client.pods().get("high").spec.node_name == "n1"
        )
        assert _wait(lambda: client.pods().get("high").spec.node_name == "n1")
        assert "low" not in {p.metadata.name for p in client.pods().list()}
    finally:
        svc.shutdown_scheduler()


def test_live_preemption_device_engine():
    """Same loop through the device wave engine: wave losers run the
    host-side PostFilter chain."""
    from minisched_tpu.service.config import default_full_roster_config
    from minisched_tpu.service.service import SchedulerService

    client = Client()
    svc = SchedulerService(client)
    cfg = default_full_roster_config(time_scale=0.01)
    cfg.queue_opts = {"initial_backoff_s": 0.05, "max_backoff_s": 0.2}
    svc.start_scheduler(cfg, device_mode=True, max_wave=16)
    try:
        client.nodes().create(
            make_node("n1", capacity={"cpu": "2", "memory": "8Gi", "pods": 10})
        )
        client.pods().create(make_pod("low", requests={"cpu": "2"}, priority=1))
        assert _wait(lambda: client.pods().get("low").spec.node_name == "n1", 60)
        client.pods().create(make_pod("high", requests={"cpu": "2"}, priority=100))
        assert _wait(
            lambda: client.pods().get("high").spec.node_name == "n1", 60
        )
        assert "low" not in {p.metadata.name for p in client.pods().list()}
    finally:
        svc.shutdown_scheduler()


def test_wave_preemption_at_scale_completes_quickly():
    """A burst of high-priority pods against a cluster FULL of evictable
    low-priority pods must preempt its way in promptly.  Regression: the
    per-probe pre-filter rebuild (InterPodAffinity's reverse walk is
    O(assigned)) made a 2k-node version of this scenario complete ZERO
    preemptions in 240s; the shared per-loser pre-filter state fixed it
    (512/512 in ~13s).  Scaled down here: 64 preemptors over 200 full
    nodes must all bind well inside the budgeted window."""
    import time

    from minisched_tpu.controlplane.client import Client
    from minisched_tpu.service.config import default_full_roster_config
    from minisched_tpu.service.service import SchedulerService

    client = Client()
    for i in range(200):
        client.nodes().create(
            make_node(f"node{i:03d}", capacity={"cpu": "4", "memory": "8Gi", "pods": 4})
        )
    for i in range(400):
        client.pods().create(
            make_pod(f"low{i:04d}", requests={"cpu": "1900m"}, priority=1)
        )
    svc = SchedulerService(client)
    placed = {}
    svc.start_scheduler(
        default_full_roster_config(), device_mode=True, max_wave=128,
        on_decision=lambda p, n, s: placed.__setitem__(p.metadata.name, n),
    )
    try:
        deadline = time.monotonic() + 90
        while time.monotonic() < deadline:
            if sum(1 for k, v in placed.items() if k.startswith("low") and v) >= 400:
                break
            time.sleep(0.2)
        assert sum(1 for k, v in placed.items() if k.startswith("low") and v) == 400

        for i in range(64):
            client.pods().create(
                make_pod(f"high{i:03d}", requests={"cpu": "2100m"}, priority=100)
            )
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            if sum(1 for k, v in placed.items() if k.startswith("high") and v) >= 64:
                break
            time.sleep(0.2)
        bound = sum(1 for k, v in placed.items() if k.startswith("high") and v)
        assert bound == 64, f"only {bound}/64 high-priority pods preempted in 60s"
    finally:
        svc.close()
