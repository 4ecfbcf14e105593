"""Required pod anti-affinity on many services' own labels, on the shape of
the deployment ``benchmarks/configs/antiaffinity-svc-5000n.json``: pod ``i``
is of service ``k = service_of(i)`` (service k holds ln((k+1)/k) ÷
ln(services+1) of the pods), labelled ``app=svc-<k>``, with one required
anti-affinity term ``app In (svc-<k>)`` under the hostname key: one
replica of a service a host.  Each service is an interaction group of its
own, so a backlog of several services fills the blocked lane's WIDE layout
(32 pods of 32 services a step) where it is more than one narrow call
holds, and runs a pod a step in that one call where it fits; the reverse
direction of the placed pods' terms is one row of the combo axis a
service.

The guarantee is restated here in plain code (``_shared_hosts``: no two
pods of one service on one host).  Everything is seeded and on the CPU.
"""

from __future__ import annotations

import dataclasses
import math
import time

import jax.numpy as jnp
import pytest

from minisched_tpu.api.objects import (
    Affinity,
    LabelSelector,
    LabelSelectorRequirement,
    PodAffinityTerm,
    PodAntiAffinity,
    make_node,
    make_pod,
)
from minisched_tpu.engine.device_scheduler import DeviceScheduler
from minisched_tpu.engine.scan_groups import interaction_sets, order_into_blocks
from minisched_tpu.engine.scheduler import schedule_pods_sequentially
from minisched_tpu.framework.nodeinfo import build_node_infos
from minisched_tpu.models.constraint_index import ConstraintIndex
from minisched_tpu.models.constraints import build_constraint_tables
from minisched_tpu.models.tables import build_node_table, build_pod_table
from minisched_tpu.observability import counters, hist
from minisched_tpu.ops.sequential import BlockedSequentialScheduler
from minisched_tpu.plugins.interpodaffinity import InterPodAffinity
from minisched_tpu.plugins.noderesources import (
    NodeResourcesBalancedAllocation,
    NodeResourcesFit,
    NodeResourcesLeastAllocated,
)
from minisched_tpu.plugins.nodeunschedulable import NodeUnschedulable

HOST = "kubernetes.io/hostname"
GOLDEN = 0.6180339887498949
#: the engine's copy: 64 nodes, 40 services (more than the 32 rows of the
#: combo axis's first tier), two backlogs of 128 dealt from index 0 and 128
SERVICES = 40


def service_of(i, services=SERVICES):
    return int((services + 1) ** math.modf(i * GOLDEN)[0])


def _nodes(n):
    """Hostname-labelled nodes of 4 cpu / 32Gi / 110 pods."""
    return [
        make_node(f"n{i:03d}", capacity={"cpu": "4", "memory": "32Gi", "pods": 110}, labels={HOST: f"n{i:03d}"})
        for i in range(n)
    ]


def _pod(name, app, node="", cpu="100m"):
    pod = make_pod(name, requests={"cpu": cpu, "memory": "500Mi"}, labels={"app": app})
    pod.spec.affinity = Affinity(
        pod_anti_affinity=PodAntiAffinity(
            required=[
                PodAffinityTerm(
                    label_selector=LabelSelector(match_expressions=[LabelSelectorRequirement("app", "In", [app])]),
                    topology_key=HOST,
                )
            ]
        )
    )
    pod.spec.node_name = node
    pod.metadata.uid = f"default/{name}"
    return pod


def _dealt(start, count, services=SERVICES):
    return [_pod(f"p{i:05d}", f"svc-{service_of(i, services)}") for i in range(start, start + count)]


def _shared_hosts(on):
    """(app, node) pairs that hold more than one pod, over (app, node)
    placements: what the deployment's guarantee forbids."""
    seen = {}
    for app, node in on:
        if node:
            seen[(app, node)] = seen.get((app, node), 0) + 1
    return sorted(k for k, n in seen.items() if n > 1)


def _chains():
    ipa = InterPodAffinity()
    return (
        [NodeUnschedulable(), NodeResourcesFit(), ipa],
        [ipa],
        [NodeResourcesLeastAllocated(), NodeResourcesBalancedAllocation(), ipa],
    )


# -- the kernel: one call, blocks in order -----------------------------------


def _one_call(block_size, drop_carry=False):
    """One blocked-kernel call over three services' pods (6, 4 and 3) laid
    out as the grouping lays them (a pod of each service a block), at
    ``block_size`` rows a block, on 8 hosts of which the first is the
    emptiest by far: every pod wants it, and only the ban that an earlier
    block's commit carries keeps a second pod of its service off it.
    ``drop_carry`` builds the call's tables as if no pending pod matched a
    term, so that nothing a commit writes reaches a later block.  Returns
    [(app, node)] in row order and the pods in that order."""
    nodes = _nodes(8)
    filler = [make_pod(f"f{i}", requests={"cpu": "2", "memory": "8Gi"}) for i in range(1, 8)]
    for i, f in enumerate(filler, start=1):
        f.spec.node_name = f"n{i:03d}"
    pending = [_pod(f"a{i}", "svc-a") for i in range(6)] + [_pod(f"b{i}", "svc-b") for i in range(4)]
    pending += [_pod(f"c{i}", "svc-c") for i in range(3)]
    blocks = order_into_blocks(pending, interaction_sets(pending), 32)
    assert [sum(m is not None for m in blk) for blk in blocks] == [3, 3, 3, 2, 1, 1]
    rows = [m for blk in blocks for m in (blk if block_size > 1 else [x for x in blk if x is not None])]
    rows += [None] * (-len(rows) % block_size)
    dummy = make_pod("scan-pad")
    row_pods = [m if m is not None else dummy for m in rows]
    by_node = {}
    for f in filler:
        by_node.setdefault(f.spec.node_name, []).append(f)
    node_table, names = build_node_table(nodes, by_node)
    cap = -(-len(rows) // 128) * 128
    pod_table, _ = build_pod_table(row_pods, capacity=cap, invalid_rows=[i for i, m in enumerate(rows) if m is None])
    extra = build_constraint_tables(row_pods, nodes, filler, pod_capacity=cap, node_capacity=node_table.capacity)
    if drop_carry:
        extra = dataclasses.replace(extra, pod_matches_combo=jnp.zeros_like(extra.pod_matches_combo))
    _, choice, _, _ = BlockedSequentialScheduler(*_chains(), block_size=block_size)(pod_table, node_table, extra)
    choice = choice.tolist()
    live = [(i, m) for i, m in enumerate(rows) if m is not None]
    on = [(m.metadata.labels["app"], names[choice[i]] if choice[i] >= 0 else "") for i, m in live]
    return on, [m for _i, m in live], nodes, filler


@pytest.mark.parametrize("block_size", [1, 32])
def test_each_committed_pod_bans_its_host_to_later_blocks_of_its_call(block_size):
    on, pods, nodes, filler = _one_call(block_size)
    assert all(node for _app, node in on)  # 6 pods of one service on 8 hosts
    assert _shared_hosts(on) == []
    # each service's first pod took the emptiest host, and no later one did
    firsts = {}
    for app, node in on:
        firsts.setdefault(app, node)
    assert set(firsts.values()) == {"n000"}
    assert sum(node == "n000" for _app, node in on) == 3
    if block_size == 1:  # a pod a step is the sequential order itself
        want = schedule_pods_sequentially(*_chains(), {}, pods, build_node_infos(nodes, filler))
        assert [node for _app, node in on] == want
    # the same call with the carry gone: later blocks take the host again
    dropped, *_ = _one_call(block_size, drop_carry=True)
    assert ("svc-a", "n000") in _shared_hosts(dropped)


# -- the reverse rows: one a service placed ----------------------------------


def _indexed(nodes, placed):
    index = ConstraintIndex()
    index._node_get = {n.metadata.name: n for n in nodes}.get
    for p in placed:
        index.add_pod(p)
    return index


@pytest.mark.parametrize("through_index", [False, True])
def test_the_excl_counters_hold_one_row_a_service_placed(through_index):
    """Placed: 7 services on 10 hosts (service k on k hosts, 28 in all);
    pending: two of them and one not placed.  The build holds one reverse
    row a placed service and bans exactly the hosts each holds."""
    nodes = _nodes(10)
    placed = [_pod(f"s{k}-{h}", f"svc-{k}", node=f"n{h:03d}") for k in range(1, 8) for h in range(k)]
    pending = [_pod("x1", "svc-1"), _pod("x2", "svc-7"), _pod("x3", "svc-9")]
    before = counters.snapshot()
    extra = build_constraint_tables(
        pending, nodes, () if through_index else placed, index=_indexed(nodes, placed) if through_index else None
    )

    def moved(name):
        return counters.get(name) - before.get(name, 0)

    assert moved("scan.excl_terms") == 7
    assert moved("scan.excl_nodes") == sum(range(1, 8)) == int(jnp.sum(extra.combo_excl))
    assert moved("scan.excl_capacity") == 7 * 10
    assert moved("scan.combos_live") == 8  # the seven placed and svc-9
    assert moved("scan.combos_total") == 32


# -- the served engine on a 64-host copy -------------------------------------


def _wait(cond, seconds=300.0):
    t_end = time.monotonic() + seconds
    while not cond():
        assert time.monotonic() < t_end, "the engine never got there"
        time.sleep(0.1)


def _serve(by_fill):
    """Two backlogs of 128 pods dealt over 40 services (38 and 40 distinct),
    the first pending before the engine starts, the second created in one
    call once the first is bound: what the counters, the engine's combo
    capacity and its scan programs read after each.  ``by_fill`` drives
    the wide layout directly: the engine lays every grouping out by its
    fill (``_calls_by_fill``, a wide head and a narrow tail), where its own
    plan runs a backlog this small as one narrow call."""
    from minisched_tpu.controlplane.client import Client
    from minisched_tpu.service.config import default_full_roster_config
    from minisched_tpu.service.service import SchedulerService

    client = Client()
    for node in _nodes(64):
        client.nodes().create(node)
    first, second = _dealt(0, 128), _dealt(128, 128)
    for pod in first:
        client.pods().create(pod)
    before = counters.snapshot()
    out = {"first": first, "second": second}

    def programs(sched):
        return {
            lane: len(ev._packed_caller._fns) if ev is not None else 0
            for lane, ev in (("wide", sched._blocked_scheduler), ("narrow", sched._narrow_scheduler))
        }

    with pytest.MonkeyPatch.context() as mp:
        if by_fill:
            mp.setattr(DeviceScheduler, "_plan_blocked_calls", DeviceScheduler._calls_by_fill)
        svc = SchedulerService(client)
        sched = svc.start_scheduler(default_full_roster_config(), device_mode=True, max_wave=256)
        try:
            _wait(lambda: all(p.spec.node_name for p in client.pods().list()))
            out["cap_after_first"] = sched._scan_combo_cap
            out["programs_after_first"] = programs(sched)
            out["moved_first"] = {k: v - before.get(k, 0) for k, v in counters.snapshot().items()}
            client.pods().create_many(second)
            _wait(lambda: all(p.spec.node_name for p in client.pods().list()))
            out["programs_after_second"] = programs(sched)
            out["moved"] = {k: v - before.get(k, 0) for k, v in counters.snapshot().items()}
        finally:
            svc.shutdown_scheduler()
    out["bound"] = client.pods().list()
    return out


@pytest.fixture(scope="module")
def served():
    return _serve(by_fill=True)


@pytest.fixture(scope="module")
def served_folded():
    return _serve(by_fill=False)


def _one_replica_a_host(bound):
    assert len(bound) == 256 and all(p.spec.node_name for p in bound)
    assert _shared_hosts([(p.metadata.labels["app"], p.spec.node_name) for p in bound]) == []
    # every pod was read back with its own service's term
    for p in bound:
        (term,) = p.spec.affinity.pod_anti_affinity.required
        assert term.label_selector.match_expressions[0].values == [p.metadata.labels["app"]]


def test_a_backlog_of_many_services_goes_wide_and_keeps_each_one_a_host(served):
    first = served["first"]
    blocks = order_into_blocks(first, interaction_sets(first), DeviceScheduler.SCAN_BLOCK_SIZE)
    assert max(len({m.metadata.labels["app"] for m in blk if m is not None}) for blk in blocks) >= 2
    moved = served["moved_first"]
    assert moved["scan.calls_wide"] >= 1 and moved["sched.lane_pods.wide"] > 0
    assert 0 < moved["scan.rows_live"] < moved["scan.rows_total"]
    assert served["moved"]["scan.calls_folded"] == 0
    _one_replica_a_host(served["bound"])


def test_a_backlog_that_fits_one_narrow_call_takes_one_and_keeps_each_one_a_host(served_folded):
    """The same two backlogs under the engine's own plan: each grouping
    (128 live pods, a wide head and a narrow tail by its fill) runs as
    one narrow call, a pod a step, and the guarantee holds as in the wide
    layout."""
    first = served_folded["first"]
    blocks = order_into_blocks(first, interaction_sets(first), DeviceScheduler.SCAN_BLOCK_SIZE)
    assert len(DeviceScheduler._calls_by_fill(blocks)) == 2
    moved = served_folded["moved_first"]
    assert moved["scan.calls_folded"] >= 1 and moved["scan.calls_narrow"] >= 1
    assert moved["scan.calls_wide"] == 0 and moved["sched.lane_pods.wide"] == 0
    assert moved["sched.lane_pods.narrow"] > 0
    assert moved["scan.rows_narrow"] == moved["scan.rows_live"] == moved["scan.rows_total"]
    assert served_folded["programs_after_first"]["wide"] == 0
    _one_replica_a_host(served_folded["bound"])


def test_past_32_services_the_combo_axis_holds_256_rows_and_later_builds_add_no_program(served):
    assert len({p.metadata.labels["app"] for p in served["first"]}) > 32
    assert served["cap_after_first"] == 256
    assert served["programs_after_first"]["wide"] >= 1
    assert served["programs_after_second"] == served["programs_after_first"]
    moved, first = served["moved"], served["moved_first"]
    assert moved["scan.combos_total"] % 256 == 0
    # every build of the second backlog held a reverse row for each of the
    # 38 services the first one placed
    builds = (moved["scan.combos_total"] - first["scan.combos_total"]) // 256
    assert builds >= 1
    assert moved["scan.excl_terms"] - first["scan.excl_terms"] >= 38 * builds


def test_a_delete_frees_its_host_for_the_next_pod_of_that_service():
    """Three hosts hold the service's three pods; a fourth finds no host,
    counts as unschedulable and parks; the DELETE of one moves it back and
    it binds on the host that pod left."""
    from minisched_tpu.controlplane.client import Client
    from minisched_tpu.service.config import default_full_roster_config
    from minisched_tpu.service.service import SchedulerService

    fresh = counters.Counters()
    real, counters.GLOBAL = counters.GLOBAL, fresh
    try:
        client = Client()
        svc = SchedulerService(client)
        svc.start_scheduler(default_full_roster_config(), device_mode=True, max_wave=128)
        try:
            for n in _nodes(3):
                client.nodes().create(n)
            for i in range(3):
                client.pods().create(_pod(f"r{i}", "svc-1"))
            _wait(lambda: all(client.pods().get(f"r{i}").spec.node_name for i in range(3)))
            hosts = {client.pods().get(f"r{i}").spec.node_name for i in range(3)}
            assert len(hosts) == 3
            client.pods().create(_pod("r3", "svc-1"))
            _wait(lambda: fresh.get("sched.unschedulable_pods") >= 1)
            assert not client.pods().get("r3").spec.node_name
            freed = client.pods().get("r1").spec.node_name
            client.pods().delete("r1")
            _wait(lambda: client.pods().get("r3").spec.node_name != "")
            assert client.pods().get("r3").spec.node_name == freed
        finally:
            svc.shutdown_scheduler()
    finally:
        counters.GLOBAL = real


def test_the_combo_rows_span_is_on_metrics_from_import_and_counts_a_build():
    text = hist.render_prometheus()
    for series in ("constraints_combo_rows_seconds_count", "constraints_combo_rows_cpu_seconds_count"):
        assert series in text
    n = hist.GLOBAL.merged("constraints.combo_rows_s")[3]
    build_constraint_tables([_pod("x", "svc-1")], _nodes(4), [_pod("y", "svc-1", node="n000")])
    assert hist.GLOBAL.merged("constraints.combo_rows_s")[3] == n + 1
