"""The many-service deployment (``benchmarks/configs/mixed-5000n.json``) on
a 64-node copy: 255 services that each spread over the zones under a
selector of their own, a tainted pool, cordoned nodes, pinned tenants.

What it forces on the program is the combo axis ``C`` of the constraint
tables (one row a (namespaces, selector, topology key) among a build's
pods): its capacity is a tier (``constraints.cap_tier``), the engine's
scan lanes hold the largest tier they have reached, and a padded row
never matches and never counts — the kernel gives the scalar oracle's
placements at every padded size.
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np
import pytest

from minisched_tpu.api.objects import make_pod
from minisched_tpu.engine.device_scheduler import DeviceScheduler
from minisched_tpu.engine.scan_groups import interaction_sets, order_into_blocks
from minisched_tpu.engine.scheduler import schedule_pods_sequentially
from minisched_tpu.framework.nodeinfo import build_node_infos
from minisched_tpu.models.constraints import build_constraint_tables, cap_tier, combo_rows
from minisched_tpu.models.tables import build_node_table, build_pod_table
from minisched_tpu.observability import counters
from minisched_tpu.ops.sequential import BlockedSequentialScheduler
from minisched_tpu.plugins.nodeaffinity import NodeAffinity
from minisched_tpu.plugins.noderesources import NodeResourcesFit
from minisched_tpu.plugins.nodeunschedulable import NodeUnschedulable
from minisched_tpu.plugins.podtopologyspread import PodTopologySpread
from minisched_tpu.plugins.tainttoleration import TaintToleration

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmarks")
SEED = 3300000019
LANES = ("wave", "wide", "narrow", "exact")  # the programs that place pods


@pytest.fixture(scope="module")
def deployment():
    """(config cut to 64 nodes, its maker, its own reference, the common
    reference): the benchmark's own files, so the copy is the deployment."""
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    import reference
    from makers import mixed as maker
    from references import mixed as own

    with open(os.path.join(BENCH, "configs", "mixed-5000n.json")) as f:
        config = json.load(f)
    config["nodes"]["count"] = 64
    return config, maker, own, reference


def _nodes(deployment):
    config, maker, _own, _ref = deployment
    return sorted(maker.make_nodes(config, SEED), key=lambda n: n.metadata.name)


def _measured(deployment, start, count):
    config, maker, _own, _ref = deployment
    return maker.make_pods(config["measured_pods"], f"s{SEED}-pod", start, count)


def test_the_copy_holds_what_the_deployment_is_made_of(deployment):
    nodes = _nodes(deployment)
    assert sum(bool(n.spec.taints) for n in nodes) == 6  # n % 10 == 9
    assert sum(n.spec.unschedulable for n in nodes) == 3  # n % 20 == 10
    pods = _measured(deployment, 5000, 8192)
    service = [p for p in pods if p.spec.topology_spread_constraints]
    apps = {p.metadata.labels["app"] for p in service}
    assert len(service) == 6144 and len(apps) == 255  # every service in one backlog
    by_app = sorted((sum(p.metadata.labels["app"] == a for p in service) for a in apps), reverse=True)
    assert 0.12 < by_app[0] / len(service) < 0.13 and by_app[-1] >= 1  # an eighth the largest
    tenants = [p for p in service if p.spec.node_selector]
    assert tenants and all(p.spec.tolerations for p in tenants)
    plain = [p for p in pods if not p.spec.topology_spread_constraints]
    assert sum(bool(p.spec.node_selector) for p in plain) == 512  # i % 16 == 0
    assert sum(bool(p.spec.tolerations) for p in plain) == 512  # i % 16 == 8


# -- the combo axis' shape discipline ----------------------------------------


@pytest.mark.parametrize(
    "services,rows", [(1, 32), (32, 32), (33, 256), (255, 256), (256, 256), (257, 2048)]
)
def test_the_combo_axis_holds_a_tier(deployment, services, rows):
    """1 to 32 services build 32 combo rows, 33 to 256 build 256, then
    2,048: the tiers ``cap_tier`` states and no size between them; the
    rows past the live combos are all zero."""
    nodes = _nodes(deployment)
    pods = [p for p in _measured(deployment, 5000, 8192) if p.spec.topology_spread_constraints]
    if services <= 255:
        first = {}
        for p in pods:
            first.setdefault(p.metadata.labels["app"], p)
        burst = list(first.values())[:services]
    else:  # more services than the deployment has: made up
        burst = [pods[0].clone() for _ in range(services)]
        for i, p in enumerate(burst):
            p.metadata.labels["app"] = p.spec.topology_spread_constraints[0].label_selector.match_labels["app"] = f"x{i}"
    assert rows == cap_tier(services)
    t = build_constraint_tables(burst, nodes, [], pod_capacity=2048)
    assert combo_rows(t) == rows
    for plane in ("combo_dsum", "combo_here", "combo_haskey", "combo_excl", "rev_weight"):
        assert np.asarray(getattr(t, plane)).shape[0] == rows, plane
        assert not np.asarray(getattr(t, plane))[services:].any(), plane
    matches = np.asarray(t.pod_matches_combo)
    assert matches.shape == (2048, rows)
    assert not matches[:, services:].any() and matches[: len(burst), :services].sum() == len(burst)
    packed = build_constraint_tables(burst, nodes, [], pod_capacity=2048, device=False, elide_zeros=False)
    assert combo_rows(packed) == rows


def test_a_build_takes_the_callers_combo_capacity_as_its_floor(deployment):
    nodes = _nodes(deployment)
    one = [p for p in _measured(deployment, 5000, 64) if p.spec.topology_spread_constraints][:1]
    assert combo_rows(build_constraint_tables(one, nodes, [])) == 32
    assert combo_rows(build_constraint_tables(one, nodes, [], combo_capacity=256)) == 256
    assert cap_tier(0) == 32 and [cap_tier(n) for n in (2048, 2049)] == [2048, 16384]


# -- the kernel against the scalar oracle -------------------------------------


def _chains():
    ts = PodTopologySpread()
    filters = (NodeUnschedulable(), TaintToleration(), NodeAffinity(), NodeResourcesFit(), ts)
    return filters, (ts,), (ts,)


def _run_calls(nodes, calls, combo_capacity):
    """The kernel calls one grouping takes, in order, each on top of what
    the calls before it placed: pod name -> node name ('' for none)."""
    chains = _chains()
    schedulers = {True: BlockedSequentialScheduler(*chains, block_size=DeviceScheduler.SCAN_NARROW_WIDTH),
                  False: BlockedSequentialScheduler(*chains, block_size=DeviceScheduler.SCAN_BLOCK_SIZE)}
    dummy = make_pod("scan-pad")
    placed, out, sizes = [], {}, []
    for narrow, rows, cap in calls:
        row_pods = [m if m is not None else dummy for m in rows]
        by_node = {}
        for p in placed:
            by_node.setdefault(p.spec.node_name, []).append(p)
        node_table, names = build_node_table(nodes, by_node)
        pod_table, _ = build_pod_table(
            row_pods, capacity=cap, invalid_rows=[i for i, m in enumerate(rows) if m is None]
        )
        extra = build_constraint_tables(
            row_pods, nodes, placed, pod_capacity=cap, node_capacity=node_table.capacity,
            combo_capacity=combo_capacity,
        )
        sizes.append(combo_rows(extra))
        _, choice, _, accepted = schedulers[narrow](pod_table, node_table, extra)
        choice, accepted = choice.tolist(), accepted.tolist()
        for i, m in enumerate(rows):
            if m is None:
                continue
            assert choice[i] < 0 or accepted[i], m.metadata.name  # no capacity race at this size
            out[m.metadata.name] = names[choice[i]] if choice[i] >= 0 else ""
            if choice[i] >= 0:
                bound = m.clone()
                bound.spec.node_name = names[choice[i]]
                placed.append(bound)
    return out, sizes


def test_the_kernel_places_the_backlog_like_the_scalar_oracle_at_every_combo_capacity(deployment):
    """A backlog of the deployment's service pods with more than 32
    services, pool tenants among them, on nodes with taints and a cordoned
    node, laid out by its fill: the grouping's head goes wide and its tail
    narrow (``_calls_by_fill``), and pod for pod the kernel gives the scalar
    oracle's sequential placements — at the combo capacity the build finds
    for itself and with spare tiers forced on top: a padded combo row
    never matches and never counts."""
    nodes = _nodes(deployment)
    pods = [p for p in _measured(deployment, 5000, 220) if p.spec.topology_spread_constraints]
    apps = [p.metadata.labels["app"] for p in pods]
    assert len(set(apps)) > 32 and any(p.spec.node_selector for p in pods)
    filters, pres, scores = _chains()
    want = schedule_pods_sequentially(
        list(filters), list(pres), list(scores), {}, pods, build_node_infos(nodes, [])
    )
    assert all(want), "the copy holds the backlog"
    blocks = order_into_blocks(pods, interaction_sets(pods), DeviceScheduler.SCAN_BLOCK_SIZE)
    calls = DeviceScheduler._calls_by_fill(blocks)
    assert [narrow for narrow, _, _ in calls] == [False, True]  # wide head, narrow tail
    assert sum(m is not None for _, rows, _ in calls[1:] for m in rows) > 1
    by_floor = {}
    for floor in (0, 256, 2048):
        got, sizes = _run_calls(nodes, calls, floor)
        by_floor[floor] = sizes
        assert [got[p.metadata.name] for p in pods] == want, floor
    # left to itself the head builds 256 rows and the one-service tail 32;
    # the floor the engine keeps makes them one size
    assert by_floor[0] == [256, 32] and by_floor[256] == [256, 256] and by_floor[2048] == [2048, 2048]
    # what the oracle's guarantees say of the placements
    node_of = {n.metadata.name: n for n in nodes}
    for p, name in zip(pods, want):
        node = node_of[name]
        assert not node.spec.unschedulable
        assert all(node.metadata.labels.get(k) == v for k, v in p.spec.node_selector.items())
        assert not node.spec.taints or p.spec.tolerations
    for app in set(apps):
        zones = {}
        for p, name in zip(pods, want):
            if p.metadata.labels["app"] == app:
                z = node_of[name].metadata.labels["topology.kubernetes.io/zone"]
                zones[z] = zones.get(z, 0) + 1
        counts = [zones.get(z, 0) for z in ("moon-1", "moon-2", "moon-3")]
        assert max(counts) - min(counts) <= 1, (app, counts)


def test_the_one_narrow_call_a_small_backlog_takes_places_it_like_the_scalar_oracle(deployment):
    """The same backlog under the engine's own plan: its live pods fit one
    narrow call, so the wide head and the narrow tail become that one
    call, every pod in block order a step of its own, and pod for pod the
    kernel gives the scalar oracle's sequential placements at the combo
    capacity the engine keeps."""
    nodes = _nodes(deployment)
    pods = [p for p in _measured(deployment, 5000, 220) if p.spec.topology_spread_constraints]
    filters, pres, scores = _chains()
    want = schedule_pods_sequentially(
        list(filters), list(pres), list(scores), {}, pods, build_node_infos(nodes, [])
    )
    blocks = order_into_blocks(pods, interaction_sets(pods), DeviceScheduler.SCAN_BLOCK_SIZE)
    folded = counters.get("scan.calls_folded")
    calls = DeviceScheduler._plan_blocked_calls(blocks)
    assert counters.get("scan.calls_folded") == folded + 1
    ((narrow, rows, cap),) = calls
    assert narrow and cap == DeviceScheduler.SCAN_MAX_CHUNK * DeviceScheduler.SCAN_NARROW_WIDTH
    assert [m for m in rows if m is not None] == [m for blk in blocks for m in blk if m is not None]
    got, sizes = _run_calls(nodes, calls, 256)
    assert sizes == [256]
    assert [got[p.metadata.name] for p in pods] == want


# -- the live engine: lanes taking turns, the counters ------------------------


def _serve_mix(deployment, by_fill):
    """The served engine on the copy, over a backlog of 320 measured pods
    pending before it starts: (the pods, what was bound, the engine, the
    counters moved, the deployment's violations by its two references).
    ``by_fill`` drives the wide layout directly: every grouping is laid
    out by its fill (``_calls_by_fill``: a wide head, a narrow tail),
    where the engine's own plan runs a backlog this small as one narrow
    call."""
    from minisched_tpu.controlplane.checkpoint import _encode
    from minisched_tpu.controlplane.client import Client
    from minisched_tpu.service.config import default_full_roster_config
    from minisched_tpu.service.service import SchedulerService

    config, _maker, own, reference = deployment
    before = counters.snapshot()
    client = Client()
    for node in _nodes(deployment):
        client.nodes().create(node)
    pods = _measured(deployment, 5000, 320)
    for pod in pods:
        client.pods().create(pod)
    with pytest.MonkeyPatch.context() as mp:
        if by_fill:
            mp.setattr(DeviceScheduler, "_plan_blocked_calls", DeviceScheduler._calls_by_fill)
        svc = SchedulerService(client)
        sched = svc.start_scheduler(default_full_roster_config(), device_mode=True, max_wave=256)
        deadline = time.monotonic() + 240
        while time.monotonic() < deadline:
            if all(p.spec.node_name for p in client.pods().list()):
                break
            time.sleep(0.2)
        svc.shutdown_scheduler()
    bound = client.pods().list()
    moved = {k: v - before.get(k, 0) for k, v in counters.snapshot().items()}
    nodes_json = [_encode(n) for n in client.nodes().list()]
    pods_json = [_encode(p) for p in bound]
    record = {"sent": [p.metadata.name for p in bound], "acks": {p.metadata.name: p.spec.node_name for p in bound}}
    return pods, bound, sched, moved, (
        reference.violations(nodes_json, pods_json),
        own.violations(nodes_json, pods_json, config, record),
    )


def _holds_the_deployment(pods, bound, violations):
    """Every pod bound and every guarantee of the deployment's references
    held on what was bound."""
    assert all(p.spec.node_name for p in bound), [p.metadata.name for p in bound if not p.spec.node_name]
    common, own = violations
    assert {k: v for k, v in common.items() if k != "spread_groups"} == {
        "unbound": 0, "on_unknown_node": 0, "on_unschedulable": 0, "selector_broken": 0,
        "nodes_over_allocatable": 0, "skew_over_max": 0,
    }, common
    assert common["spread_groups"] == len({p.metadata.labels["app"] for p in pods if "app" in p.metadata.labels})
    assert own == {"taint_not_tolerated": 0, "service_constraint_dropped": 0, "kinds_missing": 0}


def test_the_engine_binds_the_mix_lane_by_lane_and_counts_it(deployment):
    """The served engine on the copy, laying groupings out by their fill:
    the packed wave takes the plain, pinned and tolerating pods, the
    blocked scan the services (head wide, tail narrow), every guarantee
    of the deployment's references holds on what it bound, the scan lanes
    end at one combo capacity, and the counters say what ran."""
    from minisched_tpu.observability import hist

    pods, bound, sched, moved, violations = _serve_mix(deployment, by_fill=True)
    _holds_the_deployment(pods, bound, violations)
    service = sum(bool(p.spec.topology_spread_constraints) for p in pods)
    lanes = {lane: moved.get("sched.lane_pods." + lane, 0) for lane in LANES}
    assert sum(lanes.values()) == len(pods), lanes
    assert lanes["wave"] == len(pods) - service and lanes["wide"] > 0 and lanes["narrow"] > 0, lanes
    assert lanes["wide"] + lanes["narrow"] + lanes["exact"] == service
    assert moved["scan.calls_wide"] >= 1 and moved["scan.calls_narrow"] >= 1
    assert moved["scan.calls_folded"] == 0
    assert moved["scan.rows_narrow"] >= lanes["narrow"]  # a retry round counts its pods again
    assert 0 < moved["scan.rows_live"] < moved["scan.rows_total"]
    # more than 32 services were pending at once: the lanes went to 256
    # combo rows and stayed, whatever a later call held
    assert sched._scan_combo_cap == 256
    assert moved["scan.combos_total"] % 32 == 0 and 0 < moved["scan.combos_live"] < moved["scan.combos_total"]
    # the scan programs keep the constraint buffer's narrow columns behind
    # a fence (unpack_columns); the wave's buffers are as they were
    programs = sched.dispatched_programs()
    for lane in ("blocked_scan", "narrow_scan"):
        assert programs[lane] and all("optimization_barrier" in text for text in programs[lane]), lane
    assert not any("optimization_barrier" in text for text in programs["wave"])
    metrics = hist.render_prometheus()
    for name in counters.LANE_COUNTERS:
        assert f"\n{name.replace('.', '_')} {counters.get(name)}\n" in "\n" + metrics, name


def test_the_engine_runs_a_service_backlog_that_fits_one_narrow_call_as_one(deployment):
    """The same backlog under the engine's own plan: its service pods
    (fewer than one narrow call holds, a wide head and a narrow tail by
    their fill) run a pod a step in one narrow call a grouping, the wide
    program never runs, and every guarantee holds as in the wide layout."""
    pods, bound, sched, moved, violations = _serve_mix(deployment, by_fill=False)
    _holds_the_deployment(pods, bound, violations)
    service = [p for p in pods if p.spec.topology_spread_constraints]
    blocks = order_into_blocks(service, interaction_sets(service), DeviceScheduler.SCAN_BLOCK_SIZE)
    assert [narrow for narrow, _, _ in DeviceScheduler._calls_by_fill(blocks)] == [False, True]
    lanes = {lane: moved.get("sched.lane_pods." + lane, 0) for lane in LANES}
    assert lanes["wave"] == len(pods) - len(service) and lanes["wide"] == 0 and lanes["narrow"] > 0, lanes
    assert lanes["narrow"] + lanes["exact"] == len(service)
    assert moved["scan.calls_folded"] >= 1 and moved["scan.calls_narrow"] >= 1
    assert moved["scan.calls_wide"] == 0
    assert moved["scan.rows_narrow"] == moved["scan.rows_live"] == moved["scan.rows_total"]
    assert sched._scan_combo_cap == 256
    programs = sched.dispatched_programs()
    assert programs["narrow_scan"] and not programs["blocked_scan"]


def test_the_lane_counters_stand_at_zero_from_the_engines_construction():
    from minisched_tpu.controlplane.client import Client
    from minisched_tpu.service.config import default_full_roster_config
    from minisched_tpu.service.service import SchedulerService

    assert len(counters.LANE_COUNTERS) == 16 + len(LANES)
    assert {"sched.lane_pods." + lane for lane in LANES} <= set(counters.LANE_COUNTERS)
    fresh = counters.Counters()
    real, counters.GLOBAL = counters.GLOBAL, fresh
    try:
        svc = SchedulerService(Client())
        svc.start_scheduler(default_full_roster_config(), device_mode=True, max_wave=128)
        svc.shutdown_scheduler()
    finally:
        counters.GLOBAL = real
    assert {name: fresh.get(name) for name in counters.LANE_COUNTERS} == dict.fromkeys(counters.LANE_COUNTERS, 0)
    assert set(counters.LANE_COUNTERS) <= set(fresh.snapshot())


@pytest.mark.parametrize(
    "shape, fenced",
    [((64, 4), True), ((64, 12), True), ((2, 3, 8), True), ((64, 127), True),
     ((64,), False), ((4, 128), False), ((32, 640), False), ((3, 4, 256), False)],
)
def test_a_narrow_column_is_unpacked_behind_a_fence(shape, fenced):
    """``unpack_columns(fence_narrow=True)``: a column whose last axis is
    narrower than a lane row has its slice kept apart from its reshape
    (the compiler otherwise lays the whole buffer out ``[len / 4, 4]`` to
    reach it, PERF.md section 6, PR 33); every other column, and every
    column of a call that does not ask, is unpacked as before, and the
    values are the same either way."""
    import jax

    from minisched_tpu.models.tables import NARROW_LAST_AXIS, pack_columns, unpack_columns

    assert NARROW_LAST_AXIS == 128
    rng = np.random.default_rng(len(shape) * 1000 + shape[-1])
    host = {
        "lead": rng.integers(-9, 9, size=(7,), dtype=np.int32),
        "col": rng.integers(-9, 9, size=shape, dtype=np.int32),
        "flag": rng.integers(0, 2, size=shape).astype(bool),
        "tail": rng.integers(-9, 9, size=(5, 256), dtype=np.int32),
    }
    metas, flat = pack_columns(host)
    plain = unpack_columns(flat, metas)
    fence = unpack_columns(flat, metas, fence_narrow=True)
    for name, want in host.items():
        assert np.array_equal(np.asarray(plain[name]), want), name
        assert np.array_equal(np.asarray(fence[name]), want), name
    count = lambda **kw: str(jax.make_jaxpr(lambda f: unpack_columns(f, metas, **kw))(flat)).count(
        "optimization_barrier"
    )
    assert count() == 0
    assert count(fence_narrow=True) == (2 if fenced else 0)  # `col` and `flag`
