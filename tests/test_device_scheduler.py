"""Device-backed live engine: TPU wave evaluation behind the control plane.

The DeviceScheduler shares the queue/informer/permit machinery with the
scalar engine but evaluates whole waves on device in repair mode — these
tests drive it through the SAME control-plane scenarios the scalar engine
passes."""

from __future__ import annotations

import os
import time

import pytest

from minisched_tpu.api.objects import make_node, make_pod
from minisched_tpu.controlplane.client import Client
from minisched_tpu.service.config import (
    default_full_roster_config,
    default_scheduler_config,
)
from minisched_tpu.service.service import SchedulerService


def _wait(pred, timeout=60.0, interval=0.02):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(interval)
    return pred()


def test_readme_scenario_on_device_engine():
    """9 unschedulable nodes → pod pends; node10 appears → pod binds —
    the integration scenario, evaluated on device."""
    client = Client()
    svc = SchedulerService(client)
    svc.start_scheduler(
        default_scheduler_config(time_scale=0.01), device_mode=True, max_wave=64
    )
    try:
        for i in range(9):
            client.nodes().create(make_node(f"node{i}", unschedulable=True))
        client.pods().create(make_pod("pod1"))
        assert _wait(
            lambda: svc.scheduler.queue.stats()["unschedulable"] == 1,
            timeout=300.0,  # first wait absorbs the evaluator compile
        ), "pod1 should park in unschedulableQ"
        assert client.pods().get("pod1").spec.node_name == ""

        client.nodes().create(make_node("node10"))
        assert _wait(lambda: client.pods().get("pod1").spec.node_name == "node10")
    finally:
        svc.shutdown_scheduler()


def test_resource_wave_fills_cluster_without_overcommit():
    """A burst of pods larger than capacity: the device wave places what
    fits (repair mode — no double-booking) and parks the rest."""
    client = Client()
    svc = SchedulerService(client)
    svc.start_scheduler(
        default_full_roster_config(time_scale=0.01), device_mode=True, max_wave=64
    )
    try:
        for i in range(4):
            client.nodes().create(
                make_node(
                    f"node{i}",
                    capacity={"cpu": "2", "memory": "8Gi", "pods": 110},
                )
            )
        for i in range(12):  # 12 × 1cpu into 4 × 2cpu → 8 fit
            client.pods().create(make_pod(f"pod{i}", requests={"cpu": "1"}))

        assert _wait(
            lambda: sum(
                1 for p in client.pods().list() if p.spec.node_name
            ) == 8,
            timeout=300.0,  # first wait absorbs the evaluator compile
        ), "exactly the fitting 8 pods must bind"
        # accounting: no node exceeds 2 cpu
        usage = {}
        for p in client.pods().list():
            if p.spec.node_name:
                usage[p.spec.node_name] = usage.get(p.spec.node_name, 0) + 1000
        assert all(v <= 2000 for v in usage.values())
        # the 4 unplaced pods stay pending (bind events re-gate them through
        # active/backoff/unschedulable, so count across all three)
        assert _wait(
            lambda: sum(svc.scheduler.queue.stats().values()) == 4
        )

        # capacity arrives → the parked pods schedule (event-gated requeue)
        for i in range(2):
            client.nodes().create(
                make_node(f"extra{i}", capacity={"cpu": "2", "memory": "8Gi", "pods": 110})
            )
        assert _wait(
            lambda: sum(1 for p in client.pods().list() if p.spec.node_name) == 12
        )
    finally:
        svc.shutdown_scheduler()


def test_device_engine_matches_scalar_engine_placements():
    """Same cluster, same burst: device waves and the scalar loop must
    agree on WHICH pods are placeable (counts and feasibility), even
    though ordering differs."""
    def run(device_mode: bool):
        client = Client()
        svc = SchedulerService(client)
        svc.start_scheduler(
            default_full_roster_config(time_scale=0.01),
            device_mode=device_mode,
            max_wave=32,
        )
        try:
            client.nodes().create(
                make_node("big", capacity={"cpu": "4", "memory": "16Gi", "pods": 110})
            )
            client.nodes().create(
                make_node("small", capacity={"cpu": "1", "memory": "2Gi", "pods": 110})
            )
            for i in range(4):
                client.pods().create(
                    make_pod(f"pod{i}", requests={"cpu": "1", "memory": "1Gi"})
                )
            assert _wait(
                lambda: sum(1 for p in client.pods().list() if p.spec.node_name) == 4
                or svc.scheduler.queue.stats()["unschedulable"] > 0,
                timeout=300.0,  # first wait absorbs the evaluator compile
            )
            time.sleep(0.3)
            return sorted(
                (p.metadata.name, bool(p.spec.node_name))
                for p in client.pods().list()
            )
        finally:
            svc.shutdown_scheduler()

    assert run(False) == run(True)  # all 5 cpu requested fit in 4+1 cpu


def test_wave_loser_diagnosis_matches_scalar_engine():
    """Per-pod unschedulable_plugins from the wave diagnostics must equal
    the scalar engine's Diagnosis on the same cluster — the device path's
    event-gated requeue then behaves identically (VERDICT round-1 item 8)."""
    from minisched_tpu.engine.scheduler import schedule_pod_once
    from minisched_tpu.framework.nodeinfo import build_node_infos
    from minisched_tpu.framework.types import FitError
    from minisched_tpu.models.tables import build_node_table, build_pod_table
    from minisched_tpu.ops.repair import RepairingEvaluator
    from minisched_tpu.plugins.nodeaffinity import NodeAffinity
    from minisched_tpu.plugins.noderesources import NodeResourcesFit
    from minisched_tpu.plugins.nodeunschedulable import NodeUnschedulable

    nodes = [
        make_node("cordoned", unschedulable=True),
        make_node("small", capacity={"cpu": "1", "memory": "2Gi", "pods": 10}),
        make_node(
            "labeled",
            labels={"disk": "ssd"},
            capacity={"cpu": "1", "memory": "2Gi", "pods": 10},
        ),
    ]
    pods = [
        # huge request: NodeUnschedulable rejects cordoned first; Fit
        # rejects the other two
        make_pod("huge", requests={"cpu": "64"}),
        # selector matches nothing feasible: NodeAffinity everywhere but
        # cordoned (NodeUnschedulable first there), Fit never reached
        make_pod("picky", node_selector={"disk": "nvme"}),
        # schedulable: must NOT appear as a loser
        make_pod("fits", requests={"cpu": "500m"}),
    ]
    filters = [NodeUnschedulable(), NodeAffinity(), NodeResourcesFit()]
    infos = build_node_infos(nodes, [])

    scalar_sets = {}
    for pod in pods:
        try:
            schedule_pod_once(filters, [], [], {}, pod, infos)
            scalar_sets[pod.metadata.name] = None  # placed
        except FitError as err:
            scalar_sets[pod.metadata.name] = set(
                err.diagnosis.unschedulable_plugins
            )

    node_table, _ = build_node_table(sorted(nodes, key=lambda n: n.metadata.name))
    pod_table, _ = build_pod_table(pods)
    ev = RepairingEvaluator(filters, [], [], with_diagnostics=True)
    _, choice, _, unsched = ev(pod_table, node_table)
    unsched = unsched.tolist()
    names = [p.name() for p in filters]
    for i, pod in enumerate(pods):
        if int(choice[i]) >= 0:
            assert scalar_sets[pod.metadata.name] is None
            continue
        device_set = {n for k, n in enumerate(names) if unsched[k][i]}
        assert device_set == scalar_sets[pod.metadata.name], pod.metadata.name
    assert scalar_sets["huge"] == {"NodeUnschedulable", "NodeResourcesFit"}
    assert scalar_sets["picky"] == {"NodeUnschedulable", "NodeAffinity"}


def test_live_engine_sharded_over_mesh():
    """device_mesh: the live wave engine evaluates SHARDED over the 8-dev
    virtual mesh (pods data-parallel x nodes model-parallel) and still
    binds everything correctly with per-pod diagnosis intact.

    Runs in a SUBPROCESS: compiling the blocked-scan kernel earlier in
    the same process corrupts jaxlib state for the SPMD mesh executable
    (wave 2+ dispatches fail with "Execution supplied N buffers but
    compiled program expected M", and the interpreter SIGABRTs at exit
    — reproducible on jax 0.9.0 with a fresh compilation cache, with
    donation disabled, and with keep_unused; see
    parallel/sharding._CompiledShardedStep's hardening).  One engine per
    process is the deployed topology (bench children, dryrun_multichip),
    so process isolation here matches reality rather than hiding a
    product defect."""
    import subprocess
    import sys

    if os.environ.get("MINISCHED_MESH_TEST_SUBPROC") != "1":
        env = dict(os.environ, MINISCHED_MESH_TEST_SUBPROC="1")
        proc = subprocess.run(
            [
                sys.executable, "-m", "pytest", "-q", "-x",
                f"{__file__}::test_live_engine_sharded_over_mesh",
                "--no-header", "-p", "no:cacheprovider",
            ],
            env=env,
            capture_output=True,
            timeout=580,
        )
        assert proc.returncode == 0, (
            proc.stdout.decode()[-2000:] + proc.stderr.decode()[-500:]
        )
        return
    import time

    from minisched_tpu.api.objects import make_node, make_pod
    from minisched_tpu.controlplane.client import Client
    from minisched_tpu.parallel.sharding import make_mesh
    from minisched_tpu.service.config import default_full_roster_config
    from minisched_tpu.service.service import SchedulerService

    client = Client()
    for i in range(24):
        client.nodes().create(
            make_node(
                f"node{i:02d}",
                unschedulable=i % 6 == 0,
                capacity={"cpu": "2", "memory": "4Gi", "pods": 110},
            )
        )
    for i in range(40):
        client.pods().create(make_pod(f"pod{i}", requests={"cpu": "500m"}))
    # one genuinely unschedulable pod: per-pod diagnosis must park it
    client.pods().create(
        make_pod("picky", requests={"cpu": "500m"},
                 node_selector={"nope": "true"})
    )
    svc = SchedulerService(client)
    sched = svc.start_scheduler(
        default_full_roster_config(), device_mode=True, max_wave=16,
        device_mesh=make_mesh(8),
    )
    try:
        deadline = time.time() + 120
        while time.time() < deadline:
            bound = [p for p in client.pods().list() if p.spec.node_name]
            if len(bound) == 40 and sched.queue.stats()["unschedulable"] == 1:
                break
            time.sleep(0.25)
        assert len(bound) == 40, f"only {len(bound)} bound"
        assert sched.queue.stats()["unschedulable"] == 1
        [qpi] = sched.queue.pending_unschedulable()
        assert qpi.pod.metadata.name == "picky"
        assert "NodeAffinity" in qpi.unschedulable_plugins
        per_node = {}
        for p in bound:
            per_node[p.spec.node_name] = per_node.get(p.spec.node_name, 0) + 1
            node = client.nodes().get(p.spec.node_name)
            assert not node.spec.unschedulable
        for name, cnt in per_node.items():
            assert cnt * 500 <= 2000, (name, cnt)
    finally:
        svc.shutdown_scheduler()


def test_cross_pod_wave_partition_is_bind_exact():
    """Pods with cross-pod constraints ride the sequential scan inside the
    device wave (plain pods the repair path) — their placements must be
    BIT-EXACT with the scalar sequential oracle in pop order, including
    DoNotSchedule spread skew enforced between same-wave pods (the repair
    wave alone is blind to intra-wave commits in the combo planes)."""
    from minisched_tpu.api.objects import LabelSelector, TopologySpreadConstraint
    from minisched_tpu.engine.scheduler import schedule_pods_sequentially
    from minisched_tpu.framework.nodeinfo import build_node_infos
    from minisched_tpu.plugins.registry import build_plugins
    from minisched_tpu.service.service import _inject

    client = Client()
    nodes = []
    for i in range(32):
        n = make_node(
            f"node{i:03d}",
            labels={"zone": f"z{i % 4}"},
            capacity={"cpu": "8", "memory": "16Gi", "pods": 110},
        )
        client.nodes().create(n)
        nodes.append(n)
    pods = []
    for i in range(24):
        app = f"app{i % 2}"
        p = make_pod(
            f"pod{i:03d}", labels={"app": app},
            requests={"cpu": "500m", "memory": "256Mi"},
        )
        p.spec.topology_spread_constraints = [
            TopologySpreadConstraint(
                max_skew=1, topology_key="zone",
                when_unsatisfiable="DoNotSchedule",
                label_selector=LabelSelector(match_labels={"app": app}),
            )
        ]
        if i % 5 == 0:
            p.spec.node_selector = {"zone": "z1"}
        pods.append(p)

    cfg = default_full_roster_config()
    svc = SchedulerService(client)
    svc.start_scheduler(cfg, device_mode=True, max_wave=32)
    try:
        for p in pods:
            client.pods().create(p)
        assert _wait(
            lambda: all(
                client.pods().get(p.metadata.name).spec.node_name
                for p in pods
            ),
            timeout=300.0,  # absorbs the scan compile
        ), "all constrained pods should bind"
    finally:
        svc.shutdown_scheduler()

    # scalar sequential oracle on the same cluster, same order, same
    # store-assigned uids (the tie-break seed)
    chains = build_plugins(cfg)
    for pl in chains.needs_client:
        _inject(pl, "store_client", Client())
    fresh = []
    for p in pods:
        sp = client.pods().get(p.metadata.name).clone()
        sp.spec.node_name = ""
        fresh.append(sp)
    want = schedule_pods_sequentially(
        chains.filter, chains.pre_score, chains.score, cfg.score_weights(),
        fresh, build_node_infos(nodes, []),
    )
    got = [client.pods().get(p.metadata.name).spec.node_name for p in pods]
    assert want == got, [
        (p.metadata.name, w, g)
        for p, w, g in zip(pods, want, got)
        if w != g
    ][:5]


@pytest.mark.parametrize("n_apps", [6, 1])
def test_blocked_scan_lane_under_mesh(n_apps):
    """A cross-pod burst bigger than SCAN_BLOCK_SIZE on a live MESH
    engine: the blocked scan lane must compose with sharded waves —
    every pod binds, DoNotSchedule skew holds, no node over capacity.
    Six services fill their blocks (the wide layout); one service is a
    block a pod (the narrow layout).  (The sharded dryrun covers the
    exact per-pod scan; this covers the blocked lane, which runs
    node-sharded inside the mesh engine.)"""
    import time

    from minisched_tpu.api.objects import LabelSelector, TopologySpreadConstraint
    from minisched_tpu.parallel.sharding import make_mesh

    client = Client()
    n_zones = 4
    for i in range(32):
        client.nodes().create(
            make_node(
                f"node{i:03d}",
                labels={"zone": f"z{i % n_zones}"},
                capacity={"cpu": "8", "memory": "16Gi", "pods": 110},
            )
        )
    n_spread, n_plain = 48, 40
    for i in range(n_plain):
        client.pods().create(
            make_pod(f"plain{i:03d}", requests={"cpu": "250m"})
        )
    for i in range(n_spread):
        app = f"app{i % n_apps}"
        p = make_pod(
            f"spread{i:03d}", labels={"app": app},
            requests={"cpu": "250m", "memory": "128Mi"},
        )
        p.spec.topology_spread_constraints = [
            TopologySpreadConstraint(
                max_skew=1, topology_key="zone",
                when_unsatisfiable="DoNotSchedule",
                label_selector=LabelSelector(match_labels={"app": app}),
            )
        ]
        client.pods().create(p)

    from minisched_tpu.engine.device_scheduler import DeviceScheduler

    assert 1 < DeviceScheduler.SCAN_BLOCK_SIZE < n_spread
    svc = SchedulerService(client)
    sched = svc.start_scheduler(
        default_full_roster_config(), device_mode=True, max_wave=128,
        device_mesh=make_mesh(8),
    )
    try:
        deadline = time.time() + 300
        total = n_plain + n_spread
        bound = []
        while time.time() < deadline:
            bound = [p for p in client.pods().list() if p.spec.node_name]
            if len(bound) == total:
                break
            time.sleep(0.25)
        assert len(bound) == total, f"only {len(bound)}/{total} bound"
        zone_of = {
            n.metadata.name: n.metadata.labels["zone"]
            for n in client.nodes().list()
        }
        per_app: dict = {}
        cpu: dict = {}
        for p in bound:
            cpu[p.spec.node_name] = cpu.get(p.spec.node_name, 0) + 250
            if p.metadata.name.startswith("spread"):
                app = p.metadata.labels["app"]
                zones = per_app.setdefault(
                    app, {f"z{k}": 0 for k in range(n_zones)}
                )
                zones[zone_of[p.spec.node_name]] += 1
        for app, zones in per_app.items():
            counts = list(zones.values())
            assert max(counts) - min(counts) <= 1, (app, zones)
        assert all(v <= 8000 for v in cpu.values())
        programs = sched.dispatched_programs()
        lane = "blocked_scan" if n_apps > 1 else "narrow_scan"
        assert programs[lane], {k: len(v) for k, v in programs.items()}
    finally:
        svc.shutdown_scheduler()


def test_scan_backlog_flushes_within_wave_bound():
    """A sustained stream of FULL plain waves must not starve deferred
    cross-pod pods: the backlog flushes after SCAN_DEFER_MAX_WAVES even
    though neither a partial pop, a drain, nor the size threshold
    arrives while plain pods keep coming."""
    from minisched_tpu.api.objects import LabelSelector, TopologySpreadConstraint

    client = Client()
    for i in range(16):
        client.nodes().create(
            make_node(
                f"node{i:03d}",
                labels={"zone": f"z{i % 4}"},
                capacity={"cpu": "64", "memory": "256Gi", "pods": 500},
            )
        )
    cfg = default_full_roster_config()
    svc = SchedulerService(client)
    # max_wave=8: a couple hundred plain pods sustain full waves long
    # enough that only the wave-count bound can flush the one spread pod
    svc.start_scheduler(cfg, device_mode=True, max_wave=8)
    try:
        spread = make_pod(
            "spread-first", labels={"app": "s"},
            requests={"cpu": "100m", "memory": "64Mi"},
        )
        spread.spec.topology_spread_constraints = [
            TopologySpreadConstraint(
                max_skew=2, topology_key="zone",
                when_unsatisfiable="DoNotSchedule",
                label_selector=LabelSelector(match_labels={"app": "s"}),
            )
        ]
        client.pods().create(spread)
        for i in range(240):
            client.pods().create(
                make_pod(
                    f"plain{i:03d}",
                    requests={"cpu": "100m", "memory": "64Mi"},
                )
            )
        # the spread pod must bind while plain pods are STILL flowing —
        # record how many remained unbound the moment it landed (a flush
        # that only happened at drain would leave zero)
        state = {}

        def spread_bound():
            if not client.pods().get("spread-first").spec.node_name:
                return False
            if "plain_left" not in state:
                state["plain_left"] = sum(
                    1
                    for i in range(240)
                    if not client.pods().get(f"plain{i:03d}").spec.node_name
                )
            return True

        assert _wait(spread_bound, timeout=300.0), "deferred pod starved"
        assert state["plain_left"] > 0, (
            "spread pod only bound at drain — the wave-count bound did "
            "not flush the backlog"
        )
    finally:
        svc.shutdown_scheduler()


def test_flush_drops_deleted_and_refreshes_updated_backlog_pods():
    """The deferral window is wide enough for deletes/updates to land
    while a constrained pod sits in _scan_backlog — flush must drop the
    gone and schedule the changed from their CURRENT spec, not the
    popped snapshot (the queue's own update/delete handling can't reach
    popped pods)."""
    from minisched_tpu.api.objects import LabelSelector, TopologySpreadConstraint
    from minisched_tpu.framework.types import PodInfo, QueuedPodInfo

    client = Client()
    for i in range(8):
        client.nodes().create(
            make_node(
                f"node{i:03d}",
                labels={"zone": f"z{i % 2}", "tier": "a" if i == 7 else "b"},
                capacity={"cpu": "8", "memory": "16Gi", "pods": 110},
            )
        )
    cfg = default_full_roster_config()
    svc = SchedulerService(client)
    svc.start_scheduler(cfg, device_mode=True, max_wave=8)
    try:
        sched = svc.scheduler

        def spread(name):
            p = make_pod(
                name, labels={"app": "s"},
                requests={"cpu": "100m", "memory": "64Mi"},
            )
            p.spec.topology_spread_constraints = [
                TopologySpreadConstraint(
                    max_skew=4, topology_key="zone",
                    when_unsatisfiable="DoNotSchedule",
                    label_selector=LabelSelector(match_labels={"app": "s"}),
                )
            ]
            return p

        # "deleted while deferred": snapshot taken, then removed from the
        # store before the flush
        ghost = spread("ghost")
        client.pods().create(ghost)
        ghost_snap = client.pods().get("ghost").clone()
        client.pods().delete("ghost")
        # "updated while deferred": the live spec now pins to node007
        upd = spread("upd")
        client.pods().create(upd)
        snap = client.pods().get("upd").clone()
        live = client.pods().get("upd").clone()
        live.spec.node_selector = {"tier": "a"}
        client.pods().update(live)

        # flush validates against the informer cache — wait for it to
        # reflect the delete/update (dispatch thread), as it would have
        # by any real flush point
        pod_inf = sched.informer_factory.informer_for("Pod")
        def informer_caught_up():
            upd_cached = pod_inf.get("default/upd")
            return (
                pod_inf.get("default/ghost") is None
                and upd_cached is not None
                and upd_cached.metadata.resource_version
                == client.pods().get("upd").metadata.resource_version
            )

        assert _wait(informer_caught_up)
        sched._scan_backlog = [
            QueuedPodInfo(pod_info=PodInfo(pod=ghost_snap)),
            QueuedPodInfo(pod_info=PodInfo(pod=snap)),
        ]
        sched._flush_scan_backlog()
        assert _wait(
            lambda: client.pods().get("upd").spec.node_name, timeout=120.0
        )
        # the updated pod scheduled from its CURRENT spec (tier=a pins
        # node007); the deleted one was dropped, not parked as a zombie
        assert client.pods().get("upd").spec.node_name == "node007"
        stats = sched.queue.stats()
        assert stats.get("unschedulable", 0) == 0, stats
    finally:
        svc.shutdown_scheduler()


def test_scan_backlog_priority_bypass_flushes_before_plain_wave():
    """Deferral must not invert priorities (advisor r4): when a deferred
    cross-pod pod outranks the plain pods about to run, the backlog
    flushes FIRST — the wave-count bound is disabled here, so only the
    bypass (not age, size, or drain) can bind the spread pod while
    lower-priority plain pods are still flowing."""
    from minisched_tpu.api.objects import LabelSelector, TopologySpreadConstraint

    client = Client()
    for i in range(16):
        client.nodes().create(
            make_node(
                f"node{i:03d}",
                labels={"zone": f"z{i % 4}"},
                capacity={"cpu": "64", "memory": "256Gi", "pods": 500},
            )
        )
    cfg = default_full_roster_config()
    svc = SchedulerService(client)
    svc.start_scheduler(cfg, device_mode=True, max_wave=8)
    try:
        sched = svc.scheduler
        # age/size bounds out of the picture: only the priority bypass
        # (or the eventual queue drain) can flush
        sched.SCAN_DEFER_MAX_WAVES = 10**6
        spread = make_pod(
            "spread-hi", labels={"app": "s"},
            requests={"cpu": "100m", "memory": "64Mi"},
            priority=100,
        )
        spread.spec.topology_spread_constraints = [
            TopologySpreadConstraint(
                max_skew=2, topology_key="zone",
                when_unsatisfiable="DoNotSchedule",
                label_selector=LabelSelector(match_labels={"app": "s"}),
            )
        ]
        client.pods().create(spread)
        for i in range(240):
            client.pods().create(
                make_pod(
                    f"plain{i:03d}",
                    requests={"cpu": "100m", "memory": "64Mi"},
                    priority=0,
                )
            )
        state = {}

        def spread_bound():
            if not client.pods().get("spread-hi").spec.node_name:
                return False
            if "plain_left" not in state:
                state["plain_left"] = sum(
                    1
                    for i in range(240)
                    if not client.pods().get(f"plain{i:03d}").spec.node_name
                )
            return True

        assert _wait(spread_bound, timeout=300.0), "high-prio pod starved"
        assert state["plain_left"] > 0, (
            "spread pod only bound at drain — the priority bypass did "
            "not flush ahead of the lower-priority plain waves"
        )
    finally:
        svc.shutdown_scheduler()


def test_failed_scan_flush_parks_backlog_not_drops_it():
    """A raise inside the scan lane must route the (already swapped-out)
    backlog through error_func → unschedulableQ, not drop it (advisor
    r4): the run loop's catch-all would otherwise leave the pods
    Pending with no requeue path until an unrelated event."""
    from minisched_tpu.api.objects import LabelSelector, TopologySpreadConstraint

    client = Client()
    for i in range(4):
        client.nodes().create(
            make_node(
                f"node{i:03d}",
                labels={"zone": f"z{i % 2}"},
                capacity={"cpu": "8", "memory": "16Gi", "pods": 110},
            )
        )
    cfg = default_full_roster_config()
    svc = SchedulerService(client)
    svc.start_scheduler(cfg, device_mode=True, max_wave=8)
    try:
        sched = svc.scheduler
        victim = make_pod(
            "victim", labels={"app": "s"},
            requests={"cpu": "100m", "memory": "64Mi"},
        )
        victim.spec.topology_spread_constraints = [
            TopologySpreadConstraint(
                max_skew=2, topology_key="zone",
                when_unsatisfiable="DoNotSchedule",
                label_selector=LabelSelector(match_labels={"app": "s"}),
            )
        ]
        # the lane blows up BEFORE the pod exists — the live loop then
        # pops it, defers it, drain-flushes, hits the raise, and must
        # park it (installing the boom later races the loop, which can
        # bind the pod first)
        def boom(*a, **kw):
            raise RuntimeError("scan lane exploded")

        sched._schedule_scan = boom
        client.pods().create(victim)

        def parked():
            stats = sched.queue.stats()
            return (
                stats.get("unschedulable", 0) + stats.get("backoff", 0) >= 1
            )

        assert _wait(parked, timeout=120.0), (
            f"backlog pod dropped on scan failure: {sched.queue.stats()}"
        )
        assert not client.pods().get("victim").spec.node_name
        assert sched._scan_backlog == []
    finally:
        svc.shutdown_scheduler()


def test_park_scan_failures_redefers_assumed_pod_when_store_unreachable():
    """ADVICE r5 #1/#2: a pod that was assumed but whose commit can't be
    verified (authoritative store unreachable) must be RE-DEFERRED for a
    later flush, not silently dropped while its assumption double-books
    the node; and an un-assumed parked pod whose spec changed while
    deferred must requeue with the REFRESHED spec."""
    from minisched_tpu.faults import InjectedFault
    from minisched_tpu.framework.types import PodInfo, QueuedPodInfo

    client = Client()
    client.nodes().create(
        make_node("node000", capacity={"cpu": "8", "memory": "16Gi", "pods": 110})
    )
    svc = SchedulerService(client)
    svc.start_scheduler(
        default_full_roster_config(), device_mode=True, max_wave=8
    )
    sched = svc.scheduler
    try:
        # stop the loop from racing the hand-driven park below
        sched.stop()
        assumed_pod = make_pod("assumed1", requests={"cpu": "100m"})
        stale_pod = make_pod("stale1", requests={"cpu": "100m"})
        client.pods().create(assumed_pod)
        client.pods().create(stale_pod)
        snap_assumed = client.pods().get("assumed1").clone()
        snap_stale = client.pods().get("stale1").clone()
        # the stale pod's live spec moves on while it sits deferred
        live = client.pods().get("stale1")
        live.metadata.labels = {"v": "2"}
        client.pods().update(live)
        pod_inf = sched.informer_factory.informer_for("Pod")
        assert _wait(
            lambda: (
                pod_inf.get("default/assumed1") is not None
                and (pod_inf.get("default/stale1") or snap_stale)
                .metadata.resource_version
                != snap_stale.metadata.resource_version
            )
        )
        sched._assume(snap_assumed, "node000")

        def unreachable(op, kind, key):
            if op == "get" and kind == "Pod":
                raise InjectedFault("injected: store unreachable")

        client.store.fault_injector = unreachable
        qpi_assumed = QueuedPodInfo(pod_info=PodInfo(pod=snap_assumed))
        qpi_stale = QueuedPodInfo(pod_info=PodInfo(pod=snap_stale))
        sched._park_scan_failures(
            [qpi_assumed, qpi_stale], RuntimeError("scan failed")
        )
        client.store.fault_injector = None
        # the assumed pod re-deferred (assumption intact), NOT dropped
        assert sched._scan_backlog == [qpi_assumed]
        with sched._assumed_lock:
            assert snap_assumed.metadata.uid in sched._assumed
        # the stale pod went through error_func with its REFRESHED spec
        # (it stays queued — here the informer ADD had already queued it,
        # so the park deduped by uid; the refresh is the point)
        assert qpi_stale.pod.metadata.labels == {"v": "2"}
        stats = sched.queue.stats()
        assert (
            stats.get("unschedulable", 0)
            + stats.get("backoff", 0)
            + stats.get("active", 0)
        ) >= 1
    finally:
        svc.shutdown_scheduler()


def test_wave_metric_observed_on_every_exit_path():
    """ADVICE r5 #3: schedule_wave must observe the 'wave' metric on the
    empty-node and scan-only exits too — the bench asserts the loop's
    phases sum to its wall clock, and invisible exits break that."""
    from minisched_tpu.framework.types import PodInfo, QueuedPodInfo
    from minisched_tpu.observability.profiling import CycleMetrics

    client = Client()  # NO nodes: the empty-node early return
    svc = SchedulerService(client)
    svc.start_scheduler(
        default_full_roster_config(), device_mode=True, max_wave=8
    )
    sched = svc.scheduler
    try:
        sched.stop()
        sched.metrics = CycleMetrics()
        pod = make_pod("p1", requests={"cpu": "100m"})
        client.pods().create(pod)
        qpi = QueuedPodInfo(pod_info=PodInfo(pod=client.pods().get("p1")))
        sched.schedule_wave([qpi])
        snap = sched.metrics.snapshot()
        assert snap.get("wave", {}).get("count", 0) == 1, snap

        # scan-only wave (every pod constrained → deferred): same rule
        from minisched_tpu.api.objects import (
            LabelSelector,
            TopologySpreadConstraint,
        )

        spread = make_pod("p2", requests={"cpu": "100m"}, labels={"app": "s"})
        spread.spec.topology_spread_constraints = [
            TopologySpreadConstraint(
                max_skew=1, topology_key="zone",
                when_unsatisfiable="DoNotSchedule",
                label_selector=LabelSelector(match_labels={"app": "s"}),
            )
        ]
        client.pods().create(spread)
        qpi2 = QueuedPodInfo(pod_info=PodInfo(pod=client.pods().get("p2")))
        sched.schedule_wave([qpi2])
        snap = sched.metrics.snapshot()
        assert snap.get("wave", {}).get("count", 0) == 2, snap
        assert sched._scan_backlog == [qpi2]
    finally:
        svc.shutdown_scheduler()


def test_bind_batch_transaction_failure_fails_items_individually():
    """A raised bind TRANSACTION (engine.bind injection = transport
    failure after the remote client's own retries) must fail every item
    through error_func — releasing the assumptions — instead of escaping
    to the loop catch-all and stranding the wave's winners."""
    from minisched_tpu.faults import FaultFabric
    from minisched_tpu.framework.types import CycleState, PodInfo, QueuedPodInfo
    from minisched_tpu.observability import counters

    client = Client()
    client.nodes().create(
        make_node("node000", capacity={"cpu": "8", "memory": "16Gi", "pods": 110})
    )
    svc = SchedulerService(client)
    svc.start_scheduler(
        default_full_roster_config(), device_mode=True, max_wave=8
    )
    sched = svc.scheduler
    try:
        sched.stop()
        counters.reset()
        client.pods().create(make_pod("b1", requests={"cpu": "100m"}))
        pod = client.pods().get("b1")
        sched._assume(pod, "node000")
        sched.faults = FaultFabric(1).on("engine.bind", rate=1.0, max_fires=1)
        qpi = QueuedPodInfo(pod_info=PodInfo(pod=pod))
        sched._bind_batch([(qpi, pod, "node000", CycleState())])
        # transaction failed: nothing bound, assumption RELEASED
        assert not client.pods().get("b1").spec.node_name
        with sched._assumed_lock:
            assert pod.metadata.uid not in sched._assumed
        assert counters.get("engine.bind_batch_failed") == 1
        # the injected budget is spent: the retried bind lands
        sched._assume(pod, "node000")
        sched._bind_batch([(qpi, pod, "node000", CycleState())])
        assert client.pods().get("b1").spec.node_name == "node000"
    finally:
        svc.shutdown_scheduler()
