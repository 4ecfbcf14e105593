"""ConstraintIndex equivalence: the incremental assigned-pod aggregates
must reproduce build_constraint_tables' from-scratch walk bit-for-bit.

The index is fed ONLY through informer events (the production wiring);
after each churn phase the assembled tables are compared against a
from-scratch build over the same live state.  Ex-term planes are
compared as canonicalized row sets — their row ORDER is registry-driven
on the index path and assigned-order-driven on the walk, while every
consumer reduces over the term axis order-independently.
"""

from __future__ import annotations

import random
import time

import numpy as np
import pytest

from minisched_tpu.api.objects import (
    Affinity,
    LabelSelector,
    ObjectMeta,
    PersistentVolume,
    PersistentVolumeClaim,
    PodAffinity,
    PodAffinityTerm,
    PodAntiAffinity,
    PVCSpec,
    PVSpec,
    TopologySpreadConstraint,
    make_node,
    make_pod,
)
from minisched_tpu.controlplane.client import Client
from minisched_tpu.controlplane.informer import SharedInformerFactory
from minisched_tpu.models.constraint_index import ConstraintIndex
from minisched_tpu.models.constraints import build_constraint_tables
from minisched_tpu.models.tables import pad_to


def _wait(pred, timeout=5.0, what="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return
        time.sleep(0.01)
    raise AssertionError(f"timed out waiting for {what}")


def _pending_pods(rng, n=24):
    pods = []
    for i in range(n):
        app = f"app{rng.randrange(4)}"
        pod = make_pod(f"pend{i:03d}", labels={"app": app})
        pod.spec.topology_spread_constraints = [
            TopologySpreadConstraint(
                max_skew=2,
                topology_key="zone",
                when_unsatisfiable="DoNotSchedule",
                label_selector=LabelSelector(match_labels={"app": app}),
            )
        ]
        pod.spec.affinity = Affinity(
            pod_affinity=PodAffinity(
                required=[
                    PodAffinityTerm(
                        label_selector=LabelSelector(match_labels={"app": app}),
                        topology_key="zone",
                    )
                ]
            ),
            pod_anti_affinity=PodAntiAffinity(
                required=[
                    PodAffinityTerm(
                        label_selector=LabelSelector(
                            match_labels={"app": f"app{(i + 1) % 4}"}
                        ),
                        topology_key="zone",
                    )
                ]
            ),
        )
        if i % 3 == 0:
            pod.spec.volumes = [f"claim{i % 6}"]
        pods.append(pod)
    return pods


def _assigned_pod(rng, i, nodes):
    p = make_pod(f"asg{i:04d}", labels={"app": f"app{rng.randrange(4)}"})
    if i % 4 == 0:
        p.spec.affinity = Affinity(
            pod_anti_affinity=PodAntiAffinity(
                required=[
                    PodAffinityTerm(
                        label_selector=LabelSelector(
                            match_labels={"app": f"app{rng.randrange(4)}"}
                        ),
                        topology_key="zone",
                    )
                ]
            )
        )
    if i % 5 == 0:
        p.spec.volumes = [f"claim{rng.randrange(6)}"]
    p.spec.node_name = rng.choice(nodes).metadata.name
    return p


def _assert_equal(a, b):
    """a = incremental build, b = from-scratch build: every plane, the
    reverse anti-affinity bans (``combo_excl``, a row a distinct term)
    among them."""
    for name in type(a).__dataclass_fields__:
        va, vb = np.asarray(getattr(a, name)), np.asarray(getattr(b, name))
        assert va.shape == vb.shape, f"{name}: {va.shape} != {vb.shape}"
        assert np.array_equal(va, vb), f"{name} differs"


@pytest.fixture()
def live_index():
    client = Client()
    factory = SharedInformerFactory(client.store)
    index = ConstraintIndex()
    index.wire(factory)
    factory.start()
    assert factory.wait_for_cache_sync()
    yield client, factory, index
    factory.shutdown()


def _build_both(client, index, pending, extra=()):
    nodes = sorted(client.nodes().list(), key=lambda n: n.metadata.name)
    assigned = [
        p for p in client.pods().list() if p.spec.node_name
    ] + list(extra)
    pvcs = client.store.list("PersistentVolumeClaim")
    pvs = client.store.list("PersistentVolume")
    kw = dict(
        pod_capacity=pad_to(max(len(pending), 1)),
        node_capacity=pad_to(max(len(nodes), 1)),
        pvcs=pvcs,
        pvs=pvs,
        scan_planes=True,
    )
    inc = build_constraint_tables(
        pending, nodes, (), index=index, extra_assigned=extra, **kw
    )
    scratch = build_constraint_tables(pending, nodes, assigned, **kw)
    return inc, scratch


def test_index_matches_scratch_through_churn(live_index):
    client, factory, index = live_index
    rng = random.Random(42)
    nodes = [
        make_node(f"node{i:03d}", labels={"zone": f"z{i % 5}"})
        for i in range(40)
    ]
    for n in nodes:
        client.nodes().create(n)
    for i in range(6):
        pvc = PersistentVolumeClaim(
            metadata=ObjectMeta(name=f"claim{i}"), spec=PVCSpec()
        )
        if i % 2 == 0:
            pvc.spec.volume_name = f"pv{i}"
            client.store.create(
                "PersistentVolume",
                PersistentVolume(
                    metadata=ObjectMeta(name=f"pv{i}", namespace=""),
                    spec=PVSpec(driver=["", "ebs", "gcepd"][i % 3]),
                ),
            )
        client.store.create("PersistentVolumeClaim", pvc)
    for i in range(120):
        client.pods().create(_assigned_pod(rng, i, nodes))
    _wait(lambda: len(index.assigned_uids()) == 120, what="index sync")

    pending = _pending_pods(rng)
    inc, scratch = _build_both(client, index, pending)
    _assert_equal(inc, scratch)

    # churn: deletes, new binds, node label move, PVC binding flips
    for i in range(0, 40, 4):
        client.pods().delete(f"asg{i:04d}")
    for i in range(120, 150):
        client.pods().create(_assigned_pod(rng, i, nodes))
    n0 = client.nodes().get("node003")
    n0.metadata.labels["zone"] = "z9"
    client.nodes().update(n0)
    pvc = client.store.get("PersistentVolumeClaim", "default", "claim1")
    pvc.spec.volume_name = "pvlate"
    client.store.create(
        "PersistentVolume",
        PersistentVolume(
            metadata=ObjectMeta(name="pvlate", namespace=""),
            spec=PVSpec(driver="ebs"),
        ),
    )
    client.store.update("PersistentVolumeClaim", pvc)
    _wait(lambda: len(index.assigned_uids()) == 140, what="index churn sync")
    time.sleep(0.2)  # node/PVC re-resolution rides the same dispatch thread

    inc, scratch = _build_both(client, index, pending)
    _assert_equal(inc, scratch)


def test_index_folds_assumed_pods(live_index):
    client, factory, index = live_index
    rng = random.Random(7)
    nodes = [
        make_node(f"node{i:03d}", labels={"zone": f"z{i % 3}"})
        for i in range(12)
    ]
    for n in nodes:
        client.nodes().create(n)
    for i in range(30):
        client.pods().create(_assigned_pod(rng, i, nodes))
    _wait(lambda: len(index.assigned_uids()) == 30, what="index sync")

    # assumed pods: binds the index has NOT seen (never written to store)
    extra = []
    for i in range(100, 106):
        p = _assigned_pod(rng, i, nodes)
        p.metadata.uid = f"assumed-{i}"
        extra.append(p)
    pending = _pending_pods(rng, n=12)
    inc, scratch = _build_both(client, index, pending, extra=tuple(extra))
    _assert_equal(inc, scratch)


def test_new_combo_backfills_existing_population(live_index):
    client, factory, index = live_index
    rng = random.Random(9)
    nodes = [
        make_node(f"node{i:03d}", labels={"zone": f"z{i % 2}"})
        for i in range(8)
    ]
    for n in nodes:
        client.nodes().create(n)
    for i in range(40):
        client.pods().create(_assigned_pod(rng, i, nodes))
    _wait(lambda: len(index.assigned_uids()) == 40, what="index sync")

    # first wave registers combos for app0 only; a LATER wave brings a
    # fresh selector — its aggregate must be backfilled over the already-
    # assigned population
    first = _pending_pods(rng, n=4)
    inc, scratch = _build_both(client, index, first)
    _assert_equal(inc, scratch)

    late = make_pod("late", labels={"team": "x"})
    late.spec.topology_spread_constraints = [
        TopologySpreadConstraint(
            max_skew=1,
            topology_key="zone",
            when_unsatisfiable="ScheduleAnyway",
            label_selector=LabelSelector(match_labels={"app": "app2"}),
        )
    ]
    inc, scratch = _build_both(client, index, [late])
    _assert_equal(inc, scratch)


def test_signature_tables_recycle_under_unique_label_churn(live_index):
    """StatefulSet-like populations (a unique label per pod) must not
    grow the signature tables one entry per pod ever assigned: freed
    signature ids are recycled, reps are namespace/labels shims (no pod
    object retained), and a combo registered after heavy churn still
    backfills correctly over whatever is live."""
    client, factory, index = live_index
    nodes = [
        make_node(f"node{i:03d}", labels={"zone": f"z{i % 2}"})
        for i in range(6)
    ]
    for n in nodes:
        client.nodes().create(n)
    # three generations of unique-labeled pods; each fully replaced
    for gen in range(3):
        for i in range(25):
            p = make_pod(
                f"ss-{gen}-{i:02d}",
                labels={"pod-name": f"ss-{gen}-{i:02d}", "app": "ss"},
            )
            p.spec.node_name = nodes[i % len(nodes)].metadata.name
            client.pods().create(p)
        _wait(
            lambda: len(index.assigned_uids()) == 25,
            what=f"gen {gen} sync",
        )
        if gen < 2:
            for i in range(25):
                client.pods().delete(f"ss-{gen}-{i:02d}")
            _wait(
                lambda: len(index.assigned_uids()) == 0,
                what=f"gen {gen} drain",
            )
    # live signatures ≤ live pods; freed ids were recycled, not appended
    live_sigs = sum(1 for r in index._sig_rep if r is not None)
    assert live_sigs <= 25
    assert len(index._sig_rep) <= 50  # bounded by peak, not total churn
    # reps are shims, not pods (no spec to pin)
    assert all(
        not hasattr(r, "spec") for r in index._sig_rep if r is not None
    )
    # a combo first queried NOW must backfill over the live generation
    late = make_pod("late", labels={"team": "x"})
    late.spec.topology_spread_constraints = [
        TopologySpreadConstraint(
            max_skew=1,
            topology_key="zone",
            when_unsatisfiable="ScheduleAnyway",
            label_selector=LabelSelector(match_labels={"app": "ss"}),
        )
    ]
    inc, scratch = _build_both(client, index, [late])
    _assert_equal(inc, scratch)


def test_failed_contribution_strands_no_signature():
    """A raise mid-_contribution (advisor r4: e.g. a PVC lookup blowing
    up) must not strand a refcount-0 signature in the registry —
    apply_events swallows per-event exceptions, so a stranded entry
    would leak forever and keep paying matcher calls on every
    register_combo backfill."""
    index = ConstraintIndex()

    def boom(_key):
        raise RuntimeError("pvc cache exploded")

    index._pvc_lister = boom
    pod = make_pod("vol-pod", labels={"leak": "check"})
    pod.spec.node_name = "node0"
    pod.spec.volumes = ["claim-a"]
    try:
        index._add(pod)
    except RuntimeError:
        pass
    key = (
        pod.metadata.namespace,
        tuple(sorted(pod.metadata.labels.items())),
    )
    assert key not in index._sig_ids, "refcount-0 signature stranded"
    assert pod.metadata.uid not in index._records
