"""Wave engine: build/evaluate overlap must not change WHAT gets
scheduled, and a wave is one body whoever built it.

Four layers:

* wave-engine-vs-scalar-engine parity — same workload, a chain whose
  placements are bind-independent (the nodenumber roster): the pipelined
  wave engine and the scalar oracle must produce IDENTICAL placements,
  and every pod binds exactly once.
* one wave body — a batch the worker built ahead and the same batch
  handed back raw place identically and finish in the same function.
* staleness re-arbitration — a wave built from a snapshot the overlapped
  previous wave's commits staled must reject (and requeue) winners that
  no longer fit, never over-commit (the deterministic forced-conflict
  test drives the pipeline's build stage by hand).
* the incremental aggregate base (models/tables.py) — dirty-row builds
  must be bit-identical to a from-scratch build, and the record_results
  recorder's own build must leave that base alone.
"""

from __future__ import annotations

import time

import numpy as np
import pytest

from minisched_tpu.api.objects import Binding, make_node, make_pod
from minisched_tpu.controlplane.client import Client
from minisched_tpu.observability import counters
from minisched_tpu.service.config import (
    default_full_roster_config,
    default_scheduler_config,
)
from minisched_tpu.service.service import SchedulerService


def _wait(pred, timeout=180.0, interval=0.02):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(interval)
    return pred()


def _run_nodenumber_workload(device_mode: bool):
    """One full engine run of 48 bind-independent pods over 10 nodes;
    returns ({pod: node}, bind decisions)."""
    import threading

    client = Client()
    svc = SchedulerService(client)
    binds = []
    mu = threading.Lock()

    def on_decision(pod, node_name, status):
        if node_name:
            with mu:
                binds.append(pod.metadata.name)

    svc.start_scheduler(
        default_scheduler_config(time_scale=0.01),
        device_mode=device_mode,
        max_wave=16,
        on_decision=on_decision,
    )
    try:
        for i in range(10):
            client.nodes().create(make_node(f"node{i}"))
        client.pods().create_many(
            [make_pod(f"pp{i:03d}") for i in range(48)], return_objects=False
        )
        assert _wait(
            lambda: sum(1 for p in client.pods().list() if p.spec.node_name)
            == 48,
            timeout=300.0,  # first wait absorbs the evaluator compile
        ), "all 48 pods must bind"
        placements = {
            p.metadata.name: p.spec.node_name for p in client.pods().list()
        }
    finally:
        svc.shutdown_scheduler()
    with mu:
        decisions = list(binds)
    return placements, decisions


def test_wave_engine_vs_scalar_engine_parity():
    """The pipelined wave engine against the scalar engine (the oracle):
    a bind-independent chain must place every pod IDENTICALLY (wave
    composition is the wave engine's own — placements are not), the
    worker really built waves ahead, and the exactly-once bind audit
    holds on both."""
    counters.reset()
    scalar, scalar_binds = _run_nodenumber_workload(device_mode=False)
    assert counters.get("wave_pipeline.waves") == 0
    waved, waved_binds = _run_nodenumber_workload(device_mode=True)
    assert counters.get("wave_pipeline.waves") > 0, "no wave was built ahead"
    assert scalar == waved, {
        k: (scalar[k], waved[k]) for k in scalar if scalar[k] != waved[k]
    }
    # exactly-once: one successful bind decision per pod, both engines
    assert sorted(scalar_binds) == sorted(set(scalar_binds))
    assert sorted(waved_binds) == sorted(set(waved_binds))
    assert len(waved_binds) == 48


def _popped_cluster(n_pods: int = 8):
    """A loop-less wave engine over 6 unequal nodes with ``n_pods``
    popped off its queue: (client, factory, sched, qpis in name order)."""
    from minisched_tpu.controlplane.informer import SharedInformerFactory
    from minisched_tpu.engine.device_scheduler import new_device_scheduler

    client = Client()
    factory = SharedInformerFactory(client.store)
    sched = new_device_scheduler(
        client, factory, default_full_roster_config(time_scale=0.01),
        max_wave=16,
    )
    factory.start()
    assert factory.wait_for_cache_sync()
    for i in range(6):
        client.nodes().create(
            make_node(
                f"n{i}",
                capacity={"cpu": str(2 + i), "memory": "8Gi", "pods": 10},
            )
        )
    assert _wait(lambda: len(sched.cache.snapshot()) == 6)
    client.pods().create_many(
        [
            make_pod(f"wp{i}", requests={"cpu": f"{900 + 100 * i}m"})
            for i in range(n_pods)
        ],
        return_objects=False,
    )
    qpis = []

    def drained():
        qpis.extend(sched.queue.pop_batch(16, timeout=0.2))
        return len(qpis) == n_pods

    assert _wait(drained, timeout=30.0)
    qpis.sort(key=lambda q: q.pod.metadata.name)
    return client, factory, sched, qpis


def _placed(client, n):
    assert _wait(
        lambda: sum(1 for p in client.pods().list() if p.spec.node_name) == n,
        timeout=300.0,
    )
    return {p.metadata.name: p.spec.node_name for p in client.pods().list()}


@pytest.mark.parametrize("worker_exists", [False, True])
def test_raw_and_built_ahead_waves_share_one_body(worker_exists):
    """The same batch on the same cluster, once built ahead by the
    worker's build and once handed to ``schedule_wave`` raw (with no
    worker: a tracked snapshot; beside a worker: an untracked one):
    identical placements, and each passes through ``_finish_wave`` exactly
    once — there is no second wave body to drift."""
    from minisched_tpu.engine.pipeline import WavePipeline

    def spied(sched, calls):
        finish = sched._finish_wave

        def spy(prepared):
            calls.append((prepared.built_ahead, len(prepared.qpis)))
            return finish(prepared)

        sched._finish_wave = spy

    placements, calls = {}, {}
    for side in ("ahead", "raw"):
        client, factory, sched, qpis = _popped_cluster()
        calls[side] = []
        spied(sched, calls[side])
        try:
            if side == "ahead":
                prepared = WavePipeline(sched)._build(qpis)
                sched._run_prepared_wave(prepared)
            else:
                if worker_exists:
                    sched._pipeline = WavePipeline(sched)  # never started
                sched.schedule_wave(qpis)
            placements[side] = _placed(client, len(qpis))
        finally:
            sched._pipeline = None
            sched.stop()
            factory.shutdown()
    assert calls == {"ahead": [(True, 8)], "raw": [(False, 8)]}
    assert placements["ahead"] == placements["raw"]
    assert len(set(placements["raw"].values())) > 1


def test_all_constrained_raw_wave_returns_before_any_snapshot():
    """A batch of cross-pod-constrained pods only (every measured batch
    of a spread drain): the worker hands it back, ``schedule_wave``
    defers all of it to the scan backlog and returns — no snapshot, no
    build, no finish."""
    from minisched_tpu.api.objects import (
        LabelSelector,
        TopologySpreadConstraint,
    )
    from minisched_tpu.engine.pipeline import WavePipeline, _BuildFallback

    client, factory, sched, qpis = _popped_cluster(n_pods=3)
    try:
        for q in qpis:
            q.pod.spec.topology_spread_constraints = [
                TopologySpreadConstraint(
                    max_skew=1,
                    topology_key="zone",
                    when_unsatisfiable="DoNotSchedule",
                    label_selector=LabelSelector(match_labels={"a": "b"}),
                )
            ]
        with pytest.raises(_BuildFallback):
            WavePipeline(sched)._build(qpis)

        def boom(*a, **k):
            raise AssertionError("snapshot taken for an empty wave")

        sched._snapshot_for_tables = boom
        sched._finish_wave = boom
        sched.schedule_wave(list(qpis))
        assert sched._scan_backlog == qpis
    finally:
        sched._scan_backlog = []
        sched.stop()
        factory.shutdown()


def test_pipelined_overcommit_burst_never_overcommits():
    """8 × 1cpu pods into 2 × 2cpu nodes through small overlapped waves:
    exactly 4 bind, the rest park, and no node exceeds allocatable even
    though later waves were built from snapshots the earlier waves
    staled (re-arbitration + the bind transaction's OutOfCapacity are
    the two backstops this exercises end-to-end)."""
    client = Client()
    svc = SchedulerService(client)
    sched = svc.start_scheduler(
        default_full_roster_config(time_scale=0.01),
        device_mode=True,
        max_wave=4,
    )
    try:
        for i in range(2):
            client.nodes().create(
                make_node(
                    f"n{i}", capacity={"cpu": "2", "memory": "8Gi", "pods": 110}
                )
            )
        client.pods().create_many(
            [make_pod(f"op{i}", requests={"cpu": "1"}) for i in range(8)],
            return_objects=False,
        )
        assert _wait(
            lambda: sum(1 for p in client.pods().list() if p.spec.node_name)
            == 4,
            timeout=300.0,
        ), "exactly the fitting 4 pods must bind"
        assert _wait(
            lambda: sched.queue.stats()["unschedulable"] == 4, timeout=120.0
        ), "the surplus must park unschedulable"
        per_node = {}
        for p in client.pods().list():
            if p.spec.node_name:
                per_node[p.spec.node_name] = (
                    per_node.get(p.spec.node_name, 0)
                    + p.resource_requests().milli_cpu
                )
        assert all(v <= 2000 for v in per_node.values()), per_node
    finally:
        svc.shutdown_scheduler()


def test_stale_prepared_wave_rearbitrates():
    """The forced-conflict case, deterministically: wave N+1 is built BY
    HAND from a snapshot taken before wave N commits; running it after
    wave N's commit must reject its winner at re-arbitration (capacity
    gone) and requeue it — not double-book the node."""
    from minisched_tpu.controlplane.informer import SharedInformerFactory
    from minisched_tpu.engine.device_scheduler import new_device_scheduler
    from minisched_tpu.engine.pipeline import WavePipeline

    counters.reset()
    client = Client()
    factory = SharedInformerFactory(client.store)
    sched = new_device_scheduler(
        client, factory, default_full_roster_config(time_scale=0.01),
        max_wave=8,
    )
    factory.start()
    assert factory.wait_for_cache_sync()
    try:
        client.nodes().create(
            make_node("n1", capacity={"cpu": "1", "memory": "4Gi", "pods": 10})
        )
        assert _wait(lambda: len(sched.cache.snapshot()) == 1)
        client.pods().create(make_pod("pa", requests={"cpu": "800m"}))
        client.pods().create(make_pod("pb", requests={"cpu": "800m"}))
        qpis = []

        def drained():
            qpis.extend(sched.queue.pop_batch(8, timeout=0.2))
            return len(qpis) == 2

        assert _wait(drained, timeout=30.0)
        qa = next(q for q in qpis if q.pod.metadata.name == "pa")
        qb = next(q for q in qpis if q.pod.metadata.name == "pb")

        # build wave N+1 (pb) from the PRE-COMMIT snapshot: n1 has 1000m
        # free, so the device places pb there
        pipe = WavePipeline(sched)
        prepared = pipe._build([qb])
        assert prepared.node_names

        # wave N (pa) commits on the loop thread's own path, staling it
        sched.schedule_wave([qa])
        assert _wait(
            lambda: client.pods().get("pa").spec.node_name == "n1",
            timeout=120.0,
        )

        # running the stale wave must re-arbitrate pb away, not bind it
        sched._run_prepared_wave(prepared)
        assert client.pods().get("pb").spec.node_name == ""
        assert counters.get("wave_pipeline.rearb_requeued") >= 1
        # the rejected winner went back through the active queue
        assert sched.queue.stats()["active"] >= 1
    finally:
        sched.stop()
        factory.shutdown()


def test_rearbitration_unit():
    """_rearbitrate_winners against a live cache: an assumed pod eats the
    node's remaining capacity; winners that still fit keep their slot and
    debit it for later winners in the same wave."""
    from minisched_tpu.controlplane.informer import SharedInformerFactory
    from minisched_tpu.engine.device_scheduler import new_device_scheduler

    client = Client()
    factory = SharedInformerFactory(client.store)
    sched = new_device_scheduler(
        client, factory, default_full_roster_config(), max_wave=8
    )
    factory.start()
    assert factory.wait_for_cache_sync()
    try:
        client.nodes().create(
            make_node("n1", capacity={"cpu": "2", "memory": "8Gi", "pods": 10})
        )
        assert _wait(lambda: len(sched.cache.snapshot()) == 1)
        taken = make_pod("taken", requests={"cpu": "1"})
        taken.metadata.uid = "uid-taken"
        sched._assume(taken, "n1")

        def win(name, cpu):
            pod = make_pod(name, requests={"cpu": cpu})
            pod.metadata.uid = f"uid-{name}"
            return (None, pod, "n1")

        # 1000m left after the assumption: w1 (600m) fits, w2 (600m)
        # loses to w1's local debit, w3 (300m) fits behind w1
        kept, rejected = sched._rearbitrate_winners(
            [win("w1", "600m"), win("w2", "600m"), win("w3", "300m")]
        )
        assert [w[1].metadata.name for w in kept] == ["w1", "w3"]
        assert [w[1].metadata.name for w in rejected] == ["w2"]

        # a chain without NodeResourcesFit never re-arbitrates (the
        # scalar engine would over-book identically — parity first)
        sched._rearb_capacity = False
        kept2, rejected2 = sched._rearbitrate_winners(
            [win("w4", "600m"), win("w5", "600m")]
        )
        assert len(kept2) == 2 and not rejected2
    finally:
        sched.stop()
        factory.shutdown()


def test_incremental_agg_base_matches_full_build():
    """Dirty-row aggregate builds are bit-identical to from-scratch
    builds — including port-column clearing and the assume-delta staying
    out of the persistent base."""
    from minisched_tpu.framework.nodeinfo import build_node_infos
    from minisched_tpu.models.tables import CachedNodeTableBuilder

    nodes = [
        make_node(
            f"n{i:02d}", capacity={"cpu": "8", "memory": "16Gi", "pods": 110}
        )
        for i in range(10)
    ]
    infos = build_node_infos(nodes, [])
    inc = CachedNodeTableBuilder()
    _, agg0, _ = inc.build_packed(infos, dirty=None)  # full: base seeded

    def bound(name, node, cpu="1", ports=()):
        p = make_pod(name, requests={"cpu": cpu})
        p.metadata.uid = name
        p.spec.node_name = node
        if ports:
            p.spec.containers[0].ports = list(ports)
        return p

    by_name = {ni.name: ni for ni in infos}
    by_name["n02"].add_pod(bound("x1", "n02", "1"))
    by_name["n05"].add_pod(bound("x2", "n05", "2", ports=(8080,)))
    _, agg1, _ = inc.build_packed(infos, dirty={"n02", "n05"})
    assert inc.last_dirty_rows == 2
    fresh = CachedNodeTableBuilder()
    _, full1, _ = fresh.build_packed(infos, dirty=None)
    np.testing.assert_array_equal(agg1.flat, full1.flat)

    # ports must CLEAR on re-encode (shorter row must not keep slots)
    by_name["n05"].remove_pod(bound("x2", "n05", "2", ports=(8080,)))
    _, agg2, _ = inc.build_packed(infos, dirty={"n05"})
    fresh2 = CachedNodeTableBuilder()
    _, full2, _ = fresh2.build_packed(infos, dirty=None)
    np.testing.assert_array_equal(agg2.flat, full2.flat)

    # the per-wave assume-delta folds into the COPY, never the base:
    # a delta'd build followed by a no-delta build must equal the full
    delta = {"n03": [500, 64, 0, 1, 500, 64, []]}
    inc.build_packed(infos, agg_delta=delta, dirty=set())
    _, agg3, _ = inc.build_packed(infos, dirty=set())
    np.testing.assert_array_equal(agg3.flat, full2.flat)

    # an UNTRACKED build (scan lane) between dirty builds must not eat
    # pending increments: base stays consistent with the drain sequence
    by_name["n07"].add_pod(bound("x3", "n07", "1"))
    inc.build_packed(infos)  # untracked: fresh fill, base untouched
    _, agg4, _ = inc.build_packed(infos, dirty={"n07"})
    fresh3 = CachedNodeTableBuilder()
    _, full3, _ = fresh3.build_packed(infos, dirty=None)
    np.testing.assert_array_equal(agg4.flat, full3.flat)

    # node membership change arrives as dirty=None → full rebuild
    infos2 = build_node_infos(nodes[:8], [])
    _, agg5, _ = inc.build_packed(infos2, dirty=None)
    fresh4 = CachedNodeTableBuilder()
    _, full4, _ = fresh4.build_packed(infos2, dirty=None)
    np.testing.assert_array_equal(agg5.flat, full4.flat)


def test_recorder_build_leaves_the_engine_builder_alone():
    """build, record, build: the record_results recorder builds device
    tables of its own, untracked — the cache's dirty set stays pending
    for the engine's one tracked consumer and ``_table_builder``'s
    aggregate base is not touched, so the tracked build after a record
    equals a fresh full build."""
    from minisched_tpu.engine.pipeline import build_wave
    from minisched_tpu.models.tables import CachedNodeTableBuilder
    from minisched_tpu.observability.resultstore import Store

    client, factory, sched, qpis = _popped_cluster()
    try:
        sched.result_store = Store(client)
        first = build_wave(sched, qpis[:4], sched._snapshot_for_tables())
        base_before = {
            k: v.copy() for k, v in sched._table_builder._agg_base.items()
        }
        # the cluster moves on: a pod lands on n3 behind the engine's back
        client.pods().bind_many(
            [Binding("wp7", "default", "n3")], return_objects=False
        )
        assert _wait(
            lambda: any(
                ni.name == "n3" and ni.pods for ni in sched.cache.snapshot()
            )
        )
        infos, delta, assumed = sched._snapshot_for_wave()
        sched._record_wave(
            qpis[:4], infos, (), delta, first.pod_table.capacity
        )
        assert sched.result_store.has_data("default/wp0")
        assert sched._record_builder is not sched._table_builder
        for k, v in sched._table_builder._agg_base.items():
            np.testing.assert_array_equal(v, base_before[k])
        # the dirt is still there for the tracked consumer, which then
        # builds what a from-scratch builder builds
        infos, delta, _, dirty, epoch = sched._snapshot_for_tables()
        assert dirty == {"n3"}
        _, agg, _ = sched._table_builder.build_packed(
            infos, agg_delta=delta, dirty=dirty, epoch=epoch
        )
        _, full, _ = CachedNodeTableBuilder().build_packed(
            infos, agg_delta=delta, dirty=None
        )
        np.testing.assert_array_equal(
            np.asarray(agg.flat), np.asarray(full.flat)
        )
    finally:
        sched.stop()
        factory.shutdown()


def test_cache_dirty_tracking():
    """SchedulerCache drains dirty names atomically with the snapshot;
    membership changes collapse to a full-rebuild signal; plain
    snapshots leave the set alone."""
    from minisched_tpu.engine.cache import SchedulerCache

    cache = SchedulerCache()
    cache.add_node(make_node("a"))
    cache.add_node(make_node("b"))
    infos, _assigned, dirty, _epoch = cache.snapshot_for_tables()
    assert dirty is None  # first drain: everything
    p = make_pod("p1", requests={"cpu": "1"})
    p.metadata.uid = "u1"
    p.spec.node_name = "a"
    cache.add_pod(p)
    # a plain snapshot must NOT drain
    cache.snapshot_with_assigned()
    _, _, dirty, _ = cache.snapshot_for_tables()
    assert dirty == {"a"}
    _, _, dirty, _ = cache.snapshot_for_tables()
    assert dirty == set()
    cache.delete_pod(p)
    _, _, dirty, _ = cache.snapshot_for_tables()
    assert dirty == {"a"}
    cache.add_node(make_node("c"))  # membership: full rebuild again
    _, _, dirty, _ = cache.snapshot_for_tables()
    assert dirty is None
