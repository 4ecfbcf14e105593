"""Resultstore + simulator plugin wrapper (observability pipeline).

Mirrors the reference's test strategy (SURVEY.md §4):
``resultstore/store_test.go`` (state transitions + annotation flushing via
a fake client and a real informer) and ``plugin/plugins_test.go`` (wrapper
behavior with hand-written fake plugins and a mock store)."""

from __future__ import annotations

import json
import time
from unittest import mock

from minisched_tpu.api.objects import make_node, make_pod
from minisched_tpu.controlplane.client import Client
from minisched_tpu.controlplane.informer import (
    ResourceEventHandlers,
    SharedInformerFactory,
)
from minisched_tpu.framework.nodeinfo import build_node_infos
from minisched_tpu.framework.types import CycleState, NodeScore, Status
from minisched_tpu.observability import annotation
from minisched_tpu.observability.resultstore import PASSED_FILTER_MESSAGE, Store
from minisched_tpu.plugins.simulator import (
    convert_for_simulator,
    make_simulator_plugin,
    plugin_name,
    register_simulator_plugins,
)
from minisched_tpu.service.config import (
    PluginEnabled,
    PluginSet,
    default_full_roster_config,
)
from minisched_tpu.utils.retry import (
    RetryTimeoutError,
    retry_with_exponential_backoff,
)


# ---------------------------------------------------------------------------
# fake plugins (plugins_test.go:981-1042)
# ---------------------------------------------------------------------------


class FakeFilterPlugin:
    def __init__(self, reject: bool = False):
        self.reject = reject

    def name(self):
        return "FakeFilter"

    def filter(self, state, pod, node_info):
        if self.reject:
            return Status.unschedulable("fake says no")
        return Status.success()


class FakeScorePlugin:
    def name(self):
        return "FakeScore"

    def score(self, state, pod, node_name):
        return len(node_name), Status.success()

    def score_extensions(self):
        return None


class FakeNormalizingScorePlugin:
    def name(self):
        return "FakeNorm"

    def score(self, state, pod, node_name):
        return 10, Status.success()

    def score_extensions(self):
        outer = self

        class Ext:
            def normalize_score(self, state, pod, scores):
                for ns in scores:
                    ns.score = ns.score * 2
                return Status.success()

        return Ext()


# ---------------------------------------------------------------------------
# retry util (util/retry.go)
# ---------------------------------------------------------------------------


def test_retry_succeeds_after_failures():
    sleeps = []
    calls = {"n": 0}

    def fn():
        calls["n"] += 1
        return calls["n"] >= 3

    retry_with_exponential_backoff(fn, sleep=sleeps.append)
    assert calls["n"] == 3
    assert sleeps == [0.1, 0.1 * 3]  # 100ms initial, factor 3


def test_retry_exhausts():
    import pytest

    with pytest.raises(RetryTimeoutError):
        retry_with_exponential_backoff(lambda: False, sleep=lambda _: None)


# ---------------------------------------------------------------------------
# store state transitions (store_test.go:17-406)
# ---------------------------------------------------------------------------


def test_store_records_and_deletes():
    s = Store()
    s.add_filter_result("default/p1", "n1", "PluginA", "reason")
    s.add_score_result("default/p1", "n1", "PluginA", 42)
    s.add_normalized_score_result("default/p1", "n1", "PluginA", 50, weight=2)
    f, sc, fin = s.get_data("default/p1")
    assert f == {"n1": {"PluginA": "reason"}}
    assert sc == {"n1": {"PluginA": 42}}
    assert fin == {"n1": {"PluginA": 100}}  # normalized × weight
    assert s.has_data("default/p1")
    s.delete_data("default/p1")
    assert not s.has_data("default/p1")


def test_store_flush_to_annotations_via_informer():
    """store.go:62-67,90-135: a pod Update event flushes results onto the
    pod's annotations and clears the entry."""
    client = Client()
    store = Store(client)
    factory = SharedInformerFactory(client.store)
    factory.informer_for("Pod").add_event_handlers(
        ResourceEventHandlers(on_update=store.add_scheduling_result_to_pod)
    )
    factory.start()
    pod = client.pods().create(make_pod("p1"))
    store.add_filter_result(pod.metadata.key, "n1", "PluginA", PASSED_FILTER_MESSAGE)
    store.add_normalized_score_result(pod.metadata.key, "n1", "PluginA", 77)
    client.pods().update(pod.clone())  # any update triggers the flush

    deadline = time.time() + 5
    while time.time() < deadline:
        got = client.pods().get("p1")
        if annotation.FILTER_RESULT in got.metadata.annotations:
            break
        time.sleep(0.05)
    got = client.pods().get("p1")
    assert json.loads(got.metadata.annotations[annotation.FILTER_RESULT]) == {
        "n1": {"PluginA": "passed"}
    }
    assert json.loads(got.metadata.annotations[annotation.FINAL_SCORE_RESULT]) == {
        "n1": {"PluginA": 77}
    }
    assert not store.has_data(pod.metadata.key)
    factory.shutdown()


# ---------------------------------------------------------------------------
# simulator wrapper (plugins_test.go:389-772)
# ---------------------------------------------------------------------------


def test_wrapper_records_filter_results():
    store = mock.Mock(spec=Store)
    node = make_node("n1")
    [ni] = build_node_infos([node], [])
    pod = make_pod("p")
    ok = make_simulator_plugin(FakeFilterPlugin(), store)
    assert ok.name() == "FakeFilterForSimulator"
    st = ok.filter(CycleState(), pod, ni)
    assert st.is_success()
    store.add_filter_result.assert_called_once_with(
        "default/p", "n1", "FakeFilter", PASSED_FILTER_MESSAGE
    )

    store2 = mock.Mock(spec=Store)
    bad = make_simulator_plugin(FakeFilterPlugin(reject=True), store2)
    st = bad.filter(CycleState(), pod, ni)
    assert not st.is_success()
    store2.add_filter_result.assert_called_once_with(
        "default/p", "n1", "FakeFilter", "fake says no"
    )


def test_wrapper_records_scores_without_extensions():
    """A plugin without NormalizeScore records raw × weight as final."""
    store = mock.Mock(spec=Store)
    pod = make_pod("p")
    w = make_simulator_plugin(FakeScorePlugin(), store, weight=3)
    score, st = w.score(CycleState(), pod, "node-a")
    assert score == len("node-a") and st.is_success()
    store.add_score_result.assert_called_once_with(
        "default/p", "node-a", "FakeScore", 6
    )
    store.add_normalized_score_result.assert_called_once_with(
        "default/p", "node-a", "FakeScore", 6, 3
    )


def test_wrapper_records_normalized_scores():
    store = mock.Mock(spec=Store)
    pod = make_pod("p")
    w = make_simulator_plugin(FakeNormalizingScorePlugin(), store, weight=2)
    w.score(CycleState(), pod, "n1")
    store.add_normalized_score_result.assert_not_called()  # waits for normalize
    scores = [NodeScore("n1", 10), NodeScore("n2", 5)]
    st = w.score_extensions().normalize_score(CycleState(), pod, scores)
    assert st.is_success()
    assert [ns.score for ns in scores] == [20, 10]
    store.add_normalized_score_result.assert_any_call(
        "default/p", "n1", "FakeNorm", 20, 2
    )
    store.add_normalized_score_result.assert_any_call(
        "default/p", "n2", "FakeNorm", 10, 2
    )


def test_wrapper_capability_truthful():
    from minisched_tpu.framework.plugin import implements_filter, implements_score

    store = Store()
    f = make_simulator_plugin(FakeFilterPlugin(), store)
    s = make_simulator_plugin(FakeScorePlugin(), store)
    assert implements_filter(f) and not implements_score(f)
    assert implements_score(s) and not implements_filter(s)


# ---------------------------------------------------------------------------
# config conversion (ConvertForSimulator, plugins.go:146-202)
# ---------------------------------------------------------------------------


def test_convert_for_simulator():
    ps = PluginSet(
        enabled=[PluginEnabled("NodeResourcesFit"), PluginEnabled("TaintToleration", 3)]
    )
    out = convert_for_simulator(ps)
    assert [e.name for e in out.enabled] == [
        "NodeResourcesFitForSimulator",
        "TaintTolerationForSimulator",
    ]
    assert out.enabled[1].weight == 3
    assert out.disabled == ["*"]


def test_registered_simulator_plugins_build():
    from minisched_tpu.plugins.registry import build_plugins
    from minisched_tpu.plugins.simulator import convert_configuration_for_simulator

    store = Store()
    cfg = default_full_roster_config()
    register_simulator_plugins(store, {e.name: e.weight for e in cfg.score.enabled})
    converted = convert_configuration_for_simulator(cfg)
    chains = build_plugins(converted)
    assert all(p.name().endswith("ForSimulator") for p in chains.filter)
    assert all(p.name().endswith("ForSimulator") for p in chains.score)
    assert {p.name() for p in chains.filter} == {
        plugin_name(e.name) for e in cfg.filter.enabled
    }


# ---------------------------------------------------------------------------
# end-to-end: live scheduler with result recording
# ---------------------------------------------------------------------------


def test_live_scheduler_records_results_onto_annotations():
    from minisched_tpu.service.config import default_scheduler_config
    from minisched_tpu.service.service import SchedulerService

    client = Client()
    svc = SchedulerService(client)
    svc.start_scheduler(
        default_scheduler_config(time_scale=0.01), record_results=True
    )
    client.nodes().create(make_node("node1"))
    client.pods().create(make_pod("pod1"))
    deadline = time.time() + 10
    while time.time() < deadline:
        got = client.pods().get("pod1")
        if (
            got.spec.node_name
            and annotation.FILTER_RESULT in got.metadata.annotations
        ):
            break
        time.sleep(0.05)
    got = client.pods().get("pod1")
    svc.shutdown_scheduler()
    assert got.spec.node_name == "node1"
    filt = json.loads(got.metadata.annotations[annotation.FILTER_RESULT])
    assert filt["node1"]["NodeUnschedulable"] == PASSED_FILTER_MESSAGE
    final = json.loads(got.metadata.annotations[annotation.FINAL_SCORE_RESULT])
    assert final["node1"]["NodeNumber"] == 10  # pod1 suffix matches node1


def test_restart_keeps_result_recording():
    """restart_scheduler must re-wire the flush handler and avoid double
    conversion (regression: results accumulated forever after restart)."""
    from minisched_tpu.service.config import default_scheduler_config
    from minisched_tpu.service.service import SchedulerService

    client = Client()
    svc = SchedulerService(client)
    svc.start_scheduler(
        default_scheduler_config(time_scale=0.01), record_results=True
    )
    svc.restart_scheduler()
    cfg = svc.get_scheduler_config()
    # stored config is the pre-conversion one: no ForSimulatorForSimulator
    assert all("ForSimulator" not in e.name for e in cfg.filter.enabled)
    client.nodes().create(make_node("node1"))
    client.pods().create(make_pod("pod1"))
    deadline = time.time() + 10
    while time.time() < deadline:
        got = client.pods().get("pod1")
        if got.spec.node_name and annotation.FILTER_RESULT in got.metadata.annotations:
            break
        time.sleep(0.05)
    got = client.pods().get("pod1")
    svc.shutdown_scheduler()
    assert got.spec.node_name == "node1"
    assert annotation.FILTER_RESULT in got.metadata.annotations
    assert not svc.result_store.has_data("default/pod1")


def test_flush_does_not_clobber_concurrent_bind():
    """The annotation flush must be an atomic mutate: a bind landing
    between read and write survives (regression: last-writer-wins race)."""
    from minisched_tpu.api.objects import Binding

    client = Client()
    store = Store(client)
    pod = client.pods().create(make_pod("p1"))
    store.add_filter_result(pod.metadata.key, "n1", "PluginA", "passed")

    real_mutate = client.store.mutate
    bound = {"done": False}

    def racing_mutate(kind, ns, name, fn):
        # simulate the binding goroutine landing first
        if not bound["done"]:
            bound["done"] = True
            client.pods().bind(Binding("p1", "default", "n1"))
        return real_mutate(kind, ns, name, fn)

    client.store.mutate = racing_mutate
    try:
        store.add_scheduling_result_to_pod(pod, pod)
    finally:
        client.store.mutate = real_mutate
    got = client.pods().get("p1")
    assert got.spec.node_name == "n1"  # bind survived
    assert annotation.FILTER_RESULT in got.metadata.annotations


# ---------------------------------------------------------------------------
# batch bridge: the fused kernel's diagnostics land in the same store
# ---------------------------------------------------------------------------


def test_record_batch_result_from_diagnostics():
    from minisched_tpu.models.tables import build_node_table, build_pod_table
    from minisched_tpu.ops import fused
    from minisched_tpu.plugins.nodenumber import NodeNumber
    from minisched_tpu.plugins.nodeunschedulable import NodeUnschedulable

    nodes = [make_node("n0", unschedulable=True), make_node("n1")]
    pods = [make_pod("p1")]
    node_table, node_names = build_node_table(nodes)
    pod_table, _ = build_pod_table(pods)
    nn = NodeNumber()
    ev = fused.FusedEvaluator(
        [NodeUnschedulable()], [nn], [nn], with_diagnostics=True
    )
    result = ev(pod_table, node_table)
    store = Store()
    store.record_batch_result(
        result,
        ["default/p1"],
        node_names,
        ["NodeUnschedulable"],
        ["NodeNumber"],
        reasons={"NodeUnschedulable": "node(s) were unschedulable"},
    )
    filt, score, final = store.get_data("default/p1")
    assert filt["n0"]["NodeUnschedulable"] == "node(s) were unschedulable"
    assert filt["n1"]["NodeUnschedulable"] == PASSED_FILTER_MESSAGE
    assert score["n1"]["NodeNumber"] == 10  # raw score (pre-normalize)
    assert final["n1"]["NodeNumber"] == 10


def _recorded_pods(pods, nodes, n_bound):
    """Drive ``record_results=True, device_mode=True`` over ``nodes`` and
    ``pods``; returns (the engine, the bound pods) once ``n_bound`` pods
    are bound AND carry both result annotations."""
    import time

    from minisched_tpu.controlplane.client import Client
    from minisched_tpu.observability.annotation import (
        FILTER_RESULT,
        SCORE_RESULT,
    )
    from minisched_tpu.service.config import default_full_roster_config
    from minisched_tpu.service.service import SchedulerService

    client = Client()
    for node in nodes:
        client.nodes().create(node)
    for pod in pods:
        client.pods().create(pod)
    svc = SchedulerService(client)
    sched = svc.start_scheduler(
        default_full_roster_config(), record_results=True, device_mode=True,
        max_wave=8,
    )
    try:
        deadline = time.time() + 120
        while time.time() < deadline:
            bound = [p for p in client.pods().list() if p.spec.node_name]
            withann = [
                p for p in bound
                if FILTER_RESULT in p.metadata.annotations
                and SCORE_RESULT in p.metadata.annotations
            ]
            if len(bound) == n_bound and len(withann) == n_bound:
                return sched, withann
            time.sleep(0.1)
        raise AssertionError(
            f"{len(bound)} bound, {len(withann)} annotated of {n_bound}"
        )
    finally:
        svc.shutdown_scheduler()


def test_device_mode_records_wave_results_onto_annotations():
    """record_results=True + device_mode=True: the engine is the one
    pipelined, packed engine (a build worker exists), it ingests a
    diagnostics evaluation per wave (record_batch_result) and the flush
    hook lands the same scheduler-simulator/* annotations the scalar
    recorders produce on EVERY bound pod (SURVEY §2 row 10 — the batch
    path emits the same artifact)."""
    import json

    from minisched_tpu.api.objects import make_node, make_pod
    from minisched_tpu.engine.pipeline import WavePipeline
    from minisched_tpu.observability.annotation import (
        FILTER_RESULT,
        SCORE_RESULT,
    )

    sched, annotated = _recorded_pods(
        [make_pod(f"pod{i}", requests={"cpu": "250m"}) for i in range(3)],
        [
            make_node(f"node{i}", capacity={"cpu": "2", "memory": "4Gi",
                                            "pods": 110})
            for i in range(4)
        ],
        n_bound=3,
    )
    assert isinstance(sched._pipeline, WavePipeline)
    rec = json.loads(annotated[0].metadata.annotations[FILTER_RESULT])
    # per-node filter verdicts for the in-tree roster, unwrapped names
    assert "node0" in rec
    assert rec["node0"]["NodeUnschedulable"] == "passed"
    assert "NodeResourcesFit" in rec["node0"]
    score = json.loads(annotated[0].metadata.annotations[SCORE_RESULT])
    assert "TaintToleration" in score["node0"]


def test_device_mode_records_exact_scan_lane_results():
    """The twin through the cross-pod lane: a zone-spread pod is deferred
    to the scan backlog, rides the exact scan (a burst of one), and gets
    the same per-plugin record — PodTopologySpread's verdicts among them —
    beside the plain pod of its batch."""
    import json

    from minisched_tpu.api.objects import (
        LabelSelector,
        TopologySpreadConstraint,
        make_node,
        make_pod,
    )
    from minisched_tpu.observability import counters
    from minisched_tpu.observability.annotation import FILTER_RESULT

    spread = make_pod("spread0", requests={"cpu": "250m"},
                      labels={"app": "web"})
    spread.spec.topology_spread_constraints = [
        TopologySpreadConstraint(
            max_skew=1,
            topology_key="zone",
            when_unsatisfiable="DoNotSchedule",
            label_selector=LabelSelector(match_labels={"app": "web"}),
        )
    ]
    scans_before = counters.get("scan.rows_live")
    sched, annotated = _recorded_pods(
        [spread, make_pod("plain0", requests={"cpu": "250m"})],
        [
            make_node(f"node{i}", labels={"zone": f"z{i % 2}"},
                      capacity={"cpu": "2", "memory": "4Gi", "pods": 110})
            for i in range(4)
        ],
        n_bound=2,
    )
    assert sched._scan_scheduler is not None, "the exact lane never ran"
    assert counters.get("scan.rows_live") == scans_before  # not the blocked
    rec = json.loads(
        next(p for p in annotated if p.metadata.name == "spread0")
        .metadata.annotations[FILTER_RESULT]
    )
    assert set(rec) == {f"node{i}" for i in range(4)}
    assert rec["node0"]["PodTopologySpread"] == "passed"
