"""``mixed-5000n``: its plain reference on placements written by hand, the
controls of its three numbers in the CPU rehearsal, and the two readers its
cell's new metrics bring."""

import importlib
import os

import pytest

import faults_mixed
import manifest
import run
from test_rehearsal import COMPARED, rehearse
from test_span_readers import MS, hand_trace, span

CELL = "mixed-5000n.drain"
OWN = ["taint_not_tolerated", "service_constraint_dropped", "kinds_missing"]
ZONE = "topology.kubernetes.io/zone"
CONFIG = run.load_json("configs", "mixed-5000n.json")
MIX = CONFIG["measured_pods"]["mix"]
reference = importlib.import_module("references.mixed")


def node(name, zone, pool=False, cordoned=False):
    labels = {ZONE: zone, **({"pool": "dedicated"} if pool else {})}
    taints = [{"key": "dedicated", "value": "true", "effect": "NoSchedule"}] if pool else []
    return {"metadata": {"name": name, "labels": labels}, "spec": {"unschedulable": cordoned, "taints": taints}}


NODES = [node("a", "moon-1"), node("b", "moon-2"), node("c", "moon-3"), node("p", "moon-1", pool=True)]
TOLERATION = {"key": "dedicated", "operator": "Equal", "value": "true", "effect": "NoSchedule"}


def sent(i, on):
    """Measured pod ``i`` as the maker sends it, read back bound to ``on``."""
    kind, labels, selector, selects, tolerates = reference.expected(i, MIX)
    constraints = []
    if selects:
        constraints = [{
            "max_skew": 1, "topology_key": ZONE, "when_unsatisfiable": "DoNotSchedule",
            "label_selector": {"match_labels": dict(selects), "match_expressions": []},
        }]
    return {
        "metadata": {"name": f"s7-pod-{i:07d}", "namespace": "default", "labels": dict(labels)},
        "spec": {"node_name": on, "node_selector": dict(selector), "tolerations": [TOLERATION] if tolerates else [],
                 "topology_spread_constraints": constraints},
    }


def first(kind, start=5000):
    return next(i for i in range(start, start + 4096) if reference.expected(i, MIX)[0] == kind)


def record(pods, bound=None):
    names = [p["metadata"]["name"] for p in pods]
    return {"sent": names, "acks": {n: "a" for n in (names if bound is None else bound)}}


def one_of_each():
    return [sent(first(k), "p" if k == "tenant" else "a") for k in reference.KINDS]


def test_the_reference_says_the_makers_dealing_again():
    """Index for index, what ``makers/mixed.py`` makes is what the
    reference expects to read back; and the shares are the configuration's."""
    from makers import mixed as maker
    from minisched_tpu.controlplane.checkpoint import _encode

    pods = [_encode(p) for p in maker.make_pods(CONFIG["measured_pods"], "s7-pod", 5000, 4096)]
    for p in pods:
        p["spec"]["node_name"] = "p"  # every kind may stand on a pool node if it tolerates it
    kinds = [reference.expected(5000 + i, MIX)[0] for i in range(4096)]
    got = reference.violations(NODES, pods, CONFIG, record(pods))
    assert got["service_constraint_dropped"] == 0 and got["kinds_missing"] == 0
    assert got["taint_not_tolerated"] == sum(k in ("service", "pinned", "plain") for k in kinds)
    assert [kinds.count(k) for k in ("pinned", "tolerating", "plain")] == [256, 256, 512]
    services = {reference.expected(i, MIX)[1]["app"] for i in range(5000, 5000 + 8192) if i % 4}
    assert len(services) == MIX["services"] == 255
    init = [_encode(p) for p in maker.make_pods(CONFIG["init_pods"], "s7-init", 0, 8)]
    for p in init:
        p["spec"]["node_name"] = "a"
    assert reference.violations(NODES, init, CONFIG, record(init)) == dict.fromkeys(OWN, 0)


@pytest.mark.parametrize(
    "change,number",
    [
        (lambda pods: None, None),
        (lambda pods: pods[4]["spec"].update(node_name="p"), "taint_not_tolerated"),  # plain on the pool
        (lambda pods: pods[0]["spec"].update(topology_spread_constraints=[]), "service_constraint_dropped"),
        (lambda pods: pods[0]["spec"]["topology_spread_constraints"][0]["label_selector"].update(match_labels={}),
         "service_constraint_dropped"),
        (lambda pods: pods[1]["spec"].update(node_selector={}), "service_constraint_dropped"),
        (lambda pods: pods[1]["spec"].update(tolerations=[]), "service_constraint_dropped"),
        (lambda pods: pods[2]["spec"].update(node_selector={}), "service_constraint_dropped"),
        (lambda pods: pods[4]["metadata"].update(labels={"app": "svc-1"}), "service_constraint_dropped"),
    ],
)
def test_the_reference_counts_what_the_deployments_guarantees_forbid(change, number):
    pods = one_of_each()
    change(pods)
    got = reference.violations(NODES, pods, CONFIG, record(pods))
    if number == "service_constraint_dropped" and pods[1]["spec"]["tolerations"] == []:
        got["taint_not_tolerated"] = 0  # the tenant stands on the pool without its toleration: both say so
    assert got == {name: int(name == number) for name in OWN}


def test_a_kind_that_bound_no_pod_is_missing():
    pods = one_of_each()
    names = [p["metadata"]["name"] for p in pods]
    assert reference.violations(NODES, pods, CONFIG, record(pods, bound=names[:2] + names[3:]))["kinds_missing"] == 1
    assert reference.violations(NODES, pods, CONFIG, record(pods, bound=[]))["kinds_missing"] == 5
    # a run too short to have sent every kind says nothing of kinds
    assert reference.violations(NODES, pods[:2], CONFIG, record(pods[:2], bound=[]))["kinds_missing"] == 0


def test_the_reference_imports_nothing_of_the_program_and_the_maker_refuses_an_older_one():
    with open(os.path.join(manifest.HERE, "references", "mixed.py")) as f:
        source = f.read()
    assert [line for line in source.splitlines() if line.startswith(("import ", "from "))] == ["import math"]
    assert "__import__" not in source and "importlib" not in source
    with open(os.path.join(manifest.HERE, "makers", "mixed.py")) as f:
        assert "from minisched_tpu.observability.counters import LANE_COUNTERS" in f.read()


def test_the_rehearsal_compares_the_nineteen_and_then_these_three(capfd):
    result, _err = rehearse(capfd, CELL, 0)
    assert list(result["compared"]) == COMPARED + OWN
    assert result["correct"] is True


@pytest.mark.parametrize(
    "make_fault,number",
    [
        (faults_mixed.untolerated_into_pool, "taint_not_tolerated"),
        (faults_mixed.constraint_stripped, "service_constraint_dropped"),
        (faults_mixed.pinned_never_bound, "kinds_missing"),
    ],
)
def test_each_of_the_three_numbers_has_its_control(capfd, monkeypatch, make_fault, number):
    result, err = rehearse(capfd, CELL, 0, fault=make_fault(monkeypatch.setattr))
    assert result["correct"] is False and result["failed"] > 0
    assert result["compared"][number]["number"] >= 1
    assert err.strip().splitlines()[-1] == "correct: False"


# -- the readers ---------------------------------------------------------------


def test_counter_share():
    read = importlib.import_module("readers.counter_share").read
    before = [("scan_rows_live", (), 10.0), ("scan_rows_total", (), 100.0)]
    after = [("scan_rows_live", (), 110.0), ("scan_rows_total", (), 500.0)]
    args = {"counter": "scan_rows_live", "of": "scan_rows_total"}
    assert read({"before": before, "after": after}, **args) == 25.0
    assert read({"before": after, "after": after}, **args) is None  # the lane did not run
    assert read({"before": before[:1], "after": after[:1]}, **args) is None  # the parent: no such counter
    assert read({"before": before[1:], "after": after[1:]}, **args) is None


def test_trace_module_per_kilo():
    """Two calls of the program, 10 ms carrying 4 pods and 30 ms carrying
    36: 40 ms for 40 pods, a second a thousand; a call that starts inside
    no span of that name is left out on both sides."""
    read = importlib.import_module("readers.trace_module_per_kilo").read
    trace = hand_trace()
    trace.host += [
        span("sched.scan_evaluate", 5, 25, 0, call=1, n=4),
        span("sched.scan_evaluate", 55, 95, 0, call=2, n=36),
    ]
    d = trace.devices[0]
    d.modules[:] = [("jit_scan_blocked(3)", 10 * MS, 10 * MS), ("jit_scan_blocked(3)", 60 * MS, 30 * MS),
                    ("jit_scan_blocked(3)", 97 * MS, 2 * MS), ("jit_wave(7)", 26 * MS, 1 * MS)]
    args = {"module": "jit_scan_blocked", "span": "sched.scan_evaluate"}
    assert read({"trace": trace}, **args) == pytest.approx(1000.0)
    assert read({"trace": trace}, module="jit_scan_exact", span="sched.scan_evaluate") is None
    assert read({"trace": trace}, module="jit_scan_blocked", span="sched.no_such") is None
    assert read({"trace": None}, **args) is None
