"""``antiaffinity-svc-5000n``: its plain reference on placements written by
hand, what its maker makes and how it deals, the cell's contract line in the
CPU rehearsal, and the two controls of ``anti_affinity_broken``."""

import collections
import importlib
import os

import pytest

import faults_antiaffinity_svc
import manifest
import run
from test_rehearsal import COMPARED, holds_the_contract_line, rehearse

CELL = "antiaffinity-svc-5000n.recycle-1k"
OWN = ["anti_affinity_broken", "term_dropped"]
CONFIG = run.load_json("configs", "antiaffinity-svc-5000n.json")
reference = importlib.import_module("references.antiaffinity_svc")


def pod(name, on, app=None, term_app=None, term=True):
    """A pod as read back: its service is what its name deals; ``app`` and
    ``term_app`` alter the label and the term's value, ``term=False`` drops
    the term."""
    labels, want = reference.dealt(name, CONFIG)
    app = app or labels["app"]
    required = []
    if term:
        t = dict(want, label_selector={"match_labels": {}, "match_expressions": [
            {"key": "app", "operator": "In", "values": [term_app or labels["app"]]}]})
        required = [t]
    return {
        "metadata": {"name": name, "namespace": "default", "labels": {"app": app}},
        "spec": {"node_name": on, "affinity": {"pod_anti_affinity": {"required": required, "preferred": []}}},
    }


def record(bound_at=None, deleted=None, acks=None):
    deleted = deleted or {}
    return {"acks": acks or {}, "sent": [], "rebinds": [], "deleted": list(deleted), "delete_errors": 0,
            "bound_at": bound_at or {}, "deleted_at": deleted}


def _of(service, start=0, phase="pod"):
    """The first names from ``start`` that the dealing gives ``service``."""
    mix = CONFIG["measured_pods"]["mix"]
    return [f"s7-{phase}-{i:07d}" for i in range(start, start + 400) if reference.service_of(i, mix) == service]


S1 = _of(1)
S2 = _of(2)
INIT1 = _of(1, phase="init")[0]


@pytest.mark.parametrize(
    "pods,rec,want",
    [
        # two services on one host, one pod each: nothing
        ([pod(S1[0], "n1"), pod(S2[0], "n1")], record({S1[0]: 1.0, S2[0]: 2.0}), (0, 0)),
        # two pods of one service on one host at the read-back
        ([pod(S1[0], "n1"), pod(S1[1], "n1")], record({S1[0]: 1.0, S1[1]: 2.0}), (1, 0)),
        # a standing pod and a measured pod of one service share a host
        ([pod(INIT1, "n1"), pod(S1[0], "n1")], record({INIT1: 0.5, S1[0]: 2.0}), (1, 0)),
        # three of one service on one host: three pairs
        ([pod(S1[0], "n1"), pod(S1[1], "n1"), pod(S1[2], "n1")], record({S1[0]: 1.0, S1[1]: 2.0, S1[2]: 3.0}),
         (3, 0)),
        # the first was deleted (sent 2.0) before the second was seen bound (3.0): no overlap
        ([pod(S1[1], "n1")], record({S1[0]: 1.0, S1[1]: 3.0}, {S1[0]: [2.0, 2.1]}, {S1[0]: "n1"}), (0, 0)),
        # the second was seen bound (1.5) while the first was certainly still there (sent 2.0)
        ([pod(S1[1], "n1")], record({S1[0]: 1.0, S1[1]: 1.5}, {S1[0]: [2.0, 2.1]}, {S1[0]: "n1"}), (1, 0)),
        # both deleted since, and they shared the host from 2.0 to 3.0
        ([], record({S1[0]: 1.0, S1[1]: 2.0}, {S1[0]: [3.0, 3.1], S1[1]: [6.0, 6.1]}, {S1[0]: "n1", S1[1]: "n1"}),
         (1, 0)),
        # read back without the term, with another service's label, with another service's term
        ([pod(S1[0], "n1", term=False)], record({S1[0]: 1.0}), (0, 1)),
        ([pod(S1[0], "n1", app="svc-2")], record({S1[0]: 1.0}), (0, 1)),
        ([pod(S1[0], "n1", term_app="svc-2")], record({S1[0]: 1.0}), (0, 1)),
        # a pod whose name deals no service is not the deployment's
        ([dict(pod(S1[0], "n1"), metadata={"name": "x", "namespace": "default", "labels": {"app": "svc-1"}}),
          pod(S1[0], "n1")], record({"x": 1.0, S1[0]: 1.5}), (0, 0)),
    ],
)
def test_the_reference_counts_what_the_deployments_guarantees_forbid(pods, rec, want):
    assert reference.violations([], pods, CONFIG, rec) == dict(zip(OWN, want))


def test_the_configuration_is_held_to_the_dealing():
    """``mixed-5000n``'s dealing over 255 services; a standing fleet of
    20,000 that holds every service, half the hosts to the largest; a cap
    the window never reaches; the rehearsal's fleet in the same proportion."""
    from makers import antiaffinity_svc as maker
    from makers import mixed

    assert maker.service_of is mixed.service_of
    mixed_mix = run.load_json("configs", "mixed-5000n.json")["measured_pods"]["mix"]
    for kind in ("init_pods", "measured_pods"):
        mix = CONFIG[kind]["mix"]
        assert (mix["services"], mix["golden"], mix["service_label"], mix["service_prefix"]) == (
            mixed_mix["services"], mixed_mix["golden"], mixed_mix["service_label"], mixed_mix["service_prefix"])
    mix = CONFIG["init_pods"]["mix"]
    fleet = collections.Counter(reference.service_of(i, mix) for i in range(CONFIG["init_pods"]["count"]))
    assert (CONFIG["init_pods"]["count"], fleet[1], fleet[2], len(fleet)) == (20000, 2502, 1462, 255)
    assert all(maker.service_of(i, mix) == reference.service_of(i, mix) for i in range(0, 40000, 7))
    mixture = run.load_json("traffic", "recycle-1k.json")
    warm = run.load_json("workloads", CELL + ".json")["params"]["warm_bursts"]
    need = CONFIG["init_pods"]["count"] + sum(warm) + mixture["live_target"] + mixture["outstanding"]
    assert need < CONFIG["live_pod_cap"] == 40000 == CONFIG["nodes"]["count"] * 8  # 12.5 %: one a host
    r = CONFIG["rehearsal"]
    assert r["init_pods"] + sum(r["warm_bursts"]) + r["live_target"] + r["outstanding"] < r["live_pod_cap"]
    rehearsal_fleet = collections.Counter(reference.service_of(i, mix) for i in range(r["init_pods"]))
    assert rehearsal_fleet[1] * 2 > r["nodes"] > rehearsal_fleet[1]  # half the hosts, as 2,502 of 5,000
    assert CONFIG["reduced"] == ["measured_pods"]


def test_what_the_maker_makes_carries_what_the_reference_looks_for():
    from makers import antiaffinity_svc as maker
    from minisched_tpu.controlplane.checkpoint import _encode

    nodes = maker.make_nodes(CONFIG, 7)
    assert len({n.metadata.labels[CONFIG["nodes"]["hostname_label"]] for n in nodes}) == 5000
    init = [_encode(p) for p in maker.make_pods(CONFIG["init_pods"], "s7-init", 0, 40)]
    measured = [_encode(p) for p in maker.make_pods(CONFIG["measured_pods"], "s7-pod", 40, 40)]
    mix = CONFIG["measured_pods"]["mix"]
    assert [p["metadata"]["labels"]["app"] for p in init + measured] == [
        f"svc-{reference.service_of(i, mix)}" for i in range(80)]
    for i, p in enumerate(init + measured):
        p["spec"]["node_name"] = f"n{i}"
    bound_at = {p["metadata"]["name"]: float(i) for i, p in enumerate(init + measured)}
    assert reference.violations([], init + measured, CONFIG, record(bound_at)) == dict.fromkeys(OWN, 0)
    # the same pods, two of one service on one host
    first, second = [p for p in init + measured if p["metadata"]["labels"]["app"] == "svc-1"][:2]
    second["spec"]["node_name"] = first["spec"]["node_name"]
    assert reference.violations([], init + measured, CONFIG, record(bound_at))["anti_affinity_broken"] == 1


def test_the_reference_imports_nothing_of_the_program():
    """Only the interval rule of ``antiaffinity-host-5000n``'s reference,
    which imports nothing of the program either."""
    with open(os.path.join(manifest.HERE, "references", "antiaffinity_svc.py")) as f:
        source = f.read()
    imports = [line for line in source.splitlines() if line.startswith(("import ", "from "))]
    assert imports == ["import math", "from references.antiaffinity_host import NEVER_SEEN, READ_BACK, _overlapping_pairs"]
    assert "__import__" not in source and "importlib" not in source


@pytest.mark.parametrize("trace", [0, 1])
def test_the_rehearsal_compares_the_nineteen_and_then_these_two(capfd, trace):
    result, err = rehearse(capfd, CELL, trace)
    holds_the_contract_line(result, err, CELL, trace)
    assert list(result["compared"]) == COMPARED + OWN
    assert result["window"]["deleted_in_window"] > 0  # the services rolled
    if trace:
        assert 0 < result["metrics"]["scan.excl_nodes_share"]["value"] < 100
        assert result["metrics"]["scan.combo_rows_ms_per_call"]["value"] > 0
        assert "scan.aff_nodes_share" not in result["metrics"]


@pytest.mark.parametrize("make_fault", [faults_antiaffinity_svc.svc_as_plain, faults_antiaffinity_svc.block_carry_dropped])
def test_each_control_breaks_one_replica_a_host(capfd, monkeypatch, make_fault):
    result, err = rehearse(capfd, CELL, 0, fault=make_fault(monkeypatch.setattr))
    assert result["correct"] is False and result["failed"] > 0
    assert result["compared"]["anti_affinity_broken"]["number"] >= 1
    assert result["compared"]["term_dropped"]["number"] == 0  # the store kept what was sent
    assert err.strip().splitlines()[-1] == "correct: False"
