"""``antiaffinity-host-5000n``: its plain reference on placements written
by hand, the cell's contract line in the CPU rehearsal, the control of
``anti_affinity_broken``, and what its maker refuses."""

import importlib
import os

import pytest

import faults_antiaffinity_host
import manifest
import run
from test_rehearsal import COMPARED, holds_the_contract_line, rehearse

CELL = "antiaffinity-host-5000n.recycle-1k"
OWN = ["anti_affinity_broken", "term_dropped"]
CONFIG = run.load_json("configs", "antiaffinity-host-5000n.json")
TERM = CONFIG["measured_pods"]["anti_affinity"]
reference = importlib.import_module("references.antiaffinity_host")


def green(name, on, term=True):
    required = [{
        "topology_key": TERM["topology_key"], "namespaces": list(TERM["namespaces"]),
        "label_selector": {"match_labels": dict(TERM["match_labels"]), "match_expressions": []},
    }]
    return {
        "metadata": {"name": name, "namespace": "default", "labels": {"color": "green"}},
        "spec": {"node_name": on, "affinity": {"pod_anti_affinity": {"required": required if term else []}}},
    }


def record(bound_at=None, deleted=None, acks=None):
    deleted = deleted or {}
    return {"acks": acks or {}, "sent": [], "rebinds": [], "deleted": list(deleted), "delete_errors": 0,
            "bound_at": bound_at or {}, "deleted_at": deleted}


@pytest.mark.parametrize(
    "pods,rec,want",
    [
        # a host each: nothing
        ([green("a", "n1"), green("b", "n2")], record({"a": 1.0, "b": 2.0}), (0, 0)),
        # two on one host at the read-back
        ([green("a", "n1"), green("b", "n1")], record({"a": 1.0, "b": 2.0}), (1, 0)),
        # three on one host: three pairs
        ([green("a", "n1"), green("b", "n1"), green("c", "n1")], record({"a": 1.0, "b": 2.0, "c": 3.0}), (3, 0)),
        # the host's first pod was deleted (sent at 4.0) before the second was seen bound (5.0): no overlap
        ([green("b", "n1")], record({"a": 1.0, "b": 5.0}, {"a": [4.0, 4.1]}, {"a": "n1", "b": "n1"}), (0, 0)),
        # the second was seen bound (3.0) while the first was certainly still there (its DELETE sent at 4.0)
        ([green("b", "n1")], record({"a": 1.0, "b": 3.0}, {"a": [4.0, 4.1]}, {"a": "n1", "b": "n1"}), (1, 0)),
        # both deleted since, and they shared the host from 2.0 to 3.0
        ([], record({"a": 1.0, "b": 2.0}, {"a": [3.0, 3.1], "b": [6.0, 6.1]}, {"a": "n1", "b": "n1"}), (1, 0)),
        # a green pod read back without its term; a pod of another colour is not the deployment's
        ([green("a", "n1", term=False)], record({"a": 1.0}), (0, 1)),
        ([green("a", "n1"), dict(green("x", "n1"), metadata={"name": "x", "namespace": "default", "labels": {}})],
         record({"a": 1.0, "x": 1.5}), (0, 0)),
    ],
)
def test_the_reference_counts_what_the_deployments_guarantees_forbid(pods, rec, want):
    assert reference.violations([], pods, CONFIG, rec) == dict(zip(OWN, want))


def test_what_the_maker_makes_carries_the_term_the_reference_looks_for():
    from makers import antiaffinity_host as maker
    from minisched_tpu.controlplane.checkpoint import _encode

    nodes = maker.make_nodes(CONFIG, 7)
    assert len({n.metadata.labels[CONFIG["nodes"]["hostname_label"]] for n in nodes}) == 5000
    for kind in ("init_pods", "measured_pods"):
        pods = [_encode(p) for p in maker.make_pods(CONFIG[kind], "s7-pod", 0, 4)]
        for i, p in enumerate(pods):
            p["spec"]["node_name"] = f"n{i}"
        assert reference.violations([], pods, CONFIG, record()) == dict.fromkeys(OWN, 0)


def test_the_reference_imports_nothing_of_the_program_and_the_maker_refuses_an_older_one(monkeypatch):
    with open(os.path.join(manifest.HERE, "references", "antiaffinity_host.py")) as f:
        source = f.read()
    assert [line for line in source.splitlines() if line.startswith(("import ", "from "))] == ["import bisect", "import sys"]
    assert "__import__" not in source and "importlib" not in source
    # a program without this PR's counters (the parent) is refused when the maker is loaded, at ``hello``
    import sys

    from minisched_tpu.observability import counters

    monkeypatch.setattr(counters, "LANE_COUNTERS", tuple(c for c in counters.LANE_COUNTERS if "excl" not in c))
    monkeypatch.delitem(sys.modules, "makers.antiaffinity_host", raising=False)
    with pytest.raises(ImportError, match="occupied node"):
        importlib.import_module("makers.antiaffinity_host")
    monkeypatch.delitem(sys.modules, "makers.antiaffinity_host", raising=False)


def test_the_configuration_is_the_fixtures_number_for_number():
    """What PR 29 laid ready is what runs: everything but the names, the
    source's wording, ``architecture`` and the rehearsal's sizes."""
    fixture = run.load_json("tests", "fixtures", "antiaffinity", "configs", "antiaffinity-5000n.json")
    own = {"name", "source", "maker", "reference", "architecture", "rehearsal", "rehearsal_why"}
    assert {k: v for k, v in CONFIG.items() if k not in own} == {k: v for k, v in fixture.items() if k not in own}
    mix = run.load_json("traffic", "recycle-1k.json")
    was = run.load_json("tests", "fixtures", "antiaffinity", "traffic", "churn-1k.json")
    own = ("name", "why", "deleters")  # 32 deleters where the fixture had 8: PERF.md section 6, PR 35
    assert {k: v for k, v in mix.items() if k not in own} == {k: v for k, v in was.items() if k not in own}
    assert (mix["outstanding"], mix["chunk"], mix["senders"], mix["live_target"], mix["deleters"]) == (1024, 256, 4, 1024, 32)


@pytest.mark.parametrize("trace", [0, 1])
def test_the_rehearsal_compares_the_nineteen_and_then_these_two(capfd, trace):
    result, err = rehearse(capfd, CELL, trace)
    holds_the_contract_line(result, err, CELL, trace)
    assert list(result["compared"]) == COMPARED + OWN
    assert result["window"]["deleted_in_window"] > 0  # the delete path was on the way
    if trace:
        assert {"rest.delete_ms", "rest.delete_cpu_share", "scan.excl_nodes_share", "queue.unschedulable_share"} <= set(result["metrics"])
        assert result["metrics"]["rest.delete_ms"]["value"] > 0
        assert 0 < result["metrics"]["scan.excl_nodes_share"]["value"] <= 100


def test_green_as_plain_is_the_control_of_anti_affinity_broken(capfd, monkeypatch):
    result, err = rehearse(capfd, CELL, 0, fault=faults_antiaffinity_host.green_as_plain(monkeypatch.setattr))
    assert result["correct"] is False and result["failed"] > 0
    assert result["compared"]["anti_affinity_broken"]["number"] >= 1
    assert err.strip().splitlines()[-1] == "correct: False"
