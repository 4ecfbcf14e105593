"""``colocate-host-5000n``: its plain reference on placements written by
hand, what its maker makes, the cell's contract line in the CPU rehearsal,
and the control of ``affinity_broken``."""

import importlib
import os

import pytest

import faults_colocate_host
import manifest
import run
from test_rehearsal import COMPARED, holds_the_contract_line, rehearse

CELL = "colocate-host-5000n.recycle-1k"
OWN = ["affinity_broken", "anti_affinity_broken", "term_dropped"]
CONFIG = run.load_json("configs", "colocate-host-5000n.json")
reference = importlib.import_module("references.colocate_host")


def _term(spec):
    return {
        "topology_key": spec["topology_key"], "namespaces": [],
        "label_selector": {"match_labels": {}, "match_expressions": [dict(e) for e in spec["match_expressions"]]},
    }


def pod(name, on, labels=None, anti=True, aff=True):
    """A pod as read back: its tier is what its name deals (``init`` and an
    even index a cache, an odd index a web); ``labels``, ``anti`` and
    ``aff`` alter what it carries."""
    tier = reference.tier_of(name, CONFIG)
    affinity = {
        "pod_anti_affinity": {"required": [_term(tier["anti_affinity"])] if anti else [], "preferred": []},
        "pod_affinity": {"required": [_term(tier["affinity"])] if aff and tier["affinity"] else [], "preferred": []},
    }
    return {
        "metadata": {"name": name, "namespace": "default", "labels": dict(tier["labels"] if labels is None else labels)},
        "spec": {"node_name": on, "affinity": affinity},
    }


def record(bound_at=None, deleted=None, acks=None):
    deleted = deleted or {}
    return {"acks": acks or {}, "sent": [], "rebinds": [], "deleted": list(deleted), "delete_errors": 0,
            "bound_at": bound_at or {}, "deleted_at": deleted}


C0, C2, C4 = "s7-pod-0000010", "s7-pod-0000012", "s7-pod-0000014"  # caches: even indices
W1, W3 = "s7-pod-0000011", "s7-pod-0000013"  # webs: odd indices
INIT = "s7-init-0000003"  # every init pod is a cache


@pytest.mark.parametrize(
    "pods,rec,want",
    [
        # a cache, then its web, on one host; a cache alone on another
        ([pod(C0, "n1"), pod(W1, "n1"), pod(C2, "n2")], record({C0: 1.0, W1: 2.0, C2: 3.0}), (0, 0, 0)),
        # a web on a host that never held a cache
        ([pod(C0, "n1"), pod(W1, "n2")], record({C0: 1.0, W1: 2.0}), (1, 0, 0)),
        # a cache and a web whose binds were read at one instant (one transaction)
        ([pod(C0, "n1"), pod(W1, "n1")], record({C0: 1.0, W1: 1.0}), (0, 0, 0)),
        # the web's bind was read before the only cache's on its host
        ([pod(C0, "n1"), pod(W1, "n1")], record({C0: 2.0, W1: 1.0}), (1, 0, 0)),
        # the standing cache tier admits a web
        ([pod(INIT, "n1"), pod(W1, "n1")], record({INIT: 0.5, W1: 2.0}), (0, 0, 0)),
        # a cache deleted just before its web was placed (the scheduler still held it): not flagged
        ([pod(W1, "n1")], record({C0: 1.0, W1: 2.5}, {C0: [2.0, 2.1]}, {C0: "n1", W1: "n1"}), (0, 0, 0)),
        # the orphan's host takes the next cache: nothing broken
        ([pod(W1, "n1"), pod(C2, "n1")], record({C0: 1.0, W1: 1.5, C2: 3.0}, {C0: [2.0, 2.1]}, {C0: "n1", W1: "n1"}),
         (0, 0, 0)),
        # a web deleted since, whose acknowledged host never held a cache
        ([pod(C0, "n1")], record({C0: 1.0, W1: 2.0}, {W1: [3.0, 3.1]}, {C0: "n1", W1: "n3"}), (1, 0, 0)),
        # two caches on one host at the read-back; two webs on one host beside a cache
        ([pod(C0, "n1"), pod(C2, "n1")], record({C0: 1.0, C2: 2.0}), (0, 1, 0)),
        ([pod(C0, "n1"), pod(W1, "n1"), pod(W3, "n1")], record({C0: 1.0, W1: 2.0, W3: 3.0}), (0, 1, 0)),
        # a cache and a web on one host are no pair; a cache deleted (sent 2.0) before the next was seen (3.0) is none
        ([pod(C2, "n1")], record({C0: 1.0, C2: 3.0}, {C0: [2.0, 2.1]}, {C0: "n1", C2: "n1"}), (0, 0, 0)),
        ([pod(C2, "n1"), pod(C4, "n1")], record({C0: 1.0, C2: 1.5, C4: 3.0}, {C0: [2.0, 2.1]}, {C0: "n1"}), (0, 2, 0)),
        # read back without a term, or without the tier's label
        ([pod(C0, "n1"), pod(W1, "n1", aff=False)], record({C0: 1.0, W1: 2.0}), (0, 0, 1)),
        ([pod(C0, "n1", anti=False), pod(W1, "n1", labels={"app": "web"})], record({C0: 1.0, W1: 2.0}), (0, 0, 2)),
        # a pod whose name deals no tier is not the deployment's
        ([dict(pod(C0, "n1"), metadata={"name": "x", "namespace": "default", "labels": {}})], record({"x": 1.0}),
         (0, 0, 0)),
    ],
)
def test_the_reference_counts_what_the_deployments_guarantees_forbid(pods, rec, want):
    assert reference.violations([], pods, CONFIG, rec) == dict(zip(OWN, want))


K0, K2 = "s7-pod-0000000", "s7-pod-0000002"  # caches
V3 = "s7-pod-0000003"  # a web: with 2 outstanding at most, created after the 2nd bind was read


@pytest.mark.parametrize(
    "deleted,phase_of_web,outstanding,other_cache_on,want",
    [
        # the only cache on the web's host was answered deleted before the web was created
        ({K0: [1.4, 1.5]}, "pod", 2, "n2", 1),
        # ... answered after the instant the web was certainly created by: the scheduler may still have held it
        ({K0: [1.4, 2.5]}, "pod", 2, "n2", 0),
        # ... but another cache on the host, bound before the web, is still there
        ({K0: [1.4, 1.5]}, "pod", 2, "n1", 0),
        # a web of the warm-up, whose creation the record does not bound
        ({K0: [1.4, 1.5]}, "warm", 2, "n2", 0),
        # the configuration's own 1,024 outstanding: pod 3 was created before any bind was read
        ({K0: [1.4, 1.5]}, "pod", 1024, "n2", 0),
    ],
)
def test_a_web_is_held_to_a_cache_not_yet_gone_when_it_was_created(deleted, phase_of_web, outstanding, other_cache_on,
                                                                   want):
    """Binds read at 1.0 (K0 on n1), 2.0 (K2) and 3.0 (the web on n1)."""
    web = V3.replace("-pod-", f"-{phase_of_web}-")
    pods = [pod(K2, other_cache_on), pod(web, "n1")]
    rec = record({K0: 1.0, K2: 2.0, web: 3.0}, deleted, {K0: "n1", K2: other_cache_on, web: "n1"})
    got = reference.violations([], pods, dict(CONFIG, outstanding_at_most=outstanding), rec)
    assert got == {"affinity_broken": want, "anti_affinity_broken": 0, "term_dropped": 0}


def test_a_pod_of_the_window_was_created_after_the_binds_its_index_says():
    binds = [1.0, 2.0, 3.0, 4.0]
    assert reference.created_after("s7-pod-0000003", binds, 2) == 2.0
    assert reference.created_after("s7-pod-0000005", binds, 2) == 4.0
    assert reference.created_after("s7-pod-0000001", binds, 2) == float("-inf")
    assert reference.created_after("s7-warm-0000003", binds, 2) == float("-inf")
    assert reference.created_after("s7-pod-0000009", binds, 2) == float("-inf")  # more binds than the record holds


def test_what_the_maker_makes_carries_what_the_reference_looks_for():
    from makers import colocate_host as maker
    from minisched_tpu.controlplane.checkpoint import _encode

    nodes = maker.make_nodes(CONFIG, 7)
    assert len({n.metadata.labels[CONFIG["nodes"]["hostname_label"]] for n in nodes}) == 5000
    init = [_encode(p) for p in maker.make_pods(CONFIG["init_pods"], "s7-init", 0, 4)]
    measured = [_encode(p) for p in maker.make_pods(CONFIG["measured_pods"], "s7-pod", 4, 6)]
    assert [p["metadata"]["labels"]["app"] for p in init + measured] == ["store"] * 4 + ["store", "web-store"] * 3
    # a web beside each measured cache, the init caches alone
    for i, p in enumerate(init + measured):
        p["spec"]["node_name"] = f"n{i if i < 4 else 4 + (i - 4) // 2}"
    bound_at = {p["metadata"]["name"]: float(i) for i, p in enumerate(init + measured)}
    assert reference.violations([], init + measured, CONFIG, record(bound_at)) == dict.fromkeys(OWN, 0)


def test_the_reference_imports_nothing_of_the_program():
    """Only the interval rule of ``antiaffinity-host-5000n``'s reference,
    which imports nothing of the program either."""
    with open(os.path.join(manifest.HERE, "references", "colocate_host.py")) as f:
        source = f.read()
    imports = [line for line in source.splitlines() if line.startswith(("import ", "from "))]
    assert imports == ["from references.antiaffinity_host import NEVER_SEEN, READ_BACK, _overlapping_pairs"]
    assert "__import__" not in source and "importlib" not in source


@pytest.mark.parametrize("trace", [0, 1])
def test_the_rehearsal_compares_the_nineteen_and_then_these_three(capfd, trace):
    result, err = rehearse(capfd, CELL, trace)
    holds_the_contract_line(result, err, CELL, trace)
    assert list(result["compared"]) == COMPARED + OWN
    assert result["window"]["deleted_in_window"] > 0  # both tiers rolled
    if trace:
        assert 0 < result["metrics"]["scan.aff_nodes_share"]["value"] < 100
        assert 0 < result["metrics"]["scan.excl_nodes_share"]["value"] < 100


def test_web_as_plain_is_the_control_of_affinity_broken(capfd, monkeypatch):
    result, err = rehearse(capfd, CELL, 0, fault=faults_colocate_host.web_as_plain(monkeypatch.setattr))
    assert result["correct"] is False and result["failed"] > 0
    assert result["compared"]["affinity_broken"]["number"] >= 1
    assert result["compared"]["term_dropped"]["number"] == 0  # the store kept what was sent
    assert err.strip().splitlines()[-1] == "correct: False"


def test_cache_delete_late_is_the_control_of_a_cache_gone_before_its_web(capfd, monkeypatch):
    """The rehearsal keeps the configuration's own ``outstanding`` for its
    closed loop, so the reference is told that one and not the cell's."""
    load_cell = run.load_cell

    def rehearsal_cell(name):
        cell = load_cell(name)
        cell["config_data"]["outstanding_at_most"] = cell["config_data"]["rehearsal"]["outstanding"]
        return cell

    monkeypatch.setattr(run, "load_cell", rehearsal_cell)
    fault = faults_colocate_host.cache_delete_late(monkeypatch.setattr, late_s=1.0)
    result, err = rehearse(capfd, CELL, 0, fault=fault, seconds="4")
    assert result["correct"] is False
    assert result["compared"]["affinity_broken"]["number"] >= 1
    assert result["compared"]["term_dropped"]["number"] == 0
    assert err.strip().splitlines()[-1] == "correct: False"
