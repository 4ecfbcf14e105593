"""The three doors of ISSUE 28: a configuration's maker and reference found
by name, a mix that deletes, each shown by fixture files laid over a copy
of ``benchmarks/`` (``overlay.py``) with no file that is there replaced.
And ISSUE 29's: a configuration states its own rehearsal, its own numbers
come after the common ones, and the client's record keeps the instants a
reference needs under a mix that deletes; the deployment they are for
(``fixtures/antiaffinity/``) is rehearsed through them.
"""

import filecmp
import importlib
import json
import os
import sys
import threading
import types

import pytest

import audit
import client
import faults
import manifest
import overlay
import run
import wire
from test_rehearsal import COMPARED as COMMON, NODES, SOUND, acks_for, holds_the_contract_line, pod, rehearse

AA_CELL = "antiaffinity-5000n.churn-1k"


@pytest.fixture
def laid_over(tmp_path, monkeypatch):
    """``benchmarks/`` with the churn mix's and the fixture deployments'
    files added, and this process pointed at it."""
    copy_dir = overlay.build(tmp_path, "churn", "pool", "antiaffinity")
    overlay.use(copy_dir, monkeypatch.setattr, monkeypatch.setenv, monkeypatch.syspath_prepend)
    return copy_dir


def test_a_deployment_and_a_mix_are_new_files(laid_over):
    """No file that was there differs, and the manifest built from the copy
    holds the fixture configuration, both cells and their metrics, and
    stands."""
    for folder, _dirs, files in os.walk(overlay.BENCH):
        rel = os.path.relpath(folder, overlay.BENCH)
        if rel.split(os.sep)[0] in ("tests", ".trace") or "__pycache__" in rel:
            continue
        for name in files:
            assert filecmp.cmp(os.path.join(folder, name), os.path.join(laid_over, rel, name), shallow=False), name
    built = manifest.build()
    assert manifest.check(built) == []
    assert {"pool-64n", "basic-5000n", "spread-5000n", "antiaffinity-5000n"} == {c["name"] for c in built["configs"]}
    assert {"pool-64n.churn", "basic-5000n.churn", AA_CELL} <= {w["name"] for w in built["workloads"]}
    by_name = {m["name"]: m for m in built["end_to_end"] + built["per_layer"]}
    assert {"pool-64n.churn", "basic-5000n.churn"} <= set(by_name["pods_bound_per_s"]["workloads"])
    assert by_name["rest.delete_ms.churn"]["workloads"] == [AA_CELL, "basic-5000n.churn", "pool-64n.churn"]
    assert by_name["wave_build.dirty_rows_per_wave"]["layer"] == "wave_build"


def test_a_fixture_may_not_replace_a_file(tmp_path):
    os.makedirs(os.path.join(overlay.FIXTURES, "churn"), exist_ok=True)
    with pytest.raises(FileExistsError):
        overlay.build(tmp_path, "churn", "churn")


@pytest.mark.parametrize("config", wire.CONFIGS)
def test_the_old_configurations_put_the_parents_objects_on_the_wire(config):
    """Seed for seed what ``cluster.py`` made at the parent commit
    (``recordings/wire.json``, written by ``wire.py`` from an unpacked
    ed30d7b): the door to a maker changed nothing for those that name none."""
    with open(os.path.join(overlay.HERE, "recordings", "wire.json")) as f:
        recording = json.load(f)
    data = run.load_json("configs", config + ".json")
    assert "maker" not in data and "reference" not in data
    got = wire.digests(client.load_maker(data), data, recording["seed"])
    assert got == recording["configs"][config]


@pytest.mark.parametrize("trace", [0, 1])
def test_the_rehearsal_of_the_churn_mix_deletes_and_is_correct(capfd, laid_over, trace):
    result, err = rehearse(capfd, "basic-5000n.churn", trace, seconds="3")
    assert result["correct"] is True and result["failed"] == 0
    assert list(result["compared"]) == COMMON
    assert all(v["number"] == 0 and v["limit"] == 0 for v in result["compared"].values())
    assert result["window"]["deleted_in_window"] > 0
    assert "live set of 192 filled" in err
    if trace:
        assert {"rest.delete_ms.churn", "wave_build.dirty_rows_per_wave"} <= set(result["metrics"])
        assert result["metrics"]["rest.delete_ms.churn"]["value"] > 0


@pytest.mark.xfail(
    strict=False,
    reason="the façade answers a small request in two segments (headers, then body) with Nagle on, so a "
    "keep-alive connection gets one answer every 40-44 ms; two deleters delete 45 pods a second and the "
    "rehearsal binds 160 (PERF.md §7 row 2): passes once the program answers in one segment",
)
def test_the_deleters_hold_the_live_set(capfd, laid_over):
    result, _err = rehearse(capfd, "basic-5000n.churn", 0, seconds="3")
    w = result["window"]
    # a wave may bind the whole backlog after the deleters' last look
    target, backlog = run.REHEARSAL["live_target"], run.REHEARSAL["outstanding"]
    assert target - run.REHEARSAL["chunk"] <= w["live_at_close"] <= target + backlog, w
    assert abs(w["deleted_in_window"] - w["bound_in_window"]) <= backlog + run.REHEARSAL["chunk"], w


def test_a_configurations_own_reference_is_compared_after_the_common_numbers(capfd, laid_over):
    result, _err = rehearse(capfd, "pool-64n.churn", 0, seconds="3")
    assert result["correct"] is True
    assert list(result["compared"]) == COMMON + ["outside_pool"]
    assert result["compared"]["outside_pool"] == {"number": 0, "limit": 0}
    assert result["window"]["deleted_in_window"] > 0


def selector_dropped(patch):
    """The fixture deployment's guarantee broken where the pod is made: the
    maker's pods lose their selector on the way into the store."""

    def fault(_service):
        from minisched_tpu.controlplane import store

        real = store.ObjectStore.create_many

        def create_many(self, kind, objs, *args, **kw):
            for o in objs:
                if kind == "Pod":
                    o.spec.node_selector = {}
            return real(self, kind, objs, *args, **kw)

        patch(store.ObjectStore, "create_many", create_many)

    return fault


@pytest.mark.parametrize(
    "cell,make_fault,number",
    [
        ("basic-5000n.churn", faults.delete_swallowed, "deleted_still_there"),
        ("pool-64n.churn", selector_dropped, "outside_pool"),
        (AA_CELL, faults.green_as_plain, "anti_affinity_broken"),
    ],
)
def test_a_broken_delete_or_a_broken_deployment_is_not_correct(capfd, monkeypatch, laid_over, cell, make_fault, number):
    result, err = rehearse(capfd, cell, 0, fault=make_fault(monkeypatch.setattr), seconds="3")
    assert result["correct"] is False and result["failed"] > 0
    assert result["compared"][number]["number"] > 0
    assert err.strip().splitlines()[-1] == "correct: False"


# -- ISSUE 29: the anti-affinity deployment, through the three edits ----------


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", ["basic-5000n.drain", "basic-5000n.trickle", "spread-5000n.drain", AA_CELL])
def test_the_contract_line_holds_with_the_deployment_laid_over(capfd, laid_over, cell, trace):
    """All four cells of the tree the next ``model_config`` PR makes: the
    three that are there read nineteen numbers as ever, the deployment's own
    cell twenty-one, at the sizes its configuration states."""
    result, err = rehearse(capfd, cell, trace, seconds="3" if cell == AA_CELL else "2")
    holds_the_contract_line(result, err, cell, trace)
    if cell == AA_CELL:
        assert list(result["compared"]) == COMMON + ["anti_affinity_broken", "term_dropped"]
        assert result["window"]["deleted_in_window"] > 0
        own = run.load_json("configs", "antiaffinity-5000n.json")["rehearsal"]
        assert f"init pods: {own['init_pods']} sent" in err and f"live set of {own['live_target']} filled" in err


def test_the_record_keeps_the_instants():
    """``acks`` answers what it answered, and the two maps of instants."""
    gen = object.__new__(client.Generator)
    gen.mu = threading.Condition()
    gen.bound = {"a": ("n1", 10.0), "b": ("n2", 11.5)}
    gen.due = {"a": 9.0, "b": 9.5, "c": 9.7}
    gen.rebinds, gen.deleted, gen.delete_errors = [], ["a"], 0
    gen.deleted_at = {"a": [12.0, 12.25]}
    assert gen.acks({}) == {
        "acks": {"a": "n1", "b": "n2"}, "sent": ["a", "b", "c"], "rebinds": [], "deleted": ["a"],
        "delete_errors": 0, "bound_at": {"a": 10.0, "b": 11.5}, "deleted_at": {"a": [12.0, 12.25]},
    }


GREEN_TERM = {"topology_key": "kubernetes.io/hostname", "match_labels": {"color": "green"}, "namespaces": ["default"]}
AA_CONFIG = {"measured_pods": {"labels": {"color": "green"}, "anti_affinity": GREEN_TERM}}


def green(name, on, term=True):
    p = pod(name, on)
    p["metadata"]["labels"] = {"color": "green"}
    required = [{"label_selector": {"match_labels": {"color": "green"}, "match_expressions": []},
                 "topology_key": "kubernetes.io/hostname", "namespaces": ["default"]}]
    p["spec"]["affinity"] = {"node_affinity": None, "pod_affinity": None,
                             "pod_anti_affinity": {"required": required if term else [], "preferred": []}}
    return p


def record(bound_at, deleted_at, nodes):
    """The client's record of green pods: who was bound where and when, and
    whose ``DELETE`` was sent and answered when."""
    return {"acks": dict(nodes), "sent": sorted(nodes), "rebinds": [], "deleted": list(deleted_at),
            "delete_errors": 0, "bound_at": bound_at, "deleted_at": deleted_at}


@pytest.mark.parametrize(
    "pods,rec,want",
    [
        # a sound record: g1 left node a (DELETE sent at 5) before g2 was seen bound there (at 6)
        ([green("g2", "a"), green("g3", "b")],
         record({"g1": 1.0, "g2": 6.0, "g3": 2.0}, {"g1": [5.0, 5.5]}, {"g1": "a", "g2": "a", "g3": "b"}),
         {"anti_affinity_broken": 0, "term_dropped": 0}),
        # the same two on a, but g2 was seen bound (at 4) before g1's DELETE was even sent (at 5)
        ([green("g2", "a"), green("g3", "b")],
         record({"g1": 1.0, "g2": 4.0, "g3": 2.0}, {"g1": [5.0, 5.5]}, {"g1": "a", "g2": "a", "g3": "b"}),
         {"anti_affinity_broken": 1, "term_dropped": 0}),
        # g2 bound while g1's DELETE was in flight (sent 5, answered 5.5): not certain, not counted
        ([green("g2", "a")],
         record({"g1": 1.0, "g2": 5.2}, {"g1": [5.0, 5.5]}, {"g1": "a", "g2": "a"}),
         {"anti_affinity_broken": 0, "term_dropped": 0}),
        # two that are both still there on read-back, on one node
        ([green("g1", "a"), green("g2", "a")], record({"g1": 1.0, "g2": 2.0}, {}, {"g1": "a", "g2": "a"}),
         {"anti_affinity_broken": 1, "term_dropped": 0}),
        # both deleted since, and their lifetimes on a overlapped: held to the record alone
        ([], record({"g1": 1.0, "g2": 2.0}, {"g1": [3.0, 3.1], "g2": [4.0, 4.1]}, {"g1": "a", "g2": "a"}),
         {"anti_affinity_broken": 1, "term_dropped": 0}),
        # three at once on one node are three pairs
        ([green("g1", "a"), green("g2", "a"), green("g3", "a")],
         record({"g1": 1.0, "g2": 2.0, "g3": 3.0}, {}, {"g1": "a", "g2": "a", "g3": "a"}),
         {"anti_affinity_broken": 3, "term_dropped": 0}),
        # a green pod that lost its term on the way into the store
        ([green("g1", "a"), green("g2", "b", term=False)], record({"g1": 1.0, "g2": 2.0}, {}, {"g1": "a", "g2": "b"}),
         {"anti_affinity_broken": 0, "term_dropped": 1}),
        # a plain pod beside a green one breaks nothing
        ([green("g1", "a"), pod("p", "a")], record({"g1": 1.0, "p": 2.0}, {}, {"g1": "a", "p": "a"}),
         {"anti_affinity_broken": 0, "term_dropped": 0}),
    ],
)
def test_the_anti_affinity_reference_on_records_written_by_hand(laid_over, pods, rec, want):
    reference = importlib.import_module("references.antiaffinity")
    assert reference.violations(NODES, pods, AA_CONFIG, rec) == want


def test_the_anti_affinity_reference_imports_nothing_of_the_program(laid_over):
    with open(os.path.join(laid_over, "references", "antiaffinity.py")) as f:
        source = f.read()
    assert "minisched" not in source and "import cluster" not in source


# -- the audit's new numbers, on records written by hand ---------------------


GONE = pod("gone", "b")


@pytest.mark.parametrize(
    "pods,acks,number",
    [
        (SOUND, acks_for(SOUND + [GONE], deleted=["gone"]), None),
        (SOUND + [GONE], acks_for(SOUND + [GONE], deleted=["gone"]), "deleted_still_there"),
        (SOUND, acks_for(SOUND + [GONE], deleted=["gone"], delete_errors=2), "delete_errors"),
        (SOUND, acks_for(SOUND + [pod("gone", "nowhere")], deleted=["gone"]), "deleted_on_unknown_node"),
        (SOUND, acks_for(SOUND + [GONE]), "pods_missing"),  # absent and never deleted
    ],
)
def test_audit_knows_a_pod_may_go(pods, acks, number):
    compared = audit.checks(NODES, pods, acks, len(NODES), {})
    assert audit.verdict(compared) == (number is None), compared
    if number:
        assert compared[number][0] > 0


def test_a_reference_may_not_take_a_common_numbers_name(monkeypatch):
    fake = types.ModuleType("references.greedy")
    fake.violations = lambda nodes, pods, config, record: {"pods_missing": 0}
    monkeypatch.setitem(sys.modules, "references.greedy", fake)
    with pytest.raises(ValueError, match="pods_missing"):
        audit.checks(NODES, SOUND, acks_for(SOUND), len(NODES), {}, {"reference": "greedy"})


def test_counter_ratio(laid_over):
    read = importlib.import_module("readers.counter_ratio").read
    before = [("wave_pipeline_waves", (), 10.0), ("wave_pipeline_dirty_rows", (), 100.0)]
    after = [("wave_pipeline_waves", (), 14.0), ("wave_pipeline_dirty_rows", (), 2100.0)]
    args = {"counter": "wave_pipeline_dirty_rows", "per": "wave_pipeline_waves"}
    assert read({"before": before, "after": after}, **args) == 500.0
    assert read({"before": before[:1], "after": after[:1]}, **args) == 0.0  # not registered yet: no row was dirty
    assert read({"before": after, "after": after}, **args) is None
