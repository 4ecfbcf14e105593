"""The tiny-size run of every cell on the CPU, the TPU gate, and the faults
that ``correct`` has to catch.

The rehearsal is reached only as a Python argument of ``run.main``: the
same argv through the command line ends at the TPU gate.
"""

import importlib
import json
import os
import subprocess
import sys

import pytest

import audit
import faults
import manifest
import reference
import run

CELLS = [w["name"] for w in manifest.build()["workloads"]]
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
COMPARED = [
    "nodes_missing", "pods_missing", "pods_unsent", "pods_twice", "unbound_after_grace", "on_unknown_node",
    "on_unschedulable", "selector_broken", "nodes_over_allocatable", "skew_over_max", "ack_not_on_readback",
    "bound_never_acked", "acked_twice", "wave_parked", "dispatch_healed", "compiles_in_window",
    "deleted_still_there", "delete_errors", "deleted_on_unknown_node",
]


def rehearse(capfd, cell, trace, fault=None, seconds="2"):
    argv = ["--workload", cell, "--seed", "3000000019", "--seconds", seconds, "--trace", str(trace)]
    assert run.main(argv, rehearsal=True, fault=fault) == 0
    out, err = capfd.readouterr()
    return json.loads(out.strip().splitlines()[-1]), err


def own_numbers(config):
    """The names a configuration's own reference returns (none where it
    names none), asked of the reference itself with nothing to count."""
    if not config.get("reference"):
        return []
    nothing = {"acks": {}, "sent": [], "rebinds": [], "deleted": [], "delete_errors": 0, "bound_at": {}, "deleted_at": {}}
    return list(importlib.import_module("references." + config["reference"]).violations([], [], config, nothing))


def holds_the_contract_line(result, err, cell, trace):
    """What every sound rehearsal's last line and last lines on standard
    error have to say; ``test_doors.py`` holds the fixture cells to it too."""
    assert RESULT_KEYS <= set(result)
    assert list(result)[-1] == "compared"
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 0
    assert result["device"]["platform"] == "cpu"
    assert "platform=cpu" in err
    if trace:
        assert {"busy_s", "window_s"} <= set(result["device"])
        assert set(result["metrics"]) <= {m["name"] for m in run.cell_metrics(run.load_cell(cell))}
    else:
        wanted = set(run.load_cell(cell)["traffic_data"]["end_to_end"]) | {"setup_s"}
        assert set(result["metrics"]) == wanted
        assert all(m["value"] > 0 for m in result["metrics"].values())
    # the sixteen numbers every run has compared since PR 25, under their
    # names and in their order, then the three a mix that deletes adds; after
    # these nineteen the configuration's own, as its reference names them
    compared = list(result["compared"])
    assert compared[:19] == COMPARED
    assert compared[19:] == own_numbers(run.load_cell(cell)["config_data"])
    assert all(result["compared"][name] == {"number": 0, "limit": 0} for name in compared)
    # every number compared is printed beside its limit, last on stderr
    assert err.strip().splitlines()[-1] == "correct: True"
    assert err.strip().splitlines()[-1 - len(compared):-1] == [f"compared {name}: 0 (limit 0)" for name in compared]


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_prints_the_contract_line(capfd, cell, trace):
    result, err = rehearse(capfd, cell, trace)
    holds_the_contract_line(result, err, cell, trace)


# -- what the rehearsal is cut to ------------------------------------------


def cut(config_rehearsal=None, cell="spread-5000n.drain"):
    loaded = run.load_cell(cell)
    if config_rehearsal is not None:
        loaded["config_data"]["rehearsal"] = config_rehearsal
    run.shrink(loaded)
    return loaded


@pytest.mark.parametrize("cell", CELLS)
def test_a_configuration_that_states_no_rehearsal_is_cut_to_the_common_one(cell):
    """Pinned against ``REHEARSAL`` itself: both configurations that exist
    name none and shrink as they did before a configuration could."""
    assert "rehearsal" not in run.load_json("configs", run.load_cell(cell)["config"] + ".json")
    got, R = cut(cell=cell), run.REHEARSAL
    cfg, traffic = got["config_data"], got["traffic_data"]
    assert (cfg["nodes"]["count"], cfg["init_pods"]["count"], cfg["live_pod_cap"]) == (
        R["nodes"], R["init_pods"], R["live_pod_cap"])
    whole = run.load_cell(cell)["traffic_data"]
    for key in ("outstanding", "chunk", "rate_per_s", "warm_stretch_s", "warm_max_stretches", "grace_s",
                "trace_s", "live_target", "deleters"):
        assert traffic.get(key) == (R[key] if whole.get(key) is not None else whole.get(key)), key
    if got["params"].get("warm_bursts"):
        assert got["params"]["warm_bursts"] == R["warm_bursts"]


def test_a_configuration_may_state_its_rehearsal():
    own = {"nodes": 32, "init_pods": 8, "outstanding": 16, "chunk": 8, "live_pod_cap": 32, "warm_bursts": [9]}
    got, R = cut(own), run.REHEARSAL
    cfg, traffic = got["config_data"], got["traffic_data"]
    assert (cfg["nodes"]["count"], cfg["init_pods"]["count"], cfg["live_pod_cap"]) == (32, 8, 32)
    assert (traffic["outstanding"], traffic["chunk"], got["params"]["warm_bursts"]) == (16, 8, [9])
    # what it does not state, and what is not a deployment's to state, stays
    assert (traffic["warm_stretch_s"], traffic["grace_s"]) == (R["warm_stretch_s"], R["grace_s"])
    assert traffic.get("live_target") is None  # the drain holds no live set: nothing to cut


@pytest.mark.parametrize("key", ["grace_s", "warm_stretch_s", "deadline_s", "seconds", "zones"])
def test_a_rehearsal_key_that_is_not_a_deployments_size_is_refused(key):
    assert key not in run.REHEARSAL_OWN
    with pytest.raises(ValueError, match=key):
        cut({"nodes": 32, key: 1})


def test_what_a_configuration_may_state_are_sizes_of_the_common_rehearsal():
    assert set(run.REHEARSAL_OWN) <= set(run.REHEARSAL)


def test_the_command_line_ends_at_the_tpu_gate():
    proc = subprocess.run(
        [sys.executable, os.path.join(manifest.HERE, "run.py"), "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=manifest.ROOT, capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu", "BENCH_RUN": "7"},
    )
    assert proc.returncode != 0
    assert "No fallback" in proc.stderr
    assert proc.stdout.strip() == ""


def test_alone_with_the_manifest_it_prints_no_result(tmp_path):
    import shutil

    shutil.copy(os.path.join(manifest.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(manifest.HERE, tmp_path / "benchmarks", ignore=shutil.ignore_patterns("__pycache__", ".trace"))
    proc = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", CELLS[0], "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode != 0 and proc.stdout.strip() == ""


FAULTS = [
    ("basic-5000n.drain", faults.overcommit, "nodes_over_allocatable"),
    ("basic-5000n.drain", faults.drop_half, "unbound_after_grace"),
    ("basic-5000n.drain", faults.drop_all, "unbound_after_grace"),
    ("spread-5000n.drain", faults.one_zone, "skew_over_max"),
    ("spread-5000n.drain", faults.drop_half, "unbound_after_grace"),
    ("basic-5000n.trickle", faults.overcommit, "nodes_over_allocatable"),
]


@pytest.mark.parametrize("cell,make_fault,number", [f for f in FAULTS if f[0] in CELLS])
def test_a_broken_timed_path_is_not_correct(capfd, monkeypatch, cell, make_fault, number):
    """The rest of a run, with the bind path broken underneath."""
    result, err = rehearse(capfd, cell, 0, fault=make_fault(monkeypatch.setattr))
    assert result["correct"] is False
    assert result["failed"] > 0
    assert result["compared"][number]["number"] > result["compared"][number]["limit"]
    assert err.strip().splitlines()[-1] == "correct: False"


# -- the reference and the audit, on placements written by hand ------------


def node(name, zone=None, unschedulable=False, cpu=4000, mem=32 << 30, pods=110):
    labels = {"topology.kubernetes.io/zone": zone} if zone else {}
    return {
        "metadata": {"name": name, "labels": labels},
        "spec": {"unschedulable": unschedulable},
        "status": {"allocatable": {"milli_cpu": cpu, "memory": mem, "pods": pods}},
    }


def pod(name, on, cpu=100, mem=500 << 20, selector=None, spread=False):
    spec = {
        "node_name": on,
        "containers": [{"requests": {"milli_cpu": cpu, "memory": mem}}],
        "node_selector": selector or {},
        "topology_spread_constraints": [],
    }
    labels = {}
    if spread:
        labels = {"color": "blue"}
        spec["topology_spread_constraints"] = [{
            "max_skew": 1, "topology_key": "topology.kubernetes.io/zone",
            "when_unsatisfiable": "DoNotSchedule",
            "label_selector": {"match_labels": {"color": "blue"}, "match_expressions": []},
        }]
    return {"metadata": {"name": name, "namespace": "default", "labels": labels}, "spec": spec}


NODES = [node("a", "z1"), node("b", "z2"), node("c", "z3"), node("d", "z1", unschedulable=True)]
SOUND = [pod("p0", "a"), pod("p1", "b", spread=True), pod("p2", "c", spread=True), pod("p3", "a", spread=True)]


@pytest.mark.parametrize(
    "pods,number",
    [
        (SOUND, None),
        (SOUND + [pod(f"x{i}", "a") for i in range(40)], "nodes_over_allocatable"),  # 4.4 cpu on 4
        (SOUND + [pod("x", "a", mem=33 << 30)], "nodes_over_allocatable"),
        (SOUND + [pod("x", "d")], "on_unschedulable"),
        (SOUND + [pod("x", "nowhere")], "on_unknown_node"),
        (SOUND + [pod("x", "")], "unbound"),
        (SOUND + [pod("x", "b", selector={"topology.kubernetes.io/zone": "z1"})], "selector_broken"),
        (SOUND + [pod("x", "a", spread=True), pod("y", "a", spread=True)], "skew_over_max"),
    ],
)
def test_reference_counts_what_the_guarantees_forbid(pods, number):
    got = reference.violations(NODES, pods)
    for key in ("unbound", "on_unknown_node", "on_unschedulable", "selector_broken", "nodes_over_allocatable"):
        assert (got[key] > 0) == (key == number), (key, got)
    assert (got["skew_over_max"] > 0) == (number == "skew_over_max"), got


def acks_for(pods, **change):
    acks = {"acks": {p["metadata"]["name"]: p["spec"]["node_name"] for p in pods},
            "sent": [p["metadata"]["name"] for p in pods], "rebinds": []}
    acks.update(change)
    return acks


def test_audit_accepts_a_sound_placement():
    compared = audit.checks(NODES, SOUND, acks_for(SOUND), len(NODES), {"wave_parked": 0})
    assert audit.verdict(compared), compared


@pytest.mark.parametrize(
    "pods,acks,counters,number",
    [
        (SOUND + [pod(f"x{i}", "a") for i in range(40)], None, {}, "nodes_over_allocatable"),
        (SOUND, acks_for(SOUND, acks={"p0": "b", "p1": "b", "p2": "c", "p3": "a"}), {}, "ack_not_on_readback"),
        (SOUND, acks_for(SOUND, rebinds=[["p0", "a", "b"]]), {}, "acked_twice"),
        (SOUND, acks_for(SOUND, sent=["p0", "p1", "p2", "p3", "lost"]), {}, "pods_missing"),
        (SOUND, acks_for(SOUND[:3], sent=["p0", "p1", "p2", "p3"]), {}, "bound_never_acked"),
        (SOUND + [SOUND[0]], acks_for(SOUND), {}, "pods_twice"),
        (SOUND, None, {"wave_parked": 1}, "wave_parked"),
        (SOUND, None, {"compiles_in_window": 2}, "compiles_in_window"),
    ],
)
def test_audit_refuses(pods, acks, counters, number):
    compared = audit.checks(NODES, pods, acks or acks_for(pods), len(NODES), counters)
    assert not audit.verdict(compared)
    assert compared[number][0] > compared[number][1], compared
