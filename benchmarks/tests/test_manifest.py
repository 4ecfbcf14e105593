"""``BENCHMARK.json`` keeps to the driver's alphabet and limits, and agrees
with the files under ``benchmarks/`` (PR 22 died of one space)."""

import copy
import json
import os

import pytest

import manifest

ROOT = manifest.ROOT


@pytest.fixture(scope="module")
def on_disk():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_manifest_stands(on_disk):
    assert manifest.check(on_disk) == []


def test_manifest_agrees_with_the_files(on_disk):
    assert on_disk == manifest.build()


def test_every_cell_has_its_files_and_readers(on_disk):
    import run

    for w in on_disk["workloads"]:
        cell = run.load_cell(w["name"])
        assert cell["config"] == w["config"] and cell["traffic"] == w["traffic"]
        assert cell["chips"] == w["chips"] and cell["why"] == w["why"]
        for m in run.cell_metrics(cell):
            assert os.path.isfile(os.path.join(manifest.HERE, "readers", m["reader"] + ".py"))
            listed = next(p for p in on_disk["per_layer"] if p["name"] == m["name"])
            assert w["name"] in listed["workloads"]
        for name in cell["traffic_data"]["end_to_end"]:
            e2e = next(m for m in on_disk["end_to_end"] if m["name"] == name)
            assert w["name"] in e2e.get("workloads", [w["name"]])


def test_a_new_cell_needs_no_edit_to_a_file_that_is_there(tmp_path, monkeypatch):
    """Copy the benchmark, add one cell's file and nothing else: the
    manifest built from the copy holds the cell, its end-to-end metric and
    its per-layer metrics, and stands."""
    import shutil

    copy_dir = tmp_path / "benchmarks"
    shutil.copytree(manifest.HERE, copy_dir, ignore=shutil.ignore_patterns("__pycache__", ".trace"))
    new = dict(run_cell("spread-5000n.drain"), name="spread-5000n.trickle", traffic="trickle",
               per_layer=["queue.engine_p99_ms"], why="a later PR's cell")
    with open(copy_dir / "workloads" / "spread-5000n.trickle.json", "w") as f:
        json.dump(new, f)
    monkeypatch.setattr(manifest, "HERE", str(copy_dir))
    monkeypatch.setattr(manifest, "ROOT", str(tmp_path))
    built = manifest.build()
    assert manifest.check(built) == []
    assert "spread-5000n.trickle" in [w["name"] for w in built["workloads"]]
    by_name = {m["name"]: m for m in built["end_to_end"] + built["per_layer"]}
    assert "spread-5000n.trickle" in by_name["bind_p99_ms"]["workloads"]
    assert "spread-5000n.trickle" in by_name["queue.engine_p99_ms"]["workloads"]
    assert "workloads" not in by_name["setup_s"]


def run_cell(name):
    with open(os.path.join(manifest.HERE, "workloads", name + ".json")) as f:
        return json.load(f)


def test_command_names_nothing_outside_paths(on_disk):
    for word in on_disk["command"][1:]:
        assert not word.startswith("/") and ".." not in word
        if os.path.exists(os.path.join(ROOT, word)):
            assert any(word.startswith(p + "/") for p in on_disk["paths"])


@pytest.mark.parametrize(
    "breakage,says",
    [
        (lambda m: m["per_layer"][0].update(layer="wave build (worker thread)"), "is not a name"),
        (lambda m: m["per_layer"][0].update(name="build s/wave"), "is not a name"),
        (lambda m: m["end_to_end"][0].update(unit="pods per second"), "unit"),
        (lambda m: m["configs"][0].update(source="x" * 201), "200 characters"),
        (lambda m: m["per_layer"][0].update(moves="nothing"), "moves"),
        (lambda m: m["per_layer"][0].update(why="because"), "keys"),
        (lambda m: m["end_to_end"][0].update(bound=0.5), "bound"),
        (lambda m: m["workloads"][0].update(chips=2), "chips"),
        (lambda m: m["workloads"].append(dict(m["workloads"][0], name="again")), "appears twice"),
        (lambda m: m.update(run_seconds=52), "run_seconds"),
    ],
)
def test_check_refuses(on_disk, breakage, says):
    broken = copy.deepcopy(on_disk)
    breakage(broken)
    assert any(says in line for line in manifest.check(broken)), manifest.check(broken)


def test_a_configuration_that_names_a_maker_it_has_no_file_for_is_refused(tmp_path, monkeypatch):
    import overlay

    copy_dir = overlay.build(tmp_path, "churn", "pool")
    os.remove(os.path.join(copy_dir, "makers", "pool.py"))
    monkeypatch.setattr(manifest, "HERE", copy_dir)
    monkeypatch.setattr(manifest, "ROOT", str(tmp_path))
    assert any("no benchmarks/makers/pool.py" in line for line in manifest.check(manifest.build()))


def test_a_configuration_that_states_a_rehearsal_size_it_may_not_is_refused(tmp_path, monkeypatch):
    import overlay

    copy_dir = overlay.build(tmp_path, "churn", "antiaffinity")
    monkeypatch.setattr(manifest, "HERE", copy_dir)
    monkeypatch.setattr(manifest, "ROOT", str(tmp_path))
    assert manifest.check(manifest.build()) == []
    path = os.path.join(copy_dir, "configs", "antiaffinity-5000n.json")
    with open(path) as f:
        data = json.load(f)
    data["rehearsal"]["grace_s"] = 1
    with open(path, "w") as f:
        json.dump(data, f)
    assert any("rehearsal ['grace_s']" in line for line in manifest.check(manifest.build()))


def test_a_metric_that_moves_what_its_cell_does_not_report_is_refused(on_disk):
    broken = copy.deepcopy(on_disk)
    drains = [m for m in broken["per_layer"] if m["moves"] == "pods_bound_per_s"]
    others = [w["name"] for w in broken["workloads"] if w["traffic"] != "drain"]
    if not others:
        pytest.skip("every cell is a drain")
    drains[0]["workloads"] = others[:1]
    assert any("does not report" in line for line in manifest.check(broken))
